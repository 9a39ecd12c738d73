"""Device milliseconds of the masked_stats kernel per completed interaction."""
from . import kernel_ms_per_interaction


def read(run):
    return kernel_ms_per_interaction(run, "masked_stats")
