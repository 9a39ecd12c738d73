"""Device milliseconds of the filter_compact kernel per completed interaction."""
from . import kernel_ms_per_interaction


def read(run):
    return kernel_ms_per_interaction(run, "filter_compact")
