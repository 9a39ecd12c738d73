"""repro.data — seeded multi-session interaction traces."""
from .synth import TraceEvent, TraceSpec, poisson_trace
