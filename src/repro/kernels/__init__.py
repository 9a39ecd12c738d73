"""repro.kernels — Pallas TPU kernels for the dataframe engine's hot spots.

Each kernel module contains the pl.pallas_call + BlockSpec implementation;
`ref.py` holds the pure-jnp oracles; `ops.py` the backend-dispatching jit
wrappers used by library code.
"""
from . import ops, ref
from .filter_compact import filter_compact
from .join_probe import join_probe
from .masked_stats import masked_stats
from .segment_reduce import segment_reduce
from .topk import topk

__all__ = [
    "ops", "ref", "segment_reduce", "masked_stats", "filter_compact", "topk",
    "join_probe",
]
