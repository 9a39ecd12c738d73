"""Milliseconds per interaction the engine spends in its own code (the self
time of its ``engine.*`` spans: bookkeeping, the think-time model, cache
lookups and stores, speculation), from the program's spans."""
from .. import spans as S


def read(run):
    return S.self_ms(run, "engine.")
