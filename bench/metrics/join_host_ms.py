"""Milliseconds per interaction of the join's host side: the self time of
the ``join.build`` spans (the right side's merge, sort, uniqueness check and
upload, once per right table) and the ``join.assemble`` spans (each joined
partition's row order, row selection and right-column gathers), from the
program's spans; None where the program opens neither."""
from .. import spans as S


def read(run):
    if not any(s.name.startswith("join.") for s in S.window(run)):
        return None
    return S.self_ms(run, "join.")
