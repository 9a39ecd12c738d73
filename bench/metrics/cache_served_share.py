"""Share of the window's interactions whose node was already materialised
in the engine's cache when issued (harvested in the background, or shown
before to any analyst)."""


def read(run):
    return sum(s.cached for s in run.shown) / len(run.shown) if run.shown else None
