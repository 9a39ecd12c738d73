"""Programs JAX lowered inside the measured window (each new jit
specialisation, found in the persistent cache or not); warm-up should leave
none."""


def read(run):
    return run.window_compiles
