"""Entry layer: executables JAX built inside the measured window (every
``backend_compile_duration`` event, a persistent-cache hit included).
Should be 0: every shape is warmed in set-up."""


def read(run):
    return float(run.compiles)
