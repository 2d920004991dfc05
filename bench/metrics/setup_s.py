"""Process start to window start: imports, JAX and TPU start-up, the
catalog, the program's objects and the warm-up with its program loads
(host clock)."""


def read(run):
    return run.setup_s
