"""Process start to window start: JAX start-up, graph, fragmentation, cache
build, warm-up of every program the window runs."""


def read(run):
    return run["setup_s"]
