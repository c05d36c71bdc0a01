"""Share of the window in which no operation ran on the chip, from the
profiler trace, averaged over the cell's chips."""
from bench.window import idle_share


def read(run):
    idle = idle_share(run)
    return None if idle is None else 100.0 * idle
