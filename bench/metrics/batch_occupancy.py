"""Mean size of the chunks the scheduler formed over its batch size, from
the engine telemetry's record_batch counter, over the window."""
from bench.window import occupancy


def read(run):
    occ = occupancy(run)
    return None if occ is None else 100.0 * occ
