"""Share of the rows the session's cached group programs ran that were the
planner's padding (each group padded up to its bucket size), from the
session's ``rows_useful`` and ``rows_padded`` counters.  Those count over
the window and the drain of the reads still in flight after it (the run
diffs the session's stats across both); the kernels' own padding to their
block size is not counted."""


def read(run):
    padded = run["stats"].get("rows_padded")
    if not padded:
        return None
    return 100.0 * (padded - run["stats"]["rows_useful"]) / padded
