"""Share of the reach reads the session's cached groups answered that a
dist group answered from its distances (the planner's reach-in-dist rule),
from the session's ``reach_fused`` and ``reach_rows`` counters, over the
window and the drain of the reads still in flight after it.  None where
the session has no such counters or answered no reach read."""


def read(run):
    rows = run["stats"].get("reach_rows")
    if not rows:
        return None
    return 100.0 * run["stats"]["reach_fused"] / rows
