"""Reads answered inside the window, over the window's length (the window
closes when a batch has handed back its last answer, see
``bench.drive.run_closed``)."""


def read(run):
    t0, t1 = run["t0"], run["t1"]
    done = sum(1 for s in run["sent"] if str(s.fut.status) == "done"
               and t0 <= s.fut.resolved_at <= t1)
    return done / (t1 - t0)
