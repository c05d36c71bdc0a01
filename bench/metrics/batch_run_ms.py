"""Mean host time of one QuerySession.run call begun in the window (the
call returns host arrays, so the device work is inside it)."""
from bench.drive import SPAN_RUN
from bench.window import spans


def read(run):
    d = spans(run, SPAN_RUN)
    return 1e3 * sum(d) / len(d) if d else None
