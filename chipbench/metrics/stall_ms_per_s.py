"""Milliseconds per second of window the engine loop stood away from the
queue for longer than ``engine_trace.STALL_S`` (a dispatch turn takes
under 40 ms in the cells): the summed gaps over that between consecutive
``serve.poll`` starts in the window."""
from chipbench.engine_trace import STALL_S, window


def read(run):
    polls = window(run, "serve.poll")
    if not polls or not run.queue_until:
        return None
    starts = sorted(r["t0"] for r in polls)
    stalled = sum(b - a for a, b in zip(starts, starts[1:]) if b - a > STALL_S)
    return 1e3 * stalled / run.queue_until
