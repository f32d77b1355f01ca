"""The scheduler's host time per dispatch: every ``serve.sweep`` (stale
abort and frame drop, each loop turn) and ``serve.decide`` (the ready x
idle MapScore loop and the supernet pick) over the window, over the
dispatches in it (one ``serve.decide`` each, as ``serve_dispatch_total``
counts them)."""
from chipbench.engine_trace import window


def read(run):
    decide = window(run, "serve.decide")
    if not decide:
        return None
    spent = sum(r["t1"] - r["t0"] for r in decide + window(run, "serve.sweep"))
    return 1e6 * spent / len(decide)
