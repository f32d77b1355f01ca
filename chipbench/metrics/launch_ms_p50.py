"""Median host time to put one frame on the device: its ``serve.h2d``
(the tokens' host-to-device copy) plus its ``serve.launch`` (the handle
call, which only enqueues the program), over the window's dispatches."""
import statistics

from chipbench.engine_trace import window


def read(run):
    launches = window(run, "serve.launch")
    if not launches:
        return None
    h2d = {r["attrs"]["rid"]: r["t1"] - r["t0"]
           for r in window(run, "serve.h2d")}
    return 1e3 * statistics.median(
        r["t1"] - r["t0"] + h2d.get(r["attrs"]["rid"], 0.0)
        for r in launches)
