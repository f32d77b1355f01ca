"""95th percentile, over the frames dispatched in the window, of the time
from a frame's admission (the start of its ``job`` span: the poll that
emitted it, or its parent's retire) to the start of its ``serve.launch``."""
from chipbench.engine_trace import window
from chipbench.stats import p95


def read(run):
    launches = window(run, "serve.launch")
    if not launches:
        return None
    job = {r["attrs"]["uid"]: r for r in run.spans["records"]
           if r["kind"] == "job"}
    return 1e3 * p95([r["t0"] - job[r["attrs"]["rid"]]["t0"]
                      for r in launches])
