"""Share of the window in which ready frames were held back: the engine
slept with frames ready because every slice's virtual ``busy_until`` lay
in the future. Summed ``serve.idle`` spans of reason ``hold``, clipped to
the window before the profiler started."""
from chipbench.engine_trace import window


def read(run):
    idle = window(run, "serve.idle")
    if idle is None or not run.queue_until:
        return None
    held = sum(min(r["t1"], run.queue_until) - r["t0"] for r in idle
               if r["attrs"]["reason"] == "hold")
    return 100.0 * held / run.queue_until
