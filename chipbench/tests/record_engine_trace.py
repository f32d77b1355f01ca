"""Record the small engine trace that ``test_engine_trace.py`` reduces.

    python chipbench/tests/record_engine_trace.py [OUT_DIR]

Runs on a TPU chip (needs one): the ``tiny_call`` cell (``ar_call`` at the
reduced test sizes of its models, ``SESSIONS`` sessions) served through
``ServingEngine`` with its spans and counters on, as ``run.py``'s traced
run sets it up (the harness's ``dispatch:``, ``poll`` and ``trigger``
annotations), for ``SECONDS`` with the whole window under the profiler.
Writes ``engine.xplane.pb`` (the checkout's path scrubbed from it) and
``engine.spans.jsonl`` under ``OUT_DIR`` (default ``chipbench/tests/data``)
and prints the reduction.
"""
from __future__ import annotations

import glob
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402

from chipbench import engine_trace, run, trace  # noqa: E402

SECONDS = 0.3
SESSIONS = 2
SEED = 7
ARRIVAL = {"kind": "periodic_jitter", "jitter": 0.1}


def main() -> int:
    from repro.launch.serve import build_handle
    from repro.obs import Obs

    out = sys.argv[1] if len(sys.argv) > 1 else os.path.join(HERE, "data")
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    cfg = run.load_json(HERE, "data", "tiny_call.json")
    handles, _, _ = run.build_handles(
        cfg, SEED, lambda model, stream, seed: build_handle(model, stream,
                                                            seed=seed))
    engine, _ = run.make_engine(cfg, handles, SEED)
    engine.obs = Obs.make(engine_trace.OBS)
    for name, h in handles.items():
        h.fn = run._annotated(h.fn, f"dispatch:{name}")
    queue = run.make_queue(cfg, handles, SESSIONS, ARRIVAL, SECONDS, SEED,
                           annotate=True)
    tmp = os.path.join(out, "engine.tmp")
    shutil.rmtree(tmp, ignore_errors=True)
    # SECONDS < run.TRACE_S: the profiler starts at the first poll
    queue.on_poll, _ = run._trace_hook(SECONDS, tmp, [])
    engine.run(queue, duration_s=SECONDS)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    os.makedirs(out, exist_ok=True)
    # keep the checkout's location out of the recorded bytes (same length,
    # so the protobuf holds)
    here = (ROOT + "/").encode()
    with open(path, "rb") as f:
        data = f.read()
    xplane = os.path.join(out, "engine.xplane.pb")
    with open(xplane, "wb") as f:
        f.write(data.replace(here, b"<" * (len(here) - 1) + b"/"))
    shutil.rmtree(tmp)
    engine.obs.tracer.dump_jsonl(os.path.join(out, "engine.spans.jsonl"))
    got = engine_trace.idle_by_span(trace.reduce(xplane),
                                    engine.obs.tracer.records)
    print(json.dumps({**got, "bytes": os.path.getsize(xplane),
                      "records": len(engine.obs.tracer.records)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
