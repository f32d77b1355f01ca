"""Record the trace that shows a Pallas kernel in ``trace.reduce``'s ops.

    python chipbench/tests/record_kernel_trace.py [OUT_DIR]

Runs on a TPU chip (needs one): mamba2-130m at its published widths, cut
to ``LAYERS`` layers, with the Mamba2 scan on the program's Pallas
``ssd`` kernel (``ssm_impl="pallas"``; the served configurations run the
XLA scan), over a 32-position prompt, for ``ROUNDS`` rounds of the host
loop ``record_trace.py`` makes: poll (2 ms sleep), dispatch:kws
(enqueue), wait, 3 ms sleep. Writes ``kernel.xplane.pb.gz`` (the
checkout's path scrubbed from it) under ``OUT_DIR`` (default
``chipbench/tests/data``) and prints each operation kind's self time and
the operations of custom calls.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench import trace  # noqa: E402

LAYERS = 2
ROUNDS = 3
PROMPT = 32


def main() -> int:
    from repro.configs import get_config
    from repro.models import model as M

    out = sys.argv[1] if len(sys.argv) > 1 else os.path.join(HERE, "data")
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    cfg = dataclasses.replace(get_config("mamba2-130m"), ssm_impl="pallas",
                              num_layers=LAYERS)
    params = jax.jit(lambda k: M.init_params(
        k, cfg, weight_dtype=M.compute_dtype(cfg)))(jax.random.PRNGKey(0))
    fn = jax.jit(lambda p, t: M.forward(p, cfg, t)[0])
    tokens = jnp.zeros((1, PROMPT), jnp.int32)
    fn(params, tokens).block_until_ready()
    tmp = os.path.join(out, "kernel.tmp")
    shutil.rmtree(tmp, ignore_errors=True)
    jax.profiler.start_trace(tmp)
    for _ in range(ROUNDS):
        with jax.profiler.TraceAnnotation("poll"):
            time.sleep(0.002)
        with jax.profiler.TraceAnnotation("dispatch:kws"):
            y = fn(params, tokens)
        y.block_until_ready()
        time.sleep(0.003)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    # keep the checkout's location out of the recorded bytes (same length,
    # so the protobuf holds)
    here = (ROOT + "/").encode()
    with open(path, "rb") as f:
        data = f.read().replace(here, b"<" * (len(here) - 1) + b"/")
    os.makedirs(out, exist_ok=True)
    kept = os.path.join(out, "kernel.xplane.pb.gz")
    with gzip.open(kept, "wb") as f:
        f.write(data)
    summary = trace.reduce(path)
    shutil.rmtree(tmp)
    by_kind: dict = {}
    for s, kind in summary.ops.values():
        by_kind[kind] = by_kind.get(kind, 0.0) + s
    print(json.dumps({
        "bytes": os.path.getsize(kept), "runs": summary.runs,
        "busy_s": summary.busy_s, "by_kind": by_kind,
        "custom_calls": {k: v for k, v in summary.ops.items()
                         if v[1].startswith("custom-call")}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
