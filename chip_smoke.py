#!/usr/bin/env python
"""Smoke test of the serving path on one TPU chip, at published widths.

    python chip_smoke.py

Everything runs in this one process (a chip belongs to one process):

  (a) kernels   - each Pallas kernel compiled for the chip (not
                  interpreted) at a real model width, checked against its
                  oracle in ``repro.kernels.ref``;
  (b) serving   - gemma-2b (``detector`` stream) and mamba2-130m (``kws``)
                  at their published configs, with seeded weights, served
                  by ``ServingEngine`` with MapScore dispatch through
                  ``repro.launch.serve``;
  (c) reference - the newest retired request of each model: the engine's
                  logits against a float32-compute forward of the same
                  weights.

The script exits non-zero, without the result line, when JAX finds no TPU
or when any check fails. A passing run ends with one JSON line:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
The persistent compile cache is where ``JAX_COMPILATION_CACHE_DIR`` says,
or else ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref
from repro.launch.cache import enable_compile_cache
from repro.launch.serve import PUBLISHED, serve
from repro.models import model as M

SEED = 0
SERVE_S = 5.0           # engine.run wall-clock seconds
MIN_FRAMES = 3          # frames every served model must retire
KERNEL_TOL = 2e-2       # max |kernel - oracle| / max |oracle|
# bf16 compute drifts from float32 by ~0.4% (gemma) to ~3% (mamba2) over
# the full depth at reduced width on a CPU, 0.12% and 1.4% at published
# width on a v5e; wrong weights or a wrong path give O(1) errors and
# chance-level (1/vocab) top-1 agreement
LOGIT_TOL = 0.1         # max |engine - f32 forward| / max |f32 forward|
TOP1_MIN = 0.5          # share of positions with the same arg-max token
HBM_LIMIT = 16e9        # bytes: one v5e chip


class CompileLog:
    """Backend compiles and persistent-cache hits, from JAX's monitoring
    events (a cache hit still reports the time it took to load)."""

    def __init__(self) -> None:
        self.compiles: list[tuple[str, float]] = []   # (function, seconds)
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles.append((str(kw.get("fun_name", "?")), secs))

    def _event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"[smoke] FAILED: {what}")


def rel_err(out, want) -> float:
    out = np.asarray(out, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.abs(out - want).max() / np.abs(want).max())


def memory(dev) -> str:
    st = dev.memory_stats()
    return (f"bytes_in_use={st.get('bytes_in_use')} "
            f"peak_bytes_in_use={st.get('peak_bytes_in_use')} "
            f"bytes_limit={st.get('bytes_limit')}")


# ---------------------------------------------------------------- (a)

def kernel_cases(key):
    """(name, kernel, oracle, args) at the published widths of the models
    that use each kernel; inputs are seeded and made on the device."""
    ks = iter(jax.random.split(key, 32))

    def normal(shape, dtype=jnp.bfloat16, scale=1.0):
        return (scale * jax.random.normal(next(ks), shape)).astype(dtype)

    f32 = jnp.float32
    cases = []
    for arch, (n, kv, h) in (("gemma-2b", (8, 1, 256)),
                             ("qwen1.5-4b", (20, 20, 128))):
        cases.append((f"flash_attention {arch}",
                      functools.partial(ops.flash_attention, interpret=False),
                      ref.attention,
                      (normal((1, 512, n, h)), normal((1, 512, kv, h)),
                       normal((1, 512, kv, h)))))
        cases.append((f"decode_attention {arch}",
                      functools.partial(ops.decode_attention, interpret=False),
                      ref.decode_attention,
                      (normal((1, n, h)), normal((1, 2048, kv, h)),
                       normal((1, 2048, kv, h)), jnp.array([1500], jnp.int32))))
    b, s, h, p, n = 1, 512, 24, 64, 128                      # mamba2-130m
    cases.append(("ssd mamba2-130m",
                  functools.partial(ops.ssd, chunk=256, interpret=False),
                  ref.ssd,
                  (normal((b, s, h, p), f32),
                   jax.nn.softplus(normal((b, s, h), f32)),
                   -jnp.exp(normal((h,), f32, 0.5)),
                   normal((b, s, n), f32), normal((b, s, n), f32),
                   jnp.ones((h,), f32))))
    t, d, f, e = 1024, 4096, 6400, 16                        # phi3.5-moe
    sizes = jnp.bincount(jax.random.randint(next(ks), (t,), 0, e), length=e)
    cases.append(("gmm phi3.5-moe",
                  functools.partial(ops.gmm, interpret=False), ref.gmm,
                  (normal((t, d)), normal((e, d, f), scale=d ** -0.5),
                   sizes.astype(jnp.int32))))
    return cases


def phase_kernels(dev) -> None:
    for name, kernel, oracle, args in kernel_cases(jax.random.PRNGKey(SEED)):
        t0 = time.perf_counter()
        compiled = jax.jit(kernel).lower(*args).compile()
        compile_s = time.perf_counter() - t0
        check("tpu_custom_call" in compiled.as_text(),
              f"{name}: no Mosaic kernel in the compiled program")
        outs = jax.block_until_ready(compiled(*args))
        # the oracle in float32 at full matmul precision
        args32 = [a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a
                  for a in args]
        with jax.default_matmul_precision("highest"):
            want = jax.jit(oracle)(*args32)
        outs = outs if isinstance(outs, tuple) else (outs,)
        want = want if isinstance(want, tuple) else (want,)
        errs = [rel_err(o, w) for o, w in zip(outs, want)]
        abs_errs = [float(np.abs(np.asarray(o, np.float32)
                                 - np.asarray(w, np.float32)).max())
                    for o, w in zip(outs, want)]
        print(f"[smoke] kernel {name:26s} compile={compile_s:.2f}s "
              f"max_abs_err={abs_errs} rel_err={errs} (tol {KERNEL_TOL})")
        check(all(e <= KERNEL_TOL for e in errs), f"{name} != oracle")
    print(f"[smoke] kernels ok; {memory(dev)}")


# ---------------------------------------------------------------- (b)

def phase_serving(dev, log: CompileLog):
    run = serve(PUBLISHED, published=True, duration_s=SERVE_S, seed=SEED)
    forward_compiles = [s for f, s in log.compiles if "forward_logits" in f]
    check(len(forward_compiles) == len(PUBLISHED),
          f"one forward compile per model, got {forward_compiles}")
    for st, compile_s in zip(PUBLISHED, forward_compiles):
        ms = run.report.per_model.get(st.name, {"frames": 0})
        h = run.handles[st.name]
        weights = sum(x.nbytes for x in jax.tree.leaves(h.params))
        print(f"[smoke] serve {st.name:>8s} {st.arch:12s} "
              f"compile={compile_s:.2f}s "
              f"warmup={run.engine.warmup_s[st.name]:.3f}s "
              f"lat_table[big0]={run.engine.lat_table[(st.name, 'big0')]:.6f}s "
              f"frames={ms['frames']} violated={ms.get('violated')} "
              f"uxcost={ms.get('uxcost')} weights_bytes={weights}")
        check(ms["frames"] >= MIN_FRAMES,
              f"{st.name} retired {ms['frames']} frames (< {MIN_FRAMES})")
    peak = dev.memory_stats()["peak_bytes_in_use"]
    print(f"[smoke] serving ok: {run.report.summary()} {memory(dev)}")
    check(peak < HBM_LIMIT, f"peak device memory {peak} >= {HBM_LIMIT:.0f}")
    return run


# ---------------------------------------------------------------- (c)

def phase_reference(run, dev) -> None:
    for st in PUBLISHED:
        req = run.engine.last_retired.get(st.name)
        check(req is not None, f"{st.name}: no retired request")
        got = np.asarray(req.result)
        h = run.handles[st.name]
        # the same weights in float32, in place: serving is over, and a
        # second copy of gemma-2b beside the first would not fit
        M.cast_matmul_weights(h.params, jnp.float32)
        cfg32 = dataclasses.replace(h.cfg, dtype="float32")
        with jax.default_matmul_precision("highest"):
            want = np.asarray(jax.jit(
                lambda p, t: M.forward(p, cfg32, t)[0])(
                    h.params, jnp.asarray(req.tokens)))
        err = rel_err(got, want)
        top1 = float(np.mean(got.argmax(-1) == want.argmax(-1)))
        print(f"[smoke] reference {st.name:>8s} logits{list(got.shape)} "
              f"max_abs_err={float(np.abs(got - want).max()):.6g} "
              f"max|f32 logit|={float(np.abs(want).max()):.6g} "
              f"rel_err={err:.6g} (tol {LOGIT_TOL}) "
              f"top1_agreement={top1:.4f} (min {TOP1_MIN})")
        check(np.isfinite(got).all(), f"{st.name}: non-finite logits")
        check(err <= LOGIT_TOL and top1 >= TOP1_MIN,
              f"{st.name}: engine logits off the float32 forward")
    print(f"[smoke] reference ok; {memory(dev)}")


def main() -> int:
    cache_dir = enable_compile_cache()
    devs = jax.devices()
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs)}
    print(f"[smoke] device {json.dumps(device)}")
    if dev.platform != "tpu":
        print("[smoke] no TPU: this smoke runs on the chip only",
              file=sys.stderr)
        return 1
    log = CompileLog()
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    print(f"[smoke] compile cache {cache_dir} ({entries} entries)")

    t0 = time.perf_counter()
    phase_kernels(dev)
    run = phase_serving(dev, log)
    phase_reference(run, dev)

    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    total_compile = sum(s for _, s in log.compiles)
    print(f"[smoke] compile: {len(log.compiles)} programs, "
          f"{total_compile:.2f}s; cache hits={log.hits} misses={log.misses}; "
          f"{cache_dir} now {entries} entries; "
          f"wall {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
