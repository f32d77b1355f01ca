"""Kernel microbench: analytic roofline terms + CPU-oracle timings.

The timings here are of the pure-jnp oracles in ``kernels/ref.py`` on
whatever backend JAX picks, usually the host CPU: they compare oracle
formulations (e.g. sequential vs chunked SSD) and are not device kernel
times. The meaningful kernel outputs are the analytic per-call FLOPs / HBM
bytes / VMEM working set that the BlockSpec tiling commits to. Compiled
kernels at real widths run on the chip in ``chip_smoke.py``.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

# note: `from repro.kernels import flash_attention` would resolve to the
# ops wrapper *function* re-exported by the package, not the module
import repro.kernels.flash_attention as fa
import repro.kernels.decode_attention as da
import repro.kernels.ssd as ssd_mod
from repro.kernels import ref

from .common import save_artifact


def _time(fn, *args, reps: int = 3) -> float:
    fn(*args)
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps


def run() -> dict:
    key = jax.random.PRNGKey(0)
    rows = []

    # flash attention: gemma2-class local layer tile
    b, s, n, kv, h = 1, 512, 4, 2, 64
    q = jax.random.normal(key, (b, s, n, h), jnp.float32)
    k = jax.random.normal(key, (b, s, kv, h), jnp.float32)
    v = jax.random.normal(key, (b, s, kv, h), jnp.float32)
    t_ref = _time(lambda *a: ref.attention(*a, window=128), q, k, v)
    rows.append({
        "kernel": "flash_attention",
        "shape": f"b{b} s{s} n{n} kv{kv} h{h} w128",
        "analytic_flops": fa.flops(b, s, s, n, h, causal=True),
        "vmem_bytes_per_step": fa.vmem_bytes(128, 128, h),
        "oracle_ms": t_ref * 1e3,
    })

    # decode attention: 32k cache read
    s_kv = 4096
    kc = jax.random.normal(key, (b, s_kv, kv, h), jnp.float32)
    vc = jax.random.normal(key, (b, s_kv, kv, h), jnp.float32)
    q1 = jax.random.normal(key, (b, n, h), jnp.float32)
    pos = jnp.full((b,), s_kv - 1, jnp.int32)
    t_ref = _time(lambda *a: ref.decode_attention(*a), q1, kc, vc, pos)
    rows.append({
        "kernel": "decode_attention",
        "shape": f"b{b} skv{s_kv} n{n} kv{kv} h{h}",
        "analytic_hbm_bytes": da.hbm_bytes(b, s_kv, kv, h),
        "oracle_ms": t_ref * 1e3,
    })

    # ssd: mamba2-130m-class block
    hh, p, nn, ch = 8, 64, 64, 64
    x = jax.random.normal(key, (b, 1024, hh, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(key, (b, 1024, hh), jnp.float32))
    A = -jnp.exp(jax.random.normal(key, (hh,), jnp.float32) * 0.3)
    B = jax.random.normal(key, (b, 1024, nn), jnp.float32)
    C = jax.random.normal(key, (b, 1024, nn), jnp.float32)
    D = jnp.ones((hh,), jnp.float32)
    t_seq = _time(lambda *a: ref.ssd(*a)[0], x, dt, A, B, C, D)
    t_chunk = _time(
        lambda *a: ref.ssd_chunked(*a, chunk=ch)[0], x, dt, A, B, C, D)
    rows.append({
        "kernel": "ssd",
        "shape": f"b{b} s1024 h{hh} p{p} n{nn} chunk{ch}",
        "analytic_flops": ssd_mod.flops(b, 1024, hh, p, nn, ch),
        "oracle_sequential_ms": t_seq * 1e3,
        "oracle_chunked_ms": t_chunk * 1e3,
        "chunked_speedup": t_seq / t_chunk,
    })

    out = {"backend": jax.default_backend(), "rows": rows}
    save_artifact("kernels_bench", out)
    return out


def main() -> None:
    out = run()
    print(f"kernel microbench (oracle timings on {jax.default_backend()}, "
          "not device kernel times; analytic TPU terms):")
    for r in out["rows"]:
        print("  " + ", ".join(f"{k}={v}" for k, v in r.items()))


if __name__ == "__main__":
    main()
