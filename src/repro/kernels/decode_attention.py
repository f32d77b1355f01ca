"""Single-token decode attention kernel (Pallas / TPU).

The decode hot loop is memory-bound: one query token attends over a long KV
cache, so the roofline term is KV bytes / HBM bandwidth. The kernel streams
the cache through VMEM in (block_k x head_dim) tiles along the innermost
sequential grid axis, carrying flash-style running (m, l, acc) statistics in
VMEM scratch, and masks by the per-sequence cache length ``pos`` (tiles past
the newest token are skipped entirely — crucial when the cache is allocated
at max_seq but only partially filled).

All query heads of one KV head are processed together ([group, H] q tile):
with GQA this turns the per-tile work into a [group, H] x [H, BK] MXU matmul
instead of a bandwidth-starved GEMV, and each KV byte fetched from HBM is
reused ``group`` times — the classic GQA decode win.

The cache is read as [B, S, K*H] (a free reshape): KV head k is lane block
k, so each tile is (block_k, H) without moving the cache. Mosaic takes that
tile when H is a multiple of 128 or K == 1.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

NEG_INF = -2.3819763e38
DEFAULT_BLOCK_K = 256


def _decode_kernel(
    pos_ref,                     # SMEM scalar-prefetch: [B] int32
    q_ref, k_ref, v_ref,         # VMEM tiles
    o_ref,
    m_scr, l_scr, acc_scr,
    *,
    scale: float,
    window: Optional[int],
    softcap: Optional[float],
    block_k: int,
    group: int,
):
    b = pl.program_id(0)
    kb = pl.program_id(2)
    nkb = pl.num_programs(2)
    p = pos_ref[b]

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    k_start = kb * block_k
    run = k_start <= p
    if window is not None:
        run = jnp.logical_and(run, k_start + block_k - 1 > p - window)

    @pl.when(run)
    def _body():
        q = q_ref[...].astype(jnp.float32)                  # [G, H]
        k = k_ref[...].astype(jnp.float32)                  # [BK, H]
        v = v_ref[...].astype(jnp.float32)                  # [BK, H]
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # [G, BK]
        if softcap is not None:
            logits = softcap * jnp.tanh(logits / softcap)
        ki = k_start + jax.lax.broadcasted_iota(jnp.int32, (group, block_k), 1)
        mask = ki <= p
        if window is not None:
            mask = mask & (ki > p - window)
        logits = jnp.where(mask, logits, NEG_INF)
        m_prev = m_scr[...]                                 # [G, 1]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
        m_safe = jnp.where(m_new == -jnp.inf, 0.0, m_new)
        pexp = jnp.where(mask, jnp.exp(logits - m_safe), 0.0)
        alpha = jnp.where(m_prev == -jnp.inf, 0.0, jnp.exp(m_prev - m_safe))
        l_scr[...] = alpha * l_scr[...] + jnp.sum(pexp, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            pexp, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(kb == nkb - 1)
    def _finalize():
        l = l_scr[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_scr[...] / l_safe).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "window", "softcap", "block_k", "interpret"))
def decode_attention(
    q: Array,                    # [B, N, H]
    k_cache: Array,              # [B, S, K, H]
    v_cache: Array,              # [B, S, K, H]
    pos: Array,                  # [B] int32
    *,
    scale: Optional[float] = None,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = True,
) -> Array:
    b, n, h = q.shape
    _, s, kv, _ = k_cache.shape
    assert n % kv == 0
    group = n // kv
    scale = scale if scale is not None else h ** -0.5
    block_k = min(block_k, s)
    assert s % block_k == 0
    grid = (b, kv, s // block_k)

    # regroup q so each kv head's query group is contiguous: [B, KV, G, H]
    qg = q.reshape(b, kv, group, h)

    kernel = functools.partial(
        _decode_kernel, scale=scale, window=window, softcap=softcap,
        block_k=block_k, group=group)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((None, None, group, h),
                             lambda bb, kk, kb, pos_ref: (bb, kk, 0, 0)),
                pl.BlockSpec((None, block_k, h),
                             lambda bb, kk, kb, pos_ref: (bb, kb, kk)),
                pl.BlockSpec((None, block_k, h),
                             lambda bb, kk, kb, pos_ref: (bb, kb, kk)),
            ],
            out_specs=pl.BlockSpec((None, None, group, h),
                                   lambda bb, kk, kb, pos_ref: (bb, kk, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((group, 1), jnp.float32),
                pltpu.VMEM((group, 1), jnp.float32),
                pltpu.VMEM((group, h), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kv, group, h), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(pos.astype(jnp.int32), qg, k_cache.reshape(b, s, kv * h),
      v_cache.reshape(b, s, kv * h))
    return out.reshape(b, n, h)


def hbm_bytes(b: int, s: int, kv: int, h: int, dtype_bytes: int = 2) -> int:
    """Dominant HBM traffic of one decode step (the KV cache read)."""
    return 2 * b * s * kv * h * dtype_bytes
