"""Flash attention forward kernel (Pallas / TPU).

TPU-native blocked attention: the grid walks (batch, q_head, q_block,
k_block) with the k_block axis innermost — TPU grids execute sequentially,
so VMEM scratch carries the running softmax statistics (m, l) and the
output accumulator across k-blocks of one q-block. The wrapper moves heads
ahead of the sequence ([B, N, S, H]) so every BlockSpec tiles the last two
dims as (block x head_dim): the tiling Mosaic requires (sublane multiple of
8, full-extent head_dim). The MXU sees [block_q, head_dim] x
[head_dim, block_k] matmuls.

GQA is folded into the index maps (query head n reads kv head n * K // N),
so no jnp.repeat materializes the expanded KV. Causal and sliding-window
masks are applied per-tile; fully-masked tiles are skipped via pl.when
(this is what makes the local-attention layers of gemma2 O(S*window)).

Softcap (gemma2's tanh logit cap) happens pre-max in fp32.
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
DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128


def _flash_kernel(
    q_ref, k_ref, v_ref,          # VMEM tiles
    o_ref,                        # output tile
    m_scr, l_scr, acc_scr,        # VMEM scratch (carried across k-blocks)
    *,
    scale: float,
    causal: bool,
    window: Optional[int],
    softcap: Optional[float],
    block_q: int,
    block_k: int,
):
    qb = pl.program_id(2)
    kb = pl.program_id(3)
    nkb = pl.num_programs(3)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qb * block_q
    k_start = kb * block_k

    # tile-level mask pruning: skip tiles that are entirely masked
    run = jnp.bool_(True)
    if causal:
        run = jnp.logical_and(run, k_start <= q_start + block_q - 1)
    if window is not None and causal:
        # the whole tile is below every query's window iff its newest key
        # (k_start + block_k - 1) is <= oldest query (q_start) - window
        run = jnp.logical_and(run, k_start + block_k - 1 > q_start - window)

    @pl.when(run)
    def _body():
        q = q_ref[...].astype(jnp.float32)                 # [BQ, H]
        k = k_ref[...].astype(jnp.float32)                 # [BK, H]
        v = v_ref[...].astype(jnp.float32)                 # [BK, H]
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # [BQ, BK]
        if softcap is not None:
            logits = softcap * jnp.tanh(logits / softcap)
        qi = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        ki = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask = mask & (ki <= qi)
        if window is not None:
            mask = mask & (ki > qi - window)
        logits = jnp.where(mask, logits, NEG_INF)

        m_prev = m_scr[...]                                 # [BQ, 1]
        l_prev = l_scr[...]
        m_cur = jnp.max(logits, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        m_safe = jnp.where(m_new == -jnp.inf, 0.0, m_new)   # all-masked rows
        p = jnp.exp(logits - m_safe)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.where(m_prev == -jnp.inf, 0.0,
                          jnp.exp(m_prev - m_safe))
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new
        acc_scr[...] = acc

    @pl.when(kb == nkb - 1)
    def _finalize():
        l = l_scr[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_scr[...] / l_safe).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "causal", "window", "softcap",
                     "block_q", "block_k", "interpret"))
def flash_attention(
    q: Array,                    # [B, Sq, N, H]
    k: Array,                    # [B, Sk, K, H]
    v: Array,                    # [B, Sk, K, H]
    *,
    scale: Optional[float] = None,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = True,
) -> Array:
    """Blocked flash attention. Sq/Sk must be divisible by the block sizes
    (the ops wrapper pads); GQA handled via index maps (N % K == 0)."""
    b, sq, n, h = q.shape
    _, sk, kv, _ = k.shape
    assert n % kv == 0, (n, kv)
    scale = scale if scale is not None else h ** -0.5
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0
    grid = (b, n, sq // block_q, sk // block_k)
    q_heads_per_kv = n // kv

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, block_q=block_q, block_k=block_k)

    # head-major layout: the tiled dims (seq, head_dim) go last
    qh, kh, vh = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, block_q, h),
                         lambda bb, nn, qb, kb: (bb, nn, qb, 0)),
            pl.BlockSpec((None, None, block_k, h),
                         lambda bb, nn, qb, kb: (bb, nn // q_heads_per_kv, kb, 0)),
            pl.BlockSpec((None, None, block_k, h),
                         lambda bb, nn, qb, kb: (bb, nn // q_heads_per_kv, kb, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, block_q, h),
                               lambda bb, nn, qb, kb: (bb, nn, qb, 0)),
        out_shape=jax.ShapeDtypeStruct((b, n, sq, h), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, h), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qh, kh, vh)
    return out.transpose(0, 2, 1, 3)


def vmem_bytes(block_q: int, block_k: int, head_dim: int,
               dtype_bytes: int = 2) -> int:
    """VMEM working set of one grid step (tiles + scratch), for block tuning."""
    tiles = (block_q + 2 * block_k) * head_dim * dtype_bytes
    # m and l are [block_q, 1] f32 columns, lane-padded to 128 in VMEM
    scratch = (2 * block_q * 128 + block_q * head_dim) * 4
    out = block_q * head_dim * dtype_bytes
    return tiles + scratch + out


def flops(b: int, sq: int, sk: int, n: int, h: int, causal: bool) -> int:
    """Analytic MACs (QK^T + PV)."""
    full = 2 * b * n * sq * sk * h
    return full // 2 if causal and sq == sk else full
