"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantic ground truth: each kernel's test sweeps shapes/dtypes
and asserts allclose against the function here. They are also the XLA
fallback paths used on CPU (the dry-run compiles these; the Pallas kernels
target TPU and are validated in interpret mode).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

Array = jax.Array

NEG_INF = -2.3819763e38


def _softcap(x: Array, cap: Optional[float]) -> Array:
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


def _repeat_kv(k: Array, num_heads: int) -> Array:
    kv = k.shape[2]
    if kv == num_heads:
        return k
    return jnp.repeat(k, num_heads // kv, axis=2)


# ---------------------------------------------------------------------------
# flash attention oracle
# ---------------------------------------------------------------------------


def attention(
    q: Array,                  # [B, Sq, N, H]
    k: Array,                  # [B, Sk, K, H]
    v: Array,                  # [B, Sk, K, H]
    *,
    scale: Optional[float] = None,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    q_offset: int = 0,
) -> Array:
    """Reference multi-head attention with GQA, causal/local masking, softcap."""
    n = q.shape[2]
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    kh = _repeat_kv(k, n)
    vh = _repeat_kv(v, n)
    logits = jnp.einsum("bqnh,bknh->bnqk", q, kh).astype(jnp.float32) * scale
    logits = _softcap(logits, softcap)
    qi = jnp.arange(q.shape[1])[:, None] + q_offset
    ki = jnp.arange(k.shape[1])[None, :]
    mask = jnp.ones((q.shape[1], k.shape[1]), bool)
    if causal:
        mask = mask & (ki <= qi)
    if window is not None:
        mask = mask & (ki > qi - window)
    logits = jnp.where(mask[None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bnqk,bknh->bqnh", probs, vh)


# ---------------------------------------------------------------------------
# decode attention oracle
# ---------------------------------------------------------------------------


def decode_attention(
    q: Array,                  # [B, N, H] — one query token per sequence
    k_cache: Array,            # [B, S, K, H]
    v_cache: Array,            # [B, S, K, H]
    pos: Array,                # [B] int32 — index of the newest token
    *,
    scale: Optional[float] = None,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
) -> Array:
    """Reference single-token decode attention over a KV cache."""
    n = q.shape[1]
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    kh = _repeat_kv(k_cache, n)
    vh = _repeat_kv(v_cache, n)
    logits = jnp.einsum("bnh,bknh->bnk", q, kh).astype(jnp.float32) * scale
    logits = _softcap(logits, softcap)
    ki = jnp.arange(k_cache.shape[1])[None, None, :]
    p = pos[:, None, None]
    mask = ki <= p
    if window is not None:
        mask = mask & (ki > p - window)
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bnk,bknh->bnh", probs, vh)


# ---------------------------------------------------------------------------
# Mamba2 SSD oracle (sequential scan — the definition)
# ---------------------------------------------------------------------------


def ssd(
    x: Array,                  # [B, S, H, P]
    dt: Array,                 # [B, S, H]  (already softplus'd, > 0)
    A: Array,                  # [H]        (negative decay rates)
    B: Array,                  # [B, S, N]  (shared across heads, G=1)
    C: Array,                  # [B, S, N]
    D: Array,                  # [H]
    init_state: Optional[Array] = None,   # [B, H, N, P]
) -> tuple[Array, Array]:
    """Reference SSD: h_t = exp(A*dt_t) h_{t-1} + dt_t B_t x_t^T,
    y_t = C_t^T h_t + D x_t. Returns (y [B,S,H,P], final_state [B,H,N,P])."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    dt32 = dt.astype(jnp.float32)
    B32 = B.astype(jnp.float32)
    C32 = C.astype(jnp.float32)
    A32 = A.astype(jnp.float32)
    state0 = (jnp.zeros((b, h, n, p), jnp.float32) if init_state is None
              else init_state.astype(jnp.float32))

    def step(state, inp):
        xt, dtt, Bt, Ct = inp            # [B,H,P], [B,H], [B,N], [B,N]
        a = jnp.exp(A32[None, :] * dtt)  # [B,H]
        upd = jnp.einsum("bn,bh,bhp->bhnp", Bt, dtt, xt)
        state = a[:, :, None, None] * state + upd
        y = jnp.einsum("bn,bhnp->bhp", Ct, state)
        return state, y

    xs = (jnp.moveaxis(x32, 1, 0), jnp.moveaxis(dt32, 1, 0),
          jnp.moveaxis(B32, 1, 0), jnp.moveaxis(C32, 1, 0))
    final, ys = jax.lax.scan(step, state0, xs)
    y = jnp.moveaxis(ys, 0, 1)           # [B,S,H,P]
    y = y + D.astype(jnp.float32)[None, None, :, None] * x32
    return y.astype(dtype), final


def ssd_chunked(
    x: Array, dt: Array, A: Array, B: Array, C: Array, D: Array,
    chunk: int = 64, init_state: Optional[Array] = None,
) -> tuple[Array, Array]:
    """Chunked (state-space dual) formulation in pure jnp — the algorithm the
    Pallas kernel implements. Mathematically identical to ``ssd``."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    dtype = x.dtype
    x32 = x.astype(jnp.float32).reshape(b, nc, chunk, h, p)
    dt32 = dt.astype(jnp.float32).reshape(b, nc, chunk, h)
    B32 = B.astype(jnp.float32).reshape(b, nc, chunk, n)
    C32 = C.astype(jnp.float32).reshape(b, nc, chunk, n)
    A32 = A.astype(jnp.float32)

    la = A32[None, None, None, :] * dt32            # [b,nc,L,h] log-decay
    cum = jnp.cumsum(la, axis=2)                    # inclusive
    # intra-chunk: y_i += sum_{j<=i} exp(cum_i - cum_j) (C_i.B_j) dt_j x_j
    seg = jnp.exp(cum[:, :, :, None, :] - cum[:, :, None, :, :])  # [b,nc,i,j,h]
    idx = jnp.arange(chunk)
    mask = (idx[:, None] >= idx[None, :])[None, None, :, :, None]
    seg = jnp.where(mask, seg, 0.0)
    cb = jnp.einsum("bcin,bcjn->bcij", C32, B32)    # [b,nc,i,j]
    m = seg * cb[..., None] * dt32[:, :, None, :, :]
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", m, x32)

    # inter-chunk: sequential state carry at chunk granularity
    chunk_decay = jnp.exp(cum[:, :, -1, :])         # [b,nc,h]
    # state update contribution of chunk c: sum_j exp(cum_L - cum_j) dt_j B_j x_j^T
    w = jnp.exp(cum[:, :, -1:, :] - cum) * dt32      # [b,nc,L,h]
    upd = jnp.einsum("bcjn,bcjh,bcjhp->bchnp", B32, w, x32)

    state0 = (jnp.zeros((b, h, n, p), jnp.float32) if init_state is None
              else init_state.astype(jnp.float32))

    def carry(state, inp):
        dec, u = inp                                 # [b,h], [b,h,n,p]
        new = dec[:, :, None, None] * state + u
        return new, state                            # emit state *entering* chunk

    final, states_in = jax.lax.scan(
        carry, state0,
        (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(upd, 1, 0)))
    states_in = jnp.moveaxis(states_in, 0, 1)        # [b,nc,h,n,p]
    y_inter = jnp.einsum("bcin,bcih,bchnp->bcihp",
                         C32, jnp.exp(cum), states_in)
    y = y_intra + y_inter + D.astype(jnp.float32)[None, None, None, :, None] * x32
    return y.reshape(b, s, h, p).astype(dtype), final


# ---------------------------------------------------------------------------
# grouped matmul oracle (MoE expert GEMM)
# ---------------------------------------------------------------------------


def gmm(x: Array, w: Array, group_sizes: Array) -> Array:
    """x: [T, D] rows sorted by group; w: [E, D, F]; group_sizes: [E] int32.
    Row t belongs to group g(t) = searchsorted(cumsum(sizes), t, 'right').
    Returns [T, F] with out[t] = x[t] @ w[g(t)]."""
    t, e = x.shape[0], w.shape[0]
    bounds = jnp.cumsum(group_sizes)
    gid = jnp.searchsorted(bounds, jnp.arange(t), side="right")
    # rows spread over a one-hot expert axis instead of gathering w per
    # row: [T, E, D] stays within memory at real widths, [T, D, F] cannot
    onehot = (gid[:, None] == jnp.arange(e)[None, :]).astype(x.dtype)
    xe = x[:, None, :] * onehot[:, :, None]
    return jnp.einsum("ted,edf->tf", xe, w.astype(x.dtype))
