"""Mamba2 SSD (state-space duality) chunked-scan kernel (Pallas / TPU).

The SSD insight: the selective-state recurrence

    h_t = exp(A dt_t) h_{t-1} + dt_t B_t x_t^T        y_t = C_t h_t + D x_t

decomposes into (i) an intra-chunk part that is a masked, decay-weighted
attention-like matmul (MXU-friendly: [L, L] x [L, P]) and (ii) an
inter-chunk state carry at chunk granularity (a [N, P] state per head).
This trades the sequential length-S scan for S/L sequential steps of dense
[L,·] matmuls — exactly the restructuring TPU wants (long vector scans are
VPU-serial; chunk matmuls hit the MXU).

Kernel layout: grid (batch, head, chunk), chunk innermost/sequential; the
running [N, P] state lives in VMEM scratch across chunk steps. The wrapper
moves heads ahead of the sequence (x: [B, H, S, P], dt: [B, H, 1, S]) so
every tile's last two dims are (chunk, P) / (1, chunk): the tiling Mosaic
requires. The per-head scalars A and D sit whole in SMEM. B/C are shared
across heads (G=1), so their tiles are indexed by (batch, chunk) only and
revisit across the head axis — acceptable: N is small (64-128) and the x/y
tiles dominate VMEM.

The chunk's cumulative log-decay is a masked lane reduction of the dt row,
so no scan or transpose runs inside the kernel.

All math in fp32 (the recurrence is exp-weighted; bf16 decays drift).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _ssd_kernel(
    x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref,
    y_ref, fin_ref,
    state_scr,
    *,
    chunk: int,
):
    hh = pl.program_id(1)
    cb = pl.program_id(2)
    ncb = pl.num_programs(2)

    @pl.when(cb == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[...].astype(jnp.float32)               # [L, P]
    dt_row = dt_ref[...].astype(jnp.float32)         # [1, L]
    a = a_ref[hh]                                    # scalar (this head)
    bmat = b_ref[...].astype(jnp.float32)            # [L, N]
    cmat = c_ref[...].astype(jnp.float32)            # [L, N]
    dd = d_ref[hh]                                   # scalar

    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = ii >= jj
    la_row = a * dt_row                              # [1, L] log-decays (<= 0)
    # inclusive cumsum as a column: cum_i = sum_{j<=i} la_j
    cum = jnp.sum(jnp.where(causal, la_row, 0.0), axis=1, keepdims=True)
    diag = ii == jj
    cum_row = jnp.sum(jnp.where(diag, cum, 0.0), axis=0,
                      keepdims=True)                 # [1, L] (same values)
    dt = jnp.sum(jnp.where(diag, dt_row, 0.0), axis=1,
                 keepdims=True)                      # [L, 1]
    total = jnp.sum(la_row, axis=1, keepdims=True)   # [1, 1] = cum_L

    # intra-chunk: y_i = sum_{j<=i} exp(cum_i - cum_j) (C_i . B_j) dt_j x_j
    seg = jnp.where(causal, jnp.exp(cum - cum_row), 0.0)          # [L, L]
    m = jax.lax.dot_general(cmat, bmat, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)   # [L, L]
    m = m * seg * dt_row
    y = jax.lax.dot_general(m, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # [L, P]

    # inter-chunk: y_i += exp(cum_i) * C_i @ state_in
    state = state_scr[...]                           # [N, P]
    y_in = jax.lax.dot_general(cmat, state, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
    y = y + jnp.exp(cum) * y_in + dd * x
    y_ref[...] = y.astype(y_ref.dtype)

    # state update: state' = exp(cum_L) state + sum_j exp(cum_L - cum_j) dt_j B_j x_j^T
    w = jnp.exp(total - cum) * dt                    # [L, 1]
    upd = jax.lax.dot_general(bmat * w, x,
                              (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # [N, P]
    state_scr[...] = jnp.exp(total) * state + upd

    @pl.when(cb == ncb - 1)
    def _emit_final():
        fin_ref[...] = state_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(
    x: Array,                    # [B, S, H, P]
    dt: Array,                   # [B, S, H]  (softplus'd)
    A: Array,                    # [H]        (negative)
    B: Array,                    # [B, S, N]
    C: Array,                    # [B, S, N]
    D: Array,                    # [H]
    *,
    chunk: int = 64,
    interpret: bool = True,
) -> tuple[Array, Array]:
    """Chunked SSD scan. Returns (y [B,S,H,P], final_state [B,H,N,P] fp32)."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    grid = (b, h, s // chunk)

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)

    # head-major layout: the tiled dims (seq, P) / (1, seq) go last
    xh = x.transpose(0, 2, 1, 3)
    dth = dt.transpose(0, 2, 1)[:, :, None, :]
    y, fin = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, chunk, p),
                         lambda bb, hh, cc: (bb, hh, cc, 0)),
            pl.BlockSpec((None, None, 1, chunk),
                         lambda bb, hh, cc: (bb, hh, 0, cc)),
            smem,
            pl.BlockSpec((None, chunk, n), lambda bb, hh, cc: (bb, cc, 0)),
            pl.BlockSpec((None, chunk, n), lambda bb, hh, cc: (bb, cc, 0)),
            smem,
        ],
        out_specs=[
            pl.BlockSpec((None, None, chunk, p),
                         lambda bb, hh, cc: (bb, hh, cc, 0)),
            pl.BlockSpec((None, None, n, p), lambda bb, hh, cc: (bb, hh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, p), x.dtype),
            jax.ShapeDtypeStruct((b, h, n, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xh, dth, A.astype(jnp.float32), B, C, D.astype(jnp.float32))
    return y.transpose(0, 2, 1, 3), fin


def flops(b: int, s: int, h: int, p: int, n: int, chunk: int) -> int:
    """Analytic MACs: CB^T [L,N,L] + M@x [L,L,P] + state in/out [L,N,P] each."""
    nc = s // chunk
    per_chunk = chunk * chunk * n + chunk * chunk * p + 2 * chunk * n * p
    return b * h * nc * per_chunk
