"""Mixture-of-Experts layer: top-k token-choice routing.

Two interchangeable dispatch implementations (``cfg.moe_impl``):

* ``"einsum"`` — GSPMD-style capacity dispatch: tokens are blocked into
  groups, a one-hot dispatch tensor routes each token to its experts' slots,
  experts run as one batched einsum, and a combine einsum weights the results
  back. Robust under pjit automatic sharding (expert-parallel all-to-alls are
  generated by XLA from the shardings); dispatch/combine add
  ``group * topk * capacity_factor * d_model`` MACs per token — the price of
  static shapes.

* ``"gmm"`` — sort-based dispatch feeding the Pallas grouped-matmul kernel
  (``repro.kernels.gmm``): tokens are sorted by expert, experts run as a
  ragged grouped GEMM with no dispatch-einsum overhead, and the inverse
  permutation scatters results back. The optimized path of §Perf.

Router: softmax over expert logits, top-k, renormalized weights; auxiliary
load-balancing loss (Switch-style) returned for training.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from .layers import Array, dense_init

Constrain = Callable[[Array, tuple], Array]
_id = lambda x, _: x


@dataclass(frozen=True)
class MoEConfig:
    d_model: int
    d_ff: int                   # per-expert hidden
    num_experts: int
    top_k: int
    capacity_factor: float = 1.25
    group_size: int = 256       # dispatch group (einsum impl)
    act: str = "silu"
    impl: str = "einsum"        # "einsum" | "gmm"


def moe_init(key: Array, cfg: MoEConfig) -> dict:
    ks = jax.random.split(key, 4)
    e, d, f = cfg.num_experts, cfg.d_model, cfg.d_ff
    return {
        "router": dense_init(ks[0], (d, e), d),
        "wi": dense_init(ks[1], (e, d, f), d),
        "wg": dense_init(ks[2], (e, d, f), d),
        "wo": dense_init(ks[3], (e, f, d), f),
    }


def moe_axes() -> dict:
    return {
        "router": (None, None),
        "wi": ("experts", "fsdp", "ffn_noshard"),
        "wg": ("experts", "fsdp", "ffn_noshard"),
        "wo": ("experts", "ffn_noshard", "fsdp"),
    }


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------


def route(params: dict, cfg: MoEConfig, x: Array
          ) -> tuple[Array, Array, Array]:
    """Top-k routing. x: [T, D] ->
    (weights [T, k], expert_idx [T, k] int32, aux_loss scalar)."""
    logits = jnp.einsum("td,de->te", x, params["router"].astype(x.dtype))
    logits = logits.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, idx = jax.lax.top_k(probs, cfg.top_k)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    # Switch-style load-balance aux loss: E * sum_e f_e * p_e
    e = cfg.num_experts
    me = jnp.mean(probs, axis=0)                       # mean router prob
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(idx, e, dtype=jnp.float32), axis=1), axis=0)
    aux = e * jnp.sum(me * ce)
    return weights.astype(x.dtype), idx.astype(jnp.int32), aux


# ---------------------------------------------------------------------------
# einsum (capacity) dispatch
# ---------------------------------------------------------------------------


def _capacity(cfg: MoEConfig, group: int) -> int:
    c = int(math.ceil(group * cfg.top_k * cfg.capacity_factor
                      / cfg.num_experts))
    return max(c, cfg.top_k)


def moe_einsum(params: dict, cfg: MoEConfig, x: Array,
               constrain: Constrain = _id) -> tuple[Array, Array]:
    """x: [B, S, D] -> ([B, S, D], aux_loss). Capacity-dropped dispatch."""
    b, s, d = x.shape
    t = b * s
    g = min(cfg.group_size, t)
    while t % g:                 # largest divisor of t <= group_size
        g -= 1
    n_groups = t // g
    xt = x.reshape(t, d)
    weights, idx, aux = route(params, cfg, xt)

    e = cfg.num_experts
    cap = _capacity(cfg, g)
    idx_g = idx.reshape(n_groups, g, cfg.top_k)
    w_g = weights.reshape(n_groups, g, cfg.top_k)
    x_g = xt.reshape(n_groups, g, d)

    # slot position of each (token, choice) inside its expert's capacity
    onehot = jax.nn.one_hot(idx_g, e, dtype=jnp.int32)      # [G, g, k, e]
    kth_flat = onehot.reshape(n_groups, g * cfg.top_k, e)
    pos = jnp.cumsum(kth_flat, axis=1) - kth_flat            # rank in expert
    pos = pos.reshape(n_groups, g, cfg.top_k, e)
    in_cap = (pos < cap) & (onehot > 0)
    # dispatch/combine tensors [G, g, e, cap]
    cap_oh = jax.nn.one_hot(jnp.sum(pos * onehot, axis=-1), cap,
                            dtype=x.dtype)                    # [G, g, k, cap]
    keep = jnp.sum(in_cap, axis=-1).astype(x.dtype)           # [G, g, k]
    disp = jnp.einsum("Ggke,Ggkc,Ggk->Ggec",
                      onehot.astype(x.dtype), cap_oh, keep)
    comb = jnp.einsum("Ggec,Ggk,Ggke->Ggec", disp, w_g,
                      onehot.astype(x.dtype))

    xe = jnp.einsum("Ggec,Ggd->Gecd", disp, x_g)              # to expert slots
    xe = constrain(xe, ("batch", "act_experts", None, None))
    h = jnp.einsum("Gecd,edf->Gecf", xe, params["wi"].astype(x.dtype))
    gt = jnp.einsum("Gecd,edf->Gecf", xe, params["wg"].astype(x.dtype))
    act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
    h = act(gt) * h
    ye = jnp.einsum("Gecf,efd->Gecd", h, params["wo"].astype(x.dtype))
    ye = constrain(ye, ("batch", "act_experts", None, None))
    y = jnp.einsum("Ggec,Gecd->Ggd", comb, ye)
    return y.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# sort-based dispatch + grouped matmul (the optimized path)
# ---------------------------------------------------------------------------


def moe_gmm(params: dict, cfg: MoEConfig, x: Array,
            constrain: Constrain = _id, interpret: Optional[bool] = None
            ) -> tuple[Array, Array]:
    """x: [B, S, D] -> ([B, S, D], aux). Sort + Pallas grouped matmul.

    ``interpret=None`` lets ``kernels.ops`` choose: compiled on a TPU, the
    Pallas interpreter elsewhere."""
    from repro.kernels import ops as kops

    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)
    weights, idx, aux = route(params, cfg, xt)

    k = cfg.top_k
    flat_e = idx.reshape(-1)                       # [T*k]
    order = jnp.argsort(flat_e)                    # stable
    tok_of = order // k                            # source token per slot
    xs = jnp.take(xt, tok_of, axis=0)              # [T*k, D] sorted by expert
    group_sizes = jnp.bincount(flat_e, length=cfg.num_experts)

    h = kops.gmm(xs, params["wi"].astype(x.dtype), group_sizes,
                 interpret=interpret)
    g = kops.gmm(xs, params["wg"].astype(x.dtype), group_sizes,
                 interpret=interpret)
    act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
    h = act(g) * h
    ys = kops.gmm(h, params["wo"].astype(x.dtype), group_sizes,
                  interpret=interpret)
    # combine: scatter-add weighted outputs back to tokens
    w_flat = weights.reshape(-1)[order][:, None].astype(x.dtype)
    y = jnp.zeros((t, d), x.dtype).at[tok_of].add(ys * w_flat)
    return y.reshape(b, s, d), aux


def moe_apply(params: dict, cfg: MoEConfig, x: Array,
              constrain: Constrain = _id) -> tuple[Array, Array]:
    if cfg.impl == "gmm":
        return moe_gmm(params, cfg, x, constrain)
    return moe_einsum(params, cfg, x, constrain)


def flops_per_token(cfg: MoEConfig) -> int:
    """Analytic active MACs per token (experts + dispatch overhead)."""
    expert = cfg.top_k * 3 * cfg.d_model * cfg.d_ff
    router = cfg.d_model * cfg.num_experts
    if cfg.impl == "einsum":
        disp = 2 * cfg.group_size * cfg.top_k * cfg.capacity_factor * cfg.d_model
    else:
        disp = 0
    return int(expert + router + disp)
