"""The LM: composes attention / MoE / SSD blocks into any assigned arch.

One code path serves all ten architectures; ``ArchConfig`` chooses the block
kinds. Layers are grouped into *super-blocks* (one repetition of the layer
pattern — e.g. (local, global) for gemma2, six mamba blocks + one shared
attention application for zamba2) and scanned with ``lax.scan`` over stacked
group parameters, which keeps HLO size O(1) in depth and is what makes the
94-layer MoE compile tractably on a 512-device mesh.

API (all functional, params are plain dict pytrees):
  init_params / param_axes            — parameters + logical sharding axes
  forward                             — [B,S] tokens -> (logits, aux) (train)
  init_cache / cache_spec / cache_axes— decode caches (KV / SSM state)
  prefill                             — forward + cache fill
  decode_step                         — one token per sequence
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs import ArchConfig
from . import attention as attn
from . import layers, moe, ssm

Array = jax.Array
Constrain = Callable[[Array, tuple], Array]
_id: Constrain = lambda x, _: x


# ---------------------------------------------------------------------------
# config plumbing
# ---------------------------------------------------------------------------


def attn_cfg_for(cfg: ArchConfig, kind: str) -> attn.AttnConfig:
    return attn.AttnConfig(
        d_model=cfg.d_model,
        num_heads=cfg.num_heads,
        num_kv_heads=cfg.num_kv_heads,
        head_dim=cfg.resolved_head_dim,
        qkv_bias=cfg.qkv_bias,
        rope_theta=None if cfg.pos_embed == "absolute" else cfg.rope_theta,
        logit_softcap=cfg.attn_logit_softcap,
        window=cfg.local_window if kind == "local" else None,
        scale=cfg.attn_scale,
    )


def shared_attn_cfg_for(cfg: ArchConfig) -> attn.AttnConfig:
    """Zamba2-style shared block: input is concat(x, x_embed) of width 2D."""
    return attn.AttnConfig(
        d_model=cfg.d_model,
        num_heads=cfg.num_heads,
        num_kv_heads=cfg.num_kv_heads,
        head_dim=(2 * cfg.d_model) // cfg.num_heads,
        rope_theta=cfg.rope_theta,
        q_in_dim=2 * cfg.d_model,
        out_dim=cfg.d_model,
    )


def moe_cfg_for(cfg: ArchConfig) -> moe.MoEConfig:
    return moe.MoEConfig(
        d_model=cfg.d_model,
        d_ff=cfg.d_ff,
        num_experts=cfg.num_experts,
        top_k=cfg.num_experts_per_tok,
        capacity_factor=cfg.moe_capacity_factor,
        act=cfg.mlp_act,
        impl=cfg.moe_impl,
    )


def ssm_cfg_for(cfg: ArchConfig) -> ssm.SSMConfig:
    return ssm.SSMConfig(
        d_model=cfg.d_model,
        state=cfg.ssm_state,
        heads=cfg.ssm_heads,
        expand=cfg.ssm_expand,
        conv_kernel=cfg.ssm_conv_kernel,
        chunk=cfg.ssm_chunk,
        impl=cfg.ssm_impl,
    )


def group_pattern(cfg: ArchConfig) -> tuple[str, ...]:
    """Block kinds inside one scanned super-block."""
    if cfg.shared_attn_every:
        return ("mamba",) * cfg.shared_attn_every
    return cfg.layer_pattern


def num_groups(cfg: ArchConfig) -> int:
    pat = len(group_pattern(cfg))
    assert cfg.num_layers % pat == 0, (cfg.num_layers, pat)
    return cfg.num_layers // pat


def compute_dtype(cfg: ArchConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


# ---------------------------------------------------------------------------
# per-block init / axes / apply
# ---------------------------------------------------------------------------


def _block_init(key: Array, cfg: ArchConfig, kind: str) -> dict:
    ks = jax.random.split(key, 4)
    if kind == "mamba":
        return {"ln": layers.rmsnorm_init(cfg.d_model),
                "ssm": ssm.ssm_init(ks[0], ssm_cfg_for(cfg))}
    p = {
        "ln1": layers.rmsnorm_init(cfg.d_model),
        "attn": attn.attn_init(ks[0], attn_cfg_for(cfg, kind)),
        "ln2": layers.rmsnorm_init(cfg.d_model),
    }
    if cfg.post_norms:
        p["post_ln1"] = layers.rmsnorm_init(cfg.d_model)
        p["post_ln2"] = layers.rmsnorm_init(cfg.d_model)
    if cfg.num_experts:
        p["moe"] = moe.moe_init(ks[1], moe_cfg_for(cfg))
    else:
        p["mlp"] = layers.mlp_init(ks[1], cfg.d_model, cfg.d_ff,
                                   gated=cfg.mlp_gated)
    return p


def _block_axes(cfg: ArchConfig, kind: str) -> dict:
    if kind == "mamba":
        return {"ln": layers.rmsnorm_axes(), "ssm": ssm.ssm_axes()}
    p = {
        "ln1": layers.rmsnorm_axes(),
        "attn": attn.attn_axes(attn_cfg_for(cfg, kind)),
        "ln2": layers.rmsnorm_axes(),
    }
    if cfg.post_norms:
        p["post_ln1"] = layers.rmsnorm_axes()
        p["post_ln2"] = layers.rmsnorm_axes()
    if cfg.num_experts:
        p["moe"] = moe.moe_axes()
    else:
        p["mlp"] = layers.mlp_axes(gated=cfg.mlp_gated)
    return p


def _apply_block(params: dict, cfg: ArchConfig, kind: str, x: Array,
                 positions: Array, constrain: Constrain,
                 attn_impl: str) -> tuple[Array, Array]:
    """Full-sequence block application. Returns (x, moe_aux)."""
    aux = jnp.zeros((), jnp.float32)
    if kind == "mamba":
        h = layers.rmsnorm(params["ln"], x)
        x = x + ssm.ssm_apply(params["ssm"], ssm_cfg_for(cfg), h, constrain)
        return x, aux
    h = layers.rmsnorm(params["ln1"], x)
    a = attn.attend_full(params["attn"], attn_cfg_for(cfg, kind), h,
                         positions, constrain, impl=attn_impl)
    if cfg.post_norms:
        a = layers.rmsnorm(params["post_ln1"], a)
    x = x + a
    h = layers.rmsnorm(params["ln2"], x)
    if cfg.num_experts:
        m, aux = moe.moe_apply(params["moe"], moe_cfg_for(cfg), h, constrain)
    else:
        m = layers.mlp(params["mlp"], h, act=cfg.mlp_act)
    if cfg.post_norms:
        m = layers.rmsnorm(params["post_ln2"], m)
    x = x + m
    x = constrain(x, ("batch", "act_seq", "embed"))
    return x, aux


def _apply_shared_attn(params: dict, cfg: ArchConfig, x: Array, x0: Array,
                       positions: Array, constrain: Constrain,
                       attn_impl: str) -> Array:
    """Zamba2 shared block: attn over concat(x, x0) + MLP, weights shared
    across every invocation."""
    cat = jnp.concatenate([x, x0], axis=-1)
    h = layers.rmsnorm(params["ln"], cat)
    a = attn.attend_full(params["attn"], shared_attn_cfg_for(cfg), h,
                         positions, constrain, impl=attn_impl)
    x = x + a
    h = layers.rmsnorm(params["ln2"], x)
    x = x + layers.mlp(params["mlp"], h, act=cfg.mlp_act)
    return x


# ---------------------------------------------------------------------------
# whole-model init / axes
# ---------------------------------------------------------------------------


def init_params(key: Array, cfg: ArchConfig,
                weight_dtype=jnp.float32) -> dict:
    """Seeded parameters, float32 by default. ``weight_dtype`` holds the
    matmul weights in that dtype as each group is made (the values of
    ``cast_matmul_weights`` applied afterwards), so a served model never
    holds all of its float32 weights at once."""
    pat = group_pattern(cfg)
    g = num_groups(cfg)
    keys = jax.random.split(key, 4)
    params: dict = {
        "embed": layers.embedding_init(keys[0], cfg.vocab_size, cfg.d_model,
                                       cfg.tie_embeddings),
        "final_norm": layers.rmsnorm_init(cfg.d_model),
    }
    if cfg.frontend:
        params["frontend"] = {"proj": layers.dense_init(
            keys[1], (cfg.frontend_dim, cfg.d_model), cfg.frontend_dim)}
    if cfg.shared_attn_every:
        ks = jax.random.split(keys[2], 3)
        params["shared_attn"] = {
            "ln": layers.rmsnorm_init(2 * cfg.d_model),
            "attn": attn.attn_init(ks[0], shared_attn_cfg_for(cfg)),
            "ln2": layers.rmsnorm_init(cfg.d_model),
            "mlp": layers.mlp_init(ks[1], cfg.d_model, cfg.d_ff,
                                   gated=cfg.mlp_gated),
        }
    cast_matmul_weights(params, weight_dtype)
    gkeys = jax.random.split(keys[3], g)

    def one_group(k):
        bkeys = jax.random.split(k, len(pat))
        return {str(i): _block_init(bkeys[i], cfg, kind)
                for i, kind in enumerate(pat)}

    # stack one leaf at a time and release its per-group parts as it goes:
    # the peak is all groups plus one stacked leaf, not all groups twice
    cols = []
    for k in gkeys:
        leaves, treedef = jax.tree.flatten(
            cast_matmul_weights(one_group(k), weight_dtype))
        cols.append(leaves)
    stacked = []
    for i in range(len(cols[0])):
        stacked.append(jnp.stack([c[i] for c in cols]))
        for c in cols:
            c[i] = None
    params["blocks"] = jax.tree.unflatten(treedef, stacked)
    return params


def cast_matmul_weights(params: dict, dtype) -> dict:
    """Hold every matmul weight (>= 2-D per layer) in ``dtype``, in place.

    The forward casts these weights to the compute dtype itself, so at
    that dtype the logits do not change, while the weights take half the
    memory of float32 and need no per-call copy. Norm scales and the SSM
    per-head vectors stay float32. Leaves are replaced one at a time, so
    the peak is the params plus one cast leaf, not both copies.
    """
    def walk(tree: dict, lead: int) -> None:
        for k, v in tree.items():
            if isinstance(v, dict):
                walk(v, lead)
            elif v.ndim - lead >= 2:
                tree[k] = v.astype(dtype)

    for k, v in params.items():
        walk(v, 1 if k == "blocks" else 0)   # blocks carry a group axis
    return params


def param_axes(cfg: ArchConfig) -> dict:
    pat = group_pattern(cfg)
    axes: dict = {
        "embed": layers.embedding_axes(cfg.tie_embeddings),
        "final_norm": layers.rmsnorm_axes(),
    }
    if cfg.frontend:
        axes["frontend"] = {"proj": ("fsdp", None)}
    if cfg.shared_attn_every:
        axes["shared_attn"] = {
            "ln": layers.rmsnorm_axes(),
            "attn": attn.attn_axes(shared_attn_cfg_for(cfg)),
            "ln2": layers.rmsnorm_axes(),
            "mlp": layers.mlp_axes(gated=cfg.mlp_gated),
        }
    block_axes = {str(i): _block_axes(cfg, kind) for i, kind in enumerate(pat)}
    # prepend the stacked group axis to every leaf
    axes["blocks"] = jax.tree.map(
        lambda lg: ("layers",) + lg, block_axes,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x))
    return axes


# ---------------------------------------------------------------------------
# forward (train / eval)
# ---------------------------------------------------------------------------


def _embed_input(params: dict, cfg: ArchConfig, tokens: Array,
                 frontend: Optional[Array], positions: Array,
                 constrain: Constrain) -> Array:
    dtype = compute_dtype(cfg)
    x = layers.embed_tokens(params["embed"], tokens, cfg.embed_scale, dtype)
    if cfg.frontend and frontend is not None:
        f = jnp.einsum("bfe,ed->bfd", frontend.astype(dtype),
                       params["frontend"]["proj"].astype(dtype))
        nf = f.shape[1]
        x = jnp.concatenate([f, x[:, nf:]], axis=1)  # frontend fills the head
    if cfg.pos_embed == "absolute":
        x = x + layers.sinusoidal_pos(positions, cfg.d_model, dtype)
    return constrain(x, ("batch", "act_seq", "embed"))


def forward(params: dict, cfg: ArchConfig, tokens: Array,
            frontend: Optional[Array] = None,
            constrain: Constrain = _id,
            attn_impl: str = "xla") -> tuple[Array, Array]:
    """Causal LM forward. tokens: [B, S] int32 -> (logits [B,S,V] f32, aux)."""
    b, s = tokens.shape
    pat = group_pattern(cfg)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    x = _embed_input(params, cfg, tokens, frontend, positions, constrain)
    x0 = x

    def group_body(carry, gparams):
        x, aux = carry
        if cfg.shared_attn_every:
            x = _apply_shared_attn(params["shared_attn"], cfg, x, x0,
                                   positions, constrain, attn_impl)
        for i, kind in enumerate(pat):
            x, a = _apply_block(gparams[str(i)], cfg, kind, x, positions,
                                constrain, attn_impl)
            aux = aux + a
        return (x, aux), None

    body = group_body
    if cfg.remat != "none":
        policy = {
            "full": jax.checkpoint_policies.nothing_saveable,
            "dots": jax.checkpoint_policies.checkpoint_dots,
            # MaxText-style: save projection/MLP dots but NOT the [S, S]
            # attention logits (batch-dim dots) — recompute them in the
            # backward pass. This is the policy that keeps activation
            # residuals O(S * d) instead of O(S^2).
            "dots_nobatch":
                jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
        }[cfg.remat]
        body = jax.checkpoint(group_body, policy=policy,
                              prevent_cse=not cfg.scan_layers)

    aux0 = jnp.zeros((), jnp.float32)
    if cfg.scan_layers:
        (x, aux), _ = jax.lax.scan(body, (x, aux0), params["blocks"])
    else:
        carry = (x, aux0)
        g = num_groups(cfg)
        for gi in range(g):
            gparams = jax.tree.map(lambda p: p[gi], params["blocks"])
            carry, _ = body(carry, gparams)
        x, aux = carry

    x = layers.rmsnorm(params["final_norm"], x)
    logits = layers.unembed(params["embed"], x, cfg.final_logit_softcap)
    logits = constrain(logits, ("batch", "act_seq", "vocab_out"))
    return logits, aux


# ---------------------------------------------------------------------------
# decode caches
# ---------------------------------------------------------------------------


def _group_cache(cfg: ArchConfig, batch: int, max_seq: int, dtype,
                 spec: bool) -> dict:
    pat = group_pattern(cfg)
    mk_attn = attn.cache_spec if spec else attn.init_cache
    mk_ssm = ssm.state_spec if spec else ssm.init_state
    cache: dict = {}
    for i, kind in enumerate(pat):
        if kind == "mamba":
            cache[str(i)] = mk_ssm(batch, ssm_cfg_for(cfg))
        else:
            cache[str(i)] = mk_attn(batch, max_seq, attn_cfg_for(cfg, kind),
                                    dtype)
    if cfg.shared_attn_every:
        cache["shared"] = mk_attn(batch, max_seq, shared_attn_cfg_for(cfg),
                                  dtype)
    return cache


def _stack_cache(cfg: ArchConfig, group_cache: dict, spec: bool) -> dict:
    g = num_groups(cfg)
    if spec:
        return jax.tree.map(
            lambda sd: jax.ShapeDtypeStruct((g,) + sd.shape, sd.dtype),
            group_cache)
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (g,) + x.shape), group_cache)


def init_cache(cfg: ArchConfig, batch: int, max_seq: int,
               dtype=jnp.bfloat16) -> dict:
    return _stack_cache(cfg, _group_cache(cfg, batch, max_seq, dtype, False),
                        False)


def cache_spec(cfg: ArchConfig, batch: int, max_seq: int,
               dtype=jnp.bfloat16) -> dict:
    return _stack_cache(cfg, _group_cache(cfg, batch, max_seq, dtype, True),
                        True)


def cache_axes(cfg: ArchConfig) -> dict:
    pat = group_pattern(cfg)
    ax: dict = {}
    for i, kind in enumerate(pat):
        ax[str(i)] = (ssm.state_axes() if kind == "mamba"
                      else attn.cache_axes())
    if cfg.shared_attn_every:
        ax["shared"] = attn.cache_axes()
    return jax.tree.map(
        lambda lg: ("layers",) + lg, ax,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x))


# ---------------------------------------------------------------------------
# prefill + decode
# ---------------------------------------------------------------------------


def prefill(params: dict, cfg: ArchConfig, tokens: Array, cache: dict,
            frontend: Optional[Array] = None,
            constrain: Constrain = _id,
            attn_impl: str = "xla") -> tuple[Array, dict]:
    """Run the prompt, fill the caches. Returns (logits [B,S,V], cache)."""
    b, s = tokens.shape
    pat = group_pattern(cfg)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    x = _embed_input(params, cfg, tokens, frontend, positions, constrain)
    x0 = x

    def group_body(x, xs):
        gparams, gcache = xs
        new_cache = dict(gcache)
        if cfg.shared_attn_every:
            cat = jnp.concatenate([x, x0], axis=-1)
            h = layers.rmsnorm(params["shared_attn"]["ln"], cat)
            a, kv = attn.attend_prefill(
                params["shared_attn"]["attn"], shared_attn_cfg_for(cfg), h,
                positions, gcache["shared"], constrain, impl=attn_impl)
            x = x + a
            h = layers.rmsnorm(params["shared_attn"]["ln2"], x)
            x = x + layers.mlp(params["shared_attn"]["mlp"], h,
                               act=cfg.mlp_act)
            new_cache["shared"] = kv
        for i, kind in enumerate(pat):
            bp = gparams[str(i)]
            if kind == "mamba":
                h = layers.rmsnorm(bp["ln"], x)
                y, st = ssm_prefill(bp["ssm"], ssm_cfg_for(cfg), h, constrain)
                x = x + y
                new_cache[str(i)] = st
            else:
                acfg = attn_cfg_for(cfg, kind)
                h = layers.rmsnorm(bp["ln1"], x)
                a, kv = attn.attend_prefill(bp["attn"], acfg, h, positions,
                                            gcache[str(i)], constrain,
                                            impl=attn_impl)
                if cfg.post_norms:
                    a = layers.rmsnorm(bp["post_ln1"], a)
                x = x + a
                h = layers.rmsnorm(bp["ln2"], x)
                if cfg.num_experts:
                    m, _ = moe.moe_apply(bp["moe"], moe_cfg_for(cfg), h,
                                         constrain)
                else:
                    m = layers.mlp(bp["mlp"], h, act=cfg.mlp_act)
                if cfg.post_norms:
                    m = layers.rmsnorm(bp["post_ln2"], m)
                x = x + m
                new_cache[str(i)] = kv
        return x, new_cache

    if cfg.scan_layers:
        x, cache = jax.lax.scan(group_body, x, (params["blocks"], cache))
    else:
        outs = []
        for gi in range(num_groups(cfg)):
            gp = jax.tree.map(lambda p: p[gi], params["blocks"])
            gc = jax.tree.map(lambda c: c[gi], cache)
            x, nc = group_body(x, (gp, gc))
            outs.append(nc)
        cache = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)

    x = layers.rmsnorm(params["final_norm"], x)
    logits = layers.unembed(params["embed"], x, cfg.final_logit_softcap)
    return logits, cache


def ssm_prefill(params: dict, scfg: ssm.SSMConfig, u: Array,
                constrain: Constrain = _id) -> tuple[Array, dict]:
    """Mamba2 full-sequence apply that also returns the decode state."""
    b, s, _ = u.shape
    dtype = u.dtype
    zxbcdt = jnp.einsum("bsd,dk->bsk", u, params["in_proj"].astype(dtype))
    z, xbc_pre, dt = ssm._split_proj(scfg, zxbcdt)
    xbc = jax.nn.silu(ssm._causal_conv(params, xbc_pre))
    x = xbc[..., : scfg.d_inner]
    bmat = xbc[..., scfg.d_inner: scfg.d_inner + scfg.state].astype(jnp.float32)
    cmat = xbc[..., scfg.d_inner + scfg.state:].astype(jnp.float32)
    dtp = jax.nn.softplus(dt.astype(jnp.float32)
                          + params["dt_bias"][None, None, :])
    a = -jnp.exp(params["A_log"])
    xh = x.reshape(b, s, scfg.heads, scfg.head_dim)
    y, fin = ssm._run_ssd(scfg, xh, dtp, a, bmat, cmat, params["D"])
    y = y.reshape(b, s, scfg.d_inner)
    y = layers.rmsnorm(params["norm"], y * jax.nn.silu(z))
    out = jnp.einsum("bsk,kd->bsd", y, params["out_proj"].astype(dtype))
    k = scfg.conv_kernel
    conv_state = jnp.pad(xbc_pre, ((0, 0), (max(k - 1 - s, 0), 0), (0, 0))
                         )[:, -(k - 1):, :]
    return out, {"conv": conv_state.astype(jnp.float32), "ssm": fin}


def decode_step(params: dict, cfg: ArchConfig, tokens: Array, cache: dict,
                pos: Array, constrain: Constrain = _id,
                attn_impl: str = "xla") -> tuple[Array, dict]:
    """One decode step. tokens: [B, 1], pos: [B] (write index).
    Returns (logits [B, 1, V] f32, new cache)."""
    pat = group_pattern(cfg)
    dtype = compute_dtype(cfg)
    x = layers.embed_tokens(params["embed"], tokens, cfg.embed_scale, dtype)
    if cfg.pos_embed == "absolute":
        x = x + layers.sinusoidal_pos(pos[:, None], cfg.d_model, dtype)
    x0 = x

    def group_body(x, xs):
        gparams, gcache = xs
        new_cache = dict(gcache)
        if cfg.shared_attn_every:
            cat = jnp.concatenate([x, x0], axis=-1)
            h = layers.rmsnorm(params["shared_attn"]["ln"], cat)
            a, kv = attn.attend_decode(
                params["shared_attn"]["attn"], shared_attn_cfg_for(cfg), h,
                gcache["shared"], pos, constrain)
            x = x + a
            h = layers.rmsnorm(params["shared_attn"]["ln2"], x)
            x = x + layers.mlp(params["shared_attn"]["mlp"], h,
                               act=cfg.mlp_act)
            new_cache["shared"] = kv
        for i, kind in enumerate(pat):
            bp = gparams[str(i)]
            if kind == "mamba":
                h = layers.rmsnorm(bp["ln"], x)
                y, st = ssm.ssm_decode(bp["ssm"], ssm_cfg_for(cfg), h,
                                       gcache[str(i)], constrain)
                x = x + y
                new_cache[str(i)] = st
            else:
                acfg = attn_cfg_for(cfg, kind)
                h = layers.rmsnorm(bp["ln1"], x)
                a, kv = attn.attend_decode(bp["attn"], acfg, h,
                                           gcache[str(i)], pos, constrain)
                if cfg.post_norms:
                    a = layers.rmsnorm(bp["post_ln1"], a)
                x = x + a
                h = layers.rmsnorm(bp["ln2"], x)
                if cfg.num_experts:
                    m, _ = moe.moe_apply(bp["moe"], moe_cfg_for(cfg), h,
                                         constrain)
                else:
                    m = layers.mlp(bp["mlp"], h, act=cfg.mlp_act)
                if cfg.post_norms:
                    m = layers.rmsnorm(bp["post_ln2"], m)
                x = x + m
                new_cache[str(i)] = kv
        return x, new_cache

    if cfg.scan_layers:
        x, cache = jax.lax.scan(group_body, x, (params["blocks"], cache))
    else:
        outs = []
        for gi in range(num_groups(cfg)):
            gp = jax.tree.map(lambda p: p[gi], params["blocks"])
            gc = jax.tree.map(lambda c: c[gi], cache)
            x, nc = group_body(x, (gp, gc))
            outs.append(nc)
        cache = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)

    x = layers.rmsnorm(params["final_norm"], x)
    logits = layers.unembed(params["embed"], x, cfg.final_logit_softcap)
    return logits, cache


# ---------------------------------------------------------------------------
# convenience
# ---------------------------------------------------------------------------


class LM:
    """Thin OO veneer over the functional API (examples / serving use this)."""

    def __init__(self, cfg: ArchConfig, constrain: Constrain = _id,
                 attn_impl: str = "xla"):
        self.cfg = cfg
        self.constrain = constrain
        self.attn_impl = attn_impl

    def init(self, key: Array) -> dict:
        return init_params(key, self.cfg)

    def axes(self) -> dict:
        return param_axes(self.cfg)

    def __call__(self, params, tokens, frontend=None):
        return forward(params, self.cfg, tokens, frontend, self.constrain,
                       self.attn_impl)

    def prefill(self, params, tokens, cache, frontend=None):
        return prefill(params, self.cfg, tokens, cache, frontend,
                       self.constrain, self.attn_impl)

    def decode_step(self, params, tokens, cache, pos):
        return decode_step(params, self.cfg, tokens, cache, pos,
                           self.constrain)

    def init_cache(self, batch: int, max_seq: int, dtype=jnp.bfloat16):
        return init_cache(self.cfg, batch, max_seq, dtype)
