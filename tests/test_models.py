"""Per-architecture smoke tests: reduced same-family configs, forward /
train step on CPU, shape + finiteness assertions, decode consistency."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, smoke_config
from repro.models import model as M
from repro.training import TrainConfig, OptimConfig, build_train_step, \
    init_train_state

KEY = jax.random.PRNGKey(0)


def _inputs(cfg, b=2, s=16):
    tokens = jax.random.randint(KEY, (b, s), 0, cfg.vocab_size)
    fr = (jax.random.normal(KEY, (b, cfg.frontend_tokens, cfg.frontend_dim),
                            jnp.float32) if cfg.frontend else None)
    return tokens, fr


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_shapes_and_finite(arch):
    cfg = smoke_config(arch)
    params = M.init_params(KEY, cfg)
    tokens, fr = _inputs(cfg)
    logits, aux = M.forward(params, cfg, tokens, fr)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    assert bool(jnp.all(jnp.isfinite(logits)))
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_compute_dtype_weights_keep_logits(arch):
    """Serving holds matmul weights in the compute dtype. The forward casts
    them to it anyway, so the logits are bit-identical to the float32
    params', and making them at that dtype equals casting afterwards."""
    cfg = smoke_config(arch)
    p32 = M.init_params(KEY, cfg)
    p16 = M.init_params(KEY, cfg, weight_dtype=jnp.bfloat16)
    cast = M.cast_matmul_weights(M.init_params(KEY, cfg), jnp.bfloat16)
    assert jax.tree.structure(p16) == jax.tree.structure(cast)
    for a, b in zip(jax.tree.leaves(p16), jax.tree.leaves(cast)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    assert jnp.dtype(jnp.bfloat16) in {x.dtype for x in jax.tree.leaves(p16)}
    tokens, fr = _inputs(cfg)
    fwd = jax.jit(lambda p: M.forward(p, cfg, tokens, fr)[0])
    np.testing.assert_array_equal(np.asarray(fwd(p16)), np.asarray(fwd(p32)))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step_no_nans(arch):
    cfg = dataclasses.replace(smoke_config(arch), dtype="float32")
    tcfg = TrainConfig(optim=OptimConfig(learning_rate=1e-3, warmup_steps=1,
                                         total_steps=10))
    step = jax.jit(build_train_step(cfg, tcfg))
    state = init_train_state(KEY, cfg, tcfg)
    tokens, fr = _inputs(cfg, b=2, s=8)
    batch = {"tokens": tokens, "labels": tokens}
    if fr is not None:
        batch["frontend"] = fr
    state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    for leaf in jax.tree.leaves(state["params"]):
        assert bool(jnp.all(jnp.isfinite(leaf)))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_matches_forward_fp32(arch):
    """prefill+decode_step == forward on the extended sequence (exact in
    fp32; bf16 diverges numerically through deep residual paths)."""
    cfg = dataclasses.replace(smoke_config(arch), dtype="float32")
    params = M.init_params(KEY, cfg)
    b, s = 2, 12
    tokens, fr = _inputs(cfg, b, s)
    cache = M.init_cache(cfg, b, s + 2, jnp.float32)
    plogits, cache = M.prefill(params, cfg, tokens, cache, fr)
    logits, _ = M.forward(params, cfg, tokens, fr)
    np.testing.assert_allclose(np.asarray(plogits), np.asarray(logits),
                               atol=1e-4, rtol=1e-4)
    nxt = jnp.argmax(plogits[:, -1], -1).astype(jnp.int32)[:, None]
    pos = jnp.full((b,), s, jnp.int32)
    dlogits, _ = M.decode_step(params, cfg, nxt, cache, pos)
    ext = jnp.concatenate([tokens, nxt], axis=1)
    flogits, _ = M.forward(params, cfg, ext, fr)
    np.testing.assert_allclose(np.asarray(dlogits[:, 0]),
                               np.asarray(flogits[:, -1]),
                               atol=1e-3, rtol=1e-3)


def test_scan_equals_loop():
    cfg = smoke_config("gemma2-2b")
    cfg_scan = dataclasses.replace(cfg, num_layers=4, scan_layers=True,
                                   dtype="float32")
    cfg_loop = dataclasses.replace(cfg_scan, scan_layers=False)
    params = M.init_params(KEY, cfg_scan)
    tokens = jax.random.randint(KEY, (2, 8), 0, cfg.vocab_size)
    l1, _ = M.forward(params, cfg_scan, tokens)
    l2, _ = M.forward(params, cfg_loop, tokens)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), atol=1e-5)


def test_remat_preserves_values():
    cfg = dataclasses.replace(smoke_config("qwen1.5-4b"), dtype="float32")
    params = M.init_params(KEY, cfg)
    tokens = jax.random.randint(KEY, (2, 8), 0, cfg.vocab_size)
    l1, _ = M.forward(params, cfg, tokens)
    for remat in ("dots", "full"):
        cfg_r = dataclasses.replace(cfg, remat=remat)
        l2, _ = M.forward(params, cfg_r, tokens)
        np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), atol=1e-5)


def test_local_attention_masks_differ_from_global():
    """gemma2's local layers must actually restrict the receptive field."""
    cfg = dataclasses.replace(smoke_config("gemma2-2b"), dtype="float32",
                              local_window=2)
    params = M.init_params(KEY, cfg)
    b, s = 1, 12
    tokens = jax.random.randint(KEY, (b, s), 0, cfg.vocab_size)
    logits1, _ = M.forward(params, cfg, tokens)
    # perturbing token 0 must NOT change position s-1 through local-only
    # paths... it can still flow through global layers; instead check the
    # window masks by comparing against window=s (=global everywhere)
    cfg_g = dataclasses.replace(cfg, local_window=s)
    logits2, _ = M.forward(params, cfg_g, tokens)
    assert not np.allclose(np.asarray(logits1), np.asarray(logits2))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_matches_assignment(arch):
    """The full (published) config fields match the assignment table."""
    cfg = get_config(arch)
    expected = {
        "musicgen-large": (48, 2048, 32, 32, 8192, 2048),
        "gemma2-2b": (26, 2304, 8, 4, 9216, 256000),
        "qwen1.5-4b": (40, 2560, 20, 20, 6912, 151936),
        "minitron-8b": (32, 4096, 32, 8, 16384, 256000),
        "gemma-2b": (18, 2048, 8, 1, 16384, 256000),
        "phi3.5-moe-42b-a6.6b": (32, 4096, 32, 8, 6400, 32064),
        "qwen3-moe-235b-a22b": (94, 4096, 64, 4, 1536, 151936),
        "zamba2-2.7b": (54, 2560, 32, 32, 10240, 32000),
        "phi-3-vision-4.2b": (32, 3072, 32, 32, 8192, 32064),
        "mamba2-130m": (24, 768, 0, 0, 0, 50280),
    }[arch]
    got = (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
           cfg.d_ff, cfg.vocab_size)
    assert got == expected, (arch, got, expected)
    if arch == "phi3.5-moe-42b-a6.6b":
        assert (cfg.num_experts, cfg.num_experts_per_tok) == (16, 2)
    if arch == "qwen3-moe-235b-a22b":
        assert (cfg.num_experts, cfg.num_experts_per_tok) == (128, 8)
    if arch == "zamba2-2.7b":
        assert cfg.ssm_state == 64 and cfg.shared_attn_every == 6
    if arch == "mamba2-130m":
        assert cfg.ssm_state == 128
