"""Ahead-of-time compiles of every Pallas kernel for a described TPU v5e.

The interpret-mode sweeps in ``test_kernels.py`` check semantics but not
what Mosaic accepts: block shapes that are not tile-aligned, or too much
VMEM, are refused only by the TPU compiler. These tests run that compiler
here, without a chip, for one chip of a described ``v5e:2x2`` topology at
the published widths of the models that use each kernel, and require the
compiled program to contain the kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back from the
        # persistent cache without that chip; keep it out of the cache
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    assert "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()


BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32

#: (b, s, n, kv, h): gemma-2b (MQA, head_dim 256), qwen1.5-4b (MHA 20x128)
ATTN_WIDTHS = {"gemma-2b": (1, 512, 8, 1, 256),
                "qwen1.5-4b": (1, 512, 20, 20, 128)}


@pytest.mark.parametrize("arch", sorted(ATTN_WIDTHS))
def test_flash_attention_compiles_for_v5e(one_chip, arch):
    b, s, n, kv, h = ATTN_WIDTHS[arch]
    _compile(functools.partial(ops.flash_attention, interpret=False),
             one_chip, ((b, s, n, h), BF16), ((b, s, kv, h), BF16),
             ((b, s, kv, h), BF16))


@pytest.mark.parametrize("arch", sorted(ATTN_WIDTHS))
def test_decode_attention_compiles_for_v5e(one_chip, arch):
    b, _, n, kv, h = ATTN_WIDTHS[arch]
    s = 2048                                             # cache length
    _compile(functools.partial(ops.decode_attention, interpret=False),
             one_chip, ((b, n, h), BF16), ((b, s, kv, h), BF16),
             ((b, s, kv, h), BF16), ((b,), I32))


def test_ssd_compiles_for_v5e(one_chip):
    b, s, h, p, n = 1, 512, 24, 64, 128                  # mamba2-130m
    _compile(functools.partial(ops.ssd, chunk=256, interpret=False),
             one_chip, ((b, s, h, p), F32), ((b, s, h), F32), ((h,), F32),
             ((b, s, n), F32), ((b, s, n), F32), ((h,), F32))


#: (d_in, d_out): phi3.5-moe expert up (wi/wg) and down (wo) projections
GMM_WIDTHS = {"up": (4096, 6400), "down": (6400, 4096)}


@pytest.mark.parametrize("proj", sorted(GMM_WIDTHS))
def test_gmm_compiles_for_v5e(one_chip, proj):
    d_in, d_out = GMM_WIDTHS[proj]
    t, e = 1024, 16                          # 512 tokens x top-2, 16 experts
    _compile(functools.partial(ops.gmm, interpret=False), one_chip,
             ((t, d_in), BF16), ((e, d_in, d_out), BF16), ((e,), I32))
