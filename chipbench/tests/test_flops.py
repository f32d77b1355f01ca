"""FLOPs, bytes and peaks of the yardstick, on the CPU."""
import json
import os
from types import SimpleNamespace

import jax
import pytest

from chipbench import flops, run, trace

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs")


def sizes(config, model):
    with open(os.path.join(CONFIGS, f"{config}.json")) as f:
        return json.load(f)["models"][model]["sizes"]


MAMBA = sizes("ar_call", "mamba2-130m")
ZAMBA = sizes("ar_call", "zamba2-2.7b")
PHI = sizes("ar_scene", "phi-3-vision-4.2b")


def test_phi3_flops_by_hand():
    # per layer: q, k, v, o of 3072 x 3072 and a gated MLP of 3 x 3072 x 8192
    per_layer = 4 * 3072 ** 2 + 3 * 3072 * 8192
    s = 640
    attn = 2 * 32 * 96 * (s * (s + 1) // 2)          # QK^T and PV, causal
    unembed = s * 3072 * 32064
    hand = 2 * (32 * (s * per_layer + attn) + unembed)
    assert flops.forward_flops(PHI, s) == hand
    assert hand == pytest.approx(4.85e12, rel=0.01)


def test_zamba2_flops_by_hand():
    s, d = 64, 2560
    di, n, h = 2 * d, 64, 80
    mamba = s * d * (2 * di + 2 * n + h) + s * di * d       # in/out proj
    mamba += s * 4 * (di + 2 * n) + 2 * s * h * n * (di // h)   # conv, scan
    # the shared block runs at the head of each of the 9 groups: attention
    # over concat(x, x_embed) (5120 wide, heads of 160) and a gated MLP
    shared = s * 2 * d * 160 * 96 + s * 32 * 160 * d
    shared += 2 * 32 * 160 * (s * (s + 1) // 2) + 3 * s * d * 10240
    hand = 2 * (54 * mamba + 9 * shared + s * d * 32000)
    assert flops.forward_flops(ZAMBA, s) == hand
    # the shared block's nine applications are 0.18e12 of the 0.49e12
    assert hand == pytest.approx(4.87e11, rel=0.01)


def test_mamba2_flops_by_hand():
    s, d, di, n, h = 32, 768, 1536, 128, 24
    layer = (s * d * (2 * di + 2 * n + h) + s * di * d
             + s * 4 * (di + 2 * n) + 2 * s * h * n * (di // h))
    assert flops.forward_flops(MAMBA, s) == 2 * (24 * layer + s * d * 50280)


@pytest.mark.parametrize("arch,sizes_,expect", [
    ("mamba2-130m", MAMBA, 0.258e9),
    ("zamba2-2.7b", ZAMBA, 4.814e9),
    ("phi-3-vision-4.2b", PHI, 7.452e9),
])
def test_param_bytes_match_the_program(arch, sizes_, expect):
    from repro.configs import get_config
    from repro.models import model as M
    cfg = get_config(arch)
    shapes = jax.eval_shape(
        lambda k: M.init_params(k, cfg, weight_dtype=M.compute_dtype(cfg)),
        jax.random.PRNGKey(0))
    held = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))
    assert flops.param_bytes(sizes_) == held
    assert held == pytest.approx(expect, rel=1e-3)


def test_forward_bytes_leave_out_the_unread_front_end():
    s = 640
    assert (flops.param_bytes(PHI) - flops.param_bytes(PHI, served_only=True)
            == 2 * 1024 * 3072)
    assert flops.forward_bytes(PHI, s) == (
        flops.param_bytes(PHI, served_only=True) + 4 * s * 32064)


def test_peaks():
    v5e = run.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(run.NoChip):
        run.peaks_for("TPU v4")


def test_kernel_roofline():
    """A kernel named by a model module: its forwards' least time over the
    self time of the operations its ``ops`` picks out, in every stream
    whose module names it."""
    def kernels(sizes, s):
        assert sizes == {"d": s}
        return {"ssd": {"flops": 2e9, "bytes": 1e6,
                        "ops": lambda name, kind:
                        kind == "custom-call:tpu_custom_call"}}

    named = SimpleNamespace(kernels=kernels)
    summary = trace.Summary(
        window_s=1.0, busy_s=0.5, offset_s=0.0,
        runs={"kws": [4, 0.01], "ctx": [2, 0.01], "translate": [3, 0.1]},
        ops={"kws:ssd.1": [2e-4, "custom-call:tpu_custom_call"],
             "kws:fusion.1": [5e-3, "fusion"],
             "ctx:ssd.1": [4e-4, "custom-call:tpu_custom_call"],
             "translate:ssd.1": [9e-4, "custom-call:tpu_custom_call"]})
    got = run.Run(cell="c", seconds=1.0, frames=[], setup_s=0.0,
                  build_s=0.0, warmup_s=0.0, report=None, queue_until=1.0,
                  peak=run.peaks_for("TPU v5 lite"), trace=summary,
                  modules={"kws": named, "ctx": named,
                           "translate": SimpleNamespace()},
                  sizes={"kws": {"d": 32}, "ctx": {"d": 64},
                         "translate": {}},
                  prompts={"kws": 32, "ctx": 64, "translate": 64})
    least = 6 * max(2e9 / 197e12, 1e6 / 819e9)
    assert got.kernel_roofline("ssd") == pytest.approx(
        100 * least / 6e-4, rel=1e-12)
    assert got.kernel_roofline("flash") is None
    got.trace = None
    assert got.kernel_roofline("ssd") is None
