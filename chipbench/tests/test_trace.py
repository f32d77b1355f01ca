"""The trace reduction on a small trace recorded on a v5e chip
(``record_trace.py``): six rounds of poll (2 ms sleep), dispatch of a
1.41 ms bf16 matmul program, wait, 3 ms sleep; and the table of every
operation's self time on it, on the engine's trace
(``record_engine_trace.py``) and on three forwards of a two-layer
mamba2-130m whose scan is the program's Pallas ``ssd`` kernel
(``record_kernel_trace.py``)."""
import gzip
import os

import pytest

from chipbench import trace

DATA = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")
ENGINE = os.path.join(os.path.dirname(__file__), "data",
                      "engine.xplane.pb.gz")
KERNEL = os.path.join(os.path.dirname(__file__), "data",
                      "kernel.xplane.pb.gz")
MS = 1e-3


@pytest.fixture(scope="module")
def summary():
    return trace.reduce(DATA)


def test_runs_and_busy(summary):
    n, device_s = summary.runs["mm"]
    assert n == 6
    assert device_s == pytest.approx(6 * 1.413 * MS, rel=1e-3)
    # nothing else runs on the device: busy is the six program runs
    assert summary.busy_s == pytest.approx(device_s, rel=1e-6)


def test_window_is_busy_plus_idle(summary):
    assert summary.window_s == pytest.approx(42.811485 * MS, rel=1e-6)
    idle = sum(summary.idle_by.values())
    assert summary.busy_s + idle == pytest.approx(summary.window_s, abs=1e-9)


def test_idle_attribution(summary):
    # the device is idle through every 2 ms poll (they ran 2.1-3.1 ms)
    assert 6 * 2.0 * MS < summary.idle_by["poll"] < 6 * 3.2 * MS
    # and through the 3 ms sleeps outside any annotation
    assert 6 * 3.0 * MS < summary.idle_by["engine_loop"] < 6 * 3.5 * MS
    # a dispatch only enqueues: a fraction of a millisecond each
    assert 0 < summary.idle_by["dispatch:mm"] < 6 * 0.4 * MS


def test_clock_offset(summary):
    # a run cannot start before its execute call on the host (lower
    # bound) nor end after the host's wait returned (upper bound)
    assert 1.0 * MS < summary.offset_s < 2.0 * MS


def test_top_ops(summary):
    names = [n for n, _ in summary.top_ops[:2]]
    assert sorted(names) == ["mm:fusion", "mm:fusion.1"]
    assert sum(v for _, v in summary.top_ops) == pytest.approx(
        summary.busy_s, rel=1e-3)


def test_no_work_split():
    base = trace.reduce(DATA)
    polls = trace.reduce(DATA, poll_times=[0.0] * 6, outstanding=[])
    # nothing outstanding: all idle time outside the spans is for no work
    assert polls.idle_by["no_work"] == pytest.approx(
        base.idle_by["engine_loop"], rel=1e-9)
    assert polls.idle_by.get("engine_loop", 0.0) == 0.0
    busy = trace.reduce(DATA, poll_times=[0.0] * 6,
                        outstanding=[(-1e3, 1e3)])
    assert busy.idle_by["engine_loop"] == pytest.approx(
        base.idle_by["engine_loop"], rel=1e-9)
    assert busy.idle_by.get("no_work", 0.0) == pytest.approx(0.0, abs=1e-12)


def test_interval_helpers():
    assert trace._union([(3, 4), (0, 2), (1, 3)]) == [[0, 4]]
    assert trace._subtract([(0, 10)], [(2, 3), (5, 7)]) == [
        (0, 2), (3, 5), (7, 10)]
    assert trace._overlap([(0, 5), (6, 9)], [(4, 7)]) == 2.0


def unpacked(tmp_path_factory, gz: str) -> str:
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    with gzip.open(gz, "rb") as f:
        path.write_bytes(f.read())
    return str(path)


@pytest.fixture(scope="module", params=["small", "engine", "kernel"])
def recorded(request, tmp_path_factory):
    if request.param == "small":
        return DATA
    return unpacked(tmp_path_factory,
                    {"engine": ENGINE, "kernel": KERNEL}[request.param])


def test_ops_table(recorded):
    """``ops`` holds every operation of the window: its self times add up
    to the time in which some operation ran, and its head is
    ``top_ops``."""
    from jax.profiler import ProfileData
    summary = trace.reduce(recorded)
    plane = next(p for p in ProfileData.from_file(recorded).planes
                 if p.name.startswith("/device:TPU:"))
    ops = next(ln for ln in plane.lines if ln.name == "XLA Ops")
    busy = trace._union([(e.start_ns, e.start_ns + e.duration_ns)
                         for e in ops.events])
    assert sum(s for s, _ in summary.ops.values()) == pytest.approx(
        sum(b - a for a, b in busy) * trace.NS, rel=1e-9)
    assert [[k, s] for k, (s, _) in summary.ops.items()][:10] == \
        summary.top_ops[:10]
    assert "?" not in {kind for _, kind in summary.ops.values()}


@pytest.mark.parametrize("hlo,kind", [
    ("%fusion.3 = bf16[8,128]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[8,128] "
     "%p), kind=kLoop, calls=%fused_computation.3", "fusion"),
    ("%copy-start = (bf16[8]{0:T(256)S(1)}, bf16[8]{0}, u32[]{:S(2)}) "
     "copy-start(bf16[8]{0} %a.1)", "copy-start"),
    ("%ssd.1 = (bf16[1,24,64,64]{3,2,1,0:T(8,128)(2,1)S(1)}, "
     "f32[1,24,128,64]{3,2,1,0}) custom-call(%x, %dt), "
     'custom_call_target="tpu_custom_call", backend_config={}',
     "custom-call:tpu_custom_call"),
    ("XLA program 7", "?"),
])
def test_op_kind(hlo, kind):
    assert trace.op_kind(hlo) == kind


def test_pallas_kernel_is_picked_out(tmp_path_factory):
    """The Pallas ``ssd`` kernel is one ``tpu_custom_call`` operation
    named after its jitted function; a model module's ``ops`` matcher
    finds it by kind and stem, and ``Run.kernel_roofline`` sets its
    forwards' least time against that operation's self time alone."""
    from types import SimpleNamespace

    from chipbench import run
    summary = trace.reduce(unpacked(tmp_path_factory, KERNEL))
    custom = {k: kind for k, (_, kind) in summary.ops.items()
              if kind.startswith("custom-call")}
    assert custom == {"kws:ssd.3": "custom-call:tpu_custom_call",
                      "kws:custom-call.2": "custom-call:ConcatBitcast"}
    assert summary.runs["kws"][0] == 3

    # each call reads x and writes y, [1, 32, 24, 64] bf16, in both layers
    moved = 2 * 2 * 32 * 24 * 64 * 2

    def kernels(sizes, s):
        return {"ssd": {"flops": 0, "bytes": moved, "ops": lambda name, kind:
                        kind == "custom-call:tpu_custom_call"
                        and name.split(".")[0] == "ssd"}}

    got = run.Run(cell="c", seconds=1.0, frames=[], setup_s=0.0,
                  build_s=0.0, warmup_s=0.0, report=None, queue_until=1.0,
                  peak=run.peaks_for("TPU v5 lite"), trace=summary,
                  modules={"kws": SimpleNamespace(kernels=kernels)},
                  sizes={"kws": {}}, prompts={"kws": 32})
    spent = summary.ops["kws:ssd.3"][0]
    assert got.kernel_roofline("ssd") == pytest.approx(
        100 * 3 * moved / 819e9 / spent, rel=1e-12)
    assert 0 < got.kernel_roofline("ssd") < 100
