"""Whole runs of a cell at a size a CPU holds, without the look for a chip.

``tiny_call.json`` is ``ar_call`` over the reduced test sizes of its
models. The program's own run comes out correct; the control (the
reference in float8 put in the program's place) and each fault this kind
of cell can have come out not correct. Of the faults a run can plant,
a served cell can have an answer altered where it is produced and a frame
lost from the engine's books; it keeps no training state, serves a batch
of one and runs on one chip, so the other faults have no place in it.

A model module planted in a temporary ``models`` directory takes the
place of ``reference.py`` and ``flops.py`` for its model; with none, the
run reads exactly what those two give. A traced run hands the engine's
spans to the readers.
"""
import json
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import engine_trace, flops, reference, run, trace
from chipbench.tests.test_engine_trace import SPAN_METRICS
from chipbench.traffic import Frame
from repro.launch.serve import build_handle
from repro.serving import ServingEngine

DATA = os.path.join(os.path.dirname(__file__), "data")
SECONDS = 2.0


@pytest.fixture(scope="module")
def cell():
    with open(os.path.join(DATA, "tiny_call.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    work = {"config": "tiny_call", "sessions": 1,
            "arrival": {"kind": "periodic_jitter", "jitter": 0.1}}
    return cfg, bench, work


def tiny(model, stream, seed):
    return build_handle(model, stream, seed=seed)


def go(cell, build=tiny, seed=2 ** 31 + 7, traced=False, name=None):
    cfg, bench, work = cell
    return run.run_cell(name or bench["workloads"][0]["name"], work, cfg,
                        bench, seed=seed, seconds=SECONDS, traced=traced,
                        peak=run.peaks_for("TPU v5 lite"),
                        devices=jax.devices()[:1], build=build)


def test_program_is_correct(cell):
    out = go(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 50 and out["failed"] == 0
    assert set(out["metrics"]) == {"deadline_met_pct", "setup_s"}
    assert list(out)[-1] == "checks"


def test_overload_cell_reads_its_own_metrics(cell):
    """The overload cell reports its end-to-end metrics and no other
    cell's (``BENCHMARK.json`` lists them by cell)."""
    out = go(cell, seed=2 ** 31 + 9, name="ar_call.overload")
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"completed_fps", "setup_s"}


def frame(due, ready=None, dropped=False):
    return Frame(session=0, stream="kws", index=0, due=due, deadline=due + 0.1,
                 head=True, tokens=None, ready=ready, dropped=dropped)


@pytest.mark.parametrize("name,want", [
    ("completed_fps", 4 / 1.0),        # ready inside the window, late too
    ("goodput_fps", 3 / 1.0),          # due inside, ready by the deadline
    ("drop_pct", 100.0 * 1 / 6),       # of the frames due in the window
])
def test_queue_reader_by_hand(name, want):
    frames = [frame(0.1, ready=0.15), frame(0.2, ready=0.35),
              frame(0.3, dropped=True), frame(0.5, ready=0.55),
              frame(0.6, ready=0.8), frame(0.95, ready=1.02), frame(1.05)]
    got = run.Run(cell="c", seconds=1.0, frames=frames, setup_s=0.0,
                  build_s=0.0, warmup_s=0.0, report=None, queue_until=1.0)
    assert run.reader(name)(got) == pytest.approx(want, rel=1e-12)


def test_control_is_not_correct(cell):
    cfg = cell[0]

    def control(model, stream, seed):
        h = tiny(model, stream, seed)
        sizes = cfg["models"][model]["sizes"]
        h.fn = lambda p, t: reference.forward(sizes, seed, t, control=True)
        return h

    out = go(cell, control)
    assert not out["correct"]
    assert any(v > lim for k, (v, lim) in out["checks"].items()
               if k.endswith("logit_err"))


def test_altered_answer_is_not_correct(cell):
    def altered(model, stream, seed):
        h = tiny(model, stream, seed)
        fn = h.fn

        def fn2(p, t):
            out = fn(p, t)
            return out.at[:, -1].set(jnp.roll(out[:, -1], 1, axis=-1))
        h.fn = fn2
        return h

    out = go(cell, altered)
    assert not out["correct"]
    assert out["checks"]["books"][0] == 0


def test_lost_frame_is_not_correct(cell, monkeypatch):
    finish = ServingEngine._finish_stats
    calls = {"n": 0}

    def lossy(self, req):
        calls["n"] += 1
        if calls["n"] % 10:
            finish(self, req)

    monkeypatch.setattr(ServingEngine, "_finish_stats", lossy)
    out = go(cell)
    assert not out["correct"]
    assert out["checks"]["books"][0] > 0


def test_stream_without_limit_is_not_correct(cell):
    cfg, bench, work = cell
    bare = {**cfg, "limits": {k: v for k, v in cfg["limits"].items()
                              if k != "ctx"}}
    out = go((bare, bench, work))
    assert not out["correct"]
    assert out["checks"]["ctx.logit_err"][1] is None
    assert out["checks"]["kws.logit_err"][1] is not None


# ---------------------------------------------------------------------------
# a model's own module, found by name
# ---------------------------------------------------------------------------

PLANTED = """
from chipbench import flops, reference

forward_bytes = flops.forward_bytes


def forward_flops(sizes, s):
    return 1000 + s


def forward(sizes, seed, tokens, control=False):
    return {body}
"""
BODIES = {
    "same": "reference.forward(sizes, seed, tokens, control)",
    "perturbed": "1.5 * reference.forward(sizes, seed, tokens, control)",
    "float8": "reference.forward(sizes, seed, tokens, control=True)",
}


def runs_seen(monkeypatch) -> list:
    """The ``Run`` objects the metric readers are handed."""
    seen, read = [], run.reader

    def reader(name):
        def read_and_keep(r):
            seen.append(r)
            return read(name)(r)
        return read_and_keep
    monkeypatch.setattr(run, "reader", reader)
    return seen


@pytest.mark.parametrize("body,correct", [
    ("same", True), ("perturbed", False), ("float8", False)])
def test_planted_model_module(cell, monkeypatch, tmp_path, body, correct):
    (tmp_path / "mamba2-130m.py").write_text(PLANTED.format(
        body=BODIES[body]))
    monkeypatch.setattr(run, "MODELS", str(tmp_path))
    seen = runs_seen(monkeypatch)
    out = go(cell)
    assert out["correct"] is correct, out["checks"]
    got = seen[0]
    # kws (32 positions) and ctx (64) serve mamba2-130m; translate does not
    assert got.flops["kws"] == 1032 and got.flops["ctx"] == 1064
    sizes = cell[0]["models"]["zamba2-2.7b"]["sizes"]
    assert got.flops["translate"] == flops.forward_flops(sizes, 64)
    assert got.modules["translate"].forward is reference.forward
    if not correct:   # the planted reference fails the streams it serves
        kws, translate = (out["checks"][f"{s}.logit_err"]
                          for s in ("kws", "translate"))
        assert kws[0] > kws[1] and translate[0] <= translate[1]


@pytest.mark.parametrize("config", ["ar_call", "ar_scene"])
def test_without_a_module_the_reference_and_flops_serve(config):
    cfg = run.load_json(run.BENCH, "configs", f"{config}.json")
    got = run.stream_models(cfg)
    for st in cfg["streams"]:
        name, sizes = st["name"], cfg["models"][st["model"]]["sizes"]
        assert got["modules"][name].forward is reference.forward
        assert got["sizes"][name] is sizes
        assert got["prompts"][name] == st["prompt"]
        assert got["flops"][name] == flops.forward_flops(sizes, st["prompt"])
        assert got["bytes"][name] == flops.forward_bytes(sizes, st["prompt"])


def test_without_a_module_readings_are_the_reference_s(cell):
    """``readings`` gives, bit for bit, the numbers of ``reference.py``'s
    logits, and of its control's."""
    cfg = cell[0]
    rng = np.random.default_rng(5)
    kept, seeds, want = {}, {}, {}
    for i, st in enumerate(cfg["streams"]):
        name, sizes = st["name"], cfg["models"][st["model"]]["sizes"]
        toks = rng.integers(0, sizes["vocab_size"], (2, st["prompt"]),
                            dtype=np.int32)
        got = rng.standard_normal((2, st["prompt"], sizes["vocab_size"]),
                                  dtype=np.float32)
        kept[name], seeds[name] = (toks, got), 100 + i
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(reference.forward(sizes, 100 + i, toks))
            ctl = np.asarray(reference.forward(sizes, 100 + i, toks,
                                               control=True))
        for k, v in run.logit_numbers(got, ref).items():
            want[f"{name}.{k}"] = v
        for k, v in run.logit_numbers(ctl, ref).items():
            want[f"{name}.control.{k}"] = v
    assert run.readings(cfg, kept, seeds, control=True) == want


# ---------------------------------------------------------------------------
# the engine's spans reach the readers in a traced run, and only there
# ---------------------------------------------------------------------------


def engines_made(monkeypatch) -> list:
    made, make_engine = [], run.make_engine

    def keep(*args, **kw):
        engine, warmup_s = make_engine(*args, **kw)
        made.append(engine)
        return engine, warmup_s
    monkeypatch.setattr(run, "make_engine", keep)
    return made


def test_traced_run_reads_spans(cell, monkeypatch):
    """The span metrics read through ``Run.spans`` what they read from the
    engine's own records up to the profiler's start, as the traced side
    run read them before. A CPU's trace has no TPU plane, so its two
    reductions are stood in for."""
    made, started = engines_made(monkeypatch), []

    def reduce(path, poll_times=None, outstanding=None):
        started.append(poll_times[0])
        return trace.Summary(window_s=1.0, busy_s=0.5, offset_s=0.0)

    monkeypatch.setattr(run, "TRACE_S", 1.0)
    monkeypatch.setattr(trace, "reduce", reduce)
    monkeypatch.setattr(engine_trace, "idle_by_span", lambda summ, recs: {
        "idle": {"serve.idle:no_frame": 0.25}, "tie": {"ns": 0.0}})
    out = go(cell, traced=True)
    assert out["correct"], out["checks"]
    obs = made[0].obs
    assert obs is not None and obs.tracer.records
    old = SimpleNamespace(spans={"records": obs.tracer.records,
                                 "counters": obs.metrics.snapshot()},
                          queue_until=started[0])
    for name in SPAN_METRICS:
        assert out["metrics"][name]["value"] == run.reader(name)(old), name
    assert out["spans"]["idle"] == {"serve.idle:no_frame": 0.25}
    assert out["spans"]["stalls"] == engine_trace.stalls(obs.tracer.records)
    assert list(out)[-1] == "checks"


def test_untraced_run_passes_no_obs(cell, monkeypatch):
    made, seen = engines_made(monkeypatch), runs_seen(monkeypatch)
    out = go(cell)
    assert made[0].obs is None and seen[0].spans is None
    assert "spans" not in out
