"""The serving engine's observability: off costs nothing, on changes
nothing, and the spans and counters account for every frame."""
import types

import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import critical_path, parse_prometheus
from repro.serving import (ModelHandle, RequestQueue, ServingEngine,
                           VirtualAccelerator)
from repro.serving import engine as engine_mod

OBS = {"spans": True, "metrics": True, "profile": False}


class FakeTime:
    """The engine module's ``time``: the clock moves only in ``sleep`` and
    in the model calls (``cost`` x ``scale`` seconds each, every 40th call
    ten times that: a straggler)."""

    def __init__(self):
        self.t = 0.0
        self.scale = 1.0

    def perf_counter(self) -> float:
        return self.t

    def sleep(self, dt: float) -> None:
        self.t += dt


class Recording(RequestQueue):
    """A ``RequestQueue`` that keeps every request it emitted."""

    def __init__(self):
        super().__init__(clock=lambda: 0.0)
        self.emitted = []

    def poll(self, now):
        out = super().poll(now)
        self.emitted.extend(out)
        return out

    def trigger_dependents(self, parent, now):
        out = super().trigger_dependents(parent, now)
        self.emitted.extend(out)
        return out


def fake_handle(name, clock, cost, calls, supernet=()):
    def fn(params, tokens):
        calls.append(name)
        clock.t += cost * clock.scale * (10 if len(calls) % 40 == 0 else 1)
        return jnp.zeros(tokens.shape + (8,), jnp.float32)
    return ModelHandle(name=name, cfg=None, params=None, fn=fn,
                       supernet=supernet)


def fake_run(monkeypatch, obs):
    """An overloaded deployment on the fake clock: a cascade, a supernet
    stream with a lighter variant, frame drop, stale aborts and
    adaptivity all at work."""
    clock, calls = FakeTime(), []
    monkeypatch.setattr(engine_mod, "time", clock)
    accs = [VirtualAccelerator("big", speed=1.0, power=1.0),
            VirtualAccelerator("small", speed=0.45, power=0.4)]
    eng = ServingEngine(accs, seed=3, obs=obs)
    costs = {"kws": 0.004, "translate": 0.012, "det": 0.03, "det@v1": 0.01}
    for name, cost in costs.items():
        sub = ("det@v1",) if name == "det" else ()
        eng.register(fake_handle(name, clock, cost, calls, sub),
                     np.zeros((1, 4), np.int32))
    calls.clear()                       # keep the window's calls only
    # calls run faster than calibrated, so a slice's virtual busy_until
    # outlasts the call and ready frames are held
    clock.scale = 0.6
    q = Recording()
    q.add_stream("kws", fps=90, batch=1, seq=4, vocab=8)
    q.add_stream("translate", fps=60, batch=1, seq=4, vocab=8,
                 depends_on="kws", trigger_prob=0.5)
    q.add_stream("det", fps=30, batch=1, seq=4, vocab=8)
    report = eng.run(q, duration_s=3.0)
    served = [(r.rid, r.model, r.done, r.dropped, r.completion, r.energy)
              for r in q.emitted]
    return eng, report, served, calls, q


def test_obs_off_records_nothing(monkeypatch):
    def refuse(*a, **k):
        raise AssertionError("an obs hook ran with obs off")

    monkeypatch.setattr(engine_mod, "_Recorder", refuse)
    monkeypatch.setattr(engine_mod.jax.profiler, "TraceAnnotation", refuse)
    eng, report, served, calls, _ = fake_run(monkeypatch, None)
    assert eng.obs is None
    assert report.frames > 0 and calls


def test_obs_on_changes_nothing(monkeypatch):
    off = fake_run(monkeypatch, None)
    on = fake_run(monkeypatch, True)
    eng, report, served, calls, _ = on
    assert report == off[1]
    assert served == off[2]                 # same frames, same slices
    assert calls == off[3]                  # same programs, in order
    # the run exercised the hooks it is meant to leave inert
    assert report.dropped > 0 and eng.aborted > 0 and report.redispatched > 0
    kinds = {r["kind"] for r in eng.obs.tracer.records}
    assert {"serve.drop", "serve.adapt", "serve.idle", "job"} <= kinds
    assert sum(eng.obs.metrics._metrics["serve_switch_total"]
               .children.values()) > 0


def test_fake_run_books(monkeypatch):
    """Every frame has one job span; each dispatch one decide, launch and
    wait; the counters agree with the spans."""
    eng, report, _, calls, q = fake_run(monkeypatch, OBS)
    recs = eng.obs.tracer.records
    jobs = [r for r in recs if r["kind"] == "job"]
    assert sorted(j["attrs"]["uid"] for j in jobs) == sorted(
        r.rid for r in q.emitted)
    n = len(calls)
    for kind in ("serve.decide", "serve.h2d", "serve.launch", "serve.wait",
                 "serve.retire"):
        assert sum(r["kind"] == kind for r in recs) == n, kind
    m = eng.obs.metrics._metrics
    assert sum(m["serve_dispatch_total"].children.values()) == n
    assert m["serve_turn_total"].value(kind="dispatch") == n
    outcomes = [j["attrs"]["outcome"] for j in jobs]
    assert outcomes.count("done") == n
    assert outcomes.count("dropped") == report.dropped + eng.aborted
    assert sum(r["attrs"]["redispatched"] for r in recs
               if r["kind"] == "serve.retire") == report.redispatched
    drops = [r["attrs"]["reason"] for r in recs if r["kind"] == "serve.drop"]
    assert drops.count("drop") == report.dropped
    assert drops.count("stale") == eng.aborted
    idle = [r for r in recs if r["kind"] == "serve.idle"]
    turns = m["serve_turn_total"]
    assert {r["attrs"]["reason"] for r in idle} == {"hold", "no_frame"}
    assert len(idle) <= turns.value(kind="hold") + turns.value(
        kind="no_frame")


@pytest.fixture(scope="module")
def cascade_run():
    """Tiny real models on the CPU: kws triggers translate."""
    from repro.launch.serve import build_handle
    accs = [VirtualAccelerator("big", speed=1.0, power=1.0),
            VirtualAccelerator("small", speed=0.45, power=0.4)]
    eng = ServingEngine(accs, adaptivity=False, seed=0, obs=OBS)
    for name, arch in (("kws", "mamba2-130m"), ("translate", "gemma-2b")):
        eng.register(build_handle(arch, name, layers=1),
                     np.zeros((1, 8), np.int32))
    q = Recording()
    q.add_stream("kws", fps=20, batch=1, seq=8, vocab=128)
    q.add_stream("translate", fps=20, batch=1, seq=8, vocab=128,
                 depends_on="kws", trigger_prob=1.0)
    report = eng.run(q, duration_s=1.5)
    return eng, report, q


def test_spans_account_for_every_frame(cascade_run):
    eng, report, q = cascade_run
    recs = eng.obs.tracer.to_records()
    jobs = {r["attrs"]["uid"]: r for r in recs if r["kind"] == "job"}
    assert sorted(jobs) == sorted(r.rid for r in q.emitted)
    decides = [r for r in recs if r["kind"] == "serve.decide"]
    launches = [r for r in recs if r["kind"] == "serve.launch"]
    waits = [r for r in recs if r["kind"] == "serve.wait"]
    assert len(decides) == len(launches) == len(waits) > 0
    dispatched = eng.obs.metrics._metrics["serve_dispatch_total"]
    assert sum(dispatched.children.values()) == len(launches)
    for la in launches:
        assert la["t0"] >= jobs[la["attrs"]["rid"]]["t0"]
    # a job's one exec block is its launch start to its wait end
    by_rid = {w["attrs"]["rid"]: w for w in waits}
    for la in launches:
        job = jobs[la["attrs"]["rid"]]
        assert job["attrs"]["segs"] == [[la["t0"],
                                         by_rid[la["attrs"]["rid"]]["t1"]]]


def test_critical_path_of_a_cascade(cascade_run):
    eng, _, _ = cascade_run
    recs = eng.obs.tracer.to_records()
    child = next(r for r in recs if r["kind"] == "job"
                 and r["attrs"]["model"] == "translate"
                 and r["attrs"]["outcome"] == "done")
    parent = next(r for r in recs if r["kind"] == "job"
                  and r["attrs"]["uid"] == child["attrs"]["parent"])
    assert parent["attrs"]["model"] == "kws"
    assert not parent["attrs"]["tail"] and child["attrs"]["tail"]
    cp = critical_path(recs, tail_uid=child["attrs"]["uid"])
    assert cp["chain"] == [parent["attrs"]["uid"], child["attrs"]["uid"]]
    assert cp["total_s"] == pytest.approx(
        child["t1"] - parent["attrs"]["origin"], abs=1e-12)
    assert sum(cp["by_seg"].values()) == pytest.approx(cp["total_s"],
                                                       abs=1e-12)
    assert cp["by_seg"]["exec"] > 0


def test_loop_spans_nest(cascade_run):
    """Loop spans either follow one another or nest in ``serve.idle``, so
    each instant of the loop has one innermost span."""
    eng, _, _ = cascade_run
    loop = sorted((r for r in eng.obs.tracer.records
                   if r["kind"].startswith("serve.")
                   and r["kind"] != "serve.drop"),
                  key=lambda r: (r["t0"], -r["t1"]))
    stack = []
    for r in loop:
        while stack and stack[-1]["t1"] <= r["t0"]:
            stack.pop()
        if stack:
            assert stack[-1]["kind"] == "serve.idle", (stack[-1], r)
            assert r["t1"] <= stack[-1]["t1"]
        stack.append(r)


def test_serve_obs_out(tmp_path, monkeypatch):
    """``--obs-out`` runs the engine with spans and counters and writes
    their files."""
    from repro.launch import serve as serve_mod
    from repro.obs import Obs, load_jsonl
    got = {}

    def fake_serve(deployment, **kw):
        got.update(kw)
        obs = Obs.make(kw["obs"])
        obs.tracer.event("serve.drop", 0.0, rid=1, reason="stale")
        obs.metrics.counter("serve_turn_total", "", ("kind",)).inc(
            kind="hold")
        return types.SimpleNamespace(engine=types.SimpleNamespace(obs=obs))

    monkeypatch.setattr(serve_mod, "serve", fake_serve)
    monkeypatch.setattr("repro.launch.cache.enable_compile_cache",
                        lambda: None)
    serve_mod.main(["--duration", "0.1", "--obs-out", str(tmp_path)])
    assert got["obs"] == OBS
    assert load_jsonl(str(tmp_path / "spans.jsonl"))[0]["kind"] == \
        "serve.drop"
    samples = parse_prometheus((tmp_path / "metrics.prom").read_text())
    assert samples[0]["name"] == "serve_turn_total"
