"""Serving engine integration: dispatch, cascade, drop, adaptivity."""
import numpy as np
import pytest

from repro.launch.serve import build_handle
from repro.serving import (RequestQueue, ServeRequest, ServingEngine,
                           TraceReplayQueue, VirtualAccelerator)


@pytest.fixture(scope="module")
def small_engine():
    accs = [VirtualAccelerator("big", speed=1.0, power=1.0),
            VirtualAccelerator("small", speed=0.5, power=0.4)]
    eng = ServingEngine(accs, adaptivity=False, frame_drop=True,
                        supernet_switch=True)
    h = build_handle("gemma-2b", "det", layers=1)
    hv = build_handle("gemma-2b", "det@v1", layers=1, d_model=32)
    h.supernet = ("det@v1",)
    eng.register(h, np.zeros((1, 16), np.int32))
    eng.register(hv, np.zeros((1, 16), np.int32))
    return eng


def test_calibration_builds_latency_table(small_engine):
    for acc in small_engine.accs:
        assert ("det", acc.name) in small_engine.lat_table
        assert small_engine.lat_table[("det", acc.name)] > 0
    # slower slice => higher latency entry
    assert (small_engine.lat_table[("det", "small")]
            > small_engine.lat_table[("det", "big")])


def test_mapscore_prefers_fast_slice_when_urgent(small_engine):
    # Pin the calibrated table for this check: lat_table comes from
    # wall-clock measurement, and on a fast (or loaded) machine the
    # measured latency can leave togo/slack too small for the urgency
    # product to dominate the energy term, making the comparison
    # machine-dependent rather than testing the urgency behavior.
    saved = dict(small_engine.lat_table)
    for acc in small_engine.accs:
        small_engine.lat_table[("det", acc.name)] = 0.004 / acc.speed
    try:
        req = ServeRequest(rid=0, model="det",
                           tokens=np.zeros((1, 16), np.int32),
                           arrival=0.0, deadline=0.005)
        scores = {a.name: small_engine._mapscore(req, a, now=0.004)
                  for a in small_engine.accs}
    finally:
        small_engine.lat_table.clear()
        small_engine.lat_table.update(saved)
    assert scores["big"] > scores["small"]


def test_supernet_picks_lighter_variant_when_late(small_engine):
    req = ServeRequest(rid=1, model="det",
                       tokens=np.zeros((1, 16), np.int32),
                       arrival=0.0, deadline=1e-6)     # hopeless deadline
    assert small_engine._pick_variant(req, now=0.0) == "det@v1"
    req2 = ServeRequest(rid=2, model="det",
                        tokens=np.zeros((1, 16), np.int32),
                        arrival=0.0, deadline=60.0)    # relaxed deadline
    assert small_engine._pick_variant(req2, now=0.0) == "det"


def test_end_to_end_run_with_cascade():
    accs = [VirtualAccelerator("a0", speed=1.0, power=1.0),
            VirtualAccelerator("a1", speed=0.5, power=0.5)]
    eng = ServingEngine(accs, adaptivity=True, frame_drop=True,
                        supernet_switch=False)
    parent = build_handle("gemma-2b", "parent", layers=1)
    child = build_handle("gemma-2b", "child", layers=1)
    for h in (parent, child):
        eng.register(h, np.zeros((1, 16), np.int32))
    q = RequestQueue(clock=lambda: 0.0)
    q.add_stream("parent", fps=6, batch=1, seq=16, vocab=64)
    q.add_stream("child", fps=6, batch=1, seq=16, vocab=64,
                 depends_on="parent", trigger_prob=1.0)
    report = eng.run(q, duration_s=2.0)
    assert report.frames > 0
    assert report.per_model.get("parent", {}).get("frames", 0) > 0
    # every completed parent triggers a child (prob 1.0)
    assert report.per_model.get("child", {}).get("frames", 0) > 0
    assert 0.0 <= report.dlv_rate <= 1.0


def test_serve_runs_a_deployment():
    """The entry point's serve(): every stream retires frames, the warm-up
    is recorded, and the newest retired request keeps the engine's logits."""
    from repro.launch.serve import Stream, serve
    dep = (Stream("det", "gemma-2b", fps=6, seq=16, layers=1),
           Stream("kws", "mamba2-130m", fps=6, seq=8, layers=1))
    run = serve(dep, duration_s=1.5, adaptivity=False)
    for st in dep:
        assert run.report.per_model[st.name]["frames"] > 0
        assert run.report.per_model[st.name]["uxcost"] >= 0.0
        assert run.engine.warmup_s[st.name] > 0.0
        req = run.engine.last_retired[st.name]
        assert req.result.shape == (1, st.seq, 128)
        assert not req.dropped


def test_queue_arrival_process_streams():
    """A Poisson stream drives the queue through the same ArrivalProcess
    objects the simulator consumes; draws are reproducible (crc32 seed)."""
    from repro.scenarios import Poisson

    def emitted():
        q = RequestQueue(clock=lambda: 0.0)
        q.add_stream("m", fps=100, batch=1, seq=4, vocab=8,
                     arrival=Poisson().to_config())
        return [r.arrival for r in q.poll(1.0)]

    ts = emitted()
    assert len(ts) > 10
    assert ts == emitted()                        # deterministic
    gaps = np.diff(ts)
    assert np.std(gaps) > 1e-4                    # genuinely non-periodic


def test_trace_replay_queue_feeds_recorded_arrivals():
    """A simulator-recorded trace replays through the serving queue."""
    from repro.core import build_scenario, dream_full
    from repro.core.simulator import Simulator

    sim = Simulator(build_scenario("AR_Call", 0.5), "4K_1WS2OS",
                    dream_full(), duration_s=1.0, seed=0, record=True)
    sim.run()
    expected = sim.trace.arrivals_by_model()

    q = TraceReplayQueue(clock=lambda: 0.0, trace=sim.trace)
    q.add_stream("kws_res8", fps=15, batch=1, seq=4, vocab=8)
    q.add_stream("translate_gnmt", fps=15, batch=1, seq=4, vocab=8,
                 depends_on="kws_res8", trigger_prob=1.0)
    out = q.poll(1.0)
    assert [r.arrival for r in out] == expected["kws_res8"]
    assert all(r.model == "kws_res8" for r in out)
    assert q.poll(1.0) == []                      # queue drains exactly once
    # dependents stay live (cascade-triggered, not replayed)
    assert len(q.trigger_dependents("kws_res8", now=0.5)) == 1


def test_request_queue_copies_arrival_instances():
    """Stateful arrival processes must never be shared between streams
    (same contract as Simulator._materialize_arrival)."""
    from repro.scenarios.arrivals import BurstyOnOff
    from repro.serving.engine import RequestQueue
    shared = BurstyOnOff(on_s=0.3, off_s=0.3, burst_factor=2.0)
    q = RequestQueue(clock=lambda: 0.0)
    q.add_stream("a", fps=10, batch=1, seq=8, vocab=16, arrival=shared)
    q.add_stream("b", fps=10, batch=1, seq=8, vocab=16, arrival=shared)
    assert q.streams["a"]["arrival"] is not q.streams["b"]["arrival"]
    assert q.streams["a"]["arrival"] is not shared
