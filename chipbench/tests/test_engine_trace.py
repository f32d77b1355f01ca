"""The engine-span readers and reductions: on hand-made span records,
and on a trace recorded on a v5e chip with the span records beside it."""
import gzip
import os
from types import SimpleNamespace

import pytest

from chipbench import engine_trace, run, trace
from repro.obs import load_jsonl

MS = 1e-3
#: the per-layer metrics that read the engine's spans
SPAN_METRICS = ("decide_us_per_dispatch", "hold_pct", "launch_ms_p50",
                "queue_wait_p95_ms", "stall_ms_per_s")


def rec(sid, kind, t0, t1, **attrs):
    return {"sid": sid, "kind": kind, "t0": t0, "t1": t1, "attrs": attrs}


def timeline():
    """One second of window: a poll and sweep; a held idle stretch with a
    poll inside; one dispatch of frame 0 (admitted at 0); a 389 ms stall
    before the next poll; then spans after the profiler started (1.0)."""
    recs = [
        rec(0, "serve.poll", 0.000, 0.001, frames=1),
        rec(1, "job", 0.000, 0.111, uid=0, model="kws", origin=0.0,
            parent=None, segs=[[0.1004, 0.110]], outcome="done", tail=True),
        rec(2, "serve.sweep", 0.001, 0.002),
        rec(3, "serve.idle", 0.002, 0.100, reason="hold", ready=1),
        rec(4, "serve.poll", 0.050, 0.051, frames=0),
        rec(5, "serve.decide", 0.1000, 0.1002, rid=0),
        rec(6, "serve.h2d", 0.1002, 0.1004, rid=0),
        rec(7, "serve.launch", 0.1004, 0.1010, rid=0),
        rec(8, "serve.wait", 0.1010, 0.1100, rid=0),
        rec(9, "serve.retire", 0.1100, 0.1110, rid=0),
        rec(10, "serve.poll", 0.111, 0.112, frames=0),
        rec(11, "serve.poll", 0.500, 0.501, frames=0),
        rec(12, "serve.idle", 0.501, 0.600, reason="no_frame", ready=0),
        rec(13, "serve.poll", 0.600, 0.601, frames=0),
        # after the profiler started: left out of every metric
        rec(14, "serve.poll", 1.000, 1.001, frames=1),
        rec(15, "job", 1.000, 1.3, uid=1, model="kws", origin=1.0,
            parent=None, segs=[[1.1, 1.2]], outcome="done", tail=True),
        rec(16, "serve.idle", 1.001, 1.5, reason="hold", ready=1),
        rec(17, "serve.decide", 1.5, 1.6, rid=1),
        rec(18, "serve.h2d", 1.6, 1.7, rid=1),
        rec(19, "serve.launch", 1.7, 1.8, rid=1),
        rec(20, "serve.poll", 1.9, 1.901, frames=0),
    ]
    return SimpleNamespace(spans={"records": recs, "counters": {}},
                           queue_until=1.0)


@pytest.mark.parametrize("name,want", [
    ("hold_pct", 100 * 0.098),
    ("decide_us_per_dispatch", 1e6 * (0.001 + 0.0002)),
    ("decide_us_per_dispatch.overload", 1e6 * (0.001 + 0.0002)),
    ("queue_wait_p95_ms", 100.4),
    ("launch_ms_p50", 0.2 + 0.6),
    ("stall_ms_per_s", 389.0),
])
def test_reader_by_hand(name, want):
    assert run.reader(name)(timeline()) == pytest.approx(want, rel=1e-9)


@pytest.mark.parametrize("name", SPAN_METRICS
                         + ("decide_us_per_dispatch.overload",))
def test_reader_without_spans(name):
    """A run whose engine took no ``obs`` (a parent commit) reads None."""
    parent = SimpleNamespace(queue_until=1.0)
    assert run.reader(name)(parent) is None


def test_percentiles_over_several_dispatches():
    """Nearest-rank p95 of the queue waits; the median launch."""
    recs = []
    for i, (admit, launch, h2d, call) in enumerate(
            [(0.0, 0.010, 1, 3), (0.1, 0.105, 2, 2), (0.2, 0.230, 1, 5)]):
        recs += [rec(10 * i, "job", admit, launch + 0.05, uid=i),
                 rec(10 * i + 1, "serve.decide", launch - 0.002,
                     launch - h2d * MS, rid=i),
                 rec(10 * i + 2, "serve.h2d", launch - h2d * MS, launch,
                     rid=i),
                 rec(10 * i + 3, "serve.launch", launch, launch + call * MS,
                     rid=i)]
    view = SimpleNamespace(spans={"records": recs}, queue_until=1.0)
    assert run.reader("queue_wait_p95_ms")(view) == pytest.approx(30.0)
    assert run.reader("launch_ms_p50")(view) == pytest.approx(4.0)


def test_self_time_nests():
    spans = [("serve.idle:hold", 0, 10), ("serve.poll", 2, 3),
             ("serve.sweep", 3, 4), ("serve.decide", 11, 12),
             ("a", 20, 25), ("b", 24, 27)]
    got = engine_trace.self_time(spans)
    assert got == {"serve.idle:hold": [(0, 2), (4, 10)],
                   "serve.poll": [(2, 3)], "serve.sweep": [(3, 4)],
                   "serve.decide": [(11, 12)],
                   "a": [(20, 24)], "b": [(24, 25)]}   # b cut at a's end
    by = engine_trace.attribute([(0, 12)], spans[:4])
    assert by == {"serve.idle:hold": 8, "serve.poll": 1, "serve.sweep": 1,
                  "serve.decide": 1, "unspanned": 1}


def test_stalls():
    recs = timeline().spans["records"]
    got = engine_trace.stalls(recs)         # the whole window, longest first
    assert [round(s["s"], 9) for s in got] == [0.9, 0.4, 0.389]
    late = got[0]                           # 1.0 to 1.9
    assert late["by"] == pytest.approx({
        "serve.idle:hold": 0.499, "serve.poll": 0.001,
        "serve.decide": 0.1, "serve.h2d": 0.1, "serve.launch": 0.1,
        "unspanned": 0.1})
    early = got[2]
    assert early["at"] == 0.111
    assert early["by"] == pytest.approx({"serve.poll": 0.001,
                                         "unspanned": 0.388})
    assert engine_trace.stalls(recs, top=1) == got[:1]


def test_tie_pairs_by_sid():
    recs = timeline().spans["records"]
    host = [(r["kind"], r["t0"] * 1e9 + 5e6 + (i % 3) * 1e3, 0.0, r["sid"])
            for i, r in enumerate(recs) if r["kind"].startswith("serve.")]
    host.append(("serve.poll", 0.0, 0.0, 999))       # no such record
    host.append(("serve.wait", 0.0, 0.0, 0))         # sid of another kind
    host.append(("poll", 0.0, 0.0, None))
    t = engine_trace.tie(host, recs)
    assert t["n"] == len(host) - 3
    assert t["ns"] == pytest.approx(5e6 + 1e3)
    assert t["spread_us"] <= 2.0



# ---------------------------------------------------------------------------
# a trace recorded on a v5e chip (``record_engine_trace.py``): the
# ``tiny_call`` cell served with the engine's spans on, the whole window
# under the profiler
# ---------------------------------------------------------------------------

DATA = os.path.join(os.path.dirname(__file__), "data")


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("engine") / "engine.xplane.pb"
    with gzip.open(os.path.join(DATA, "engine.xplane.pb.gz"), "rb") as f:
        path.write_bytes(f.read())
    recs = load_jsonl(os.path.join(DATA, "engine.spans.jsonl"))
    summary = trace.reduce(str(path))
    return summary, recs, engine_trace.idle_by_span(summary, recs)


def test_idle_by_span_sums_to_the_window_idle(recorded):
    summary, _, got = recorded
    idle = summary.window_s - summary.busy_s
    assert sum(b - a for a, b in summary.gaps) * trace.NS == pytest.approx(
        idle, rel=1e-9)
    assert sum(got["idle"].values()) == pytest.approx(idle, rel=1e-9)


def test_every_engine_annotation_pairs_by_sid(recorded):
    summary, recs, got = recorded
    kinds = {r["sid"]: r["kind"] for r in recs}
    ours = [(n, sid) for n, _, _, sid in summary.serve]
    assert ours and all(kinds[sid] == n for n, sid in ours)
    assert got["tie"]["n"] == len(ours)
    # the annotation opens within microseconds of the span
    assert got["tie"]["spread_us"] < 20.0


def test_idle_lies_in_engine_spans(recorded):
    summary, _, got = recorded
    idle, idle_s = got["idle"], summary.window_s - summary.busy_s
    assert idle["unspanned"] < 0.05 * idle_s
    assert idle["serve.idle:no_frame"] > 0.3 * idle_s
    for kind in ("serve.h2d", "serve.launch", "serve.wait", "serve.poll"):
        assert idle[kind] > 0, kind
