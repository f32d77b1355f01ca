"""Golden-trace regression corpus: replay each recorded fleet trace and
require digest-exact results.

The corpus (``tests/golden/``) holds six small recorded fleet runs —
whole-stream, stage-split, SLO-overload, lifecycle-churn, contended
links, tuned-score — generated by ``tests/golden/regen.py``.  For each,
this suite replays the trace through ``FleetSimulator(replay=...)`` and
checks the sha256 of the replayed result (UXCost, frames, placements,
pipeline latency, tier accounting, weights — floats serialized via
``repr``, i.e. exact) against the committed manifest.

A digest mismatch means the simulator no longer reproduces a run it
once produced: either determinism broke, or a behavior change landed
without the corpus being intentionally regenerated and reviewed.
"""
from __future__ import annotations

import hashlib
import json
import os

import pytest

from repro.cluster import FleetSimulator
from repro.cluster import trace as ftrace

GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "golden")

with open(os.path.join(GOLDEN_DIR, "manifest.json")) as _f:
    MANIFEST = json.load(_f)["corpus"]


def _digest(r, fs) -> str:
    # mirror of tests/golden/regen.py::result_digest (kept in sync so the
    # corpus can be regenerated by running that script)
    payload = {
        "uxcost": repr(r.uxcost),
        "frames": r.frames,
        "dlv_rate": repr(r.dlv_rate),
        "norm_energy": repr(r.norm_energy),
        "stream_seconds": repr(r.stream_seconds),
        "pipeline_latency_s": repr(r.pipeline_latency_s),
        "pipe_frames": r.pipe_frames,
        "migrations": r.migrations,
        "departures": r.departures,
        "jobs_purged": r.jobs_purged,
        "swaps": r.swaps,
        "rejections": r.rejections,
        "tier_dlv": {str(k): repr(v)
                     for k, v in sorted(r.tier_dlv.items())},
        "weights": ([repr(w) for w in r.weights]
                    if r.weights is not None else None),
        "stream_node": {str(k): v
                        for k, v in sorted(fs.stream_node.items())},
        "stage_node": {f"{k[0]}:{k[1]}": v
                       for k, v in sorted(fs.stage_node.items())},
    }
    blob = json.dumps(payload, sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()


@pytest.mark.parametrize("name", sorted(MANIFEST))
def test_golden_trace_replays_digest_exact(name):
    entry = MANIFEST[name]
    path = os.path.join(GOLDEN_DIR, f"{name}.trace.json")
    with open(path) as f:
        text = f.read()
    # the committed trace file itself must be pristine
    assert hashlib.sha256(text.encode()).hexdigest() \
        == entry["trace_sha256"], f"{name}: trace file modified"
    fs = FleetSimulator(replay=ftrace.loads(text))
    r = fs.run()
    assert r.frames == entry["frames"], \
        f"{name}: frames {r.frames} != golden {entry['frames']}"
    assert r.uxcost == entry["uxcost"], \
        f"{name}: uxcost {r.uxcost!r} != golden {entry['uxcost']!r}"
    assert _digest(r, fs) == entry["result_sha256"], \
        f"{name}: replayed result digest diverged from the manifest"


def test_corpus_covers_every_dimension():
    kinds = {e["kind"] for e in MANIFEST.values()}
    assert kinds == {"whole", "split", "slo", "lifecycle",
                     "lifecycle_uncontended", "tuned", "genai"}
    assert len(MANIFEST) >= 7
