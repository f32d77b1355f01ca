#!/usr/bin/env python
"""Regenerate the golden fleet-trace corpus.

Each corpus entry is a small recorded fleet run covering one placement /
scheduling dimension; ``tests/test_golden_traces.py`` replays every
trace and requires the digest of the replayed result to match the
manifest EXACTLY.  The corpus pins two contracts at once:

  * determinism — replaying a recorded trace reproduces the run
    bit-for-bit on any machine, forever;
  * representation stability — the trace format and the vectorized
    fast paths must keep producing these exact results (any diff in
    placements, UXCost, pipeline latency or tier accounting changes
    the digest).

Regenerate (ONLY after an intentional, reviewed behavior change):

    PYTHONPATH=src python tests/golden/regen.py

After a library upgrade that moves results at ulp level, keep the
committed traces and refresh only the replayed digests and UXCosts
(first confirm with ``tests/test_vectorized_equiv.py`` that the scalar
and vectorized engines still agree):

    PYTHONPATH=src python tests/golden/regen.py --digests-only

The manifest records the numpy and jax versions the digests were taken
with under ``"stack"``; the entries live under ``"corpus"``.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import platform
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                os.pardir, os.pardir, "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

GOLDEN_DIR = os.path.dirname(os.path.abspath(__file__))

#: corpus entries: name -> (scenario kind for tests.test_vectorized_equiv
#: .build_scenario, seed).  Kinds reuse the differential harness's
#: scenario builders so the corpus and the equivalence suite always
#: exercise the same code paths.
CORPUS = {
    "whole": ("whole", 11),
    "stage_split": ("split", 12),
    "slo_overload": ("slo", 13),
    "lifecycle_churn": ("lifecycle_uncontended", 14),
    "contended_links": ("lifecycle", 15),
    "tuned_score": ("tuned", 16),
    "genai_mixed": ("genai", 17),
}


def build(kind: str, seed: int):
    from test_vectorized_equiv import build_scenario
    from repro.cluster import TransferModel
    if kind == "lifecycle_uncontended":
        # lifecycle churn over uncontended (infinite-bandwidth) links:
        # isolates departure/rejoin bookkeeping from link queueing
        fscn, kw = build_scenario("lifecycle", seed)
        kw["transfer"] = TransferModel()
        return fscn, kw
    return build_scenario(kind, seed)


def result_digest(r, fs) -> str:
    """Canonical digest of a fleet result: every float serialized via
    repr (shortest round-trip form — exact), keys sorted."""
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


def stack_versions() -> dict:
    import jax
    import numpy
    return {"python": platform.python_version(), "numpy": numpy.__version__,
            "jax": jax.__version__}


def write_manifest(corpus: dict) -> None:
    mpath = os.path.join(GOLDEN_DIR, "manifest.json")
    with open(mpath, "w") as f:
        json.dump({"stack": stack_versions(), "corpus": corpus}, f,
                  indent=1, sort_keys=True)
        f.write("\n")
    print(f"golden: manifest -> {mpath}")


def refresh_digests() -> None:
    """Replay every committed trace; rewrite only result_sha256/uxcost."""
    from repro.cluster import FleetSimulator
    from repro.cluster import trace as ftrace
    with open(os.path.join(GOLDEN_DIR, "manifest.json")) as f:
        corpus = json.load(f)["corpus"]
    for name, entry in corpus.items():
        with open(os.path.join(GOLDEN_DIR, f"{name}.trace.json")) as f:
            text = f.read()
        assert hashlib.sha256(text.encode()).hexdigest() \
            == entry["trace_sha256"], f"{name}: trace file modified"
        fs = FleetSimulator(replay=ftrace.loads(text))
        r = fs.run()
        assert r.frames == entry["frames"], (name, r.frames)
        print(f"golden: {name:16s} uxcost {entry['uxcost']!r} -> "
              f"{r.uxcost!r}")
        entry["result_sha256"] = result_digest(r, fs)
        entry["uxcost"] = r.uxcost
    write_manifest(corpus)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--digests-only", action="store_true",
                    help="keep the committed traces; refresh only the "
                         "replayed result digests and UXCosts")
    if ap.parse_args().digests_only:
        refresh_digests()
        return
    from repro.cluster import FleetSimulator
    from repro.cluster import trace as ftrace
    corpus = {}
    for name, (kind, seed) in CORPUS.items():
        fscn, kw = build(kind, seed)
        policy = kw.pop("policy")
        kw["record"] = True
        fs = FleetSimulator(fscn, policy, **kw)
        r = fs.run()
        text = ftrace.dumps(r.trace)
        path = os.path.join(GOLDEN_DIR, f"{name}.trace.json")
        with open(path, "w") as f:
            f.write(text)
        corpus[name] = {
            "kind": kind,
            "seed": seed,
            "trace_sha256": hashlib.sha256(text.encode()).hexdigest(),
            "result_sha256": result_digest(r, fs),
            "uxcost": r.uxcost,
            "frames": r.frames,
        }
        print(f"golden: {name:16s} {len(text):7d} bytes  "
              f"frames={r.frames:<5d} uxcost={r.uxcost:.4f}")
    write_manifest(corpus)


if __name__ == "__main__":
    main()
