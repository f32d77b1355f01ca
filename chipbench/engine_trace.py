#!/usr/bin/env python3
"""The serving engine's own spans, tied to a profiler trace's clock.

``ServingEngine(obs=...)`` records ``serve.*`` spans around each phase of
its loop on its own clock (seconds since ``run`` began); while one is open
it is also a host annotation of the same name carrying its ``sid``. This
module reads the two together:

* ``tie``: the engine's clock against the trace's host clock, the median
  over the spans paired by ``sid`` of the host start less the span start;
* ``idle_by_span``: the device's idle time in the traced window (the
  window, device-to-host offset and busy intervals found as
  ``trace.reduce`` finds them), each idle stretch given to the innermost
  engine span open then (``serve.idle`` split by its ``reason``), the rest
  to ``unspanned``;
* ``stalls``: the longest gaps between consecutive ``serve.poll`` starts,
  over the whole window, each with the seconds of every span kind in it.

The per-layer readers (``metrics/hold_pct.py`` and its siblings) read a
run's ``spans``: ``{"records": [...], "counters": {...}}``, the engine's
span records and counter snapshot; a run without them reads None.

Run as a script, it makes one ``--trace 1`` run of a cell with the
engine's spans and counters on for the whole window, and prints
``run.py``'s result line with the span metrics added to ``metrics`` and a
``spans`` key (``idle``, ``stalls``) before ``checks``:

    python3 chipbench/engine_trace.py --workload <cell> --seed <n> --seconds <s> [--dump spans.jsonl.gz]
"""
from __future__ import annotations

import os
import statistics
import sys
from collections import defaultdict

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench.trace import _clip, _overlap, _subtract, _union  # noqa: E402

NS = 1e-9
STALL_S = 0.1          # a poll-to-poll gap longer than a dispatch turn
OBS = {"spans": True, "metrics": True, "profile": False}
#: the span metrics (``metrics/<name>.py``) and their units
METRICS = {"hold_pct": "%", "decide_us_per_dispatch": "us",
           "queue_wait_p95_ms": "ms", "launch_ms_p50": "ms",
           "stall_ms_per_s": "ms/s"}


# ---------------------------------------------------------------------------
# span records
# ---------------------------------------------------------------------------


def loop_spans(records) -> list:
    """``(label, t0, t1)`` of the engine loop's spans: every ``serve.*``
    interval (not the ``job`` spans, which overlap one another, nor the
    instantaneous ``serve.drop``), labelled by kind, ``serve.idle`` as
    ``serve.idle:<reason>``."""
    return [(r["kind"] + (f":{r['attrs']['reason']}"
                          if r["kind"] == "serve.idle" else ""),
             r["t0"], r["t1"]) for r in records
            if r["kind"].startswith("serve.") and r["t1"] > r["t0"]]


def self_time(spans) -> dict:
    """label -> sorted disjoint intervals in which a span of that label is
    the innermost one open. Spans nest or follow one another; a child that
    outlasts its parent is cut at the parent's end."""
    out = defaultdict(list)
    stack: list = []                     # [label, end, resumes at]

    def close_until(t):
        while stack and stack[-1][1] <= t:
            lab, end, cur = stack.pop()
            if end > cur:
                out[lab].append((cur, end))

    for lab, a, b in sorted(spans, key=lambda s: (s[1], -s[2])):
        close_until(a)
        if stack:
            top = stack[-1]
            b = min(b, top[1])
            if a > top[2]:
                out[top[0]].append((top[2], a))
            top[2] = max(top[2], b)
        stack.append([lab, b, a])
    close_until(float("inf"))
    return {k: sorted(v) for k, v in out.items()}


def attribute(segs, spans) -> dict:
    """Seconds of the sorted disjoint ``segs`` (same unit as ``spans``) by
    the innermost span open, and ``unspanned`` for the rest."""
    by = {}
    total = sum(b - a for a, b in segs)
    for lab, pieces in self_time(spans).items():
        cov = _overlap(segs, pieces)
        if cov:
            by[lab] = cov
    by["unspanned"] = max(total - sum(by.values()), 0.0)
    return dict(sorted(by.items(), key=lambda kv: -kv[1]))


def stalls(records, gap_s: float = STALL_S, top: int = 10) -> list:
    """The ``top`` longest gaps over ``gap_s`` between consecutive
    ``serve.poll`` starts: ``{"at", "s", "by"}``, ``by`` the seconds of
    each span kind inside the gap (innermost span), longest first."""
    starts = sorted(r["t0"] for r in records if r["kind"] == "serve.poll")
    gaps = sorted(((b - a, a, b) for a, b in zip(starts, starts[1:])
                   if b - a > gap_s), reverse=True)[:top]
    spans = loop_spans(records)
    return [{"at": a, "s": s, "by": attribute([(a, b)], spans)}
            for s, a, b in gaps]


# ---------------------------------------------------------------------------
# the readers' view of a run
# ---------------------------------------------------------------------------


def window(run, kind: str):
    """Records of ``kind`` that start before ``run.queue_until`` (the
    profiler's start in a traced run), or None where the run carries no
    engine spans."""
    sp = getattr(run, "spans", None)
    if sp is None:
        return None
    return [r for r in sp["records"]
            if r["kind"] == kind and r["t0"] < run.queue_until]


# ---------------------------------------------------------------------------
# the trace
# ---------------------------------------------------------------------------


def _load(path: str):
    """(host events ``(name, start, end, sid)``, device 0's XLA modules
    and ops ``(name, start, end)``), in nanoseconds."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    host, device = [], None
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and device is None:
            lines = {ln.name: [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                               for e in ln.events] for ln in plane.lines}
            device = (lines.get("XLA Modules", []), lines.get("XLA Ops", []))
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                for e in ln.events:
                    sid = (dict(e.stats).get("sid")
                           if e.name.startswith("serve.") else None)
                    host.append((e.name, e.start_ns,
                                 e.start_ns + e.duration_ns, sid))
    if device is None:
        raise ValueError(f"{path}: no TPU device plane")
    return host, device


def tie(host, records) -> dict:
    """The engine clock's offset to the host clock (ns), the median over
    the ``serve.*`` host events paired with records by ``sid``; ``n``
    pairs and the interquartile spread of the differences (us)."""
    t0 = {r["sid"]: (r["kind"], r["t0"]) for r in records}
    diffs = sorted(a - t0[sid][1] / NS for n, a, _, sid in host
                   if sid is not None and t0.get(sid, ("",))[0] == n)
    if len(diffs) < 2:
        raise ValueError("fewer than two engine spans in the trace")
    q = statistics.quantiles(diffs, n=4)
    return {"ns": statistics.median(diffs), "n": len(diffs),
            "spread_us": (q[2] - q[0]) * 1e-3}


def idle_by_span(path: str, records) -> dict:
    """Device-idle seconds of the traced window by innermost engine span
    (``idle``), with the window, its idle total and the clock tie."""
    host, (modules, ops) = _load(path)
    polls = sorted(a for n, a, _, _ in host if n == "poll")
    marks = [b for n, _, b, _ in host
             if n in ("poll", "trigger") or n.startswith("dispatch:")]
    if not polls:
        raise ValueError(f"{path}: no poll annotation")
    # device-to-host offset and window exactly as trace.reduce takes them
    executes = sorted(a for n, a, _, _ in host
                      if n == "PJRT_LoadedExecutable_Execute")
    mods = sorted(modules, key=lambda e: e[1])
    offset = max((x - m[1] for x, m in zip(reversed(executes),
                                            reversed(mods))), default=0.0)
    w0, w1 = polls[0], max(marks)
    w1 = max([w1] + [b + offset for _, a, b in mods if a + offset < w1])
    busy = _union(_clip([(a + offset, b + offset) for _, a, b in ops + mods],
                        w0, w1))
    gaps = _subtract([(w0, w1)], busy)
    t = tie(host, records)
    spans = [(lab, a / NS + t["ns"], b / NS + t["ns"])
             for lab, a, b in loop_spans(records)]
    idle = {k: v * NS for k, v in attribute(gaps, spans).items()}
    return {"idle": idle, "window_s": (w1 - w0) * NS,
            "idle_s": sum(b - a for a, b in gaps) * NS, "tie": t}


# ---------------------------------------------------------------------------
# one traced run with the engine's spans on
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    import argparse
    import gzip
    import json
    from types import SimpleNamespace

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--dump", help="write the span records here (gzip "
                    "JSONL)")
    args = ap.parse_args(argv)
    from chipbench import run, trace
    from repro.launch.cache import enable_compile_cache
    from repro.obs import Obs

    bench, work, cfg = run.load_cell(args.workload)
    chips = next(w["chips"] for w in bench["workloads"]
                 if w["name"] == args.workload)
    try:
        peak, devices = run.device_info(chips)
    except run.NoChip as e:
        print(str(e), file=sys.stderr)
        return 3
    enable_compile_cache()
    run.persist_everything()

    # run_cell as the benchmark makes it, with the engine's obs on and the
    # trace read for the spans before run_cell deletes it
    got = {}
    make_engine, reduce = run.make_engine, trace.reduce

    def make_engine_obs(cfg, handles, seed):
        engine, warmup_s = make_engine(cfg, handles, seed)
        engine.obs = Obs.make(OBS)
        got["obs"] = engine.obs
        return engine, warmup_s

    def reduce_and_tie(path, poll_times=None, outstanding=None, **kw):
        got["queue_until"] = poll_times[0]
        got["trace"] = idle_by_span(path, got["obs"].tracer.records)
        return reduce(path, poll_times=poll_times, outstanding=outstanding,
                      **kw)

    run.make_engine, trace.reduce = make_engine_obs, reduce_and_tie
    out = run.run_cell(args.workload, work, cfg, bench, seed=args.seed,
                       seconds=args.seconds, traced=True, peak=peak,
                       devices=devices)
    records = got["obs"].tracer.records
    view = SimpleNamespace(
        spans={"records": records,
               "counters": got["obs"].metrics.snapshot()},
        queue_until=got.get("queue_until", args.seconds))
    for name, unit in METRICS.items():
        value = run.reader(name)(view)
        if value is not None:
            out["metrics"][name] = {"value": value, "unit": unit}
    checks = out.pop("checks")
    out["spans"] = {"idle": got.get("trace", {}).get("idle"),
                    "tie": got.get("trace", {}).get("tie"),
                    "stalls": stalls(records)}
    out["checks"] = checks
    if args.dump:
        with gzip.open(args.dump, "wt") as f:
            for r in records:
                f.write(json.dumps(r) + "\n")
    for name, (value, limit) in checks.items():
        print(f"check {name}: {value} (limit {limit})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
