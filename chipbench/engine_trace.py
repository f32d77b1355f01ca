"""The serving engine's own spans, tied to a profiler trace's clock.

``ServingEngine(obs=...)`` records ``serve.*`` spans around each phase of
its loop on its own clock (seconds since ``run`` began); while one is open
it is also a host annotation of the same name carrying its ``sid``. This
module reads the two together:

* ``tie``: the engine's clock against the trace's host clock, the median
  over the spans paired by ``sid`` of the host start less the span start;
* ``idle_by_span``: the device's idle time in the traced window (the
  idle stretches of device 0 that ``trace.reduce`` finds), each stretch
  given to the innermost engine span open then (``serve.idle`` split by
  its ``reason``), the rest to ``unspanned``;
* ``stalls``: the longest gaps between consecutive ``serve.poll`` starts,
  over the whole window, each with the seconds of every span kind in it.

The per-layer readers (``metrics/hold_pct.py`` and its siblings) read a
run's ``spans``: ``{"records": [...], "counters": {...}}``, the engine's
span records and counter snapshot, which ``run.py`` sets in a ``--trace
1`` run (the engine made with ``OBS``); a run without them reads None.
"""
from __future__ import annotations

import statistics
from collections import defaultdict

from chipbench.trace import _overlap

NS = 1e-9
STALL_S = 0.1          # a poll-to-poll gap longer than a dispatch turn
#: the engine's ``obs`` in a traced run: spans and counters, no profiler
OBS = {"spans": True, "metrics": True, "profile": False}


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


def idle_by_span(summary, records) -> dict:
    """Device-idle seconds of the traced window by innermost engine span
    (``idle``) and the clock tie, from ``trace.reduce``'s summary of the
    trace (its idle ``gaps`` and ``serve`` annotations)."""
    t = tie(summary.serve, records)
    spans = [(lab, a / NS + t["ns"], b / NS + t["ns"])
             for lab, a, b in loop_spans(records)]
    idle = {k: v * NS for k, v in attribute(summary.gaps, spans).items()}
    return {"idle": idle, "tie": t}
