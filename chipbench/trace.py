"""Reduce a profiler trace (``.xplane.pb``) of a served window to numbers.

What the reduction reads:

* device planes (``/device:TPU:<n>``): the ``XLA Modules`` line (one event
  per program run) and the ``XLA Ops`` line (the operations inside it;
  a loop's operation contains its body's);
* the host plane (``/host:CPU``): the harness's own annotations,
  ``dispatch:<stream>`` around each handle call (it only enqueues),
  ``poll`` and ``trigger`` around the queue's two calls, and the
  runtime's ``PJRT_LoadedExecutable_Execute`` events;
  the engine's ``serve.*`` annotations, each with its span's ``sid``, are
  kept for ``engine_trace.idle_by_span``.

The device clock of a trace is offset from the host's by a millisecond
or two. A program cannot start before the host asked for it, so the
offset taken is the least one under which no program run starts before
its ``PJRT_LoadedExecutable_Execute`` (runs and executes paired in order,
counted from the end of the trace, where neither is cut off).

The window is the host interval from the first ``poll`` to the end of
the last harness annotation, or of the program run under way then. In it: busy is the union of the device's
operation intervals, idle the rest. Each idle stretch is attributed to
what the host was doing meanwhile: inside ``dispatch:<stream>``, inside
``poll`` or ``trigger``, and otherwise ``no_work`` where no frame was
outstanding (the caller passes those intervals) or ``engine_loop`` where
one was (the engine's own code: MapScore, bookkeeping, the host-to-device
copy, its 1 ms sleep).

Program runs are given to streams by pairing them in order with the
``dispatch:`` annotations, again from the end.

Every operation's self time (its duration less its direct children's)
is kept in ``ops`` under ``<stream>:<op>``, beside the op's kind read
from its HLO text: the opcode, and for a custom call
``custom-call:<custom_call_target>`` (a Pallas kernel on a TPU is
``custom-call:tpu_custom_call``). ``top_ops`` is the head of that table.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field

NS = 1e-9


@dataclass
class Summary:
    window_s: float
    busy_s: float                      # mean over device planes
    offset_s: float                    # host minus device clock
    runs: dict = field(default_factory=dict)    # stream -> [n, device_s]
    idle_by: dict = field(default_factory=dict)  # host activity -> s
    top_ops: list = field(default_factory=list)  # [name, self seconds]
    ops: dict = field(default_factory=dict)      # name -> [self s, kind]
    gaps: list = field(default_factory=list)     # device 0 idle, host ns
    serve: list = field(default_factory=list)    # engine annotations:
    #                                  (name, start ns, end ns, sid)


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def _subtract(segs, cover):
    """Pieces of the sorted disjoint ``segs`` outside the sorted disjoint
    ``cover``."""
    out, j = [], 0
    for a, b in segs:
        while j < len(cover) and cover[j][1] <= a:
            j += 1
        cur, k = a, j
        while k < len(cover) and cover[k][0] < b:
            if cover[k][0] > cur:
                out.append((cur, cover[k][0]))
            cur = max(cur, cover[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def _overlap(segs, cover):
    """Length of the overlap of two sorted disjoint interval lists."""
    tot, i, j = 0.0, 0, 0
    while i < len(segs) and j < len(cover):
        a, b = segs[i]
        c, d = cover[j]
        tot += max(0.0, min(b, d) - max(a, c))
        if b < d:
            i += 1
        else:
            j += 1
    return tot


def _events(line):
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def _short(hlo: str) -> str:
    return hlo.split(" ", 1)[0].lstrip("%")


_OPCODE = re.compile(r" ([a-z][a-z0-9-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]*)"')


def op_kind(hlo: str) -> str:
    """The opcode of one HLO instruction's text (``%name = <shape>
    opcode(operands), ...``), with ``:<custom_call_target>`` for a custom
    call; ``?`` where the text holds no instruction."""
    m = _OPCODE.search(hlo, hlo.find("=") + 1)
    if m is None:
        return "?"
    if m.group(1) != "custom-call":
        return m.group(1)
    target = _TARGET.search(hlo, m.end())
    return f"custom-call:{target.group(1) if target else '?'}"


def _self_times(ops, mods, owner, self_by, kinds) -> None:
    """Add each operation's self time (its duration less its direct
    children's) to ``self_by``, under ``<stream>:<operation>``, and its
    kind to ``kinds``."""
    import bisect
    starts = [m[1] for m in mods]
    stack: list = []               # [end, label, self]

    def close(rec):
        self_by[rec[1]] += rec[2] * NS

    for name, a, b in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][0] <= a:
            close(stack.pop())
        if stack:
            stack[-1][2] -= min(b, stack[-1][0]) - a
        i = bisect.bisect_right(starts, a) - 1
        stream = owner.get(mods[i][1], "?") if i >= 0 and a < mods[i][2] \
            else "?"
        label = f"{stream}:{_short(name)}"
        if label not in kinds:
            kinds[label] = op_kind(name)
        stack.append([b, label, b - a])
    while stack:
        close(stack.pop())


def reduce(path: str, poll_times=None, outstanding=None,
           top: int = 10) -> Summary:
    """Summarise one trace.

    ``poll_times`` are the queue's clock (seconds) at the traced ``poll``
    calls, in order, and ``outstanding`` the queue-clock intervals in
    which some frame was due and unfinished. With both, idle time outside
    the harness's spans is split into ``no_work`` and ``engine_loop``;
    the two clocks are tied by pairing ``poll_times`` with the ``poll``
    annotations from the end.
    """
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host, devices, serve = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: _events(ln) for ln in plane.lines}
            devices.append((lines.get("XLA Modules", []),
                            lines.get("XLA Ops", [])))
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                host.extend(_events(ln))
                serve.extend((e.name, e.start_ns,
                              e.start_ns + e.duration_ns,
                              dict(e.stats).get("sid"))
                             for e in ln.events if e.name.startswith("serve."))
    if not devices:
        raise ValueError(f"{path}: no TPU device plane")

    polls = sorted((a, b) for n, a, b in host if n == "poll")
    marks = sorted((n, a, b) for n, a, b in host
                   if n in ("poll", "trigger") or n.startswith("dispatch:"))
    if not polls:
        raise ValueError(f"{path}: no poll annotation")
    w0 = polls[0][0]
    executes = sorted(a for n, a, _ in host
                      if n == "PJRT_LoadedExecutable_Execute")
    dispatches = sorted((a, n[len("dispatch:"):]) for n, a, _ in host
                        if n.startswith("dispatch:"))

    modules0 = sorted(devices[0][0], key=lambda e: e[1])
    pairs = list(zip(reversed(executes), reversed(modules0)))
    offset = max((x - m[1] for x, m in pairs), default=0.0)
    # the window closes with the last annotation, or with the program run
    # that was under way then
    w1 = max(b for _, _, b in marks)
    w1 = max([w1] + [b + offset for _, a, b in modules0 if a + offset < w1])

    busy_total = 0.0
    runs: dict = defaultdict(lambda: [0, 0.0])
    idle_by: dict = defaultdict(float)
    self_by: dict = defaultdict(float)
    kinds: dict = {}
    for di, (modules, ops) in enumerate(devices):
        mods = sorted(((n, a + offset, b + offset) for n, a, b in modules),
                      key=lambda e: e[1])
        shifted = [(n, a + offset, b + offset) for n, a, b in ops]
        busy = _union(_clip([(a, b) for _, a, b in shifted + mods], w0, w1))
        busy_total += sum(b - a for a, b in busy)
        owner = {}
        for (_, stream), mod in zip(reversed(dispatches), reversed(mods)):
            owner[mod[1]] = stream
        mods_in = [m for m in mods if w0 <= m[1] < w1]
        for n, a, b in mods_in:
            r = runs[owner.get(a, "?")]
            r[0] += 1
            r[1] += (b - a) * NS
        if di == 0:
            gaps = _subtract([(w0, w1)], busy)
            by_label = defaultdict(list)
            for n, a, b in marks:
                by_label[n].append((a, b))
            for label, spans in by_label.items():
                cov = _overlap(gaps, _union(spans))
                if cov:
                    idle_by[label] += cov * NS
            rest = _subtract(gaps, _union([(a, b) for _, a, b in marks]))
            if poll_times and outstanding is not None:
                ties = [p[0] * NS - t for p, t in
                        zip(reversed(polls), reversed(poll_times))]
                tie = sorted(ties)[len(ties) // 2]
                work = _union([((a + tie) / NS, (b + tie) / NS)
                               for a, b in outstanding])
                busy_host = _overlap(rest, work)
                idle_by["engine_loop"] += busy_host * NS
                idle_by["no_work"] += (sum(b - a for a, b in rest)
                                       - busy_host) * NS
            else:
                idle_by["engine_loop"] += sum(b - a for a, b in rest) * NS
            _self_times([o for o in shifted if o[1] >= w0 and o[2] <= w1],
                        mods, owner, self_by, kinds)
    n_dev = len(devices)
    ops = sorted(self_by.items(), key=lambda kv: -kv[1])
    return Summary(
        window_s=(w1 - w0) * NS, busy_s=busy_total * NS / n_dev,
        offset_s=offset * NS, runs={k: [v[0], v[1]] for k, v in runs.items()},
        idle_by=dict(sorted(idle_by.items(), key=lambda kv: -kv[1])),
        top_ops=[[k, v] for k, v in ops[:top]],
        ops={k: [v, kinds[k]] for k, v in ops}, gaps=gaps, serve=serve)
