"""Multi-model RTMM serving engine with DREAM (MapScore) dispatch.

This is the production face of the paper: the *same* MapScore computation
that Level-1 validates in simulation drives dispatch of real JAX model
executions here. The engine owns:

  * a set of registered models (any ArchConfig; jitted forward per model),
  * virtual accelerator slices (on a real pod: disjoint mesh slices; on the
    CPU dev box: time-sliced executors with per-slice speed factors) with
    a measured-latency table per (model, slice) — the "offline cost model"
    input of Figure 4, here calibrated by direct measurement,
  * a real-time request queue (periodic frames, FPS targets, deadlines,
    model-cascade dependencies),
  * the four DREAM engines: MapScore calculator, frame-drop, adaptivity
    ((alpha, beta) UXCost feedback), and job assignment/dispatch,
  * straggler mitigation: jobs whose wall-clock exceeds a p99 watermark are
    re-dispatched to the next-best slice (MapScore already ranks them),
  * observability (``obs=``, the :class:`repro.obs.Obs` bundle the fleet
    takes): ``serve.*`` spans around each phase of the loop and a ``job``
    span per frame on the engine's clock, each loop span also a profiler
    annotation carrying its ``sid``, and dispatch / switch / turn counters.

Energy on the dev box is modeled as latency x slice power weight (real
deployments plug in measured per-accelerator power).
"""
from __future__ import annotations

import itertools
import time
import zlib
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ArchConfig
from repro.core.mapscore import MapScoreParams
from repro.core.uxcost import WindowStats, norm_energy, rate_dlv, uxcost
from repro.obs import Obs


# ---------------------------------------------------------------------------
# requests
# ---------------------------------------------------------------------------


@dataclass
class ServeRequest:
    rid: int
    model: str
    tokens: np.ndarray                  # [B, S] prompt batch
    arrival: float
    deadline: float
    depends_on: Optional[str] = None
    done: bool = False
    dropped: bool = False
    completion: Optional[float] = None
    result: Any = None
    energy: float = 0.0

    @property
    def violated(self) -> bool:
        return self.dropped or (self.completion is not None
                                and self.completion > self.deadline)


@dataclass
class RequestQueue:
    """Frame generator for registered model streams.

    Streams are strictly periodic from t=0 by default; pass ``arrival`` (an
    ``repro.scenarios.arrivals`` process instance or its config dict) for
    jittered / Poisson / bursty / diurnal traffic — the same processes the
    Level-1 simulator consumes, so a workload definition ports across both
    engines unchanged.
    """

    clock: Callable[[], float]
    streams: dict[str, dict] = field(default_factory=dict)
    _rid: itertools.count = field(default_factory=itertools.count)

    def add_stream(self, model: str, fps: float, batch: int, seq: int,
                   vocab: int, deadline_frac: float = 1.0,
                   depends_on: Optional[str] = None,
                   trigger_prob: float = 1.0,
                   arrival=None) -> None:
        # crc32, not hash(): string hashing is salted per process and would
        # make stream contents differ run to run
        rng = np.random.default_rng(zlib.crc32(model.encode()) & 0xFFFF)
        proc = None
        next_t = 0.0
        if arrival is not None and depends_on is None:
            import copy
            from repro.scenarios.arrivals import arrival_from_config
            # shallow-copy: processes carry per-stream state (MMPP clocks),
            # so streams must never share one instance (same contract as
            # Simulator._materialize_arrival)
            proc = (arrival_from_config(arrival) if isinstance(arrival, dict)
                    else copy.copy(arrival))
            idx = len(self.streams)
            next_t = proc.start(idx, 1.0 / fps, rng)
        self.streams[model] = dict(
            fps=fps, batch=batch, seq=seq, vocab=vocab, next_t=next_t,
            deadline=deadline_frac / fps, depends_on=depends_on,
            trigger_prob=trigger_prob, rng=rng, arrival=proc)

    def poll(self, now: float) -> list[ServeRequest]:
        """Emit any frames whose arrival time elapsed (head streams)."""
        out = []
        for name, st in self.streams.items():
            if st["depends_on"] is not None or st["next_t"] is None:
                continue
            while st["next_t"] is not None and st["next_t"] <= now:
                t = st["next_t"]
                out.append(self._make(name, st, t))
                if st["arrival"] is None:
                    st["next_t"] = t + 1.0 / st["fps"]
                else:
                    st["next_t"] = st["arrival"].next_after(
                        t, 1.0 / st["fps"], st["rng"])
        return out

    def trigger_dependents(self, parent: str, now: float) -> list[ServeRequest]:
        out = []
        for name, st in self.streams.items():
            if st["depends_on"] == parent and \
                    st["rng"].random() < st["trigger_prob"]:
                out.append(self._make(name, st, now))
        return out

    def _make(self, name: str, st: dict, t: float) -> ServeRequest:
        tokens = st["rng"].integers(
            0, st["vocab"], size=(st["batch"], st["seq"])).astype(np.int32)
        return ServeRequest(rid=next(self._rid), model=name, tokens=tokens,
                            arrival=t, deadline=t + st["deadline"],
                            depends_on=st["depends_on"])


class TraceReplayQueue(RequestQueue):
    """Replays the head arrivals of a recorded scenario trace.

    The same ``repro.scenarios.trace.Trace`` the Level-1 simulator records
    and replays drives the serving engine here: each recorded arrival time
    becomes one request for the matching registered stream (models absent
    from the stream registry are ignored, so a trace can be replayed against
    a subset deployment).  Dependent streams stay live — cascade triggering
    remains the engine's own seeded draw, exactly as in the simulator.
    """

    def __init__(self, clock: Callable[[], float], trace) -> None:
        super().__init__(clock=clock)
        self._times: dict[str, deque] = {
            name: deque(ts) for name, ts in trace.arrivals_by_model().items()
        }

    def poll(self, now: float) -> list[ServeRequest]:
        out = []
        for name, st in self.streams.items():
            if st["depends_on"] is not None:
                continue
            q = self._times.get(name)
            while q and q[0] <= now:
                out.append(self._make(name, st, q.popleft()))
        return out


# ---------------------------------------------------------------------------
# virtual accelerators (mesh slices / time-sliced executors)
# ---------------------------------------------------------------------------


@dataclass
class VirtualAccelerator:
    """One dispatch target. On a pod this wraps a mesh slice; on the CPU dev
    box it wraps the single device with a speed/power factor so that the
    heterogeneous-hardware scheduling problem is preserved end-to-end."""

    name: str
    speed: float = 1.0          # relative throughput (1.0 = fastest)
    power: float = 1.0          # relative energy per unit work
    busy_until: float = 0.0
    last_model: Optional[str] = None


@dataclass
class ModelHandle:
    name: str
    cfg: ArchConfig
    params: Any
    fn: Callable                # jitted logits fn(params, tokens)
    supernet: tuple[str, ...] = ()   # lighter variant model names
    #: ``build_handle``'s seeded-weights program: "compiled" for this
    #: handle, or "reused" from an earlier handle of the same config
    init_program: Optional[str] = None


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------


@dataclass
class EngineReport:
    frames: int
    violated: int
    dropped: int
    redispatched: int
    uxcost: float
    dlv_rate: float
    energy: float
    per_model: dict[str, dict]
    alpha: float
    beta: float

    def summary(self) -> str:
        return (f"frames={self.frames} dlv={self.dlv_rate:.3f} "
                f"drops={self.dropped} redisp={self.redispatched} "
                f"uxcost={self.uxcost:.4f} energy={self.energy:.4f}")


class ServingEngine:
    def __init__(self, accelerators: list[VirtualAccelerator],
                 alpha: float = 1.0, beta: float = 1.0,
                 adaptivity: bool = True,
                 frame_drop: bool = True,
                 supernet_switch: bool = True,
                 max_drop_per_window: int = 2, drop_window: int = 10,
                 straggler_factor: float = 3.0,
                 stale_periods: float = 2.0,
                 seed: int = 0, obs=None):
        self.accs = accelerators
        self.models: dict[str, ModelHandle] = {}
        self.lat_table: dict[tuple[str, str], float] = {}  # (model, acc) -> s
        self.warmup_s: dict[str, float] = {}    # compile + first execution
        #: newest retired (not dropped) request per executed model
        self.last_retired: dict[str, ServeRequest] = {}
        self.params = MapScoreParams(alpha=alpha, beta=beta)
        self.adaptivity = adaptivity
        self.frame_drop = frame_drop
        self.supernet_switch = supernet_switch
        self.max_drop = max_drop_per_window
        self.drop_window = drop_window
        self.straggler_factor = straggler_factor
        self.stale_periods = stale_periods
        self.aborted = 0
        self.rng = np.random.default_rng(seed)
        self.drop_hist: dict[str, list[bool]] = {}
        self.stats = WindowStats()
        self.window_stats = WindowStats()
        self.redispatched = 0
        self.dropped = 0
        self._probe: list[tuple[float, np.ndarray]] = []
        self._probe_radius = 0.4
        self._lat_samples: dict[str, list[float]] = {}
        #: ``Obs.make(obs)``; read once per ``run`` (None: no hook runs)
        self.obs = Obs.make(obs)

    # ------------------------------------------------------------ registry
    def register(self, handle: ModelHandle, calibrate_tokens: np.ndarray
                 ) -> None:
        """Register a model and calibrate its per-slice latency (the
        offline-cost-model input of the paper, measured here)."""
        self.models[handle.name] = handle
        self.drop_hist[handle.name] = []
        # run the real device once (includes compile) and wait for it, so
        # the timed samples below cannot absorb its tail; then twice timed
        t = jnp.asarray(calibrate_tokens)
        t0 = time.perf_counter()
        jax.block_until_ready(handle.fn(handle.params, t))
        self.warmup_s[handle.name] = time.perf_counter() - t0
        times = []
        for _ in range(2):
            t0 = time.perf_counter()
            jax.block_until_ready(handle.fn(handle.params, t))
            times.append(time.perf_counter() - t0)
        base = float(np.median(times))
        for acc in self.accs:
            self.lat_table[(handle.name, acc.name)] = base / acc.speed

    # ------------------------------------------------------------ mapscore
    def _mapscore(self, req: ServeRequest, acc: VirtualAccelerator,
                  now: float) -> float:
        lat = self.lat_table[(req.model, acc.name)]
        lat_all = [self.lat_table[(req.model, a.name)] for a in self.accs]
        togo = float(np.mean(lat_all))
        slack = req.deadline - now
        urgency = min(togo / slack, 20.0) if slack > 1e-6 else 0.0
        latpref = sum(lat_all) / lat
        tq = max(now - req.arrival, 0.0)
        starv = tq / togo
        en = lat * acc.power
        en_all = [self.lat_table[(req.model, a.name)] * a.power
                  for a in self.accs]
        cswitch = 0.0 if acc.last_model == req.model else 0.2
        score_energy = sum(en_all) / en - cswitch
        return (urgency * latpref + self.params.alpha * starv
                + self.params.beta * score_energy)

    # ----------------------------------------------------------- frame drop
    def _try_drop(self, now: float) -> Optional[ServeRequest]:
        """Drop at most one frame expected to miss; returns it, or None."""
        waiting = [r for r in self._waiting if not r.done]
        expected_viol = [
            r for r in waiting
            if min(self.lat_table[(r.model, a.name)] for a in self.accs)
            > max(r.deadline - now, 0.0)]
        if len(expected_viol) < 2:
            return None
        best, best_ratio = None, 0.0
        for r in expected_viol:
            hist = self.drop_hist[r.model][-self.drop_window:]
            if sum(hist) >= self.max_drop:
                continue
            mtg = min(self.lat_table[(r.model, a.name)] for a in self.accs)
            ratio = mtg / max(r.deadline - now, 1e-6)
            if ratio > best_ratio:
                best, best_ratio = r, ratio
        if best is not None:
            best.done, best.dropped = True, True
            self.dropped += 1
            self._finish_stats(best)
        return best

    # ------------------------------------------------------------ adaptivity
    def _adapt(self, window_ux: float) -> None:
        center = np.array([self.params.alpha, self.params.beta])
        self._probe.append((window_ux, center.copy()))
        if len(self._probe) >= 4:
            self._probe.sort(key=lambda x: x[0])
            (u1, p1), (u2, p2) = self._probe[0], self._probe[1]
            w1, w2 = 1 / (u1 + 1e-9), 1 / (u2 + 1e-9)
            new = np.clip((w1 * p1 + w2 * p2) / (w1 + w2), 0.0, 2.0)
            self.params = MapScoreParams(alpha=float(new[0]),
                                         beta=float(new[1]))
            self._probe = []
            self._probe_radius = max(self._probe_radius * 0.7, 0.05)
        else:
            cand = np.clip(center + self.rng.uniform(
                -self._probe_radius, self._probe_radius, 2), 0.0, 2.0)
            self.params = MapScoreParams(alpha=float(cand[0]),
                                         beta=float(cand[1]))

    # -------------------------------------------------------------- running
    def _finish_stats(self, req: ServeRequest) -> None:
        st = self.window_stats.model(req.model)
        st.frames += 1
        st.violated += int(req.violated)
        st.energy_j += req.energy
        worst = max(self.lat_table[(req.model, a.name)] * a.power
                    for a in self.accs)
        st.worst_energy_j += worst
        hist = self.drop_hist[req.model]
        hist.append(req.dropped)
        if len(hist) > self.drop_window:
            hist.pop(0)

    def _pick_variant(self, req: ServeRequest, now: float) -> str:
        """Supernet switching: lightest-necessary weight-sharing variant."""
        handle = self.models[req.model]
        if not (self.supernet_switch and handle.supernet):
            return req.model
        slack = max(req.deadline - now, 0.0)
        best_lat = min(self.lat_table[(req.model, a.name)]
                       for a in self.accs)
        if best_lat <= slack:
            return req.model
        for variant in handle.supernet:          # ordered heavy -> light
            vlat = min(self.lat_table[(variant, a.name)] for a in self.accs)
            if vlat <= slack:
                return variant
        return handle.supernet[-1]

    def run(self, queue: RequestQueue, duration_s: float,
            window_s: float = 0.5) -> EngineReport:
        """Drive the engine on the real clock until duration_s elapses."""
        t_start = time.perf_counter()
        now_fn = lambda: time.perf_counter() - t_start
        rec = _Recorder(self.obs, now_fn) if self.obs is not None else None
        self._waiting: list[ServeRequest] = []
        next_window = window_s

        while True:
            now = now_fn()
            if now >= duration_s:
                break
            if rec is not None:
                rec.open("serve.poll")
            polled = queue.poll(now)
            if rec is not None:
                rec.close(frames=len(polled))
                rec.admit(polled, now)
            self._waiting.extend(polled)
            self._waiting = [r for r in self._waiting if not r.done]
            if rec is not None:
                rec.open("serve.sweep")
            # hygiene: a frame still waiting `stale_periods` past its
            # deadline-equivalent period is abandoned (counts violated)
            for r in self._waiting:
                period = r.deadline - r.arrival
                if now > r.deadline + self.stale_periods * period:
                    r.done, r.dropped = True, True
                    self.aborted += 1
                    self._finish_stats(r)
                    if rec is not None:
                        rec.drop(r, "stale")
            self._waiting = [r for r in self._waiting if not r.done]
            if self.frame_drop:
                dropped = self._try_drop(now)
                if rec is not None and dropped is not None:
                    rec.drop(dropped, "drop")
            if rec is not None:
                rec.close()
            ready = [r for r in self._waiting if not r.done]
            idle = [a for a in self.accs if a.busy_until <= now]
            if not ready or not idle:
                if rec is not None:
                    rec.idle("hold" if ready else "no_frame", len(ready))
                nxt = min([a.busy_until for a in self.accs
                           if a.busy_until > now] + [now + 1e-3])
                time.sleep(max(min(nxt - now, 1e-3), 1e-5))
                if now >= next_window:
                    if rec is not None:
                        rec.open("serve.adapt")
                    wux = uxcost(self.window_stats)
                    if self.adaptivity and sum(
                            st.frames for st in
                            self.window_stats.per_model.values()):
                        self._adapt(wux)
                    self.stats.merge(self.window_stats)
                    self.window_stats = WindowStats()
                    next_window += window_s
                    if rec is not None:
                        rec.close(alpha=self.params.alpha,
                                  beta=self.params.beta)
                continue

            # job assignment: best (request, accelerator) MapScore pair
            if rec is not None:
                rec.end_idle()
                rec.open("serve.decide")
            best, best_score = None, -np.inf
            for r in ready:
                for a in idle:
                    s = self._mapscore(r, a, now)
                    if s > best_score:
                        best, best_score = (r, a), s
            req, acc = best
            run_as = self._pick_variant(req, now)
            if rec is not None:
                rec.decided(req, run_as, acc.name, best_score,
                            len(ready) * len(idle))
            handle = self.models[run_as]
            tok = req.tokens
            if tok.shape[1] > 0:
                t0 = time.perf_counter()
                if rec is not None:
                    rec.open("serve.h2d", rid=req.rid)
                x = jnp.asarray(tok)
                if rec is not None:
                    rec.close(bytes=int(tok.nbytes))
                    rec.open("serve.launch", rid=req.rid, run_as=run_as)
                out = handle.fn(handle.params, x)
                if rec is not None:
                    rec.close()
                    rec.open("serve.wait", rid=req.rid)
                jax.block_until_ready(out)
                if rec is not None:
                    rec.close()
                wall = time.perf_counter() - t0
                req.result = out
            else:
                wall = 0.0
            if rec is not None:
                rec.open("serve.retire", rid=req.rid)
            # straggler mitigation: re-dispatch if way past expectation
            expect = self.lat_table[(run_as, acc.name)]
            samples = self._lat_samples.setdefault(run_as, [])
            samples.append(wall)
            if wall > self.straggler_factor * expect and len(samples) > 4:
                alt = min((a for a in self.accs if a is not acc),
                          key=lambda a: self.lat_table[(run_as, a.name)],
                          default=None)
                if alt is not None:
                    self.redispatched += 1
                    acc = alt
            # virtual time accounting (speed factor models slice size)
            vlat = max(wall, self.lat_table[(run_as, acc.name)])
            done_at = now + vlat
            acc.busy_until = done_at
            acc.last_model = run_as
            req.energy = vlat * acc.power
            req.done = True
            req.completion = done_at
            self.last_retired[run_as] = req
            self._finish_stats(req)
            children = queue.trigger_dependents(req.model, done_at)
            self._waiting.extend(children)
            if rec is not None:
                rec.retired(req, acc.name, children)

        if rec is not None:
            rec.finish()
        self.stats.merge(self.window_stats)
        self.window_stats = WindowStats()
        frames = sum(st.frames for st in self.stats.per_model.values())
        viol = sum(st.violated for st in self.stats.per_model.values())
        energy = sum(st.energy_j for st in self.stats.per_model.values())
        per_model = {
            name: dict(frames=st.frames, violated=st.violated,
                       energy=st.energy_j,
                       uxcost=rate_dlv(st) * norm_energy(st))
            for name, st in self.stats.per_model.items()}
        return EngineReport(
            frames=frames, violated=viol, dropped=self.dropped,
            redispatched=self.redispatched,
            uxcost=uxcost(self.stats),
            dlv_rate=viol / frames if frames else 0.0,
            energy=energy, per_model=per_model,
            alpha=self.params.alpha, beta=self.params.beta)


class _Recorder:
    """The hooks of one ``ServingEngine.run`` with ``obs`` on.

    Spans go to ``obs.tracer`` on the engine's clock (seconds since ``run``
    began, the ``now`` the queue is given). Each ``serve.*`` span is also,
    while it is open, a ``jax.profiler.TraceAnnotation`` of the same name
    with its ``sid`` as metadata, so that a profiler trace's host events
    pair with the records by ``sid`` and the two clocks can be tied. The
    loop spans nest: ``serve.idle`` (coalesced over consecutive sleeping
    turns of one reason) holds those turns' ``serve.poll``,
    ``serve.sweep`` and ``serve.adapt``; the others follow one another.
    A ``job`` span per frame runs from its admission to its retire or drop
    in the simulator's schema, so ``critical_path`` explains a served frame.
    Counters go to ``obs.metrics``. Nothing here feeds a decision.
    """

    def __init__(self, obs: Obs, clock: Callable[[], float]):
        self.tracer = obs.tracer
        self.clock = clock
        self._stack: list[tuple] = []              # (kind, sid, ann, t0)
        self._idle: Optional[list] = None          # [sid, ann, reason, ready]
        self._jobs: dict[int, int] = {}            # rid -> job sid
        self._seg: Optional[list[float]] = None    # this dispatch's exec
        self._slice: Optional[str] = None          # this dispatch's choice
        m = obs.metrics
        if m is None:
            self.m_dispatch = self.m_switch = self.m_turn = None
        else:
            self.m_dispatch = m.counter(
                "serve_dispatch_total", "frames dispatched, by the model "
                "that ran and its slice", ("model", "slice"))
            self.m_switch = m.counter(
                "serve_switch_total", "supernet switches: a frame served by "
                "a lighter variant", ("model", "variant"))
            self.m_turn = m.counter(
                "serve_turn_total", "engine loop turns: a dispatch, or a "
                "sleep with no frame ready or every ready frame held",
                ("kind",))

    # ----------------------------------------------------------- loop spans
    def _enter(self, kind: str, **attrs) -> tuple[int, Any, float]:
        t = self.clock()
        sid = self.tracer.open(kind, t, **attrs)
        ann = jax.profiler.TraceAnnotation(kind, sid=sid)
        ann.__enter__()
        return sid, ann, t

    def _exit(self, sid: int, ann, **attrs) -> float:
        ann.__exit__(None, None, None)
        t = self.clock()
        self.tracer.close(sid, t, **attrs)
        return t

    def open(self, kind: str, **attrs) -> None:
        if self.tracer is not None:
            self._stack.append((kind, *self._enter(kind, **attrs)))

    def close(self, **attrs) -> Optional[float]:
        """Close the innermost open loop span; returns its end."""
        if self.tracer is None:
            return None
        kind, sid, ann, t0 = self._stack.pop()
        t1 = self._exit(sid, ann, **attrs)
        if kind == "serve.launch":
            self._seg = [t0, t1]
        elif kind == "serve.wait":
            self._seg[1] = t1
        return t1

    def idle(self, reason: str, ready: int) -> None:
        """A sleeping turn: extend the open ``serve.idle`` span if its
        reason holds, else close it and open one for ``reason``."""
        if self.m_turn is not None:
            self.m_turn.inc(kind=reason)
        if self.tracer is None:
            return
        cur = self._idle
        if cur is not None and cur[2] == reason:
            cur[3] = max(cur[3], ready)
            return
        self.end_idle()
        self._idle = [*self._enter("serve.idle")[:2], reason, ready]

    def end_idle(self) -> None:
        if self._idle is not None:
            sid, ann, reason, ready = self._idle
            self._exit(sid, ann, reason=reason, ready=ready)
            self._idle = None

    # ------------------------------------------------------------ decisions
    def decided(self, req: ServeRequest, run_as: str, slice_: str,
                score: float, pairs: int) -> None:
        """Close ``serve.decide`` and count the dispatch."""
        if self.tracer is not None:
            self.close(rid=req.rid, model=req.model, run_as=run_as,
                       slice=slice_, score=float(score), pairs=pairs)
            self._seg, self._slice = None, slice_
        if self.m_turn is not None:
            self.m_turn.inc(kind="dispatch")
            self.m_dispatch.inc(model=run_as, slice=slice_)
            if run_as != req.model:
                self.m_switch.inc(model=req.model, variant=run_as)

    # ----------------------------------------------------------------- jobs
    def admit(self, reqs: list[ServeRequest], t: float,
              parent: Optional[int] = None) -> None:
        if self.tracer is None:
            return
        for r in reqs:
            self._jobs[r.rid] = self.tracer.open(
                "job", t, uid=r.rid, model=r.model, origin=r.arrival,
                deadline=r.deadline, parent=parent)

    def drop(self, req: ServeRequest, reason: str) -> None:
        if self.tracer is None:
            return
        t = self.clock()
        self.tracer.event("serve.drop", t, rid=req.rid, reason=reason)
        self.tracer.close(self._jobs.pop(req.rid), t, outcome="dropped",
                          segs=[], tail=True)

    def retired(self, req: ServeRequest, slice_: str,
                children: list[ServeRequest]) -> None:
        """Close ``serve.retire`` (``slice_``: where the books put the
        frame, another than decided if it was re-dispatched) and the
        frame's job; open its children's jobs with it as their parent."""
        if self.tracer is None:
            return
        t = self.close(redispatched=slice_ != self._slice,
                       children=len(children))
        self.tracer.close(self._jobs.pop(req.rid), t, outcome="done",
                          segs=[self._seg] if self._seg else [],
                          tail=not children)
        self.admit(children, t, parent=req.rid)

    def finish(self) -> None:
        """At the window's close: end the idle span; frames still
        waiting close ``unfinished``."""
        if self.tracer is None:
            return
        self.end_idle()
        t = self.clock()
        for sid in self._jobs.values():
            self.tracer.close(sid, t, outcome="unfinished", segs=[],
                              tail=True)
        self._jobs.clear()
