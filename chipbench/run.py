#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. The cell is an entry of ``BENCHMARK.json``'s
``workloads``; its traffic is ``chipbench/workloads/<cell>.json`` and its
deployment ``chipbench/configs/<config>.json``. A run:

1. turns on JAX's persistent compile cache (``repro.launch.cache``);
2. builds one handle per stream through the program's
   ``repro.launch.serve.build_handle(model, stream, published=True,
   seed=...)`` and checks its sizes against the configuration file;
3. makes a ``ServingEngine`` on the configuration's slices and registers
   every handle with its stream's prompt shape (the only shapes warmed);
4. drives ``engine.run(queue, duration_s=--seconds)`` with the
   benchmark's own ``SessionQueue``: that call is the timed window;
5. checks what the window produced (``correct``): the engine's books
   against the frames the queue saw, and the logits of a seeded sample
   of finished frames of every stream against the model's reference
   (``model_module``);
6. prints one JSON line last on standard output: ``correct``,
   ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
   or with ``--trace 1`` its per-layer ones), ``device``, with
   ``--trace 1`` a ``breakdown``, and last ``checks``, each compared
   number beside its limit (also the last lines of standard error).

Every metric is computed by ``chipbench/metrics/<name>.py``, and each
model's reference, FLOPs and bytes by ``chipbench/models/<model>.py``
where that exists (else ``reference.py`` and ``flops.py``), found by the
names in ``BENCHMARK.json`` and the configuration: this file names no
cell, configuration, model or metric.

With ``--trace 1`` the last ``TRACE_S`` seconds of the window run under
the profiler, the handles' calls and the queue's calls carry host
annotations, the engine records its spans and counters
(``engine_trace.OBS``), and the per-layer metrics read the trace and the
spans; the queue-side and span ones are taken over the window before the
trace began. The result line then carries ``spans``: the traced window's
device-idle seconds by innermost engine span, the clock tie, and the
longest stalls of the engine loop (``engine_trace``).

The run exits non-zero without a result line when JAX finds no TPU, fewer
chips than the cell asks for, or a device kind missing from
``chipbench/peaks.json``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from types import SimpleNamespace  # noqa: E402
from typing import Any, Callable  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.dirname(os.path.abspath(__file__))
MODELS = os.path.join(BENCH, "models")
TRACE_S = 2.0          # traced seconds at the end of a --trace 1 window
MAX_SEED = 2 ** 31 - 1


class NoChip(RuntimeError):
    """No accelerator, too few chips, or a chip the peaks table lacks."""


# ---------------------------------------------------------------------------
# the benchmark's files, found by name
# ---------------------------------------------------------------------------


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str) -> tuple[dict, dict, dict]:
    """(BENCHMARK.json, the cell's traffic file, its configuration file)."""
    bench = load_json(ROOT, "BENCHMARK.json")
    if name not in {w["name"] for w in bench["workloads"]}:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    work = load_json(BENCH, "workloads", f"{name}.json")
    cfg = load_json(BENCH, "configs", f"{work['config']}.json")
    return bench, work, cfg


def _load(name: str, path: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str) -> Callable:
    """``read(run)`` of ``metrics/<metric>.py``."""
    return _load(f"chipbench.metrics.{metric}",
                 os.path.join(BENCH, "metrics", f"{metric}.py")).read


def model_module(model: str):
    """What the benchmark knows of one model (a key of a configuration's
    ``models``): ``models/<model>.py`` where it exists, else
    ``reference.py``'s ``forward`` and ``flops.py``'s counts. The module
    gives ``forward(sizes, seed, tokens, control=False)`` (float32 logits
    of the plain reference; ``control``: its low-precision control),
    ``forward_flops(sizes, s)``, ``forward_bytes(sizes, s)`` and, where
    it names kernels, ``kernels(sizes, s)`` (``Run.kernel_roofline``)."""
    path = os.path.join(MODELS, f"{model}.py")
    if os.path.exists(path):
        return _load(f"chipbench.models.{model}", path)
    from chipbench import flops, reference
    return SimpleNamespace(forward=reference.forward,
                           forward_flops=flops.forward_flops,
                           forward_bytes=flops.forward_bytes)


def metrics_for(bench: dict, cell: str, traced: bool) -> list[dict]:
    group = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


# ---------------------------------------------------------------------------
# what a run leaves for the metric readers
# ---------------------------------------------------------------------------


@dataclass
class Run:
    cell: str
    seconds: float
    frames: list                     # traffic.Frame, every frame due
    setup_s: float
    build_s: float
    warmup_s: float
    report: Any                      # the engine's EngineReport
    queue_until: float               # queue-side metrics: frames due before
    flops: dict = field(default_factory=dict)     # stream -> per forward
    bytes: dict = field(default_factory=dict)     # stream -> per forward
    peak: dict = field(default_factory=dict)      # peaks.json entry
    modules: dict = field(default_factory=dict)   # stream -> model_module
    sizes: dict = field(default_factory=dict)     # stream -> model sizes
    prompts: dict = field(default_factory=dict)   # stream -> positions
    trace: Any = None                # trace.Summary of a --trace 1 run
    spans: Any = None                # {"records", "counters"}, --trace 1

    def judged(self, until: float) -> list:
        """Frames due before ``until`` whose outcome is known: left out are
        those unfinished at the close whose deadline lies beyond it."""
        return [f for f in self.frames if f.due < until and not (
            f.outcome == "pending" and f.deadline > self.seconds)]

    def kernel_roofline(self, label: str) -> float | None:
        """Share of its roofline reached by the kernel ``label`` in the
        traced interval, over every stream whose model module names it in
        ``kernels(sizes, s)``: ``{label: {"flops", "bytes", "ops"}}``, the
        FLOPs and bytes of all its calls in one forward, and ``ops(name,
        kind)``, true for the device operations (``trace.Summary.ops``)
        that are this kernel. The least time, forwards run times the
        larger of FLOPs over the bf16 peak and bytes over HBM bandwidth,
        over the matched operations' self time. None where nothing
        matched."""
        t = self.trace
        if t is None:
            return None
        least = spent = 0.0
        for stream, mod in self.modules.items():
            named = getattr(mod, "kernels", None)
            k = named and named(self.sizes[stream],
                                self.prompts[stream]).get(label)
            runs = t.runs.get(stream, [0])[0]
            if not k or not runs:
                continue
            prefix = f"{stream}:"
            spent += sum(sec for key, (sec, kind) in t.ops.items()
                         if key.startswith(prefix)
                         and k["ops"](key[len(prefix):], kind))
            least += runs * max(k["flops"] / self.peak["bf16_flops_per_s"],
                                k["bytes"] / self.peak["hbm_bytes_per_s"])
        return 100.0 * least / spent if spent else None


# ---------------------------------------------------------------------------
# a run
# ---------------------------------------------------------------------------


def model_seed(seed: int, stream_index: int) -> int:
    """Each stream's handle gets its own weights seed, inside 31 bits."""
    import numpy as np
    return int(np.random.SeedSequence([seed, stream_index])
               .generate_state(1)[0]) & MAX_SEED


def published_handle(model: str, stream: str, seed: int):
    from repro.launch.serve import build_handle
    return build_handle(model, stream, published=True, seed=seed)


def check_sizes(handle, sizes: dict) -> None:
    for key, want in sizes.items():
        have = getattr(handle.cfg, key)
        if (tuple(want) if isinstance(want, list) else want) != have:
            raise ValueError(f"{handle.name}: the program serves {key}="
                             f"{have!r}, the configuration states {want!r}")


def device_info(chips: int) -> tuple[dict, list]:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return peaks_for(devs[0].device_kind), devs[:chips]


def peaks_for(kind: str) -> dict:
    """The published peaks of one chip of ``kind``; no default."""
    peaks = load_json(BENCH, "peaks.json")
    if kind not in peaks:
        raise NoChip(f"device kind {kind!r} is not in chipbench/peaks.json")
    return peaks[kind]


def persist_everything() -> None:
    """Let the persistent cache keep every program, not only those that
    took a second or more to compile: the eager steps of weight
    initialisation are many small programs, and a run that misses them
    all compiles for tens of seconds in set-up."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def _trace_hook(seconds: float, out_dir: str, polls: list):
    """``on_poll``: start the profiler TRACE_S before the close, then
    record the queue's clock at every traced poll."""
    import jax
    state = {"on": False, "at": None}

    def on_poll(now: float) -> None:
        if state["on"]:
            polls.append(now)
        elif now >= seconds - TRACE_S:
            jax.profiler.start_trace(out_dir)
            state["on"], state["at"] = True, now
            polls.append(now)

    return on_poll, state


_COMPILES = [0]


def _count_compiles() -> None:
    """Count backend compiles (cache loads excluded), once per process."""
    import jax

    def listen(event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            _COMPILES[0] += 1

    if not getattr(_count_compiles, "on", False):
        jax.monitoring.register_event_duration_secs_listener(listen)
        _count_compiles.on = True


def _annotated(fn, label: str):
    import jax

    def call(*args):
        with jax.profiler.TraceAnnotation(label):
            return fn(*args)
    return call


def build_handles(cfg: dict, seed: int, build: Callable = published_handle):
    """One handle per stream, each with its own weights seed."""
    import jax
    handles, seeds = {}, {}
    t0 = time.perf_counter()
    for i, st in enumerate(cfg["streams"]):
        seeds[st["name"]] = model_seed(seed, i)
        h = build(st["model"], st["name"], seeds[st["name"]])
        check_sizes(h, cfg["models"][st["model"]]["sizes"])
        jax.block_until_ready(h.params)
        handles[st["name"]] = h
    return handles, seeds, time.perf_counter() - t0


def make_engine(cfg: dict, handles: dict, seed: int, obs=None):
    """A ServingEngine on the configuration's slices with every handle
    registered (warmed) at its stream's prompt shape."""
    import numpy as np
    from repro.serving import ServingEngine, VirtualAccelerator
    engine = ServingEngine([VirtualAccelerator(**s) for s in cfg["slices"]],
                           seed=seed, obs=obs)
    t0 = time.perf_counter()
    for st in cfg["streams"]:
        engine.register(handles[st["name"]],
                        np.zeros((1, st["prompt"]), np.int32))
    return engine, time.perf_counter() - t0


def make_queue(cfg: dict, handles: dict, sessions: int, arrival: dict,
               seconds: float, seed: int, annotate: bool = False):
    from chipbench.traffic import SessionQueue, Stream
    from repro.serving import ServeRequest
    streams = [Stream(st["name"], st["fps"], st["prompt"],
                      handles[st["name"]].cfg.vocab_size,
                      st.get("depends_on"), st.get("trigger_prob", 1.0))
               for st in cfg["streams"]]
    return SessionQueue(streams, sessions, arrival, seconds, seed,
                        cfg["keep"], ServeRequest, annotate=annotate)


def books(queue, engine, report) -> tuple[dict, int]:
    """Outcome counts of the frames due, and how far the engine's own
    counts depart from them (0: every frame accounted for exactly once)."""
    outcome = {k: 0 for k in ("done", "dropped", "failed", "pending")}
    per_stream: dict = {}
    for f in queue.frames:
        outcome[f.outcome] += 1
        if f.outcome != "pending":
            per_stream[f.stream] = per_stream.get(f.stream, 0) + 1
    engine_frames = {m: v["frames"] for m, v in report.per_model.items()}
    off = (abs(report.frames - outcome["done"] - outcome["dropped"]
               - outcome["failed"])
           + abs(engine.dropped + engine.aborted - outcome["dropped"])
           + sum(abs(engine_frames.get(s, 0) - per_stream.get(s, 0))
                 for s in set(engine_frames) | set(per_stream)))
    return outcome, off


def take_kept(queue) -> dict:
    """stream -> (tokens [k, S], logits [k, S, V]) of the frames kept for
    the comparison, on the host; the queue lets go of them."""
    import numpy as np
    kept = {}
    for name, heap in queue.kept.items():
        items = sorted(heap, key=lambda it: -it[0])
        kept[name] = ((np.concatenate([it[2].tokens for it in items]),
                       np.stack([np.asarray(it[3])[0] for it in items]))
                      if items else (None, None))
    queue.kept.clear()
    return kept


def readings(cfg: dict, kept: dict, seeds: dict, control: bool = False
             ) -> dict:
    """Each stream's logits against its model's reference
    (``model_module``): ``<stream>.<number>`` -> value. With ``control``
    the reference's low-precision control is read too, on the same tokens
    (``<stream>.control.<number>``)."""
    import jax
    import numpy as np
    out = {}
    for st in cfg["streams"]:
        name = st["name"]
        toks, got = kept[name]
        if toks is None:
            continue
        sizes = cfg["models"][st["model"]]["sizes"]
        forward = model_module(st["model"]).forward
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(forward(sizes, seeds[name], toks))
            for k, v in logit_numbers(got, ref).items():
                out[f"{name}.{k}"] = v
            if control:
                ctl = np.asarray(forward(sizes, seeds[name], toks,
                                         control=True))
                for k, v in logit_numbers(ctl, ref).items():
                    out[f"{name}.control.{k}"] = v
    return out


def compare(cfg: dict, kept: dict, seeds: dict) -> dict:
    """The numbers compared, each beside its limit: name -> [value, limit].
    A stream's numbers are those its ``limits`` entry names. A stream with
    no finished frame to compare counts in ``uncompared``; one with no
    limit shows every reading beside the limit None, which fails."""
    got = readings(cfg, kept, seeds)
    checks, missing = {}, 0
    for st in cfg["streams"]:
        name = st["name"]
        if kept[name][0] is None:
            missing += 1
            continue
        lim = cfg["limits"].get(name) or {
            k.split(".", 1)[1]: None for k in got if k.startswith(name + ".")}
        for k, limit in lim.items():
            checks[f"{name}.{k}"] = [got[f"{name}.{k}"], limit]
    return {"uncompared": [missing, 0], **checks}


def stream_models(cfg: dict) -> dict:
    """``Run``'s per-stream fields: each stream's model module, sizes and
    prompt length, and its forward's FLOPs and bytes from the module."""
    streams = cfg["streams"]
    modules = {st["name"]: model_module(st["model"]) for st in streams}
    sizes = {st["name"]: cfg["models"][st["model"]]["sizes"]
             for st in streams}
    prompts = {st["name"]: st["prompt"] for st in streams}
    return {"modules": modules, "sizes": sizes, "prompts": prompts,
            "flops": {k: m.forward_flops(sizes[k], prompts[k])
                      for k, m in modules.items()},
            "bytes": {k: m.forward_bytes(sizes[k], prompts[k])
                      for k, m in modules.items()}}


def run_cell(cell: str, work: dict, cfg: dict, bench: dict, *, seed: int,
             seconds: float, traced: bool, peak: dict, devices: list,
             build: Callable = published_handle, read_control: bool = False
             ) -> dict:
    import jax
    from chipbench import engine_trace, trace

    handles, seeds, build_s = build_handles(cfg, seed, build)
    engine, warmup_s = make_engine(
        cfg, handles, seed, obs=engine_trace.OBS if traced else None)
    if traced:
        for name, h in handles.items():
            h.fn = _annotated(h.fn, f"dispatch:{name}")
    queue = make_queue(cfg, handles, work["sessions"], work["arrival"],
                       seconds, seed, annotate=traced)
    polls: list = []
    trace_dir = os.path.join(BENCH, ".trace")
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        queue.on_poll, tstate = _trace_hook(seconds, trace_dir, polls)
    gc.collect()

    _count_compiles()
    compiles = _COMPILES[0]
    setup_s = time.perf_counter() - T_START
    report = engine.run(queue, duration_s=seconds)
    close = queue.finish()
    compiles = _COMPILES[0] - compiles

    summary = spans = by_span = None
    queue_until = seconds
    if traced:
        records = engine.obs.tracer.records
        spans = {"records": records,
                 "counters": engine.obs.metrics.snapshot()}
        by_span = {"idle": None, "tie": None,
                   "stalls": engine_trace.stalls(records)}
    if traced and tstate["on"]:
        jax.profiler.stop_trace()
        queue_until = tstate["at"]
        path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True)[0]
        outstanding = [(f.due, f.closed if f.closed is not None else close)
                       for f in queue.frames]
        summary = trace.reduce(path, poll_times=polls,
                               outstanding=outstanding)
        by_span.update(engine_trace.idle_by_span(summary, records))
        shutil.rmtree(trace_dir, ignore_errors=True)
    mem_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)

    outcome, off = books(queue, engine, report)
    checks = {"books": [off, 0], "failed": [outcome["failed"], 0]}
    kept = take_kept(queue)
    for h in handles.values():       # free the program's state first
        h.params = None
    engine.models.clear()
    engine.last_retired.clear()
    gc.collect()
    if read_control:                  # every reading, none judged
        checks.update({k: [v, None] for k, v in
                       readings(cfg, kept, seeds, control=True).items()})
    else:
        checks.update(compare(cfg, kept, seeds))
    correct = all(lim is not None and v <= lim for v, lim in checks.values())

    run = Run(cell=cell, seconds=seconds, frames=queue.frames,
              setup_s=setup_s, build_s=build_s, warmup_s=warmup_s,
              report=report, queue_until=queue_until, peak=peak,
              trace=summary, spans=spans, **stream_models(cfg))
    metrics = {}
    for m in metrics_for(bench, cell, traced):
        value = reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev0 = devices[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devices), "memory_peak_bytes": mem_peak}
    out = {"correct": bool(correct),
           "attempted": sum(1 for f in queue.frames if f.due < seconds),
           "failed": outcome["failed"], "metrics": metrics,
           "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = {
            "device_ops": summary.top_ops[:10],
            "idle_gaps": [[k, v] for k, v in summary.idle_by.items()][:10]}
    if by_span is not None:
        out["spans"] = by_span
    out["window_compiles"] = compiles
    missed = [0] * math.ceil(seconds)     # frames that missed, by due second
    for f in run.judged(seconds):
        if f.ready is None or f.ready > f.deadline:
            missed[min(int(f.due), len(missed) - 1)] += 1
    out["missed_by_s"] = missed
    out["checks"] = checks
    return out


def logit_numbers(got, ref) -> dict:
    """``logit_gap``: the widest gap, over every compared position, by
    which the reference's logit of the token served as best lies below
    the reference's best. ``logit_err``: the largest absolute logit
    difference over the largest absolute reference logit."""
    import numpy as np
    best = got.argmax(-1)
    served = np.take_along_axis(ref, best[..., None], -1)[..., 0]
    gap = float((ref.max(-1) - served).max())
    err = float(np.abs(got - ref).max() / np.abs(ref).max())
    return {"logit_gap": gap, "logit_err": err}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"no program at {src}", file=sys.stderr)
        return 2
    sys.path[:0] = [ROOT, src]
    bench, work, cfg = load_cell(args.workload)
    chips = next(w["chips"] for w in bench["workloads"]
                 if w["name"] == args.workload)
    try:
        peak, devices = device_info(chips)
    except NoChip as e:
        print(str(e), file=sys.stderr)
        return 3
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    persist_everything()
    out = run_cell(args.workload, work, cfg, bench, seed=args.seed,
                   seconds=args.seconds, traced=bool(args.trace), peak=peak,
                   devices=devices)
    for name, (value, limit) in out["checks"].items():
        print(f"check {name}: {value} (limit {limit})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
