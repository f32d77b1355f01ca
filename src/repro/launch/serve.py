"""Serving entry point: a multi-model RTMM workload on the serving engine.

Registers a set of models as concurrent FPS streams (optionally with a
cascade dependency and Supernet variants), builds heterogeneous virtual
accelerator slices, and runs the DREAM-dispatch engine in real time.

Two deployments ship with it:

* ``TOY`` (default): reduced-width configs (d_model 64, vocab 128) of four
  architecture families with a detector -> verifier cascade and a
  ``context`` Supernet variant — the CPU development deployment.
* ``PUBLISHED`` (``--published``): gemma-2b and mamba2-130m at their
  published configs (full width, depth and vocabulary) — the deployment
  ``chip_smoke.py`` runs on one TPU chip.

    PYTHONPATH=src python -m repro.launch.serve --duration 10
    PYTHONPATH=src python -m repro.launch.serve --published --duration 5
    PYTHONPATH=src python -m repro.launch.serve --obs-out /tmp/obs

``--obs-out DIR`` runs the engine with spans and counters on and writes
``spans.jsonl``, ``metrics.prom`` and ``metrics.json``
(``repro.obs.Obs.export``) there.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import re
import time
from typing import Callable, Optional

import jax
import numpy as np

from repro.configs import get_config, smoke_config
from repro.models import model as M
from repro.serving import (EngineReport, ModelHandle, RequestQueue,
                           ServingEngine, VirtualAccelerator)


#: (config, weight dtype) -> the jitted ``init_params`` of that model: its
#: seeded weights as one program. The key is the argument, not a constant,
#: so every seed and every stream of one config run the same executable.
_INIT: dict[tuple, Callable] = {}


def build_handle(arch: str, name: str, *, layers: int | None = None,
                 d_model: int | None = None, seed: int = 0,
                 published: bool = False) -> ModelHandle:
    """A jitted logits handle with seeded weights.

    ``published=True`` serves ``get_config(arch)`` unchanged; otherwise
    the reduced ``smoke_config`` with a 128-token vocabulary, optionally
    cut further to ``layers`` / ``d_model``. The jitted function is named
    ``forward_<name>`` (non-word characters as ``_``), so the XLA module
    names in a device trace say which stream's model ran. The weights come
    from one jitted ``init_params``; ``init_program`` says whether this
    handle compiled it ("compiled") or found it made ("reused").
    """
    if published:
        if layers or d_model:
            raise ValueError("a published config is served unchanged")
        cfg = get_config(arch)
    else:
        cfg = smoke_config(arch)
        upd = {"vocab_size": 128, "scan_layers": False}
        if layers:
            upd["num_layers"] = layers
        if d_model:
            upd["d_model"] = d_model
            upd["d_ff"] = 2 * d_model
        cfg = dataclasses.replace(cfg, **upd)
    # matmul weights held in the compute dtype the forward casts them to:
    # the same logits, at half the memory and no per-call weight copy
    dtype = M.compute_dtype(cfg)
    init = _INIT.get((cfg, dtype))
    if init is None:
        init = _INIT[cfg, dtype] = jax.jit(functools.partial(
            M.init_params, cfg=cfg, weight_dtype=dtype))
    programs = init._cache_size()
    params = init(jax.random.PRNGKey(seed))
    grew = init._cache_size() > programs

    def forward_logits(p, tokens):
        logits, _ = M.forward(p, cfg, tokens)
        return logits

    forward_logits.__name__ = forward_logits.__qualname__ = (
        "forward_" + re.sub(r"[^A-Za-z0-9_]", "_", name))
    return ModelHandle(name=name, cfg=cfg, params=params,
                       fn=jax.jit(forward_logits),
                       init_program="compiled" if grew else "reused")


@dataclasses.dataclass(frozen=True)
class Stream:
    """One served model: its architecture and, unless it is only a
    Supernet variant of another stream's model, its frame stream."""
    name: str
    arch: str
    fps: float
    seq: int
    layers: Optional[int] = None          # depth cut (reduced configs only)
    depends_on: Optional[str] = None      # cascade parent stream
    trigger_prob: float = 1.0
    variant_of: Optional[str] = None      # Supernet variant: no own stream


TOY = (
    Stream("detector", "gemma-2b", fps=8, seq=32, layers=2),
    Stream("verifier", "qwen1.5-4b", fps=8, seq=32, layers=2,
           depends_on="detector", trigger_prob=0.5),
    Stream("context", "gemma2-2b", fps=4, seq=32, layers=4),
    Stream("context@v1", "gemma2-2b", fps=4, seq=32, layers=2,
           variant_of="context"),
    Stream("kws", "mamba2-130m", fps=12, seq=16, layers=2),
)

PUBLISHED = (
    Stream("detector", "gemma-2b", fps=10, seq=64),
    Stream("kws", "mamba2-130m", fps=20, seq=32),
)


@dataclasses.dataclass
class ServeRun:
    engine: ServingEngine
    report: EngineReport
    handles: dict[str, ModelHandle]


def serve(deployment: tuple[Stream, ...] = TOY, *, published: bool = False,
          duration_s: float = 8.0, fps_scale: float = 1.0,
          frame_drop: bool = True, supernet: bool = True,
          adaptivity: bool = True, seed: int = 0, obs=None) -> ServeRun:
    """Build, calibrate and run one deployment on the real clock.

    ``obs`` is the engine's (``repro.obs.Obs.make``): the run's spans and
    counters are then in ``run.engine.obs``."""
    # heterogeneous 3-slice system (a big fast slice + two small efficient)
    accs = [
        VirtualAccelerator("big0", speed=1.0, power=1.0),
        VirtualAccelerator("small0", speed=0.45, power=0.4),
        VirtualAccelerator("small1", speed=0.45, power=0.4),
    ]
    engine = ServingEngine(accs, adaptivity=adaptivity,
                           frame_drop=frame_drop,
                           supernet_switch=supernet, seed=seed, obs=obs)
    inits = None
    if engine.obs is not None and engine.obs.metrics is not None:
        inits = engine.obs.metrics.counter(
            "serve_init_total", "handles built, by whether the "
            "seeded-weights program was compiled or reused",
            ("model", "program"))
    handles: dict[str, ModelHandle] = {}
    for st in deployment:
        t0 = time.perf_counter()
        h = build_handle(st.arch, st.name, layers=st.layers, seed=seed,
                         published=published)
        handles[st.name] = h
        if inits is not None:
            inits.inc(model=st.name, program=h.init_program)
        # calibrate with the stream shape: a recompile at dispatch time
        # would poison the wall-clock accounting
        engine.register(h, np.zeros((1, st.seq), np.int32))
        print(f"[serve] {st.name:>12s} {st.arch} layers={h.cfg.num_layers} "
              f"d_model={h.cfg.d_model} vocab={h.cfg.vocab_size} "
              f"init+warmup={time.perf_counter() - t0:.2f}s")
    for st in deployment:
        if st.variant_of:
            handles[st.variant_of].supernet += (st.name,)

    q = RequestQueue(clock=lambda: 0.0)
    for st in deployment:
        if st.variant_of is None:
            q.add_stream(st.name, fps=st.fps * fps_scale, batch=1,
                         seq=st.seq, vocab=handles[st.name].cfg.vocab_size,
                         depends_on=st.depends_on,
                         trigger_prob=st.trigger_prob)

    report = engine.run(q, duration_s=duration_s)
    print(f"[serve] {report.summary()}")
    for name, ms in sorted(report.per_model.items()):
        print(f"[serve]   {name:>12s} frames={ms['frames']:4d} "
              f"violated={ms['violated']:4d} energy={ms['energy']:.3f}")
    print(f"[serve] final (alpha, beta) = "
          f"({report.alpha:.2f}, {report.beta:.2f}); aborted={engine.aborted}")
    return ServeRun(engine=engine, report=report, handles=handles)


def main(argv: Optional[list[str]] = None) -> None:
    from repro.launch.cache import enable_compile_cache

    ap = argparse.ArgumentParser()
    ap.add_argument("--duration", type=float, default=8.0)
    ap.add_argument("--published", action="store_true",
                    help="serve gemma-2b + mamba2-130m at their published "
                         "configs (needs an accelerator)")
    ap.add_argument("--no-drop", action="store_true")
    ap.add_argument("--no-supernet", action="store_true")
    ap.add_argument("--no-adapt", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--obs-out", metavar="DIR",
                    help="record the engine's spans and counters and write "
                         "spans.jsonl and metrics.prom to DIR")
    args = ap.parse_args(argv)
    enable_compile_cache()
    obs = ({"spans": True, "metrics": True, "profile": False}
           if args.obs_out else None)
    run = serve(PUBLISHED if args.published else TOY,
                published=args.published, duration_s=args.duration,
                frame_drop=not args.no_drop, supernet=not args.no_supernet,
                adaptivity=not args.no_adapt, seed=args.seed, obs=obs)
    if args.obs_out:
        for path in run.engine.obs.export(args.obs_out).values():
            print(f"[serve] wrote {path}")


if __name__ == "__main__":
    main()
