"""Benchmark aggregator: one module per paper figure + sweeps + kernels.

    PYTHONPATH=src python -m benchmarks.run            # everything
    PYTHONPATH=src python -m benchmarks.run --only fig7 roofline
    PYTHONPATH=src python -m benchmarks.run --only scenario_sweep \
        --seed 3 --duration 2.0 --json out.json

``--json`` aggregates every module's ``run()`` payload into one
machine-readable file (the BENCH_*.json perf-trajectory input); ``--seed``
and ``--duration`` thread through to every simulator-backed figure that
accepts them.
"""
from __future__ import annotations

import argparse
import inspect
import json
import sys
import time

MODULES = (
    ("fig2", "benchmarks.fig2_static_vs_dynamic"),
    ("fig7", "benchmarks.fig7_heterogeneous"),
    ("fig8", "benchmarks.fig8_homogeneous"),
    ("fig9", "benchmarks.fig9_breakdown"),
    ("fig10", "benchmarks.fig10_param_search"),
    ("fig12", "benchmarks.fig12_cascade_prob"),
    ("fig13", "benchmarks.fig13_metric_ablation"),
    ("fig14", "benchmarks.fig14_supernet"),
    ("scenario_sweep", "benchmarks.scenario_sweep"),
    ("fleet_sweep", "benchmarks.fleet_sweep"),
    ("kernels", "benchmarks.kernels_bench"),
    ("roofline", "benchmarks.roofline"),
)


def _filter_kwargs(fn, **kw) -> dict:
    params = inspect.signature(fn).parameters
    return {k: v for k, v in kw.items() if k in params and v is not None}


def git_provenance() -> dict:
    """Git identity of the tree that produced an artifact: {"sha": ...,
    "dirty": ...} — CI uploads these files as a trend series, so every
    point must be traceable to the exact commit (and flag uncommitted
    local edits).  The BENCH trajectory (appended by every
    ``scripts/check_bench.py`` run, which CI executes *before* this) is
    a runtime log, not a source edit, so it is excluded from the dirty
    computation — otherwise every nightly point would read dirty on a
    clean checkout.  Degrades to nulls outside a git checkout (e.g. a
    source tarball).  Shared with ``scripts/check_bench.py``."""
    import os
    import subprocess
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=root, capture_output=True,
            text=True, timeout=10, check=True).stdout.strip()
        dirty = subprocess.run(
            ["git", "status", "--porcelain", "--", ".",
             ":(exclude)benchmarks/baselines/trajectory.json"],
            cwd=root, capture_output=True,
            text=True, timeout=10, check=True).stdout.strip() != ""
        return {"sha": sha, "dirty": dirty}
    except Exception:  # noqa: BLE001 — provenance must never fail a run
        return {"sha": None, "dirty": None}


def _describe(modname: str) -> str:
    """One-line benchmark description: the first line of the module's
    docstring, read via ``ast`` so --list stays instant (no benchmark
    imports, no jax) and docs/tooling share one source of truth."""
    import ast
    import importlib.util
    try:
        spec = importlib.util.find_spec(modname)
        with open(spec.origin) as f:
            doc = ast.get_docstring(ast.parse(f.read()))
        return doc.strip().splitlines()[0] if doc else "(no description)"
    except Exception:  # noqa: BLE001 — --list must never crash
        return "(no description)"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*", default=None,
                    help="subset of benchmark tags to run")
    ap.add_argument("--list", action="store_true",
                    help="print available benchmark tags and exit")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write aggregated run() payloads to this JSON file")
    ap.add_argument("--seed", type=int, default=None,
                    help="seed threaded to simulator-backed figures")
    ap.add_argument("--duration", type=float, default=None,
                    help="per-cell simulation duration (seconds)")
    ap.add_argument("--obs", default=None, metavar="DIR",
                    help="export observability artifacts (spans/metrics/"
                         "profile) from obs-capable benchmarks to this dir")
    args = ap.parse_args()
    if args.list:
        for tag, modname in MODULES:
            print(f"{tag:>16s}  {modname}")
            print(f"{'':>16s}  {_describe(modname)}")
        return
    tags = {t for t, _ in MODULES}
    unknown = set(args.only or ()) - tags
    if unknown:
        ap.error(f"unknown benchmark tags: {sorted(unknown)}; "
                 f"choose from {sorted(tags)}")
    if args.json is not None:
        try:  # fail on an unwritable path now, not after the full run
            open(args.json, "a").close()
        except OSError as e:
            ap.error(f"--json path not writable: {e}")
    import importlib

    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    failures = []
    payloads: dict[str, object] = {}
    wall_s: dict[str, float] = {}
    for tag, modname in MODULES:
        if args.only and tag not in args.only:
            continue
        print(f"\n===== {tag} ({modname}) =====", flush=True)
        t0 = time.time()
        try:
            mod = importlib.import_module(modname)
            kw = _filter_kwargs(mod.run, seed=args.seed,
                                duration_s=args.duration,
                                obs_dir=args.obs)
            if args.json is not None:
                payloads[tag] = mod.run(**kw)
                print(f"  [{tag}] collected "
                      f"{len(json.dumps(payloads[tag]))} bytes of results")
            elif kw and len(_filter_kwargs(mod.main, **kw)) < len(kw):
                # main() can't honor the requested flags (fig mains take no
                # args) — run parametrized; results land in the artifact dir
                mod.run(**kw)
                print(f"  [{tag}] ran with {kw}; "
                      "results in benchmarks/artifacts/")
            elif kw:
                mod.main(**kw)
            else:
                mod.main()
        except Exception as e:  # noqa: BLE001
            failures.append((tag, repr(e)))
            print(f"  FAILED: {e!r}")
        wall_s[tag] = round(time.time() - t0, 3)
        print(f"  [{tag}] {wall_s[tag]:.1f}s", flush=True)
    if args.json is not None:
        out = {"seed": args.seed, "duration_s": args.duration,
               "git": git_provenance(),
               "failures": failures, "wall_s": wall_s,
               "results": payloads}
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1, sort_keys=True)
        print(f"\nwrote {args.json}")
    if failures:
        print("\nFAILED benchmarks:", failures)
        sys.exit(1)
    print("\nall benchmarks completed")


if __name__ == "__main__":
    main()
