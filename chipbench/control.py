#!/usr/bin/env python3
"""Readings of the comparison for the program and for its control, on the chip.

    python3 chipbench/control.py --workload <cell> --seeds 11,12,13 \
        --seconds 6 [--out FILE]

For every seed, in one process: a run of the cell as the benchmark makes
it (models built from the seed, the cell's own sessions and traffic, a
short window), then the comparison of the frames it kept, once for the
program and once for the reference's float8 control put in its place
(the model's ``forward(..., control=True)``, ``run.model_module``). One
JSON line per seed gives each reading. The last line gives, per stream
and number, the lower reading (the largest of the program's), the upper
one (the smallest of the control's) and the limit that ``limit`` sets
between them, where the upper is at least three times the lower.
Benchmark runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                os.path.join(os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))), "src")]

from chipbench import run as R  # noqa: E402


def limit(lower: float, upper: float) -> float | None:
    """A limit between the program's largest reading and the control's
    smallest, with more room above the lower (fresh seeds read higher
    than those it was set from): ``lower**(1/3) * upper**(2/3)``. None
    where the two do not separate by three times."""
    if not upper >= 3 * lower or lower <= 0:
        return None
    return lower ** (1 / 3) * upper ** (2 / 3)


def summarise(readings: list[dict]) -> dict:
    """``<stream>.<number>`` -> {lower, upper, limit} over the seeds."""
    out = {}
    for key in sorted(k for k in readings[0] if ".control." in k):
        stream, number = key.split(".control.")
        lower = max(r[f"{stream}.{number}"] for r in readings)
        upper = min(r[key] for r in readings)
        out[f"{stream}.{number}"] = {"lower": lower, "upper": upper,
                                     "limit": limit(lower, upper)}
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--out")
    args = ap.parse_args()
    bench, work, cfg = R.load_cell(args.workload)
    peak, devices = R.device_info(1)
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    R.persist_everything()
    lines = []
    for seed in [int(x) for x in args.seeds.split(",")]:
        out = R.run_cell(args.workload, work, cfg, bench, seed=seed,
                         seconds=args.seconds, traced=False, peak=peak,
                         devices=devices, read_control=True)
        line = {"seed": seed, "attempted": out["attempted"],
                "failed": out["failed"],
                "readings": {k: v[0] for k, v in out["checks"].items()}}
        lines.append(line)
        print(json.dumps(line), flush=True)
    summary = summarise([x["readings"] for x in lines])
    print(json.dumps({"limits": summary}), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            f.writelines(json.dumps(x) + "\n" for x in lines)
            f.write(json.dumps({"limits": summary}) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
