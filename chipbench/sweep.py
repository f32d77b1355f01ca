#!/usr/bin/env python3
"""Find a configuration's knee: step the number of sessions in one process.

    python3 chipbench/sweep.py --workload <cell> --sessions 1,2,3 \
        --seconds 15 --seed <n> [--out FILE]

The cell gives the configuration and the arrival process; its own session
count is ignored. The models are built and warmed once; each step runs a
fresh ``ServingEngine`` window with that many sessions and prints one JSON
line: offered, good and completed frames per second, the share of
deadlines met, the latency tail, drops, and the backlog (frames due and
unfinished) averaged over the second quarter and over the last quarter
of the window.

The knee is the highest session count at which at least 99% of the frames
due meet their deadline and the backlog of the last quarter is no more
than one and a half times that of the second quarter, plus one frame.
Benchmark runs never call this; it sizes the cells once, on the chip.
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


def backlog(frames, lo: float, hi: float, close: float, n: int = 50) -> float:
    """Mean number of frames due and unfinished over [lo, hi)."""
    tot = 0
    for i in range(n):
        t = lo + (hi - lo) * (i + 0.5) / n
        tot += sum(1 for f in frames if f.due <= t and (
            f.closed if f.closed is not None else close) > t)
    return tot / n


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--sessions", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out")
    args = ap.parse_args()
    bench, work, cfg = R.load_cell(args.workload)
    peak, devices = R.device_info(1)
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    R.persist_everything()
    handles, _, build_s = R.build_handles(cfg, args.seed)
    rows = []
    for n in [int(x) for x in args.sessions.split(",")]:
        engine, warm = R.make_engine(cfg, handles, args.seed)
        queue = R.make_queue(cfg, handles, n, work["arrival"], args.seconds,
                             args.seed)
        report = engine.run(queue, duration_s=args.seconds)
        close = queue.finish()
        queue.kept.clear()
        outcome, off = R.books(queue, engine, report)
        run = R.Run(cell=args.workload, seconds=args.seconds,
                    frames=queue.frames, setup_s=0.0, build_s=build_s,
                    warmup_s=warm, report=report, queue_until=args.seconds)
        s = args.seconds
        row = {"sessions": n,
               "offered_fps": sum(1 for f in queue.frames if f.due < s) / s,
               "goodput_fps": R.reader("goodput_fps")(run),
               "completed_fps": R.reader("completed_fps")(run),
               "deadline_met_pct": R.reader("deadline_met_pct")(run),
               "latency_p95_ms": R.reader("frame_latency_p95_ms")(run),
               "admit_lag_p95_ms": R.reader("admit_lag_p95_ms")(run),
               "drop_pct": R.reader("drop_pct")(run),
               "uxcost_modeled": report.uxcost,
               "backlog_q2": backlog(queue.frames, s / 4, s / 2, close),
               "backlog_q4": backlog(queue.frames, 3 * s / 4, s, close),
               "outcome": outcome, "books": off,
               "lat_table_ms": {k[0]: 1e3 * v for k, v in
                                engine.lat_table.items() if k[1] == "big0"}}
        row["holds"] = (row["deadline_met_pct"] >= 99.0
                        and row["backlog_q4"] <= 1.5 * row["backlog_q2"] + 1)
        rows.append(row)
        print(json.dumps(row), flush=True)
    knee = 0
    for row in rows:
        if not row["holds"]:
            break
        knee = row["sessions"]
    print(json.dumps({"workload": args.workload, "knee": knee}), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            for row in rows:
                f.write(json.dumps(row) + "\n")
            f.write(json.dumps({"knee": knee}) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
