"""End-to-end driver: a real-time multi-model workload served by DREAM.

Real JAX models (reduced LM configs from four assigned architecture
families) run as concurrent FPS streams with a cascade dependency and a
weight-class Supernet variant, dispatched onto heterogeneous virtual
accelerator slices by MapScore, with smart frame drop, online (alpha, beta)
adaptivity and straggler re-dispatch — the production face of the paper.

    PYTHONPATH=src python examples/serve_rtmm.py --duration 8
"""
import argparse
import sys

sys.path.insert(0, "src")

from repro.launch.serve import TOY, serve


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--duration", type=float, default=8.0)
    ap.add_argument("--overload", action="store_true",
                    help="double every FPS target to show frame drop + "
                         "supernet switching under load")
    args = ap.parse_args()
    serve(TOY, duration_s=args.duration,
          fps_scale=2.0 if args.overload else 1.0)


if __name__ == "__main__":
    main()
