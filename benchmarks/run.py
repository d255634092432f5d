"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (figures report tuple counts in
the value column; micro-benchmarks report wall time per call).

  python -m benchmarks.run            # everything
  python -m benchmarks.run --only fig # just the paper figures
"""

import argparse
import sys

from pathlib import Path

try:
    import repro  # noqa: F401 — installed, or on PYTHONPATH (ROADMAP: PYTHONPATH=src)
except ImportError:  # checkout fallback: src/ relative to this file, not the cwd
    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="", help="substring filter")
    args = ap.parse_args()

    from . import paper_figures
    from . import engine_micro
    from . import roofline

    sections = [
        ("fig2", paper_figures.fig2_comm_cost),
        ("fig3", paper_figures.fig3_crossover),
        ("fig4", paper_figures.fig4_intermediate_aggregation),
        ("fig5", paper_figures.fig5_output_reduction),
        ("fig6", paper_figures.fig6_aggregated_cost),
        ("validate", paper_figures.engine_validation),
        ("engine", engine_micro.bench_engine),
        ("roofline", roofline.bench_rows),
    ]

    print("name,us_per_call,derived")
    for name, fn in sections:
        if args.only and args.only not in name:
            continue
        for row_name, value, derived in fn():
            print(f"{row_name},{value:.6g},{derived}")


if __name__ == "__main__":
    main()
