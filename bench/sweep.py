#!/usr/bin/env python3
"""Scale sweep of one cell, on the chip.

    python3 bench/sweep.py --workload amazon_k16.agg --scales 8 9 10

Each scale is one ``harness.run`` of the cell with the configuration's
scale overridden and a short window, in a process of its own (the
parent never touches JAX, so the child can hold the chip).  The sweep
stops at the first scale that fails, or whose mean query takes longer
than ``--max-query-s`` (a larger scale only takes longer).  One JSON
line per scale: set-up (with the compile), the mean query time over the
window, ``correct`` and the peak memory.
"""

from __future__ import annotations

import argparse
import json
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--scales", type=int, nargs="+", default=[8, 9, 10])
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-query-s", type=float, default=float("inf"))
    ap.add_argument("--one", action="store_true",
                    help="run one scale in this process")
    args = ap.parse_args(argv)
    if args.one:
        sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
        import harness
        cell = harness.load_cell(args.workload)
        cell["config_data"]["graph"]["scale"] = args.scales[0]
        harness.use_compile_cache()
        r = harness.run(cell, args.seed, args.seconds, False)
        w = r["window"]
        print(json.dumps({"workload": args.workload, "scale": args.scales[0],
                          "setup_s": r["metrics"]["setup_s"]["value"],
                          "query_s": w["seconds"] / w["queries"], **r}),
              flush=True)
        return 0
    for scale in args.scales:
        out = subprocess.run(
            [sys.executable, __file__, "--one", "--workload", args.workload,
             "--scales", str(scale), "--seconds", str(args.seconds),
             "--seed", str(args.seed)],
            capture_output=True, text=True)
        sys.stderr.write(out.stderr[-4000:])
        line = out.stdout.strip().splitlines()[-1:]
        if out.returncode != 0 or not line:
            print(json.dumps({"workload": args.workload, "scale": scale,
                              "failed": out.returncode}), flush=True)
            break
        print(line[0], flush=True)
        r = json.loads(line[0])
        if not r["correct"] or r["query_s"] > args.max_query_s:
            break
    return 0


if __name__ == "__main__":
    sys.exit(main())
