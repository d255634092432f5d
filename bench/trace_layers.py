#!/usr/bin/env python3
"""One traced run of a cell, split by the program's layers.

    python3 bench/trace_layers.py --workload amazon_k16.agg --seed 7 \\
        --seconds 20 [--save trace.json.gz]

The run is ``harness.run`` itself (set-up, window, comparison with the
reference); the entry starts a profiler session at the window's first
query and ends it, with its own ``bench.window`` span, when the harness
closes it, so nothing compiles under the profiler and the end-to-end
numbers are the traced ones.  The result line gains ``layers``: every
reader of ``LAYER_METRICS`` (the program's ``join.*`` scopes, its
``engine.*`` spans and its live-row counter, read by ``bench/layers.py``),
the device seconds per scope and the idle seconds per innermost span.
Where the entry runs through ``QueryEngine.submit``, each query's
measured statistics are recorded for ``live_row_share``.  ``--save``
keeps the loaded events of the window, the form of the recorded traces
in ``tests/bench/data``.
"""

from __future__ import annotations

import argparse
import gzip
import json
import shutil
import sys
import tempfile
from pathlib import Path

BENCH = Path(__file__).resolve().parent

LAYER_METRICS = ("partition_ms_per_query", "shuffle_ms_per_query",
                 "local_sort_ms_per_query", "probe_ms_per_query",
                 "emit_ms_per_query", "groupby_ms_per_query",
                 "serving_idle_ms_per_query", "live_row_share")


def recording(cls, log_dir: str, measured: list):
    """The entry ``cls``, traced into ``log_dir`` from the first query
    after the warm one until ``close``, appending each engine query's
    measured statistics (the warm query first) to ``measured``."""
    import jax
    import tracing

    class Recording(cls):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            self.window = None
            self.calls = 0
            engine = getattr(self, "engine", None)
            if engine is None:
                return
            submit = engine.submit

            def record(*a, **kw):
                res = submit(*a, **kw)
                measured.append(res.measured)
                return res

            engine.submit = record

        def submit(self, tables, stats):
            self.calls += 1
            if self.calls == 2:
                jax.profiler.start_trace(log_dir)
                self.window = jax.profiler.TraceAnnotation(tracing.WINDOW)
                self.window.__enter__()
            return super().submit(tables, stats)

        def close(self):
            if self.window is not None:
                self.window.__exit__(None, None, None)
                jax.profiler.stop_trace()
            super().close()

    return Recording


def window_events(events: dict, window: str) -> dict:
    """The events that overlap the ``window`` span."""
    (w0, w1), = [(s, s + d) for n, s, d in events["host"] if n == window]

    def inside(s, d):
        return s < w1 and s + d > w0

    devices, scopes = {}, {}
    for plane, ops in events["devices"].items():
        keep = [i for i, (_, _, s, d) in enumerate(ops) if inside(s, d)]
        devices[plane] = [ops[i] for i in keep]
        scopes[plane] = [events["scopes"][plane][i] for i in keep]
    return {"devices": devices, "scopes": scopes,
            "host": [h for h in events["host"] if inside(h[1], h[2])],
            "spans": [s for s in events["spans"] if inside(s[1], s[2])]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--save", help="gzipped JSON of the window's events")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
    import harness
    import layers
    import tracing

    cell = harness.load_cell(args.workload)
    harness.use_compile_cache()
    try:
        devices = harness.require_chip(cell["chips"])
    except harness.NoChip as e:
        harness.log(f"trace_layers: {e}")
        return 3
    measured = []
    log_dir = tempfile.mkdtemp(prefix="bench_trace_")
    entry = recording(harness.entry_class(cell["config_data"]["entry"]),
                      log_dir, measured)
    result = harness.run(cell, args.seed, args.seconds, False,
                         devices=devices, entry_cls=entry)
    events = window_events(layers.load(tracing.find_xplane(log_dir)),
                           tracing.WINDOW)
    shutil.rmtree(log_dir, ignore_errors=True)
    if args.save:
        events["recorded"] = (f"{args.workload}, seed {args.seed}, "
                              f"{result['window']['queries']} queries on "
                              f"{devices[0].device_kind}")
        Path(args.save).parent.mkdir(parents=True, exist_ok=True)
        with gzip.open(args.save, "wt") as f:
            json.dump(events, f)
    summary = layers.reduce(events)
    queries = result["window"]["queries"]
    ctx = {"queries": queries, "trace": summary, "measured": measured[1:]}
    read = {m: harness.metric_reader(m)(ctx) for m in LAYER_METRICS}
    result["layers"] = {
        "metrics": {m: v for m, v in read.items() if v is not None},
        "busy_s": summary["busy_s"], "window_s": summary["window_s"],
        "scope_s": {str(k): v for k, v in summary["scope_s"].items()},
        "gaps_by_span": summary["gaps_by_span"]}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
