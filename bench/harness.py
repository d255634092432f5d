"""One run of one benchmark cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is data, found by name: the cell in
``BENCHMARK.json``; its configuration (``file`` of the configuration
entry) with the graph, the reducers and the entry into the program; its
traffic (``bench/traffic/<name>.json``) with the query, named for one of
``JoinQuery``'s constructors and its number of relations (``chain3``,
``cycle3``, ``star4``), each relation fed the same edge list; the entry
(``bench/entries/<entry>.py``); and each per-layer metric's reader
(``bench/metrics/<metric>.py``).

A run: set-up (data from ``--seed``, the client's statistics, compile or
load from the persistent cache, one warm query), then a closed loop of
one client that submits the next query when the last one's result is
on the host, for ``--seconds``, then the comparison of every answer with
the numpy reference.  With ``--trace 1`` the window runs under the
profiler and the result carries the per-layer metrics instead of the
end-to-end ones.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import re
import shutil
import sys
import tempfile
import time
from pathlib import Path

T_START = time.perf_counter()
BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".bench_cache" / "jax"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
QUERY_NAME = re.compile(r"(chain|cycle|star)(\d+)")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell names."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# -- what a cell is made of ----------------------------------------------------

def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def load_cell(name: str, bench: dict | None = None) -> dict:
    """The cell ``name`` with its configuration, traffic and metrics."""
    bench = bench or load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    cell = dict(cells[name])
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cell["config_data"] = json.loads((ROOT / conf["file"]).read_text())
    cell["traffic_data"] = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())

    def mine(m):
        return name in m.get("workloads", [name])
    cell["end_to_end"] = [m for m in bench["end_to_end"] if mine(m)]
    cell["per_layer"] = [m for m in bench["per_layer"] if mine(m)]
    return cell


def entry_class(entry: str):
    return importlib.import_module(f"entries.{entry}").Entry


def metric_reader(name: str):
    return importlib.import_module(f"metrics.{name}").read


# -- the machine -------------------------------------------------------------

def require_chip(chips: int):
    """The devices to run on: ``chips`` TPUs, or ``NoChip``."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"needs {chips} chips; JAX found {len(devices)}")
    return devices


def use_compile_cache() -> None:
    """JAX's persistent cache at a fixed path inside the checkout, every
    program in it, whatever the environment says."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class EventCounter:
    """Counts compile and cache events from ``jax.monitoring``."""

    def __init__(self):
        import jax
        self.counts = {}
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        self.counts[event] = self.counts.get(event, 0) + 1

    def _duration(self, event, duration, **_):
        self._event(event)

    def snapshot(self) -> dict:
        return {"compiles": self.counts.get(COMPILE_EVENT, 0),
                "cache_hits": self.counts.get(CACHE_HIT_EVENT, 0),
                "cache_misses": self.counts.get(CACHE_MISS_EVENT, 0)}


def memory_peak_bytes(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


# -- one run -----------------------------------------------------------------

def make_query(traffic: dict):
    """The traffic's query: ``<kind><n>`` is ``JoinQuery.<kind>(n)``."""
    from repro.core import JoinQuery
    name = QUERY_NAME.fullmatch(traffic["query"])
    if name is None:
        raise ValueError(f"unknown query {traffic['query']!r}")
    kind, n = name.groups()
    return getattr(JoinQuery, kind)(int(n), aggregate=traffic["aggregate"])


def structure(query):
    """The query's shape as plain names, the form the reference takes."""
    import reference
    keys = query.aggregate.keys if query.aggregate is not None else ()
    return reference.Structure(query.attrs, query.relations, tuple(keys))


def run(cell: dict, seed: int, seconds: float, trace: bool,
        devices=None, entry_cls=None) -> dict:
    """Set-up, window and check of one run; returns the result line."""
    import jax
    import numpy as np
    import graphs
    import reference
    import tracing
    from entries.common import output_columns, rows
    from repro.core import query_stats_exact

    conf, traffic = cell["config_data"], cell["traffic_data"]
    chips = cell["chips"]
    devices = require_chip(chips) if devices is None else devices
    counter = EventCounter()
    query = make_query(traffic)
    marks = [time.perf_counter()]

    # set-up: the client's data and statistics, the program, a warm query
    tables = graphs.query_tables(conf["graph"], seed, traffic["variants"])
    stats = [query_stats_exact(query, [t] * query.n_relations)
             for t in tables]
    marks.append(time.perf_counter())
    entry = (entry_cls or entry_class(conf["entry"]))(query, conf, devices)
    warm = entry.submit(tables[0], stats[0])
    marks.append(time.perf_counter())
    setup_events = counter.snapshot()
    setup_s = marks[-1] - T_START
    cached = setup_events["cache_hits"] > 0 and not setup_events["cache_misses"]
    log(f"setup: {setup_s:.3f} s (start to devices "
        f"{marks[0] - T_START:.3f}, data and statistics "
        f"{marks[1] - marks[0]:.3f}, program and warm query "
        f"{marks[2] - marks[1]:.3f}), plan {warm['plan']}, warm query ok "
        f"{warm['ok']}, compile events {setup_events}, every executable "
        f"from the persistent cache: {cached}")

    # the window: one client, closed loop, whole queries
    log_dir = None
    if trace:
        log_dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(log_dir)
    done = []
    with jax.profiler.TraceAnnotation(tracing.WINDOW):
        t0 = time.perf_counter()
        while True:
            v = len(done) % len(tables)
            done.append((v, entry.submit(tables[v], stats[v])))
            t_end = time.perf_counter()
            if t_end - t0 >= seconds:
                break
    window_s = t_end - t0
    if trace:
        jax.profiler.stop_trace()
    window_events = counter.snapshot()
    in_window = window_events["compiles"] - setup_events["compiles"]
    log(f"window: {len(done)} queries in {window_s:.3f} s, backend "
        f"compiles inside the window: {in_window}")
    peak = memory_peak_bytes(devices[:chips])
    entry.close()
    del entry

    # the check: every answer of the window against the reference
    shape = structure(query)
    want = [reference.answer(s, d, traffic["aggregate"], query=shape)
            for s, d in tables]
    columns = output_columns(query)
    not_ok, mismatched, failed, gap = 0, 0, 0, 0.0
    for v, q in done:
        got = (rows(q["output"], columns) if q["ok"]
               else np.zeros((0, len(columns))))
        wrong = reference.mismatched_rows(got, want[v])
        not_ok += not q["ok"]
        mismatched += wrong
        failed += (not q["ok"]) or wrong > 0
        if traffic["aggregate"]:
            gap = max(gap, reference.max_count_gap(got, want[v]))
    checks = {"queries_not_ok": (not_ok, 0),
              "mismatched_rows": (mismatched, 0)}
    if traffic["aggregate"]:
        checks["max_count_gap"] = (gap, 0)
    correct = all(v <= lim for v, lim in checks.values())

    if trace:
        summary = tracing.reduce(tracing.load(tracing.find_xplane(log_dir)))
        shutil.rmtree(log_dir, ignore_errors=True)
        ctx = {"queries": len(done), "trace": summary,
               "shuffled": [q["shuffled"] for _, q in done]}
        metrics = {}
        for m in cell["per_layer"]:
            value = metric_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        tuples = [reference.path_total(w, traffic["aggregate"]) for w in want]
        values = {"join_tuples_per_s": sum(tuples[v] for v, _ in done)
                  / window_s, "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell["end_to_end"]}

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": len(done), "failed": failed,
              "metrics": metrics, "device": device}
    if trace:
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        result["breakdown"] = tracing.breakdown(summary)
    result["window"] = {"queries": len(done), "seconds": window_s}
    result["compiles"] = {"setup": setup_events, "window": in_window}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        log(f"check {k}: {v} (limit {lim})")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    cell = load_cell(args.workload)
    use_compile_cache()
    try:
        result = run(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        log(f"bench: {e}")
        return 3
    print(json.dumps(result), flush=True)
    return 0
