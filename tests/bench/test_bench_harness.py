"""The benchmark harness (``bench/``) on the CPU: its discovery of cells,
configurations, traffic and metric readers by name, the numpy reference
against brute force, the trace reduction on a trace recorded on a v5e,
a rehearsal of each cell's run at a tiny scale with the Pallas kernels
in interpret mode, faults planted under the timed path that must turn
``correct`` false, and the refusal to time without a TPU.

No test touches a chip: the rehearsals hand the harness the CPU devices
in place of its look for a TPU.
"""

import copy
import functools
import importlib
import itertools
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH_DIR = ROOT / "bench"
DATA = Path(__file__).resolve().parent / "data"
SCALE = 6


@pytest.fixture(scope="module")
def bench():
    for p in (str(ROOT / "src"), str(BENCH_DIR)):
        if p not in sys.path:
            sys.path.insert(0, p)
    import harness
    return harness


@pytest.fixture(scope="module")
def reference(bench):
    return importlib.import_module("reference")


@pytest.fixture(scope="module")
def tracing(bench):
    return importlib.import_module("tracing")


@pytest.fixture
def interpret_kernels(monkeypatch):
    from repro.core import clear_compiled_caches
    from repro.kernels import backend
    used = []

    def resolve(b="auto"):
        if b != "auto":
            return b
        used.append(b)
        return "interpret"

    monkeypatch.setattr(backend, "resolve", resolve)
    clear_compiled_caches()
    yield used
    clear_compiled_caches()


def small_cell(bench, name):
    cell = copy.deepcopy(bench.load_cell(name))
    cell["config_data"]["graph"]["scale"] = SCALE
    return cell


# -- discovery by name --------------------------------------------------------

def test_every_cell_finds_its_parts(bench):
    spec = bench.load_benchmark()
    names = [w["name"] for w in spec["workloads"]]
    assert names == ["amazon_k16.agg", "amazon_4chip.enum", "amazon_k16.enum"]
    confs = {c["name"]: c for c in spec["configs"]}
    for name in names:
        cell = bench.load_cell(name)
        conf = cell["config_data"]
        assert conf["name"] == cell["config"]
        assert sorted(conf["reduced"]) == sorted(confs[conf["name"]]["reduced"])
        assert conf["chips"] == cell["chips"]
        assert callable(bench.entry_class(conf["entry"]))
        assert {m["name"] for m in cell["end_to_end"]} == {
            "join_tuples_per_s", "setup_s"}
        for m in cell["per_layer"]:
            assert callable(bench.metric_reader(m["name"]))
    cell = bench.load_cell("amazon_k16.enum")
    assert "collective_ms_per_query" not in {m["name"]
                                             for m in cell["per_layer"]}
    with pytest.raises(KeyError):
        bench.load_cell("no_such.cell")


def test_benchmark_file_keys_and_limits(bench):
    spec = bench.load_benchmark()
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    for p in spec["paths"]:
        assert (ROOT / p).is_dir()
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for m in spec["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    cells = {w["name"] for w in spec["workloads"]}
    for m in spec["per_layer"] + spec["end_to_end"]:
        assert set(m.get("workloads", [])) <= cells


def test_peak_table_refuses_unknown_devices(bench):
    peaks = importlib.import_module("peaks")
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


# -- the reference -------------------------------------------------------------

def brute_force(src, dst):
    edges = list(zip(src.tolist(), dst.tolist()))
    out = {}
    for a, b in edges:
        out.setdefault(a, []).append(b)
    return sorted((a, b, c, d) for a, b in edges for c in out.get(b, [])
                  for d in out.get(c, []))


@pytest.mark.parametrize("scale,seed", [(3, 0), (4, 1), (5, 2**31 + 5)])
def test_reference_against_brute_force(bench, reference, scale, seed):
    graphs = importlib.import_module("graphs")
    spec = {"scale": scale, "edge_factor": 3.0, "a": 0.5,
            "structure_seed": 0}
    for src, dst in graphs.query_tables(spec, seed, 2):
        want = brute_force(src, dst)
        paths = reference.answer(src, dst, aggregate=False)
        assert paths.tolist() == [list(t) for t in want]
        counts = {}
        for a, _, _, d in want:
            counts[(a, d)] = counts.get((a, d), 0) + 1
        agg = reference.answer(src, dst, aggregate=True)
        assert {(int(a), int(d)): n for a, d, n in agg} == counts
        assert reference.path_total(agg, True) == len(want)
        assert reference.mismatched_rows(agg[::-1], agg) == 0
        assert reference.mismatched_rows(agg[1:], agg) == 1
        bumped = agg.copy()
        bumped[0, 2] += 2
        assert reference.mismatched_rows(bumped, agg) == 2
        assert reference.max_count_gap(bumped, agg) == 2


def test_relabelled_variants_ask_for_the_same_work(bench, reference):
    graphs = importlib.import_module("graphs")
    spec = {"scale": 7, "edge_factor": 3.0, "a": 0.5, "structure_seed": 0}
    totals = set()
    for seed in (0, 2**31 + 11):
        tables = graphs.query_tables(spec, seed, 3)
        again = graphs.query_tables(spec, seed, 3)
        assert all(np.array_equal(x, y) for t, u in zip(tables, again)
                   for x, y in zip(t, u))
        for src, dst in tables:
            totals.add(len(reference.answer(src, dst, aggregate=False)))
    assert len(totals) == 1
    assert not np.array_equal(tables[0][0], tables[1][0])


# -- queries by name, and the reference for each shape -------------------------

@pytest.mark.parametrize("name,aggregate", list(itertools.product(
    ["chain3", "cycle3", "star3"], [True, False])))
def test_make_query_by_name(bench, name, aggregate):
    from repro.core import JoinQuery
    query = bench.make_query({"query": name, "aggregate": aggregate})
    assert query == getattr(JoinQuery, name[:-1])(3, aggregate=aggregate)
    shape = bench.structure(query)
    assert shape.relations == query.relations
    assert shape.keys == (query.aggregate.keys if aggregate else ())


@pytest.mark.parametrize("name", ["triangle", "chain", "clique3", "cycle3x",
                                  " chain3"])
def test_make_query_refuses_unknown_names(bench, name):
    with pytest.raises(ValueError, match="unknown query"):
        bench.make_query({"query": name, "aggregate": True})


def one_graph(spec, seed):
    graphs = importlib.import_module("graphs")
    return graphs.query_tables(dict(spec, structure_seed=0), seed, 1)[0]


@pytest.mark.parametrize("scale", [5, 6])
def test_reference_cycle_against_dense_matrix(bench, reference, scale):
    """Closed 3-walks: diag(A^3) for each node, trace(A^3) in all."""
    from repro.core import JoinQuery
    src, dst = one_graph({"scale": scale, "edge_factor": 14.0, "a": 0.585},
                         2**31 + scale)
    adj = np.zeros((1 << scale,) * 2, np.int64)
    adj[src, dst] = 1
    walks = np.diag(adj @ adj @ adj)
    shape = bench.structure(JoinQuery.cycle(3, aggregate=True))
    agg = reference.answer(src, dst, True, query=shape)
    assert agg.shape[1] == 2
    assert {int(a): n for a, n in agg} == {
        a: n for a, n in enumerate(walks.tolist()) if n}
    rows = reference.answer(src, dst, False, query=shape)
    assert len(rows) == walks.sum() == reference.path_total(agg, True)
    a, b, c = rows.T
    assert adj[a, b].all() and adj[b, c].all() and adj[c, a].all()
    assert len(np.unique(rows, axis=0)) == len(rows)
    bumped = agg.copy()
    bumped[-1, -1] += 3
    assert reference.max_count_gap(bumped, agg) == 3
    assert reference.max_count_gap(agg[1:], agg) == agg[0, -1]


@pytest.mark.parametrize("scale", [4, 5])
def test_reference_star_against_brute_force(bench, reference, scale):
    """Every triple of out-neighbours of every hub."""
    from repro.core import JoinQuery
    src, dst = one_graph({"scale": scale, "edge_factor": 3.0, "a": 0.5},
                         2**31 + scale)
    out = {}
    for s, d in zip(src.tolist(), dst.tolist()):
        out.setdefault(s, []).append(d)
    want = sorted((a, *leaves) for a, ns in out.items()
                  for leaves in itertools.product(ns, repeat=3))
    shape = bench.structure(JoinQuery.star(3, aggregate=True))
    assert reference.answer(src, dst, False, query=shape).tolist() == [
        list(t) for t in want]
    agg = reference.answer(src, dst, True, query=shape)
    assert {int(a): n for a, n in agg} == {a: len(ns) ** 3
                                            for a, ns in out.items()}
    assert reference.path_total(agg, True) == len(want)


# -- the trace reduction -------------------------------------------------------

def test_reduce_on_a_synthetic_trace(tracing):
    events = {
        "host": [["bench.window", 1000, 10000], ["bench.submit", 1000, 6000],
                 ["bench.fetch", 7000, 4000]],
        "devices": {"/device:TPU:0": [["sort.1", "sort", 0, 2000],
                                      ["while.2", "while", 2500, 1500],
                                      ["sort.1", "sort", 3000, 1000],
                                      ["all-to-all.3", "all-to-all", 9000,
                                       5000]],
                    "/device:TPU:1": [["sort.1", "sort", 2000, 2000]]}}
    s = tracing.reduce(events)
    assert s["window_s"] == pytest.approx(10e-6)
    # clipped to the window, device 0 is busy in [1000, 2000),
    # [2500, 4000) and [9000, 11000): 4.5 us; device 1 for 2 us
    assert s["busy_s"] == pytest.approx(3.25e-6)
    assert s["op_s"]["sort.1"] == pytest.approx((1000 + 1000 + 2000)
                                                * 1e-9 / 2)
    assert s["op_s"]["all-to-all.3"] == pytest.approx(1e-6)
    # the sort nested in the while loop is the loop's time, not twice
    assert s["op_s"]["while.2"] == pytest.approx(0.5e-6 / 2)
    assert s["kind_s"]["sort"] == s["op_s"]["sort.1"]
    # device 0's gaps, longest first, named by the host span covering
    # most of each: [4000, 9000) and [2000, 2500)
    assert s["gaps"] == [("bench.submit", pytest.approx(5e-6)),
                         ("bench.submit", pytest.approx(0.5e-6))]
    b = tracing.breakdown(s, top=1)
    assert b["device_ops"] == [["sort.1", pytest.approx(2e-6)]]
    assert len(b["idle_gaps"]) == 1


def test_op_names_and_kinds_from_hlo_text(tracing):
    name_kind = tracing.op_name_kind
    assert name_kind("%sort.76 = (s32[16,45]{0,1:T(8,128)S(1)}, s32[16,45]"
                     "{0,1:T(8,128)}) sort(s32[16,45]{0,1:T(8,128)S(1)} "
                     "%copy.807), dimensions={1}") == ("sort.76", "sort")
    assert name_kind('%closed_call.31 = f32[1,8192]{1,0:T(1,128)S(1)} '
                     'custom-call(s32[47]{0:T(128)S(1)} %fusion.601), '
                     'custom_call_target="tpu_custom_call"') == (
        "closed_call.31", "tpu_custom_call")
    assert name_kind("%all-to-all.3 = s32[4,8]{1,0} all-to-all(s32[4,8]{1,0} "
                     "%p), dimensions={0}") == ("all-to-all.3", "all-to-all")


def test_reduce_on_a_recorded_v5e_trace(bench, tracing):
    """Two queries of amazon_k16.agg at scale 8 on one v5e chip."""
    import gzip
    with gzip.open(DATA / "v5e_amazon_k16_agg.json.gz", "rt") as f:
        events = json.load(f)
    s = tracing.reduce(events)
    assert s["devices"] == 1
    assert s["window_s"] == pytest.approx(1.346325535)
    assert s["busy_s"] == pytest.approx(1.287356352)
    # self times add up to the busy time, to the few microseconds in
    # which neighbouring operations overlap: nothing counted twice
    assert sum(s["op_s"].values()) == pytest.approx(s["busy_s"], abs=1e-5)
    assert s["kind_s"]["sort"] == pytest.approx(0.054120312)
    assert s["kind_s"]["tpu_custom_call"] == pytest.approx(0.004778386)
    assert s["gaps"][0] == ("bench.submit", pytest.approx(0.007216903))
    ctx = {"queries": 2, "trace": s, "shuffled": [1.0, 1.0]}
    read = bench.metric_reader
    assert read("device_idle_share")(ctx) == pytest.approx(
        100 * (1 - 1.287356352 / 1.346325535))
    assert read("sort_ms_per_query")(ctx) == pytest.approx(27.060156)
    assert read("pallas_ms_per_query")(ctx) == pytest.approx(2.389193)
    assert read("collective_ms_per_query")(ctx) is None
    b = tracing.breakdown(s)
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10


def test_readers_on_a_synthetic_summary(bench):
    summary = {"window_s": 2.0, "busy_s": 1.5, "devices": 1, "gaps": [],
               "kind_s": {"sort": 0.3, "fusion": 1.0,
                          "tpu_custom_call": 0.05}}
    ctx = {"queries": 10, "trace": summary, "shuffled": [5.0] * 10}
    read = bench.metric_reader
    assert read("device_idle_share")(ctx) == pytest.approx(25.0)
    assert read("sort_ms_per_query")(ctx) == pytest.approx(30.0)
    assert read("pallas_ms_per_query")(ctx) == pytest.approx(5.0)
    assert read("shuffled_tuples_per_query")(ctx) == 5.0
    # nothing to read is no number, never a 0
    assert read("collective_ms_per_query")(ctx) is None


# -- rehearsals of a run, and faults under the timed path ----------------------

def faulty(cls, fault):
    """The cell's entry with one fault planted under the timed path, or
    in the control's form (``control``: buffers as tight as the
    statistics give them and the overflow flag ignored, as
    ``bench/control.py`` reads it on the chip)."""
    if fault == "control":
        return functools.partial(cls, **cls.CONTROL)

    class Faulty(cls):
        def submit(self, tables, stats):
            if fault == "half_input":
                tables = tuple(c[: len(c) // 2] for c in tables)
            out = super().submit(tables, stats)
            if fault == "altered_answer" and out["ok"]:
                cols, valid = out["output"]
                col = self.columns[-1]
                cols[col] = np.array(cols[col])
                cols[col].flat[int(np.argmax(np.ravel(valid)))] += 1
            return out

    return Faulty


@pytest.mark.parametrize("name,fault", list(itertools.product(
    ["amazon_k16.agg", "amazon_k16.enum"],
    [None, "half_input", "altered_answer", "control"])))
def test_one_chip_cell_rehearsal(bench, interpret_kernels, name, fault):
    import jax
    cell = small_cell(bench, name)
    cls = bench.entry_class(cell["config_data"]["entry"])
    # the control is read over every variant the client cycles through
    seconds = 3.0 if fault == "control" else 0.2
    r = bench.run(cell, 2**31 + 3, seconds, False, devices=jax.devices(),
                  entry_cls=faulty(cls, fault))
    assert r["attempted"] >= 1 and list(r)[-1] == "checks"
    if fault == "control":
        assert r["attempted"] >= cell["traffic_data"]["variants"]
    assert set(r["metrics"]) == {"join_tuples_per_s", "setup_s"}
    assert r["correct"] is (fault is None)
    if fault is None:
        assert r["failed"] == 0 and r["compiles"]["window"] == 0
        # the group-by's segment_sum is the aggregated plan's kernel
        assert bool(interpret_kernels) is name.endswith(".agg")
    else:
        assert r["checks"]["mismatched_rows"]["value"] > 0
    if fault == "control":
        # the truncated answers came back, and the comparison caught them
        assert r["checks"]["queries_not_ok"]["value"] == 0


@pytest.mark.parametrize("fault", [None, "altered_answer"])
def test_cycle_cell_from_data_alone(bench, interpret_kernels, fault):
    """A cell of per-node closed-walk counts (``cycle3``, aggregated) on a
    livejournal-spec graph, made of nothing but a cell entry, its
    configuration and its traffic, none of them in ``BENCHMARK.json``,
    through the serving engine's entry."""
    import jax
    base = bench.load_cell("amazon_k16.agg")
    config = dict(copy.deepcopy(base["config_data"]), name="livejournal_k16")
    config["graph"].update(scale=5, edge_factor=14.0, a=0.585)
    cell = {"name": "livejournal_k16.cycle3", "config": config["name"],
            "traffic": "cycle3", "chips": 1, "config_data": config,
            "traffic_data": dict(base["traffic_data"], query="cycle3",
                                 aggregate=True),
            "end_to_end": base["end_to_end"], "per_layer": []}
    cls = bench.entry_class(config["entry"])
    r = bench.run(cell, 2**31 + 9, 0.2, False, devices=jax.devices(),
                  entry_cls=faulty(cls, fault))
    assert r["attempted"] >= 1
    assert r["correct"] is (fault is None)
    assert r["metrics"]["join_tuples_per_s"]["value"] > 0
    if fault is None:
        assert r["failed"] == 0 and r["compiles"]["window"] == 0
    else:
        assert r["checks"]["max_count_gap"]["value"] >= 1
        assert r["checks"]["mismatched_rows"]["value"] > 0


def test_bf16_control_rounds_counts_past_256(bench):
    """The bfloat16 control on a complete digraph of 19 nodes with self
    loops, where every (a, d) group counts 19**2 = 361 paths: bfloat16
    holds no odd count past 256, so the control reads a gap."""
    import jax
    import jax.numpy as jnp
    control = importlib.import_module("control")
    reference = importlib.import_module("reference")
    n = 19
    src, dst = (a.ravel().astype(np.int32) for a in np.indices((n, n)))
    got = np.asarray(jax.jit(control.bf16_counts, static_argnums=2)(
        jnp.asarray(src), jnp.asarray(dst), n), np.float64)
    a, d = np.nonzero(got)
    rows = np.stack([a, d, got[a, d]], axis=1)
    want = reference.answer(src, dst, True)
    assert want[:, 2].max() == 361
    assert reference.max_count_gap(rows, want) >= 1


FOUR_CHIP = """
import json, sys
sys.path[:0] = [{bench!r}, {src!r}, {tests!r}]
import jax, harness, numpy as np
from repro.core import shuffle
from test_bench_harness import faulty
cell = harness.load_cell("amazon_4chip.enum")
cell["config_data"]["graph"]["scale"] = {scale}
out = {{}}
cls = harness.entry_class("shardgrid")
for fault in (None, "half_input", "altered_answer", "control"):
    out[str(fault)] = harness.run(cell, 2**31 + 7, 0.2, False,
                                  devices=jax.devices(),
                                  entry_cls=faulty(cls, fault))
shuffle.ShardGrid.all_to_all = lambda self, x, grid_axis: x
from repro.core import clear_compiled_caches
clear_compiled_caches()
out["exchange_left_out"] = harness.run(cell, 2**31 + 7, 0.2, False,
                                       devices=jax.devices())
print(json.dumps(out))
"""


def test_four_chip_cell_rehearsal_on_four_cpu_devices():
    """The four-chip cell on four emulated devices (a subprocess: the
    device count is fixed when JAX starts): sound, with half of the input
    left out, with an answer altered, in the control's form, and with the
    exchange between chips left out."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = FOUR_CHIP.format(bench=str(BENCH_DIR), src=str(ROOT / "src"),
                            tests=str(Path(__file__).resolve().parent),
                            scale=SCALE)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-4000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    sound = r.pop("None")
    assert sound["correct"] and sound["device"]["count"] == 4
    assert sound["failed"] == 0 and sound["compiles"]["window"] == 0
    for fault, broken in r.items():
        assert not broken["correct"], fault
        assert broken["checks"]["mismatched_rows"]["value"] > 0, fault
    assert r["control"]["checks"]["queries_not_ok"]["value"] == 0


def test_refuses_to_time_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "amazon_k16.agg",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == "" and "needs a TPU" in out.stderr


def test_fails_without_a_result_beside_only_its_own_files(tmp_path):
    """With nothing but BENCHMARK.json and the benchmark's directories
    it cannot reach the program."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    for p in spec["paths"]:
        src = ROOT / p
        for f in src.rglob("*"):
            if f.is_file() and "__pycache__" not in f.parts:
                dst = tmp_path / f.relative_to(ROOT)
                dst.parent.mkdir(parents=True, exist_ok=True)
                dst.write_bytes(f.read_bytes())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable] + spec["command"][1:] +
        ["--workload", "amazon_k16.agg", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0 and '"correct"' not in out.stdout
