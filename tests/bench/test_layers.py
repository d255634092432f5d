"""The per-layer attribution of ``bench/layers.py`` and its readers on
the CPU: every heavy instruction of each cell's program carries a
``join.*`` scope, the decoder of the trace's HLO protos against the
compiled text, the innermost-span attribution of idle gaps, the
readers, and the reduction of two queries recorded on a v5e with the
program's scopes and engine spans.  The old recorded trace still
reduces as ``tracing`` reduced it.
"""

import collections
import copy
import glob
import gzip
import importlib
import json
import os
import re
import subprocess
import sys
import tempfile
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH_DIR = ROOT / "bench"
DATA = Path(__file__).resolve().parent / "data"
SCALE = 6
# every instruction of these opcodes carries a join.* scope
HEAVY = ("sort", "gather", "scatter", "while", "custom-call", "all-to-all",
         "all-gather", "all-reduce", "collective-permute", "reduce-scatter")
_INSTR = re.compile(r"\s*(?:ROOT )?%(\S+) = .*? ([a-z][a-z0-9-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


@pytest.fixture(scope="module")
def bench():
    for p in (str(ROOT / "src"), str(BENCH_DIR)):
        if p not in sys.path:
            sys.path.insert(0, p)
    import harness
    return harness


@pytest.fixture(scope="module")
def layers(bench):
    return importlib.import_module("layers")


@pytest.fixture(scope="module")
def tracing(bench):
    return importlib.import_module("tracing")


def unscoped(text, scope_of):
    """(opcode counts by scope, heavy instructions with no scope) of a
    compiled program's text."""
    counts, missing = collections.Counter(), []
    for line in text.splitlines():
        m = _INSTR.match(line)
        if not m or m.group(2) not in HEAVY:
            continue
        on = _OP_NAME.search(line)
        scope = scope_of(on.group(1) if on else None)
        counts[scope] += 1
        if scope is None:
            missing.append(line.strip()[:200])
    return counts, missing


def engine_program_text(bench, name, seed):
    """The compiled text of a one-chip cell's program at ``SCALE``, as
    ``QueryEngine.submit`` builds it."""
    import graphs
    from repro.core import query_stats_exact
    from repro.serving.engine import QueryRequest
    cell = copy.deepcopy(bench.load_cell(name))
    cell["config_data"]["graph"]["scale"] = SCALE
    query = bench.make_query(cell["traffic_data"])
    tables = graphs.query_tables(cell["config_data"]["graph"], seed, 1)[0]
    entry = bench.entry_class("engine")(query, cell["config_data"], None)
    req = QueryRequest(query=query, tables=[tables] * 3,
                       stats=query_stats_exact(query, [tables] * 3))
    _, plan, _ = entry.engine._resolve(req)
    rels = entry.engine._prep_inputs(req, plan.grid_shape)
    return plan.strategy, plan.run.lower(rels).compile().as_text()


@pytest.mark.parametrize("name,strategy", [
    ("amazon_k16.agg", "cascade_pushdown"), ("amazon_k16.enum", "one_round")])
def test_one_chip_programs_scope_every_heavy_op(bench, layers, name,
                                                strategy):
    got, text = engine_program_text(bench, name, 2**31 + 3)
    assert got == strategy
    counts, missing = unscoped(text, layers.scope_of)
    assert not missing, missing[:5]
    want = {"partition", "shuffle", "sort", "probe", "emit"}
    if name.endswith(".agg"):
        want.add("groupby")
    assert set(counts) == want


FOUR_DEVICES = """
import json, sys
sys.path[:0] = [{bench!r}, {src!r}]
import jax, harness, graphs, layers
from repro.core import query_stats_exact
cell = harness.load_cell("amazon_4chip.enum")
conf = cell["config_data"]
conf["graph"]["scale"] = {scale}
query = harness.make_query(cell["traffic_data"])
tables = graphs.query_tables(conf["graph"], 2**31 + 7, 1)[0]
entry = harness.entry_class("shardgrid")(query, conf, jax.devices())
prog = entry._program(query_stats_exact(query, [tables] * 3))
text = prog["run"].lower(*entry._inputs(prog, tables)).compile().as_text()
print(json.dumps({{"plan": prog["plan"], "text": text}}))
"""


def test_four_device_program_scopes_every_heavy_op(layers):
    """The four-chip cell's ShardGrid program on four emulated devices
    (a subprocess: the device count is fixed when JAX starts): its
    collectives included."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = FOUR_DEVICES.format(bench=str(BENCH_DIR), src=str(ROOT / "src"),
                               scale=SCALE)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["plan"][0] == "one_round"
    counts, missing = unscoped(r["text"], layers.scope_of)
    assert not missing, missing[:5]
    assert any(f" {op}(" in r["text"] for op in ("all-to-all", "all-gather"))
    assert {"partition", "shuffle", "sort", "probe", "emit"} <= set(counts)


def test_scope_is_the_innermost_join_component(layers):
    scope_of = layers.scope_of
    assert scope_of("jit(run)/vmap(join.probe)/jit(searchsorted)/vmap()/"
                    "while/body/closed_call/gather") == "probe"
    assert scope_of("jit(f)/join.shuffle/vmap(join.partition)/jit(sort)/"
                    "sort") == "partition"
    assert scope_of("jit(f)/vmap(vmap(join.emit))/gather") == "emit"
    assert scope_of("jit(f)/join.probe.x/sort") is None
    assert scope_of("jit(f)/xjoin.sort/sort") is None
    assert scope_of("") is None and scope_of(None) is None


def test_hlo_protos_of_a_cpu_trace_match_the_compiled_text(layers):
    """The decoder reads each instruction's op_name from the trace's
    metadata plane exactly as the compiled text gives it."""
    import jax
    import jax.numpy as jnp

    def f(x, y):
        with jax.named_scope("join.sort"):
            s = jnp.sort(x)
        with jax.named_scope("join.probe"):
            i = jnp.searchsorted(s, y)
        return s[jnp.clip(i, 0, s.shape[0] - 1)]

    run = jax.jit(jax.vmap(f))
    x = jnp.arange(4 * 512, dtype=jnp.int32).reshape(4, 512) % 97
    run(x, x).block_until_ready()
    text = run.lower(x, x).compile().as_text()
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        run(x, x).block_until_ready()
        jax.profiler.stop_trace()
        path, = glob.glob(os.path.join(d, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        modules = layers.module_op_names(path)
    names, = [v for k, v in modules.items() if k.startswith("jit_f(")]
    want = {}
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = ", line)
        if m:
            on = _OP_NAME.search(line)
            want[m.group(1)] = on.group(1) if on else None
    assert set(names) == set(want)
    assert {k: v for k, v in names.items() if want[k]} == \
        {k: v for k, v in want.items() if v}
    scopes = {layers.scope_of(v) for v in names.values()}
    assert {"sort", "probe"} <= scopes


def synthetic_events():
    """One device, two queries; host spans nest as the engine's do."""
    return {
        "host": [["bench.window", 0, 1000], ["bench.submit", 0, 480],
                 ["bench.fetch", 480, 20], ["bench.submit", 500, 480],
                 ["bench.fetch", 980, 20]],
        "spans": [["engine.resolve", 0, 40, 0], ["engine.build", 10, 20, 0],
                  ["engine.prep", 40, 30, 0], ["engine.run", 70, 400, 0],
                  ["engine.result", 470, 10, 0],
                  ["engine.resolve", 500, 40, 1], ["engine.prep", 540, 30, 1],
                  ["engine.run", 570, 400, 1],
                  ["engine.result", 970, 10, 1]],
        "devices": {"/device:TPU:0": [
            ["while.1", "while", 80, 200], ["fusion.2", "fusion", 100, 100],
            ["sort.3", "sort", 280, 100], ["closed_call.4", "tpu_custom_call",
                                           380, 50],
            ["copy.5", "copy", 430, 20],
            ["while.1", "while", 580, 200], ["fusion.2", "fusion", 600, 100],
            ["sort.3", "sort", 780, 100], ["closed_call.4",
                                           "tpu_custom_call", 880, 50],
            ["copy.5", "copy", 930, 20]]},
        "scopes": {"/device:TPU:0": ["probe", "probe", "sort", "groupby",
                                     None] * 2},
    }


def test_reduce_attributes_scopes_and_innermost_spans(layers, tracing):
    events = synthetic_events()
    s = layers.reduce(events)
    # every key tracing gives, as tracing gives it
    old = tracing.reduce(events)
    assert {k: s[k] for k in old} == old
    # self times by scope: the fusion nested in the while loop is the
    # loop's time, not twice
    assert s["scope_s"] == pytest.approx({"probe": 400e-9, "sort": 200e-9,
                                          "groupby": 100e-9, None: 40e-9})
    assert sum(s["scope_s"].values()) == pytest.approx(s["busy_s"])
    # idle [0, 80): resolve [0,40) with build [10,30) inside it, prep
    # [40,70), run [70,80); [450,580): run, result, fetch, then the
    # second query's resolve, prep and run; [950,1000): run, result,
    # fetch
    assert s["gaps_by_span"] == pytest.approx({
        "engine.resolve": 60e-9, "engine.build": 20e-9,
        "engine.prep": 60e-9, "engine.run": 60e-9,
        "engine.result": 20e-9, "bench.fetch": 40e-9})
    assert sum(s["gaps_by_span"].values()) == pytest.approx(
        s["window_s"] - s["busy_s"])


def test_a_gap_goes_to_the_innermost_span(layers):
    spans = [("bench.submit", 0, 100), ("engine.resolve", 10, 40),
             ("engine.build", 20, 30)]
    got = layers._innermost((0, 120), spans)
    assert got == pytest.approx({"bench.submit": 70e-9,
                                 "engine.resolve": 20e-9,
                                 "engine.build": 10e-9,
                                 "(no span)": 20e-9})


def test_layer_readers_on_a_synthetic_context(bench, layers):
    summary = layers.reduce(synthetic_events())
    measured = [{"live_rows": 10.0, "buffer_rows": 100.0, "shuffled": 3.0},
                {"live_rows": 30.0, "buffer_rows": 300.0, "shuffled": 3.0}]
    ctx = {"queries": 2, "trace": summary, "shuffled": [3.0, 3.0],
           "measured": measured}
    read = bench.metric_reader
    assert read("probe_ms_per_query")(ctx) == pytest.approx(200e-6)
    assert read("local_sort_ms_per_query")(ctx) == pytest.approx(100e-6)
    assert read("groupby_ms_per_query")(ctx) == pytest.approx(50e-6)
    assert read("serving_idle_ms_per_query")(ctx) == pytest.approx(110e-6)
    assert read("live_row_share")(ctx) == pytest.approx(10.0)
    # nothing to read is no number, never a 0
    for m in ("partition_ms_per_query", "shuffle_ms_per_query",
              "emit_ms_per_query"):
        assert read(m)(ctx) is None
    bare = {"queries": 2, "trace": {"busy_s": 1.0, "window_s": 1.0},
            "shuffled": [3.0, 3.0]}
    for m in ("probe_ms_per_query", "serving_idle_ms_per_query",
              "live_row_share"):
        assert read(m)(bare) is None


def test_old_recorded_trace_reduces_as_before(bench, layers, tracing):
    """The trace recorded before the program had scopes: every key as
    ``tracing`` reads it, all busy time outside any scope, and no
    engine span to read."""
    with gzip.open(DATA / "v5e_amazon_k16_agg.json.gz", "rt") as f:
        events = json.load(f)
    s = layers.reduce(events)
    assert {k: s[k] for k in tracing.reduce(events)} == \
        tracing.reduce(events)
    assert list(s["scope_s"]) == [None]
    assert s["scope_s"][None] == pytest.approx(s["busy_s"], abs=1e-5)
    ctx = {"queries": 2, "trace": s, "shuffled": [1.0, 1.0]}
    assert bench.metric_reader("probe_ms_per_query")(ctx) is None
    assert bench.metric_reader("serving_idle_ms_per_query")(ctx) is None


# the recorded trace's window, busy time and seconds per scope, as the
# chip run that recorded it read them (bench/trace_layers.py)
WINDOW_S, BUSY_S = 1.332976074, 1.276301679
SCOPE_S = {None: 0.02108606, "partition": 0.551176615,
           "shuffle": 0.152383033, "sort": 0.000386214,
           "probe": 0.044798122, "emit": 0.439234151, "groupby": 0.067237484}
SERVING_IDLE_MS, PALLAS_MS = 26.217756, 2.387734


def test_reduce_on_a_recorded_v5e_trace_with_scopes(bench, layers,
                                                    tracing):
    """Two queries of amazon_k16.agg at scale 8 on one v5e chip, with
    the program's scopes and the engine's spans: the scopes cover all
    but 2% of the busy time, and the engine's host work (mostly
    ``engine.prep``) is most of the idle time."""
    with gzip.open(DATA / "v5e_amazon_k16_agg_layers.json.gz", "rt") as f:
        events = json.load(f)
    s = layers.reduce(events)
    assert {k: s[k] for k in tracing.reduce(events)} == \
        tracing.reduce(events)
    assert s["devices"] == 1
    assert s["window_s"] == pytest.approx(WINDOW_S)
    assert s["busy_s"] == pytest.approx(BUSY_S)
    assert sum(s["scope_s"].values()) == pytest.approx(s["busy_s"],
                                                       abs=1e-5)
    assert {k: v for k, v in s["scope_s"].items()} == pytest.approx(SCOPE_S)
    assert s["scope_s"][None] < 0.02 * s["busy_s"]
    idle = s["window_s"] - s["busy_s"]
    engine = sum(v for k, v in s["gaps_by_span"].items()
                 if k.startswith("engine."))
    assert s["gaps_by_span"]["engine.prep"] > 0.5 * idle
    assert engine > 0.8 * idle
    assert {sp[3] for sp in events["spans"]} == {1, 2}
    ctx = {"queries": 2, "trace": s, "shuffled": [13129.0] * 2}
    read = bench.metric_reader
    assert read("serving_idle_ms_per_query")(ctx) == pytest.approx(
        SERVING_IDLE_MS)
    assert read("groupby_ms_per_query")(ctx) >= \
        read("pallas_ms_per_query")(ctx)
    assert read("pallas_ms_per_query")(ctx) == pytest.approx(PALLAS_MS)
