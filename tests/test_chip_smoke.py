"""chip_smoke.py rehearsed on the CPU: its phase functions at a tiny
scale with the Pallas kernels in interpret mode, its four-chip phase on
four emulated CPU devices, and its refusal to run without a TPU.

The test steers the kernels itself: it swaps the one backend policy
(``repro.kernels.backend.resolve``) so that ``"auto"`` means
``"interpret"``, and drops the executor's compiled-program caches on
both sides so no program traced under one policy serves the other.
"""

import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.core import chain_stats_exact, clear_compiled_caches
from repro.kernels import backend

ROOT = Path(__file__).resolve().parents[1]
SCALE = 6


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def interpret_kernels(monkeypatch):
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


@pytest.fixture(scope="module")
def graph(smoke):
    src, dst = smoke.make_graph(SCALE, 0)
    return src, dst, chain_stats_exact([(src, dst)] * 3)


@pytest.mark.parametrize("join_impl", ["sort_merge", "fused"])
def test_batch_phase_rehearsal(smoke, graph, interpret_kernels, join_impl):
    src, dst, stats = graph
    r = smoke.batch_phase(src, dst, stats, join_impl=join_impl)
    assert r["paths"] == stats.prefix_joins[-1] and not r["overflow"]
    assert r["strategy"] == "cascade_pushdown" and r["grid"] == (4, 4)
    assert interpret_kernels, "no kernel dispatch went through the policy"
    # CPU programs hold interpreted kernels, never a tpu_custom_call
    assert set(r["kernels"]) == set(smoke.KERNELS)
    assert not any(r["kernels"].values())


def test_served_phase_rehearsal(smoke, graph, interpret_kernels):
    src, dst, _ = graph
    r = smoke.served_phase(src, dst)
    assert r["submits"] == 4 and r["cache_hits"] == 3
    assert r["triangles"] > 0


def test_tpu_kernels_reads_custom_calls(smoke):
    text = ('  %segment_sum.3 = f32[1,512] custom-call(%a), '
            'custom_call_target="tpu_custom_call"\n'
            '  %hash_histogram = s32[4] add(%b, %c)\n')
    assert smoke.tpu_kernels(text) == {"hash_histogram": False,
                                       "segment_sum": True,
                                       "probe_counts": False}


def test_main_refuses_without_tpu(smoke, capsys):
    import jax
    before = jax.config.jax_compilation_cache_dir
    try:
        assert smoke.main([]) == 2
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    out = capsys.readouterr()
    assert out.out == "" and "needs a TPU" in out.err


def test_alone_it_fails_without_a_result(tmp_path):
    """Copied away from the repository it cannot import the engine."""
    (tmp_path / "chip_smoke.py").write_text(
        (ROOT / "chip_smoke.py").read_text())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         capture_output=True, text=True, timeout=120,
                         env=env)
    assert out.returncode != 0 and '"ok"' not in out.stdout


def test_shard_phase_on_four_cpu_devices():
    """The four-chip phase on four emulated devices (a subprocess: the
    device count is fixed when JAX starts)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    code = ("import json, jax, chip_smoke as s; "
            "print(json.dumps(s.shard_phase(jax.devices()[:4], scale=6)))")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    r = json.loads(out.stdout.strip().splitlines()[-1])
    for strategy in ("one_round", "cascade"):
        assert r[strategy]["tuples"] == r["paths"]
        assert r[strategy]["devices"] == [0, 1, 2, 3]
