"""The engine's Pallas kernels compile for the TPU, at real sizes.

No chip is needed: the TPU compiler is installed and compiles for a
described ``v5e:2x2`` topology.  Each test compiles one kernel over
2^18 rows, plainly and vmapped over 16 reducers (how ``SimGrid`` runs
it), and checks that the compiled program calls the kernel as a
``tpu_custom_call``.  Interpret-mode tests cannot see what this sees:
block shapes Mosaic refuses, 64-bit lanes the chip does not have.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker
imports this file.
"""

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import fused_join as fj
from repro.kernels import hash_partition as hp
from repro.kernels import segment_sum as ss

ROWS = 1 << 18
REDUCERS = 16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")     # no compiler logs outside
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # A compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep it off.
        was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", was_on)
        cc.reset_cache()


def _shape(sharding, dtype, reducers):
    shape = (ROWS,) if reducers is None else (reducers, ROWS)
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, reducers, *args):
    f = fn if reducers is None else jax.vmap(fn)
    return jax.jit(f).lower(*args).compile()


def _assert_kernel(compiled, name):
    calls = [ln for ln in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert any(name in ln for ln in calls), f"no {name} tpu_custom_call"


@pytest.mark.parametrize("reducers", [None, REDUCERS])
def test_hash_histogram_compiles(one_chip, reducers):
    c = _compile(lambda k, v: hp.hash_histogram(k, v, 16), reducers,
                 _shape(one_chip, jnp.int32, reducers),
                 _shape(one_chip, jnp.bool_, reducers))
    _assert_kernel(c, "hash_histogram")


@pytest.mark.parametrize("reducers", [None, REDUCERS])
def test_segment_sum_compiles(one_chip, reducers):
    c = _compile(lambda v, i: ss.segment_sum(v, i, ROWS // 4,
                                             indices_are_sorted=True),
                 reducers, _shape(one_chip, jnp.float32, reducers),
                 _shape(one_chip, jnp.int32, reducers))
    _assert_kernel(c, "segment_sum")


@pytest.mark.parametrize("reducers", [None, REDUCERS])
def test_probe_counts_compiles(one_chip, reducers):
    c = _compile(lambda q, r: fj.probe_counts_pallas(q, r), reducers,
                 _shape(one_chip, jnp.int32, reducers),
                 _shape(one_chip, jnp.int32, reducers))
    _assert_kernel(c, "probe_counts")


def test_x64_keys_compile_or_refuse(one_chip):
    """Under x64, 64-bit keys reach the histogram and 64-bit ids the
    segment sum as 32-bit lanes; the probe refuses 64-bit keys with a
    TypeError instead of falling back."""
    with jax.enable_x64(True):
        c = _compile(lambda k, v: hp.hash_histogram(k, v, 16), None,
                     _shape(one_chip, jnp.int64, None),
                     _shape(one_chip, jnp.bool_, None))
        _assert_kernel(c, "hash_histogram")
        c = _compile(lambda v, i: ss.segment_sum(v, i, ROWS // 4,
                                                 indices_are_sorted=True),
                     None, _shape(one_chip, jnp.float32, None),
                     _shape(one_chip, jnp.int64, None))
        _assert_kernel(c, "segment_sum")
        keys = jnp.zeros((8,), jnp.int64)
        with pytest.raises(TypeError, match="32-bit keys"):
            fj.probe_counts(keys, keys, backend="pallas")

