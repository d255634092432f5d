"""Runtime configuration: 64-bit join keys and platform tuning.

JAX defaults to 32-bit integers; billion-vertex graphs alias int32 node
ids (2^31 distinct keys).  :func:`enable_x64` flips jax's ``x64`` mode
— it must run before the first jax computation (dtypes are baked into
traced programs), so production entry points call it first thing, and
tests exercise it in a subprocess (tests/_x64_check.py) to keep the
main process 32-bit.

The ``JAX_ENABLE_X64`` environment variable wins over the in-code
default, matching jax's own convention, so a launcher can flip a whole
job without touching code.

:func:`configure_platform` is the backend half of the overlapped
execution path (docs/overlap.md): it applies the latency-hiding /
async-collective XLA flags that let the chunked shuffle schedule
actually run concurrently on GPU meshes, and
``xla_force_host_platform_device_count`` so 16+-device meshes are
CI-testable on a single CPU host.  Like :func:`enable_x64` it must run
before JAX initializes its backends; afterwards it warns and leaves
the live configuration alone rather than crashing the job.

:func:`enable_compilation_cache` places JAX's persistent compilation
cache: where ``JAX_COMPILATION_CACHE_DIR`` says, else at a fixed
``.jax_cache`` directory of the checkout.
"""

from __future__ import annotations

import os
import warnings
from pathlib import Path

import jax
import jax.numpy as jnp


def enable_x64(use_x64: bool = True) -> bool:
    """Enable (or disable) 64-bit mode, honoring ``JAX_ENABLE_X64``.

    Returns the mode actually set.  Call before any jax computation:
    already-traced programs keep the dtypes they were traced with.
    """
    env = os.getenv("JAX_ENABLE_X64")
    if env is not None:
        use_x64 = env not in ("0", "false", "False", "")
    jax.config.update("jax_enable_x64", bool(use_x64))
    return bool(use_x64)


def x64_enabled() -> bool:
    return bool(jax.config.read("jax_enable_x64"))


def default_key_dtype():
    """Join-key dtype for newly built relations: int64 once x64 is on
    (ids above 2^31 stop aliasing), int32 otherwise."""
    return jnp.int64 if x64_enabled() else jnp.int32


def key_dtype_name() -> str:
    """Canonical name of the current key dtype (``"int32"`` /
    ``"int64"``) — what partitioned-store manifests and
    :class:`~repro.core.cost_model.ChainPartitioning` certificates
    record, so a certificate minted under one x64 configuration is
    rejected (not silently merge-joined on folded hashes) under the
    other."""
    return "int64" if x64_enabled() else "int32"


#: Latency-hiding / async-collective XLA flags for GPU backends — the
#: scheduler half of the overlapped shuffle: collectives issue on their
#: own stream and the scheduler reorders independent work over them.
GPU_OVERLAP_FLAGS = (
    "--xla_gpu_enable_async_collectives=true",
    "--xla_gpu_enable_latency_hiding_scheduler=true",
    "--xla_gpu_enable_highest_priority_async_stream=true",
)


def _jax_initialized() -> bool:
    """Whether JAX has already created a backend client (after which
    XLA flags and the platform name are baked in)."""
    try:
        from jax._src import xla_bridge
        return bool(xla_bridge._backends)
    except Exception:            # pragma: no cover - internals moved
        return False


def _merge_xla_flags(new_flags) -> str:
    """Merge flags into ``XLA_FLAGS``, replacing same-name entries so
    repeated configuration is idempotent and caller overrides win."""
    existing = os.environ.get("XLA_FLAGS", "").split()
    names = {f.split("=", 1)[0] for f in new_flags}
    kept = [f for f in existing if f.split("=", 1)[0] not in names]
    merged = " ".join(kept + list(new_flags))
    os.environ["XLA_FLAGS"] = merged
    return merged


def configure_platform(platform: str | None = None,
                       host_devices: int | None = None) -> bool:
    """Apply the overlap-friendly backend configuration.

    * ``platform`` — pin the JAX platform (``"cpu"`` / ``"gpu"`` /
      ``"tpu"``); ``None`` keeps JAX's own auto-detection.
    * ``host_devices`` — emulate this many CPU devices on one host
      (``--xla_force_host_platform_device_count``), the HomebrewNLP
      trick that makes 16+-device ShardGrid meshes CI-testable without
      hardware.
    * With ``platform="gpu"`` the async-collective / latency-hiding
      flags (:data:`GPU_OVERLAP_FLAGS`) are merged into ``XLA_FLAGS``
      so chunked all-to-alls overlap local join compute.  They are
      *only* added on explicit GPU request: CPU-only XLA builds treat
      unknown ``--xla_gpu_*`` flags in ``XLA_FLAGS`` as fatal.

    Must run before the first JAX computation.  If a backend already
    exists the environment is left untouched: the function **warns and
    returns False** instead of crashing (flags would silently not
    apply), so late callers degrade to the staged behaviour rather
    than killing a serving job.  Returns True when the configuration
    was applied.
    """
    if host_devices is not None and host_devices < 1:
        raise ValueError(f"host_devices must be >= 1, got {host_devices}")
    if _jax_initialized():
        warnings.warn(
            "configure_platform() called after JAX initialized its "
            "backends; XLA flags and device-count changes cannot apply. "
            "Call it before the first jax computation (flags left "
            "unchanged).", RuntimeWarning, stacklevel=2)
        return False
    flags = list(GPU_OVERLAP_FLAGS) if platform == "gpu" else []
    if host_devices is not None:
        flags.append(f"--xla_force_host_platform_device_count="
                     f"{int(host_devices)}")
    if flags:
        _merge_xla_flags(flags)
    if platform is not None:
        jax.config.update("jax_platform_name", platform)
    return True


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, names the directory and
    nothing else is configured (JAX reads the variable itself).
    Otherwise the cache lives at ``<checkout>/.jax_cache`` — a fixed
    path, because the path is part of every entry's key, and one that
    git ignores.  Call it before the first compilation.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(Path(__file__).resolve().parents[2] / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


#: Largest flat pair index the all-pairs join kernel can form without
#: overflowing its int32 arithmetic — `nl * nr` must stay below this.
INT32_PAIR_LIMIT = 2 ** 31

#: Exclusive upper bound on sort-merge output capacities (the kernel's
#: int32 position arithmetic needs out_capacity < 2**30 - 1).
SORT_MERGE_MAX_CAP = 2 ** 30 - 1
