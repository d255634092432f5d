"""Jit'd dispatch wrappers over the Pallas kernels (backend policy:
``repro.kernels.backend``)."""

from __future__ import annotations

import jax.numpy as jnp

from . import backend as _backend
from . import flash_attention as _fa
from . import hash_partition as _hp
from . import ref
from . import segment_sum as _ss


def segment_sum(values, segment_ids, num_segments: int,
                backend: str = "auto", *,
                indices_are_sorted: bool = False) -> jnp.ndarray:
    b = _backend.resolve(backend)
    if b == "ref":
        return ref.segment_sum(values.astype(jnp.float32), segment_ids,
                               num_segments)
    return _ss.segment_sum(values, segment_ids, num_segments,
                           indices_are_sorted=indices_are_sorted,
                           interpret=(b == "interpret"))


def hash_histogram(keys, valid, n_buckets: int, *, salt: int = 0,
                   block: int = 1024, backend: str = "auto") -> jnp.ndarray:
    b = _backend.resolve(backend)
    if b == "ref":
        n = keys.shape[0]
        block_r = min(block, max(128, 1 << (max(n, 1) - 1).bit_length()))
        pad = -n % block_r
        return ref.masked_hash_histogram(
            jnp.pad(keys, (0, pad)), jnp.pad(valid, (0, pad)),
            n_buckets, salt=salt, block=block_r)
    return _hp.hash_histogram(keys, valid, n_buckets, salt=salt, block=block,
                              interpret=(b == "interpret"))


def flash_attention(q, k, v, *, causal: bool = True, scale=None,
                    backend: str = "auto", block_q: int = 128,
                    block_kv: int = 128) -> jnp.ndarray:
    b = _backend.resolve(backend)
    if b == "ref":
        return ref.attention(q, k, v, causal=causal, scale=scale)
    return _fa.flash_attention(q, k, v, causal=causal, scale=scale,
                               block_q=block_q, block_kv=block_kv,
                               interpret=(b == "interpret"))
