"""Pallas TPU kernel: fused hash + per-block bucket histogram.

The map phase of every join round (paper §III–IV) hashes each tuple's
key and routes it to a reducer.  The partition plan needs per-block
bucket histograms (block offsets then follow from an exclusive scan).
TPU adaptation of the radix-partition counting pass: the salted
multiplicative hash runs on the VPU, and the histogram is a one-hot
reduction shaped for the 8×128 vector registers — no scalar loop, no
atomics (the GPU formulation), one pass over HBM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import backend as _backend

# Block row of the (1, N) lane layout; a typed int32 so that index maps
# stay 32-bit under x64.
_ROW = np.int32(0)

_KNUTH = 2654435761
_SALTS = (0x9E3779B9, 0x85EBCA6B, 0xC2B2AE35, 0x27D4EB2F)


def _fold_u32(x: jnp.ndarray) -> jnp.ndarray:
    """Keys as the 32-bit words the hash mixes: 64-bit keys fold high
    xor low word (``repro.core.hashing.bucket_hash``'s convention), so
    the kernel itself only ever sees 32-bit lanes."""
    if x.dtype.itemsize == 8:
        u64 = x.astype(jnp.uint64)
        return (u64 ^ (u64 >> jnp.uint64(32))).astype(jnp.uint32)
    return x.astype(jnp.uint32)


def _bucket_hash(u: jnp.ndarray, n_buckets: int, salt: int) -> jnp.ndarray:
    """Must match repro.core.hashing.bucket_hash bit-for-bit (on keys
    already folded to 32 bits by :func:`_fold_u32`)."""
    u = u.astype(jnp.uint32)
    u = (u ^ jnp.uint32(_SALTS[salt % len(_SALTS)])) * jnp.uint32(_KNUTH)
    u = u ^ (u >> jnp.uint32(15))
    u = u * jnp.uint32(0x846CA68B)
    u = u ^ (u >> jnp.uint32(13))
    return (u % jnp.uint32(n_buckets)).astype(jnp.int32)


def _kernel(keys_ref, valid_ref, out_ref, *, n_buckets: int, k_pad: int,
            salt: int, block: int):
    keys = keys_ref[0, :]
    valid = valid_ref[0, :] != 0
    b = _bucket_hash(keys, n_buckets, salt)
    b = jnp.where(valid, b, jnp.int32(k_pad))  # invalid rows: outside [0, k_pad)
    onehot = (
        b[:, None] == jax.lax.broadcasted_iota(jnp.int32, (block, k_pad), 1)
    ).astype(jnp.float32)
    hist = jnp.sum(onehot, axis=0, keepdims=True)  # (1, k_pad)
    out_ref[...] = hist.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("n_buckets", "salt", "block",
                                             "interpret"))
def hash_histogram(keys: jnp.ndarray, valid: jnp.ndarray, n_buckets: int, *,
                   salt: int = 0, block: int = 1024,
                   interpret: bool = False) -> jnp.ndarray:
    """Fused bucket_hash + per-block histogram.

    keys/valid: (N,) int32|int64/bool.  Returns (ceil(N/block), n_buckets)
    int32 counts; column j of row i counts block-i keys hashing to
    bucket j.

    Inputs are laid out along the lane axis, ``(1, N)`` tiled by
    ``(1, block)`` blocks, and each block's histogram row lands at lane
    offset ``i * k_pad`` of a ``(1, n_blocks * k_pad)`` output: Mosaic
    needs a block's last two dims (8, 128)-aligned or equal to the
    array's, which a ``(1, block)`` tile of an ``(n_blocks, block)``
    array is not.
    """
    n = keys.shape[0]
    block = min(block, max(128, 1 << (max(n, 1) - 1).bit_length()))
    pad_n = -n % block
    keys_p = jnp.pad(_fold_u32(keys), (0, pad_n))
    valid_p = jnp.pad(valid.astype(jnp.int32), (0, pad_n))
    n_blocks = (n + pad_n) // block
    k_pad = max(128, -(-n_buckets // 128) * 128)

    out = pl.pallas_call(
        functools.partial(_kernel, n_buckets=n_buckets, k_pad=k_pad,
                          salt=salt, block=block),
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((1, block), lambda i: (_ROW, i)),
            pl.BlockSpec((1, block), lambda i: (_ROW, i)),
        ],
        out_specs=pl.BlockSpec((1, k_pad), lambda i: (_ROW, i)),
        out_shape=jax.ShapeDtypeStruct((1, n_blocks * k_pad), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="hash_histogram",
    )(keys_p.reshape(1, -1), valid_p.reshape(1, -1))
    return out.reshape(n_blocks, k_pad)[:, :n_buckets]


def bucket_counts(keys: jnp.ndarray, valid: jnp.ndarray, n_buckets: int, *,
                  salt: int = 0, block: int = 1024,
                  backend: str = "auto") -> jnp.ndarray:
    """Global bucket-load histogram of one map-phase shuffle hop.

    This is how the chain-join executor sizes and diagnoses a round:
    the histogram's max is the most-loaded reducer (skew).  On TPU the
    fused Pallas hash+histogram kernel does it in one pass over HBM;
    elsewhere (CPU tests, SimGrid under vmap) an equivalent jnp
    scatter-add with bit-identical hash semantics
    (``backend`` as in ``repro.kernels.backend``).
    """
    b = _backend.resolve(backend)
    if b != "ref":
        return hash_histogram(keys, valid, n_buckets, salt=salt, block=block,
                              interpret=(b == "interpret")).sum(axis=0)
    b = _bucket_hash(_fold_u32(keys), n_buckets, salt)
    return (jnp.zeros((n_buckets,), jnp.int32)
            .at[b].add(valid.astype(jnp.int32), mode="drop"))


def partition_offsets(histogram: jnp.ndarray) -> jnp.ndarray:
    """Exclusive scan over (blocks × buckets) histograms -> the global
    write offset of each (block, bucket) run (bucket-major layout), i.e.
    the shuffle send-buffer plan."""
    per_bucket = jnp.cumsum(histogram.sum(axis=0))
    bucket_base = jnp.concatenate([jnp.zeros((1,), per_bucket.dtype),
                                   per_bucket[:-1]])
    within = jnp.cumsum(histogram, axis=0) - histogram
    return bucket_base[None, :] + within
