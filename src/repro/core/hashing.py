"""Bucket hash functions h and g used by the join algorithms.

The paper requires two independent hash functions ``h`` (k1 buckets, on
join attribute B) and ``g`` (k2 buckets, on join attribute C).  We use
salted multiplicative (Fibonacci) hashing on uint32, which is cheap on
TPU (one multiply + shift) and mixes well for the integer node ids of
edge-list relations.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# Plain Python ints (NOT jnp arrays): module-level jnp constants would
# capture the sharding context of their first trace and poison later
# traces under a different mesh.
_KNUTH = 2654435761  # 2^32 / phi
_SALTS = (0x9E3779B9, 0x85EBCA6B, 0xC2B2AE35, 0x27D4EB2F)


@jax.named_scope("join.partition")
def bucket_hash(x: jnp.ndarray, n_buckets: int, salt: int = 0) -> jnp.ndarray:
    """Hash int keys into [0, n_buckets) with a salted multiplicative hash.

    64-bit keys (x64 mode) fold high xor low word first, so ids that
    differ only above bit 31 stop colliding; 32-bit keys hash as before
    bit-for-bit (the fold is the identity when the high word is zero
    — and int32 inputs have no high word at all)."""
    if x.dtype.itemsize == 8:
        u64 = x.astype(jnp.uint64)
        x = (u64 ^ (u64 >> jnp.uint64(32))).astype(jnp.uint32)
    u = x.astype(jnp.uint32)
    u = (u ^ jnp.uint32(_SALTS[salt % len(_SALTS)])) * jnp.uint32(_KNUTH)
    u = u ^ (u >> jnp.uint32(15))
    u = u * jnp.uint32(0x846CA68B)
    u = u ^ (u >> jnp.uint32(13))
    return (u % jnp.uint32(n_buckets)).astype(jnp.int32)


def h(x: jnp.ndarray, k1: int) -> jnp.ndarray:
    """The paper's ``h`` — buckets attribute B into k1 reducer rows."""
    return bucket_hash(x, k1, salt=0)


def g(x: jnp.ndarray, k2: int) -> jnp.ndarray:
    """The paper's ``g`` — buckets attribute C into k2 reducer columns."""
    return bucket_hash(x, k2, salt=1)
