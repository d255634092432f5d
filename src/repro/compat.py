"""Shim over the jax API surface the engine calls in one fixed form.

``shard_map`` lives at ``jax.shard_map`` and spells its replication
check ``check_vma``; the engine always turns the check off.
"""

from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = False):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)
