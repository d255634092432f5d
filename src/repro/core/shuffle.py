"""The shuffle layer: MapReduce's sort/shuffle guarantee on a device grid.

Algorithms in ``two_way.py`` / ``one_round.py`` are written once against
the :class:`Grid` interface and run on either backend:

* :class:`SimGrid` — a *simulated* reducer grid: device axes are leading
  array axes, collectives are transposes/broadcasts, per-device code is
  ``vmap``-ed.  Runs on one CPU device; used by tests and by the
  paper-reproduction benchmarks (exact KVP accounting, any grid size).
* :class:`ShardGrid` — the production backend: code runs inside
  ``shard_map`` over a real mesh, collectives are ``lax.all_to_all`` /
  ``lax.all_gather`` / ``lax.psum``.  Used by the launcher and dry-run.

The correspondence is exact: for every method, SimGrid's global-view
semantics equal ShardGrid's per-shard semantics, which is asserted by
tests/test_shuffle_equivalence.py on a multi-device CPU subprocess.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..compat import shard_map
from .local import partition
from .relation import Relation, flatten_leading


class Grid:
    """Abstract k1×...×kn reducer grid."""

    shape: Tuple[int, ...]

    def map_devices(self, fn: Callable, *args):
        raise NotImplementedError

    def all_to_all(self, x, grid_axis: int):
        """Per-device x has leading axis of size shape[grid_axis] (bucket-
        major send buffer); returns same shape, leading axis = source."""
        raise NotImplementedError

    def all_gather(self, x, grid_axis: int):
        """Replicate per-device x along a grid axis -> leading axis=source."""
        raise NotImplementedError

    def reduce_any(self, x):
        """OR-reduce a per-device bool scalar across the whole grid."""
        raise NotImplementedError

    def reduce_sum(self, x):
        raise NotImplementedError


class SimGrid(Grid):
    """Simulated grid: arrays carry the grid axes as leading dims."""

    def __init__(self, shape: Sequence[int]):
        self.shape = tuple(shape)

    @property
    def ndim(self):
        return len(self.shape)

    def map_devices(self, fn, *args):
        f = fn
        for _ in self.shape:
            f = jax.vmap(f)
        return f(*args)

    @jax.named_scope("join.shuffle")
    def all_to_all(self, x, grid_axis: int):
        # global x: (*grid, K_dest, ...) -> swap grid axis with bucket axis.
        def swap(a):
            return jnp.swapaxes(a, grid_axis, self.ndim)
        return jax.tree.map(swap, x)

    @jax.named_scope("join.shuffle")
    def all_gather(self, x, grid_axis: int):
        # global x: (*grid, ...) -> (*grid, K_src, ...) with
        # out[g0..gn-1, s, ...] = x[g with coordinate grid_axis replaced by s]
        K = self.shape[grid_axis]

        def gather(a):
            # move the source coordinate to sit right after the grid axes
            src_last = jnp.moveaxis(a, grid_axis, self.ndim - 1)
            # re-insert a broadcast "destination" axis at grid_axis
            expanded = jnp.expand_dims(src_last, grid_axis)
            shape = list(expanded.shape)
            shape[grid_axis] = K
            return jnp.broadcast_to(expanded, tuple(shape))
        return jax.tree.map(gather, x)

    @jax.named_scope("join.shuffle")
    def reduce_any(self, x):
        return jax.tree.map(lambda a: jnp.any(a, axis=tuple(range(self.ndim))), x)

    @jax.named_scope("join.shuffle")
    def reduce_sum(self, x):
        return jax.tree.map(lambda a: jnp.sum(a, axis=tuple(range(self.ndim))), x)


class ShardGrid(Grid):
    """Production grid: runs inside shard_map over mesh axes ``axis_names``.
    A grid axis may span several mesh axes (e.g. ("pod","data") as k1)."""

    def __init__(self, mesh, axis_names: Sequence):
        self.mesh = mesh
        self.axis_names = tuple(axis_names)

        def size(a):
            if isinstance(a, str):
                return mesh.shape[a]
            n = 1
            for sub in a:
                n *= mesh.shape[sub]
            return n

        self.shape = tuple(size(a) for a in self.axis_names)

    def map_devices(self, fn, *args):
        return fn(*args)  # shard_map body is already per-device

    @jax.named_scope("join.shuffle")
    def all_to_all(self, x, grid_axis: int):
        name = self.axis_names[grid_axis]
        return jax.tree.map(
            lambda a: jax.lax.all_to_all(a, name, split_axis=0, concat_axis=0,
                                         tiled=False), x)

    @jax.named_scope("join.shuffle")
    def all_gather(self, x, grid_axis: int):
        name = self.axis_names[grid_axis]
        return jax.tree.map(
            lambda a: jax.lax.all_gather(a, name, axis=0, tiled=False), x)

    @property
    def _flat_axes(self):
        out = []
        for a in self.axis_names:
            out.extend([a] if isinstance(a, str) else list(a))
        return tuple(out)

    @jax.named_scope("join.shuffle")
    def reduce_any(self, x):
        return jax.tree.map(
            lambda a: jax.lax.psum(a.astype(jnp.int32), self._flat_axes) > 0, x)

    @jax.named_scope("join.shuffle")
    def reduce_sum(self, x):
        return jax.tree.map(lambda a: jax.lax.psum(a, self._flat_axes), x)

    def run(self, fn: Callable, *args, in_specs=None, out_specs=None):
        """Launch ``fn(grid, *args)`` under shard_map on this mesh."""
        in_specs = in_specs if in_specs is not None else P(self.axis_names[0])
        out_specs = out_specs if out_specs is not None else P(self.axis_names[0])
        body = functools.partial(fn, self)
        return shard_map(
            body, mesh=self.mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False)(*args)


# ---------------------------------------------------------------------------
# Fault-injection hook (repro.resilience.faults)
# ---------------------------------------------------------------------------

#: When a :class:`~repro.resilience.faults.FaultInjector` is installed,
#: every shuffle hop offers it the received payload at the "shuffle"
#: site — the injector may delay, raise a typed fault, or pass the
#: payload through.  ``None`` (the default) costs one attribute read
#: per hop and nothing else; the hook itself never fires under jit
#: tracing (the injector skips tracer payloads), so compiled programs
#: are never poisoned by trace-time draws.
_fault_hook = None


def set_fault_hook(hook) -> None:
    """Install (or, with ``None``, remove) the module's fault hook —
    called by ``FaultInjector.install()`` / ``uninstall()``, never
    directly."""
    global _fault_hook
    _fault_hook = hook


def _inject(site: str, payload):
    if _fault_hook is None:
        return payload
    return _fault_hook(site, payload)


# ---------------------------------------------------------------------------
# The live-row counter
# ---------------------------------------------------------------------------

#: Stats keys of the live-row counter: valid rows, and static capacity
#: in rows, summed over every buffer a query fills (the receive and
#: compacted buffers of each shuffle hop or broadcast, each local
#: join's output, each group-by's output), over the whole grid.
FILL_KEYS = ("live_rows", "buffer_rows")


def buffer_fill(grid: Grid, *rels: Relation) -> Dict[str, jnp.ndarray]:
    """The live-row counter of the grid-level buffers ``rels``, counted
    where they are produced: their valid rows over the grid and their
    capacity in rows.  Stats units, so ``merge_stats`` sums them
    across rounds."""
    if not rels:
        return {k: jnp.zeros((), jnp.float32) for k in FILL_KEYS}
    n_dev = math.prod(grid.shape)
    per_dev = sum(grid.map_devices(lambda r: r.count(), r) for r in rels)
    return {"live_rows": grid.reduce_sum(per_dev).astype(jnp.float32),
            "buffer_rows": jnp.float32(
                n_dev * sum(r.capacity for r in rels))}


def add_fill(*fills: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
    """Sum live-row counters."""
    return {k: sum(f[k] for f in fills) for k in FILL_KEYS}


# ---------------------------------------------------------------------------
# Distributed shuffle: the MapReduce sort/shuffle guarantee
# ---------------------------------------------------------------------------

@jax.named_scope("join.shuffle")
def compact_to(grid: Grid, rel: Relation, capacity: int):
    """Per-device: move valid rows to the front and shrink the buffer to
    ``capacity`` (the reducer's memory budget).  Returns (rel, overflow)."""

    def one(r: Relation):
        ovf = r.count() > capacity
        return r.compact(capacity), ovf

    out, ovf = grid.map_devices(one, rel)
    return out, jnp.any(grid.reduce_any(ovf))


@jax.named_scope("join.shuffle")
def shuffle_by_bucket(grid: Grid, rel: Relation, bucket, grid_axis: int,
                      recv_capacity: int, local_capacity: int | None = None):
    """Move every tuple to the device whose index along ``grid_axis``
    equals its bucket — the same-key→same-reducer guarantee.

    ``bucket`` is per-device (capacity,) int32 (already hashed to
    [0, shape[grid_axis])).  ``recv_capacity`` is per (device, source)
    slot capacity.  The received K×recv buffers are compacted to
    ``local_capacity`` (defaults to K·recv = lossless).  Returns
    (local Relation, overflow flag (global), the live-row counter of the
    receive buffer and, where compacted, the local buffer).
    """
    K = grid.shape[grid_axis]

    def send(r: Relation, b):
        return partition(r, b, K, recv_capacity)

    buf, ovf = grid.map_devices(send, rel, bucket)
    recv = grid.all_to_all(buf, grid_axis)
    recv = _inject("shuffle", recv)
    local = grid.map_devices(flatten_leading, recv)
    filled = [local]
    overflow = jnp.any(grid.reduce_any(ovf))
    if local_capacity is not None and local_capacity < K * recv_capacity:
        local, ovf_c = compact_to(grid, local, local_capacity)
        overflow = overflow | ovf_c
        filled.append(local)
    return local, overflow, buffer_fill(grid, *filled)


# ---------------------------------------------------------------------------
# Overlapped (chunked) shuffle schedule
# ---------------------------------------------------------------------------
#
# The staged executor blocks every reduce step on one completed
# all-to-all.  The overlapped schedule instead splits a relation's rows
# into C contiguous blocks and shuffles each block as its *own*
# independent op chain: block b+1's collective has no data dependency
# on block b's local join, so within one jitted program XLA is free to
# run them concurrently (ShardGrid: async collectives overlap compute;
# SimGrid: the identical block schedule, so results and tuple
# accounting are bit-equal and deterministic).  The blocks partition
# the rows exactly, so per-hop received counts sum to the unchunked
# count — measured==analytic accounting is unchanged.

def split_rows(rel: Relation, chunks: int):
    """Partition a relation's rows (the trailing capacity axis — works
    on flat, grid-leading, and shard-local layouts alike) into
    ``chunks`` contiguous blocks.  Valid rows need not be front-packed;
    positional slicing still partitions them exactly."""
    cap = rel.capacity
    chunks = max(1, min(int(chunks), cap))
    bounds = [(c * cap) // chunks for c in range(chunks + 1)]
    out = []
    for a, b in zip(bounds[:-1], bounds[1:]):
        cols = {n: c[..., a:b] for n, c in rel.cols.items()}
        out.append(Relation(cols, rel.valid[..., a:b]))
    return out


def concat_rows(rels) -> Relation:
    """Concatenate relations along the trailing capacity axis — the
    inverse of :func:`split_rows` up to row order (used to merge the
    per-chunk join outputs before the final compaction)."""
    rels = list(rels)
    names = rels[0].names
    cols = {n: jnp.concatenate([r.cols[n] for r in rels], axis=-1)
            for n in names}
    valid = jnp.concatenate([r.valid for r in rels], axis=-1)
    return Relation(cols, valid)


@jax.named_scope("join.shuffle")
def broadcast_along(grid: Grid, rel: Relation, grid_axis: int,
                    local_capacity: int | None = None):
    """Replicate a per-device relation along a grid axis (the 1,3J
    "row/column replication" of R and T).  Each device ends with the
    concatenation of all shards along that axis; the per-device tuple
    count multiplies by shape[grid_axis] — exactly the k·|rel|
    communication cost the paper charges.  Optionally compacts the
    result to ``local_capacity``.  Returns (rel, overflow, the live-row
    counter of the gathered buffer and, where compacted, the local
    buffer)."""
    gathered = grid.all_gather(rel, grid_axis)
    gathered = _inject("shuffle", gathered)
    out = grid.map_devices(flatten_leading, gathered)
    if local_capacity is not None:
        local, ovf = compact_to(grid, out, local_capacity)
        return local, ovf, buffer_fill(grid, out, local)
    return out, jnp.zeros((), jnp.bool_), buffer_fill(grid, out)
