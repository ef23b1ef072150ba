"""Distributed query kernels: shard_map + XLA collectives over the row mesh.

These are the TPU-native equivalents of the reference's shuffle-backed
operators (SURVEY §2.3): where dask re-partitions dataframes through a
task-graph shuffle (join.py:241 merge, utils/sort.py:82 set_index,
aggregate.py:356 tree reduction), these kernels run ONE compiled SPMD program
per stage:

- ``dist_segment_sum`` — local segment reduction + ``psum`` tree over ICI
  (groupby aggregation when the group-key domain is bounded/known).
- ``hash_exchange`` — radix partition by key hash + ``all_to_all`` (the shuffle
  for large-domain groupby / hash join); static shapes via per-bucket padding.
- ``ring_shift`` — ``ppermute`` neighbor exchange (sort/window boundaries).
- ``dist_join_broadcast`` — ``all_gather`` the (small) build side, local probe
  (the broadcast-join path; skew-free, no exchange).

All are jit-compiled over a Mesh and run on virtual CPU meshes in tests and
the driver's multi-chip dry-run identically to real ICI meshes.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import ROW_AXIS


# ---------------------------------------------------------------------------
# distributed segmented aggregation (groupby)
# ---------------------------------------------------------------------------

def dist_segment_sum(mesh: Mesh, values: jax.Array, codes: jax.Array,
                     num_groups: int) -> jax.Array:
    """Global segment_sum over a row-sharded array: local partials + psum.

    The result is replicated on every device (小 G): the SQL analogue of a
    tree-reduction groupby aggregate.
    """

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(ROW_AXIS), P(ROW_AXIS)), out_specs=P(),
    )
    def kernel(v, c):
        local = jax.ops.segment_sum(v, c, num_groups)
        return jax.lax.psum(local, ROW_AXIS)

    return kernel(values, codes)


def dist_segment_minmax(mesh: Mesh, values: jax.Array, codes: jax.Array,
                        num_groups: int, is_min: bool, sentinel) -> jax.Array:
    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(ROW_AXIS), P(ROW_AXIS)), out_specs=P(),
    )
    def kernel(v, c):
        f = jax.ops.segment_min if is_min else jax.ops.segment_max
        local = f(v, c, num_groups, indices_are_sorted=False)
        local = jnp.where(jnp.isfinite(local) | (local == sentinel), local, sentinel)
        op = jax.lax.pmin if is_min else jax.lax.pmax
        return op(local, ROW_AXIS)

    return kernel(values, codes)


# ---------------------------------------------------------------------------
# hash exchange (the all_to_all shuffle)
# ---------------------------------------------------------------------------

def hash_exchange(mesh: Mesh, codes: jax.Array, *payload: jax.Array
                  ) -> Tuple[jax.Array, ...]:
    """Radix-partition rows by ``hash(code) % n_devices`` and exchange via
    all_to_all so equal keys land on the same device.

    Static shapes: each device sends exactly ``rows_per_device`` slots per
    destination bucket (rows beyond capacity are impossible for balanced
    hashing only in expectation — capacity is the full local length, so no
    row is ever dropped; unused slots carry code -1).

    Returns (new_codes, *new_payload) with shape [n_dev * local] per device —
    i.e. a bucketed re-distribution with -1 padding.  Downstream kernels mask
    on code >= 0.
    """
    n_dev = mesh.devices.size

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(ROW_AXIS),) * (1 + len(payload)),
        out_specs=(P(ROW_AXIS),) * (1 + len(payload)),
    )
    def kernel(c, *vs):
        local = c.shape[0]
        dest = jnp.where(c >= 0, c % n_dev, 0).astype(jnp.int32)
        # stable sort rows by destination; build per-destination slots
        order = jnp.argsort(dest, stable=True)
        sorted_dest = dest[order]
        # position within destination bucket
        ones = jnp.ones_like(sorted_dest)
        pos_in_bucket = jnp.cumsum(ones) - 1
        bucket_start = jnp.searchsorted(sorted_dest, jnp.arange(n_dev))
        pos_in_bucket = pos_in_bucket - bucket_start[sorted_dest]
        # scatter into [n_dev, local] send buffer (-1 padded)
        def scatter(x, fill):
            buf = jnp.full((n_dev, local), fill, dtype=x.dtype)
            return buf.at[sorted_dest, pos_in_bucket].set(x[order])
        c_buf = scatter(c, -1)
        v_bufs = [scatter(v, 0) for v in vs]
        # exchange: dimension 0 is the destination axis
        c_out = jax.lax.all_to_all(c_buf, ROW_AXIS, 0, 0, tiled=False)
        v_outs = [jax.lax.all_to_all(v, ROW_AXIS, 0, 0, tiled=False) for v in v_bufs]
        return (c_out.reshape(-1), *[v.reshape(-1) for v in v_outs])

    return kernel(codes, *payload)


def dist_groupby_sum_exchange(mesh: Mesh, codes: jax.Array, values: jax.Array,
                              num_groups: int) -> jax.Array:
    """Large-domain groupby: hash-exchange rows so each device owns a key
    range, reduce locally, all_gather the per-device partials.

    Returns the global [num_groups] sums replicated on all devices.
    """
    new_codes, new_vals = hash_exchange(mesh, codes, values)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(ROW_AXIS), P(ROW_AXIS)), out_specs=P(),
    )
    def reduce_local(c, v):
        valid = c >= 0
        local = jax.ops.segment_sum(jnp.where(valid, v, 0),
                                    jnp.where(valid, c, 0), num_groups)
        # after exchange each key lives on exactly one device: psum merges the
        # disjoint partials
        return jax.lax.psum(local, ROW_AXIS)

    return reduce_local(new_codes, new_vals)


# ---------------------------------------------------------------------------
# broadcast join (small build side)
# ---------------------------------------------------------------------------

def dist_join_broadcast(mesh: Mesh, probe_codes: jax.Array,
                        build_codes: jax.Array, build_values: jax.Array,
                        default) -> jax.Array:
    """Broadcast-join: all_gather the build side, local sorted probe.

    Returns for each probe row the matching build value (or ``default``) —
    the inner-join gather step for 1:1 build keys (dimension tables).
    """

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(ROW_AXIS), P(ROW_AXIS), P(ROW_AXIS)), out_specs=P(ROW_AXIS),
    )
    def kernel(pc, bc, bv):
        bc_all = jax.lax.all_gather(bc, ROW_AXIS, tiled=True)
        bv_all = jax.lax.all_gather(bv, ROW_AXIS, tiled=True)
        order = jnp.argsort(bc_all, stable=True)
        sc = bc_all[order]
        sv = bv_all[order]
        pos = jnp.searchsorted(sc, pc)
        pos = jnp.clip(pos, 0, sc.shape[0] - 1)
        hit = (sc[pos] == pc) & (pc >= 0)
        return jnp.where(hit, sv[pos], default)

    return kernel(probe_codes, build_codes, build_values)


# ---------------------------------------------------------------------------
# ring boundary exchange (sort / window frames across shards)
# ---------------------------------------------------------------------------

def ring_shift(mesh: Mesh, x: jax.Array, shift: int = 1) -> jax.Array:
    """ppermute neighbor exchange: device i receives from i-shift (ring)."""
    n_dev = mesh.devices.size
    perm = [(i, (i + shift) % n_dev) for i in range(n_dev)]

    @functools.partial(shard_map, mesh=mesh, in_specs=P(ROW_AXIS),
                       out_specs=P(ROW_AXIS))
    def kernel(v):
        return jax.lax.ppermute(v, ROW_AXIS, perm)

    return kernel(x)


def dist_prefix_sum(mesh: Mesh, x: jax.Array) -> jax.Array:
    """Global inclusive prefix sum over a row-sharded array: local cumsum +
    exclusive scan of shard totals via all_gather (windows/LIMIT borders —
    the reference's partition-length cumsum, sort.py:88)."""

    @functools.partial(shard_map, mesh=mesh, in_specs=P(ROW_AXIS),
                       out_specs=P(ROW_AXIS))
    def kernel(v):
        local = jnp.cumsum(v)
        total = local[-1] if v.shape[0] else jnp.zeros((), v.dtype)
        totals = jax.lax.all_gather(total, ROW_AXIS)
        idx = jax.lax.axis_index(ROW_AXIS)
        offset = jnp.where(jnp.arange(totals.shape[0]) < idx, totals, 0).sum()
        return local + offset

    return kernel(x)
