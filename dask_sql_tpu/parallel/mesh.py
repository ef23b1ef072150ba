"""Device mesh helpers: the SPMD substrate replacing dask.distributed.

The reference scales by partitioned dataframes on a dynamic task scheduler
(SURVEY §2.3); here tables shard row-wise over a 1-D ``jax.sharding.Mesh``
axis ("data" — the SQL analogue of data parallelism), and per-query-stage
compiled SPMD programs use XLA collectives over ICI instead of task shuffles:
``all_to_all`` for hash exchange (join/groupby/sort), ``psum``/``all_gather``
for aggregations and small build-side broadcasts, ``ppermute`` for
sort/window boundary exchange.  Multi-host attaches via
``jax.distributed.initialize`` + the same mesh spanning hosts (DCN).
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

ROW_AXIS = "data"


def default_mesh(n_devices: Optional[int] = None) -> Mesh:
    """1-D row mesh over the first n devices (all by default)."""
    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    return Mesh(np.array(devices), (ROW_AXIS,))


def row_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P(ROW_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def pad_to_multiple(n: int, k: int) -> int:
    return ((n + k - 1) // k) * k


def shard_table_with_validity(table, mesh: Mesh):
    """Mesh-mode catalog placement: pad rows to device-count divisibility,
    row-shard every column, and return a row-validity mask (same sharding)
    marking the real rows. Column NULL masks are untouched — padding
    visibility is a TABLE property (COUNT(*) must not see pad rows), which
    the compiled executor's validity-mask pipeline consumes directly
    (physical/traced.py _VT)."""
    import jax.numpy as jnp

    from ..table import Column, Table

    n = table.num_rows
    k = mesh.devices.size
    padded = pad_to_multiple(max(n, 1), k)
    sh = row_sharding(mesh)
    pad = padded - n
    cols = []
    for c in table.columns:
        data = c.data
        mask = c.mask
        if pad:
            data = jnp.concatenate([data, jnp.zeros(pad, dtype=data.dtype)])
            if mask is not None:
                mask = jnp.concatenate([mask, jnp.zeros(pad, dtype=bool)])
        data = jax.device_put(data, sh)
        if mask is not None:
            mask = jax.device_put(mask, sh)
        cols.append(Column(data, c.stype, mask, c.dictionary))
    row_valid = jax.device_put(
        jnp.arange(padded) < n, sh) if pad else None
    return Table(list(table.names), cols), row_valid


def shard_table(table, mesh: Mesh):
    """Place every column row-sharded on the mesh (pads to divisibility).

    Returns (padded_table, valid_row_count).  Padding rows are masked invalid
    so kernels that respect masks ignore them; count-style kernels must slice
    to ``valid_row_count``.
    """
    import jax.numpy as jnp

    from ..table import Column, Table

    n = table.num_rows
    k = mesh.devices.size
    padded = pad_to_multiple(max(n, 1), k)
    sh = row_sharding(mesh)
    cols = []
    for c in table.columns:
        data = c.data
        mask = c.valid_mask() if (c.mask is not None or padded != n) else None
        if padded != n:
            pad = padded - n
            data = jnp.concatenate([data, jnp.zeros(pad, dtype=data.dtype)])
            if mask is not None:
                mask = jnp.concatenate([mask, jnp.zeros(pad, dtype=bool)])
        data = jax.device_put(data, sh)
        if mask is not None:
            mask = jax.device_put(mask, sh)
        cols.append(Column(data, c.stype, mask, c.dictionary))
    return Table(list(table.names), cols), n


def init_multihost(coordinator_address: Optional[str] = None,
                   num_processes: Optional[int] = None,
                   process_id: Optional[int] = None) -> Mesh:
    """Attach this host to a multi-host mesh (DCN) and return the row mesh.

    The reference attaches a `dask.distributed.Client` to an external
    scheduler (SURVEY §2.3, fixtures.py:291-297); the SPMD equivalent is
    ``jax.distributed.initialize`` — every host runs the same driver
    program, the mesh spans all hosts' devices, and XLA routes collectives
    over ICI within a slice and DCN across slices. On a single host (or
    under test) this degrades to the local mesh.
    """
    if coordinator_address is not None:
        try:
            jax.distributed.initialize(coordinator_address=coordinator_address,
                                       num_processes=num_processes,
                                       process_id=process_id)
        except RuntimeError as e:
            # already initialized: degrade to the existing mesh, as promised
            if "already" not in str(e).lower():
                raise
    return default_mesh()
