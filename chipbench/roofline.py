"""Peaks by device kind, and the bytes a shape's scan has to read."""
from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks_for(device_kind: str) -> dict:
    """The published peaks of ``device_kind``.  A device that is not in
    ``peaks.json`` is an error, not a default."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise SystemExit(f"chipbench: no peaks for device kind "
                         f"{device_kind!r} in chipbench/peaks.json")
    return table[device_kind]


def catalog_columns(context) -> dict:
    """{table: {column: (rows, itemsize)}} of the arrays the catalog holds
    on the device, from their own shapes and dtypes."""
    out = {}
    for name, entry in context.schema[context.schema_name].tables.items():
        out[name] = {col_name: (int(col.data.shape[0]),
                                int(col.data.dtype.itemsize))
                     for col_name, col in zip(entry.table.names,
                                              entry.table.columns)}
    return out


def scan_bytes(scan_columns: dict, catalog: dict) -> int:
    """Bytes one pass over ``scan_columns`` ({table: columns}) reads:
    rows x itemsize of each column, as the catalog holds it."""
    total = 0
    for table, columns in scan_columns.items():
        for column in columns:
            rows, itemsize = catalog[table][column]
            total += rows * itemsize
    return total


def scan_roofline_share(run: dict, shape: str):
    """The least time the chip could take for ``shape`` -- the bytes its
    scan has to read over the peak HBM bandwidth -- as a share (%) of the
    device-busy time of one request of it, the median over the traced
    requests.  None where the trace holds no whole request of the shape."""
    trace = run["trace"]
    if trace is None or shape not in trace["median_busy_s_by_shape"]:
        return None
    least_s = run["scan_bytes"][shape] / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / trace["median_busy_s_by_shape"][shape]
