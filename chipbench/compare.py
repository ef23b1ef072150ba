"""The comparison that decides ``correct``.

An engine answer is held against its shape's plain pandas reference,
computed in float64 on the frames the tables were loaded from.  Three
numbers come out, each with a limit of its own (``LIMITS``):

``max_rel_gap``
    the widest gap, over every floating cell of every compared answer,
    between the engine's value and the reference's, relative to the
    reference's.  The configurations promise exact SQL answers with DOUBLE
    aggregates, so the only room is float64's own: a sum of n doubles taken
    in another order may differ by n x 2**-53 of it, 6.7e-10 at SF1's six
    million rows.  The limit is 3e-9.  Sound runs read 1.24e-10 on the chip
    wherever Q1 is in the mix, the same at every seed: the eager tier's
    answer to Q1's first text, whose AVG(l_discount) is over a column the
    seed does not move; every other answer read at most 5.2e-13.  The
    control (the reference on float32 columns) reads at least 6.3e-8 over
    the answers a run compares (PERF.md, "How correct is decided").  PR
    23's finding 4, an aggregate through the MXU at default precision,
    read 8.9e-6.
``mismatched_cells``
    cells of integer, string or date columns that differ, plus one for each
    answer whose shape (columns, rows) differs.  Exact: limit 0.
``errors``
    queries that raised, were refused, or took longer than the mix's
    deadline.  Limit 0.
"""
from __future__ import annotations

import numpy as np
import pandas as pd

LIMITS = {"max_rel_gap": 3e-9, "mismatched_cells": 0, "errors": 0}


def compare_frames(got: pd.DataFrame, want: pd.DataFrame):
    """(widest relative gap of a floating cell, differing exact cells).
    Columns and rows are positional: both follow the SELECT list, and
    every multi-row shape has an ORDER BY on a unique key."""
    if got is None or got.shape != want.shape:
        return 0.0, 1
    gap, mismatched = 0.0, 0
    for i in range(want.shape[1]):
        w, g = want.iloc[:, i], got.iloc[:, i]
        if pd.api.types.is_float_dtype(w):
            w = w.to_numpy(np.float64)
            g = pd.to_numeric(g, errors="coerce").to_numpy(np.float64)
            both_nan = np.isnan(w) & np.isnan(g)
            rel = np.abs(g - w) / np.maximum(np.abs(w), 1e-300)
            rel = np.where(both_nan, 0.0, rel)
            mismatched += int(np.isnan(rel).sum())
            if (~np.isnan(rel)).any():
                gap = max(gap, float(np.nanmax(rel)))
        elif pd.api.types.is_integer_dtype(w):
            g = pd.to_numeric(g, errors="coerce").to_numpy(np.float64)
            mismatched += int((g != w.to_numpy(np.float64)).sum())
        else:
            mismatched += int((g.astype(str).to_numpy()
                               != w.astype(str).to_numpy()).sum())
    return gap, mismatched


def verdict(max_rel_gap: float, mismatched_cells: int, errors: int):
    """(correct, [(name, value, limit)]): every number beside its limit."""
    readings = [("max_rel_gap", max_rel_gap), ("mismatched_cells",
                mismatched_cells), ("errors", errors)]
    lines = [(name, value, LIMITS[name]) for name, value in readings]
    return all(value <= limit for _, value, limit in lines), lines
