"""Dictionary-cliff benchmark: LIKE over increasing string cardinality.

Review r1 weak-point 4: the dictionary walk is host-bound — fine at TPC-H
cardinalities, a cliff at ~1M distinct values (Q13's comment column).  This
script measures a Q13-shaped predicate (`o_comment NOT LIKE
'%special%requests%'`) end-to-end through Context.sql at several distinct
counts, for each of the three bitmap strategies:

- regex:      per-entry Python regex (the r1 path)
- vectorized: np.strings chunk kernels (host, C loops)
- device:     padded bytes-matrix chunk matching on the accelerator

Usage: python benchmarks/string_cliff.py   (prints one JSON line per cell)
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _make_comments(n_rows: int, n_distinct: int, seed: int = 0) -> np.ndarray:
    rng = np.random.RandomState(seed)
    words = np.array(["special", "requests", "pending", "furious", "ironic",
                      "deposits", "accounts", "packages", "theodolites"])
    parts = words[rng.randint(0, len(words), (n_distinct, 4))]
    distinct = np.array([" ".join(row) + f" #{i}"
                         for i, row in enumerate(parts)], dtype=object)
    return distinct[rng.randint(0, n_distinct, n_rows)]


def main():
    import jax
    if os.environ.get("BENCH_PLATFORM"):
        jax.config.update("jax_platforms", os.environ["BENCH_PLATFORM"])

    import pandas as pd

    from dask_sql_tpu import Context
    from dask_sql_tpu.ops import strings_fast
    from dask_sql_tpu.physical.rex import ops as rex_ops

    n_rows = int(os.environ.get("CLIFF_ROWS", "2000000"))
    reps = int(os.environ.get("CLIFF_REPS", "3"))
    query = ("SELECT COUNT(*) AS n FROM t "
             "WHERE c NOT LIKE '%special%requests%'")

    for n_distinct in (1_000, 30_000, 1_000_000):
        df = pd.DataFrame({"c": _make_comments(n_rows, n_distinct)})
        ctx = Context()
        ctx.create_table("t", df)

        for strategy in ("regex", "vectorized", "device",
                         "device_compiled"):
            if strategy == "regex":
                # force the r1 path: disable both fast bitmaps
                patch = {"like_bitmap_vectorized": lambda *a: None,
                         "threshold": 1 << 62}
            elif strategy == "vectorized":
                patch = {"threshold": 1 << 62}
            else:
                patch = {"threshold": 0}
            compiled_run = strategy == "device_compiled"
            saved = (strings_fast.like_bitmap_vectorized,
                     strings_fast.DEVICE_STRING_THRESHOLD)
            if "like_bitmap_vectorized" in patch:
                strings_fast.like_bitmap_vectorized = \
                    patch["like_bitmap_vectorized"]
            strings_fast.DEVICE_STRING_THRESHOLD = patch["threshold"]
            # ops.py imports names at call time from the module, so the
            # patch above is what the engine sees
            try:
                if not compiled_run:
                    os.environ["DSQL_COMPILE"] = "0"  # eager: per-QUERY cost
                ctx.sql(query)  # warm (dictionary matrix build for device)
                best = float("inf")
                for _ in range(reps):
                    t0 = time.perf_counter()
                    ctx.sql(query, return_futures=False)
                    best = min(best, time.perf_counter() - t0)
            finally:
                os.environ.pop("DSQL_COMPILE", None)
                (strings_fast.like_bitmap_vectorized,
                 strings_fast.DEVICE_STRING_THRESHOLD) = saved
            print(json.dumps({
                "metric": "like_notlike_wall", "n_distinct": n_distinct,
                "n_rows": n_rows, "strategy": strategy,
                "sec": round(best, 4),
            }), flush=True)


if __name__ == "__main__":
    main()
