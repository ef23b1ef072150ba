"""Streaming x mesh at scale: the SF-10 out-of-core + distributed proof.

The reference's execution model is out-of-core AND distributed by
construction (partitioned dask dataframes over a cluster,
/root/reference/dask_sql/input_utils/convert.py:38-62).  Our equivalent is
``create_table(chunked=True)`` composed with ``Context(mesh=...)``: each host
batch is row-sharded over the mesh, the per-batch compiled program runs as a
GSPMD program, and partials merge by aggregate algebra
(physical/streaming.py).  This script certifies that composition at a scale
factor far above anything resident-in-HBM testing covers:

    python benchmarks/streaming_scale.py          # SF 10, Q1/Q3/Q5/Q6/Q9
    STREAM_SCALE_SF=3 python benchmarks/streaming_scale.py

Round-4 redesign — the certifier itself is now out-of-core (the r3 run
peaked at 27 GB RSS and died incomplete because generator + oracle both
held the whole SF-10 dataset):

- data is generated in PIECES (benchmarks/tpch.py
  generate_orders_lineitem_piece) and appended to parquet on disk; no full
  lineitem frame ever exists in this process;
- the engine ingests lineitem with ``ChunkedSource.from_parquet`` (two-pass
  row-group streaming; holds encoded columnar batches, not pandas objects);
- the pandas oracle runs per query in a SUBPROCESS that loads only the
  lineitem columns that query touches, writes its expected frame to disk,
  and exits — oracle memory is returned to the OS before the engine runs.

Equality oracle: the hand-written pandas implementations
(benchmarks/pandas_tpch.py) — an independent host implementation, itself
oracle-tested against the engine (tests/integration/test_pandas_oracle.py).
The engine's own resident path is NOT the oracle here: an 8-thread GSPMD
program on this 1-core host spends minutes per collective rendezvous.

At SF >= 3 the run writes the certification artifact STREAMING_r04.json at
the repo root (per-query wall seconds, batch count/bytes, equality
verdicts, peak RSS); smaller SFs are smoke runs and write
/tmp/streaming_smoke.json so they can never clobber a certification.  The
streaming memory claim is the DEVICE working set: at most one
~BATCH_ROWS-row batch resident at a time versus the full table a resident
run uploads; ``process_peak_rss_gb`` additionally bounds the HOST side now
that generation and oracle are piecewise/subprocessed.
"""
import json
import os
import resource
import subprocess
import sys
import tempfile
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

SF = float(os.environ.get("STREAM_SCALE_SF", "10"))
QIDS = [int(q) for q in os.environ.get("STREAM_SCALE_QUERIES",
                                       "1,3,5,6,9").split(",")]
BATCH_ROWS = int(os.environ.get("STREAM_SCALE_BATCH_ROWS", str(4 << 20)))
N_PIECES = int(os.environ.get("STREAM_SCALE_PIECES",
                              str(max(1, int(2 * SF)))))
DATA_DIR = os.environ.get("STREAM_SCALE_DATA",
                          os.path.join(tempfile.gettempdir(),
                                       f"stream_scale_sf{SF:g}"))
OUT = (os.path.join(_REPO, "STREAMING_r05.json")
       if SF >= 3 else "/tmp/streaming_smoke.json")
# prior rounds' artifacts: resumable accumulation reads these too (same
# SF/rows/batch check as any resume source), so a new round re-certifies
# only what it must
_PRIOR = [os.path.join(_REPO, "STREAMING_r04.json"),
          os.path.join(_REPO, "STREAMING_r04.json.partial")]

# lineitem columns each oracle query touches (loading all 16 at SF 10 is
# the difference between a 4 GB and a 10 GB oracle subprocess)
_LI_COLS = {
    1: ["l_returnflag", "l_linestatus", "l_quantity", "l_extendedprice",
        "l_discount", "l_tax", "l_shipdate"],
    3: ["l_orderkey", "l_extendedprice", "l_discount", "l_shipdate"],
    5: ["l_orderkey", "l_suppkey", "l_extendedprice", "l_discount"],
    6: ["l_shipdate", "l_discount", "l_quantity", "l_extendedprice"],
    9: ["l_orderkey", "l_partkey", "l_suppkey", "l_extendedprice",
        "l_discount", "l_quantity"],
}


def _rss_gb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6


def _gen_to_parquet():
    """Piecewise generation straight to parquet; peak RSS = one piece."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    from benchmarks.tpch import generate_orders_lineitem_piece, generate_tpch

    os.makedirs(DATA_DIR, exist_ok=True)
    # marker carries the generation parameters: a rerun with a different
    # piece count (or SF) must regenerate, not silently certify old data
    marker = os.path.join(DATA_DIR, "COMPLETE")
    stamp = f"sf={SF:g} pieces={N_PIECES}"
    if os.path.exists(marker) and open(marker).read() == stamp:
        return
    for fn in os.listdir(DATA_DIR):
        if fn.endswith(".parquet") or fn == "COMPLETE":
            os.remove(os.path.join(DATA_DIR, fn))
    # dimension tables at full SF (customer 1.5M, part 2M, supplier 100k at
    # SF 10 — a few hundred MB); small_only skips the 10 GB fact build that
    # blew the r3 certification's RSS
    small = generate_tpch(SF, small_only=True)
    for name in ("region", "nation", "supplier", "part", "partsupp",
                 "customer"):
        small[name].to_parquet(os.path.join(DATA_DIR, f"{name}.parquet"))
    small.clear()
    writers = {}
    for piece in range(N_PIECES):
        orders, lineitem = generate_orders_lineitem_piece(SF, piece,
                                                          N_PIECES)
        for name, frame in (("orders", orders), ("lineitem", lineitem)):
            tbl = pa.Table.from_pandas(frame, preserve_index=False)
            if name not in writers:
                writers[name] = pq.ParquetWriter(
                    os.path.join(DATA_DIR, f"{name}.parquet"), tbl.schema)
            writers[name].write_table(tbl)
        del orders, lineitem
        print(f"gen piece {piece + 1}/{N_PIECES} rss={_rss_gb():.1f}GB",
              flush=True)
    for w in writers.values():
        w.close()
    with open(marker, "w") as f:
        f.write(stamp)


def _oracle_main(qid: int, out_path: str):
    """Subprocess: pandas oracle for one query over the parquet data,
    loading only the lineitem columns that query touches."""
    import pandas as pd

    from benchmarks.pandas_tpch import PANDAS_QUERIES

    data = {}
    for name in ("region", "nation", "supplier", "part", "partsupp",
                 "customer", "orders"):
        p = os.path.join(DATA_DIR, f"{name}.parquet")
        if os.path.exists(p):
            data[name] = pd.read_parquet(p)
    cols = _LI_COLS.get(qid)
    data["lineitem"] = pd.read_parquet(
        os.path.join(DATA_DIR, "lineitem.parquet"), columns=cols)
    t0 = time.perf_counter()
    want = PANDAS_QUERIES[qid](data)
    sec = time.perf_counter() - t0
    want.reset_index(drop=True).to_feather(out_path)
    print(json.dumps({"pandas_sec": round(sec, 2),
                      "oracle_rss_gb": round(_rss_gb(), 2)}), flush=True)


def _frames_equal(a, b) -> bool:
    import numpy as np
    import pandas as pd

    if len(a) != len(b) or list(a.columns) != list(b.columns):
        return False
    a = a.reset_index(drop=True)
    b = b.reset_index(drop=True)
    for col in a.columns:
        av, bv = a[col], b[col]
        if pd.api.types.is_float_dtype(av) or pd.api.types.is_float_dtype(bv):
            if not np.allclose(av.astype(float), bv.astype(float),
                               rtol=1e-6, atol=1e-9, equal_nan=True):
                return False
        elif not (av.astype(str).to_numpy() == bv.astype(str).to_numpy()).all():
            return False
    return True


def main():
    # the 8-device GSPMD programs cost minutes each to compile on this
    # host; the package's persistent XLA cache (JAX_COMPILATION_CACHE_DIR,
    # else <repo>/.jax_cache) keeps a rerun from re-paying them
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 8)

    import pandas as pd

    from benchmarks.tpch import QUERIES
    from dask_sql_tpu import Context
    from dask_sql_tpu.io.chunked import ChunkedSource
    from dask_sql_tpu.parallel.mesh import default_mesh

    t0 = time.perf_counter()
    _gen_to_parquet()
    gen_sec = time.perf_counter() - t0

    mesh = default_mesh()
    chunked = Context(mesh=mesh)
    t0 = time.perf_counter()
    source = ChunkedSource.from_parquet(
        os.path.join(DATA_DIR, "lineitem.parquet"), batch_rows=BATCH_ROWS)
    chunked.create_table("lineitem", source, chunked=True,
                         batch_rows=BATCH_ROWS)
    for name in ("region", "nation", "supplier", "part", "partsupp",
                 "customer", "orders"):
        chunked.create_table(
            name, pd.read_parquet(os.path.join(DATA_DIR,
                                               f"{name}.parquet")))
    load_sec = time.perf_counter() - t0
    li_rows = source.n_rows
    n_batches = source.n_batches
    li_bytes = sum(
        d.nbytes + (m.nbytes if m is not None else 0)
        for b in source.batches for d, m in b)

    # RESUME: fold in queries certified by a previous (complete or partial)
    # run over the SAME data — the virtual-mesh GSPMD execution runs at
    # simulator speed on this 1-core host, so one process may not fit every
    # query inside a caller's timeout; accumulation is what makes the
    # artifact completable at all
    results = {}
    for prev in [OUT, OUT + ".partial"] + _PRIOR:
        try:
            with open(prev) as f:
                d = json.load(f)
            if (d.get("sf") == SF and d.get("lineitem_rows") == li_rows
                    and d.get("batch_rows") == BATCH_ROWS):
                for k, v in d.get("queries", {}).items():
                    if "error" not in v:
                        results.setdefault(int(k), v)
        except (OSError, ValueError):
            pass
    # STREAM_SCALE_FORCE=6,... : drop these from the resume set so a query
    # whose prior number should improve (engine change) re-certifies fresh
    for q in os.environ.get("STREAM_SCALE_FORCE", "").split(","):
        if q.strip():
            results.pop(int(q), None)
    if results:
        print(f"resuming with prior results for {sorted(results)}",
              flush=True)

    def _write(done=False):
        artifact = {
            "metric": "streaming_mesh_scale",
            "sf": SF,
            "mesh_devices": int(mesh.devices.size),
            "lineitem_rows": li_rows,
            "lineitem_host_bytes": li_bytes,
            "batch_rows": BATCH_ROWS,
            "n_batches": n_batches,
            "n_gen_pieces": N_PIECES,
            "batch_device_bytes_approx": int(li_bytes / max(n_batches, 1)),
            "gen_sec": round(gen_sec, 1),
            "load_sec": round(load_sec, 1),
            "oracle": "benchmarks/pandas_tpch.py per-query subprocess over "
                      "parquet (column-pruned); itself oracle-tested in "
                      "tests/integration/test_pandas_oracle.py",
            "queries": {str(k): v for k, v in results.items()},
            "complete": done,
            "all_equal": bool(results) and all(r.get("equal")
                                               for r in results.values()),
            "process_peak_rss_gb": round(_rss_gb(), 2),
        }
        # in-flight progress goes to a sidecar; OUT itself is only ever
        # replaced by a complete run, so an interrupted rerun can't
        # overwrite a previous certification with a partial result
        path = OUT if done else OUT + ".partial"
        with open(path, "w") as f:
            json.dump(artifact, f, indent=1)
        if done:
            try:
                os.remove(OUT + ".partial")
            except OSError:
                pass
        return artifact

    for qid in QIDS:
        if qid in results:
            continue
        rec = {}
        try:
            want_path = os.path.join(DATA_DIR, f"oracle_q{qid}.feather")
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--oracle",
                 str(qid), want_path],
                capture_output=True, text=True, timeout=3600,
                env=dict(os.environ, JAX_PLATFORMS="cpu"))
            if proc.returncode != 0:
                raise RuntimeError(f"oracle rc={proc.returncode}: "
                                   f"{proc.stderr[-400:]}")
            rec.update(json.loads(proc.stdout.strip().splitlines()[-1]))
            want = pd.read_feather(want_path)

            t0 = time.perf_counter()
            got = chunked.sql(QUERIES[qid], return_futures=False)
            rec["chunked_sec"] = round(time.perf_counter() - t0, 2)
            got.columns = [c.lower() for c in got.columns]
            want.columns = [c.lower() for c in want.columns]
            for col in got.columns:
                if got[col].dtype.kind == "M":
                    got[col] = got[col].dt.strftime("%Y-%m-%d")
                if col in want.columns and want[col].dtype.kind == "M":
                    want[col] = want[col].dt.strftime("%Y-%m-%d")
            srt = list(want.columns)
            rec["equal"] = _frames_equal(
                want.sort_values(srt, ignore_index=True),
                got[srt].sort_values(srt, ignore_index=True))
            rec["rows_out"] = len(got)
        except Exception as e:  # record, keep going
            rec["error"] = f"{type(e).__name__}: {e}"[:300]
        rec["process_rss_gb"] = round(_rss_gb(), 2)
        results[qid] = rec
        _write()
        print(f"Q{qid}: {rec}", flush=True)

    artifact = _write(done=True)
    print(json.dumps({"metric": "streaming_mesh_scale",
                      "value": artifact["all_equal"],
                      "detail": OUT}))


if __name__ == "__main__":
    if len(sys.argv) > 2 and sys.argv[1] == "--oracle":
        _oracle_main(int(sys.argv[2]), sys.argv[3])
    else:
        main()
