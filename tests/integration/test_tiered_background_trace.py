"""Chrome-trace capture for background compile daemon threads
(physical/tiering._background_compile): the daemon carries its own
``background_compile`` trace, so DSQL_CHROME_TRACE_DIR sees the compile
spans that previously ran outside any QueryTrace and vanished."""
import json
import os
import time

import numpy as np
import pytest

from dask_sql_tpu import Context
from dask_sql_tpu.runtime import telemetry as tel

_needs_compiled = pytest.mark.skipif(
    os.environ.get("DSQL_COMPILE") == "0",
    reason="background compiles need the compiled path")


@_needs_compiled
def test_background_compile_emits_chrome_trace(tmp_path, monkeypatch):
    monkeypatch.setenv("DSQL_TIERED", "1")
    monkeypatch.setenv("DSQL_CHROME_TRACE_DIR", str(tmp_path))
    done0 = tel.REGISTRY.get("background_compiles_done")
    err0 = tel.REGISTRY.get("background_compile_errors")

    c = Context()
    c.create_table("t", {"a": np.arange(128, dtype=np.int64) % 7,
                         "b": np.arange(128, dtype=np.float64)})
    # cold plan: answered on the eager tier while the daemon compiles
    c.sql("SELECT a, SUM(b) AS s FROM t GROUP BY a")

    deadline = time.time() + 120
    while time.time() < deadline:
        if (tel.REGISTRY.get("background_compiles_done") > done0
                or tel.REGISTRY.get("background_compile_errors") > err0):
            break
        time.sleep(0.05)
    else:
        pytest.fail("background compile never finished")

    bg_blobs = []
    for f in sorted(tmp_path.glob("*.trace.json")):
        blob = json.loads(f.read_text())
        names = {e.get("name") for e in blob.get("traceEvents", [])}
        if "background_compile" in names:
            bg_blobs.append(blob)
    assert bg_blobs, "no chrome trace carries the background_compile root"
    # the daemon's trace contains the compile work itself, not just a root
    events = bg_blobs[0]["traceEvents"]
    assert len(events) > 1
    assert all(e.get("dur", 0) >= 0 for e in events)
