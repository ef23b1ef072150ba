"""Multi-host (multi-process) mesh execution over the DCN analogue.

The reference's CI runs its suite against an external scheduler + worker
pair (/root/reference/.github/docker-compose.yaml:1-17,
/root/reference/tests/integration/fixtures.py:291-297).  The SPMD analogue
here is ``parallel.mesh.init_multihost`` → ``jax.distributed.initialize``:
every host runs the same driver, the mesh spans all hosts' devices, and XLA
routes collectives across processes (gloo on CPU under test; ICI/DCN on real
TPU pods).  This test launches TWO real processes on localhost, each with 4
virtual CPU devices, builds the 8-device global mesh in each, runs a
compiled aggregate+join query through ``Context(mesh=...)`` on BOTH, and
checks the answer equals the single-host result — exercising the
init_multihost path that had never executed before round 4 (review r3
item 6).
"""
import json
import os
import socket
import subprocess
import sys
import textwrap

import pytest

_WORKER = textwrap.dedent("""
    import json, os, sys
    pid = int(sys.argv[1]); nproc = int(sys.argv[2]); port = sys.argv[3]
    out_path = sys.argv[4]
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_cpu_collectives_implementation", "gloo")

    from dask_sql_tpu.parallel.mesh import init_multihost
    mesh = init_multihost(coordinator_address=f"127.0.0.1:{port}",
                          num_processes=nproc, process_id=pid)
    assert mesh.devices.size == 8, mesh.devices

    import numpy as np, pandas as pd
    from dask_sql_tpu import Context

    rng = np.random.RandomState(3)  # SAME data in every process (SPMD)
    n = 1000
    orders = pd.DataFrame({"okey": np.arange(n),
                           "cust": rng.randint(0, 37, n),
                           "amount": np.round(rng.uniform(1, 100, n), 2)})
    cust = pd.DataFrame({"ckey": np.arange(37),
                         "seg": rng.choice(["A", "B", "C"], 37)})
    c = Context(mesh=mesh)
    c.create_table("orders", orders)
    c.create_table("cust", cust)
    q = ("SELECT seg, COUNT(*) AS n, SUM(amount) AS s "
         "FROM orders JOIN cust ON cust = ckey "
         "GROUP BY seg ORDER BY seg")
    got = c.sql(q, return_futures=False)
    with open(out_path, "w") as f:
        json.dump({"seg": [str(x) for x in got["seg"]],
                   "n": [int(x) for x in got["n"]],
                   "s": [round(float(x), 2) for x in got["s"]]}, f)
""")


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_two_process_mesh_query(tmp_path):
    # no pytest-timeout in this image: the 540 s communicate() below is the
    # hang bound, and a wedged pair is killed there
    port = _free_port()
    script = tmp_path / "worker.py"
    script.write_text(_WORKER)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_PLATFORMS", None)
    repo_root = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    outs = [tmp_path / "out0.json", tmp_path / "out1.json"]
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(pid), "2", str(port),
             str(outs[pid])],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True)
        for pid in (0, 1)
    ]
    logs = []
    for p in procs:
        try:
            stdout, stderr = p.communicate(timeout=540)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("multihost worker timed out")
        logs.append((p.returncode, stdout[-1000:], stderr[-2000:]))
    for rc, so, se in logs:
        assert rc == 0, f"worker failed rc={rc}\n{so}\n{se}"

    # expected result from plain single-process pandas (same seeded data)
    import numpy as np
    import pandas as pd

    rng = np.random.RandomState(3)
    n = 1000
    orders = pd.DataFrame({"okey": np.arange(n),
                           "cust": rng.randint(0, 37, n),
                           "amount": np.round(rng.uniform(1, 100, n), 2)})
    cust = pd.DataFrame({"ckey": np.arange(37),
                         "seg": rng.choice(["A", "B", "C"], 37)})
    joined = orders.merge(cust, left_on="cust", right_on="ckey")
    want = (joined.groupby("seg").agg(n=("okey", "size"),
                                      s=("amount", "sum"))
            .reset_index().sort_values("seg"))

    for out in outs:
        got = json.loads(out.read_text())
        assert got["seg"] == [str(x) for x in want["seg"]]
        assert got["n"] == [int(x) for x in want["n"]]
        for a, b in zip(got["s"], want["s"]):
            assert abs(a - float(b)) < 0.05, (got["s"], list(want["s"]))
