"""chip_smoke.py's contract, as far as a CPU can check it.

The script's real run is on the chip; here its ``main`` is called in this
process at a tiny scale factor through the rehearsal switch, and the two
rules that protect the driver's check are pinned: the last line is the ok
line only on a TPU, and a phase that raises ends the run without it.
"""
import json
import os
import subprocess
import sys

import pytest

import chip_smoke

_REPO = os.path.dirname(os.path.abspath(chip_smoke.__file__))


def _json_lines(text: str) -> list:
    return [json.loads(line) for line in text.splitlines()
            if line.startswith("{")]


def test_rehearsal_walks_every_phase_and_never_prints_ok(capsys):
    assert chip_smoke.main(["--sf", "0.002", "--allow-cpu"]) == 0
    lines = _json_lines(capsys.readouterr().out)
    phases = [line.get("phase") for line in lines[:-1]]
    assert phases == ["device", "kernel", "load",
                      "arrival", "library", "arrival", "library",
                      "arrival", "library",
                      "correctness", "server", "correctness"]
    assert [line["query"] for line in lines if line.get("phase") == "library"
            ] == list(chip_smoke.QIDS)
    for line in lines:
        if line.get("phase") == "library":
            assert line["tiers"][-1] == "compiled", line
    last = lines[-1]
    assert "ok" not in last and last["rehearsal_passed"] is True
    assert last["device"]["platform"] == "cpu"


def test_without_the_switch_a_cpu_is_refused(capsys):
    with pytest.raises(SystemExit) as stop:
        chip_smoke.main(["--sf", "0.002"])
    assert stop.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def test_a_phase_that_raises_ends_the_run_without_a_last_line(
        capsys, monkeypatch):
    def broken(*_args, **_kwargs):
        raise RuntimeError("load failed")

    monkeypatch.setattr(chip_smoke, "_load_phase", broken)
    with pytest.raises(RuntimeError, match="load failed"):
        chip_smoke.main(["--sf", "0.002", "--allow-cpu"])
    lines = _json_lines(capsys.readouterr().out)
    assert [line.get("phase") for line in lines] == ["device", "kernel"]


@pytest.mark.parametrize("from_env", [True, False],
                         ids=["JAX_COMPILATION_CACHE_DIR", "in-checkout"])
def test_compile_cache_has_one_owner(tmp_path, from_env):
    """With ``JAX_COMPILATION_CACHE_DIR`` set the package sets no cache
    directory of its own; unset, it resolves to one fixed path in the
    checkout.  A fresh interpreter: the decision is made at import."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "xla")
    out = subprocess.run(
        [sys.executable, "-c",
         "import dask_sql_tpu; print(dask_sql_tpu.compile_cache_dir())"],
        cwd=_REPO, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    expected = (str(tmp_path / "xla") if from_env
                else os.path.join(_REPO, ".jax_cache"))
    assert out.stdout.strip().splitlines()[-1] == expected
