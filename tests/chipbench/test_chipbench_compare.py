"""The comparison that decides ``correct``: what it lets through, what it
stops, and its control at a size a test can hold."""
import numpy as np
import pandas as pd
import pytest

from chipbench import compare, control, run
from chipbench.data.tpch_gen import generate as generate_tpch

SHAPES = ["q1", "q6", "q14"]  # Q12 counts rows: nothing floats in it


@pytest.fixture(scope="module")
def frames():
    return generate_tpch(0.01, 2147483777)


def test_identical_frames_pass():
    a = pd.DataFrame({"k": ["A", "B"], "s": [1.5, 2.5], "n": [3, 4]})
    assert compare.compare_frames(a.copy(), a) == (0.0, 0)
    assert compare.verdict(0.0, 0, 0)[0]


def test_pr23_finding_4_fails():
    """A SUM over 6 M rows through the MXU at default precision read
    78645834806.52 against 78646533529.18: 8.9e-6 relative."""
    want = pd.DataFrame({"revenue": [78646533529.18]})
    got = pd.DataFrame({"revenue": [78645834806.52]})
    gap, mismatched = compare.compare_frames(got, want)
    assert mismatched == 0 and 8.8e-6 < gap < 9.0e-6
    assert not compare.verdict(gap, mismatched, 0)[0]


@pytest.mark.parametrize("got,mismatched", [
    (pd.DataFrame({"k": ["A", "C"], "s": [1.5, 2.5], "n": [3, 4]}), 1),
    (pd.DataFrame({"k": ["A", "B"], "s": [1.5, 2.5], "n": [3, 5]}), 1),
    (pd.DataFrame({"k": ["A", "B"], "s": [1.5, np.nan], "n": [3, 4]}), 1),
    (pd.DataFrame({"k": ["A"], "s": [1.5], "n": [3]}), 1),
    (None, 1)])
def test_an_altered_answer_is_counted(got, mismatched):
    want = pd.DataFrame({"k": ["A", "B"], "s": [1.5, 2.5], "n": [3, 4]})
    assert compare.compare_frames(got, want)[1] == mismatched
    assert not compare.verdict(0.0, mismatched, 0)[0]


def test_an_error_makes_a_run_incorrect():
    assert not compare.verdict(0.0, 0, 1)[0]


def test_wire_values_compare_as_numbers():
    want = pd.DataFrame({"m": ["MAIL"], "n": [7], "s": [0.1 + 0.2]})
    wire = pd.DataFrame([["MAIL", 7, 0.30000000000000004]],
                        columns=["m", "n", "s"])
    assert compare.compare_frames(wire, want) == (0.0, 0)


@pytest.mark.parametrize("name", SHAPES)
def test_the_float32_control_fails(name, frames):
    shape = run.load_by_path("shapes", name)
    low = control.float32_frames(frames)
    gaps = []
    for index in (0, shape.SPACE // 5, shape.SPACE // 3, shape.SPACE // 2,
                  shape.SPACE - 1):
        params = shape.params_at(index)
        gap, mismatched = compare.compare_frames(
            shape.reference(low, **params), shape.reference(frames, **params))
        gaps.append(gap)
    # a run compares several answers of a shape and reads the widest gap
    assert max(gaps) > 3 * compare.LIMITS["max_rel_gap"], gaps
    assert not compare.verdict(max(gaps), 0, 0)[0]
