"""A SEMI / ANTI join whose residual is ``build.x OP probe.y`` is decided
from each key's count, least and greatest ``x``, twice over
(``joins.merge_exists``: one stable sort and segmented scans;
``joins.hash_table``: scatters at each key's resident row; ROADMAP D16).
The optimizer rewrites such a residual before SQL reaches the tracer with
it, so nothing else runs either: here both, on hand-built sides, against a
loop over the rows."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dask_sql_tpu.ops.hashing import _hash_parts, _join_key_parts, _keys_valid
from dask_sql_tpu.physical import joins
from dask_sql_tpu.physical.traced import _VT, ProgramFlags
from dask_sql_tpu.table import Column, Table
from dask_sql_tpu.types import BIGINT

N_PROBE, N_BUILD = 120, 90
COMPARE = {"<>": np.not_equal, "<": np.less, ">=": np.greater_equal}


def _match(formulation, jt, op, pk, py, bk, bx, pmask, bmask):
    cols = [Column(a, BIGINT, None, None) for a in (pk, py, bk, bx)]
    probe = _VT(Table(["k", "y"], cols[:2]), pmask)
    build = _VT(Table(["k", "x"], cols[2:]), bmask)
    pparts, bparts = _join_key_parts(cols[:1], cols[2:3])
    pvalid = _keys_valid(cols[:1], pmask)
    sides = (jt, probe, build, pparts, bparts, pvalid,
             _hash_parts(pparts, pvalid),
             _hash_parts(bparts, _keys_valid(cols[2:3], bmask)))
    flags = ProgramFlags()
    if formulation == "merge_exists":
        match, fetched = joins.merge_exists(*sides, (op, cols[3], cols[1]),
                                            flags)
    else:
        match, fetched = joins.hash_table(*sides, (op, cols[3], cols[1]), 0,
                                          flags)
    assert fetched is None
    return match, flags.pack(jnp.int64(0))


@pytest.mark.parametrize("jt", ["SEMI", "ANTI"])
@pytest.mark.parametrize("formulation", ["merge_exists", "hash_table"])
def test_some_build_row_of_the_key_compares_so(formulation, jt):
    rng = np.random.default_rng(5)
    pk, py = rng.integers(0, 20, N_PROBE), rng.integers(0, 12, N_PROBE)
    bk, bx = rng.integers(0, 25, N_BUILD), rng.integers(0, 12, N_BUILD)
    pmask, bmask = rng.random(N_PROBE) < 0.8, rng.random(N_BUILD) < 0.8
    for op, compare in COMPARE.items():
        match, flags = jax.jit(
            lambda *sides: _match(formulation, jt, op, *sides))(
                *(jnp.asarray(a) for a in (pk, py, bk, bx, pmask, bmask)))
        want = [pmask[i] and bool(np.any(
            bmask & (bk == pk[i]) & compare(bx, py[i])))
            for i in range(N_PROBE)]
        assert np.asarray(match).tolist() == want
        # keys held twice on the build side are no reason to answer eagerly
        assert not np.asarray(flags)[0]
