"""The Pallas kernels of the main path, compiled for a DESCRIBED v5e chip.

The TPU's compiler is installed with jax even where no TPU is attached, and
it refuses here what it would refuse on the chip (misaligned tiles, too much
VMEM, ops the X64 rewrite cannot lower).  Nothing runs: these tests say
nothing about results or times.

This is the only file of the suite that describes a TPU.  The description
happens inside a module-scoped fixture — never at import, never in
conftest — so that every xdist worker collects the same tests and only
the worker that runs this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from dask_sql_tpu.ops import pallas_kernels as pk

#: dbgen's SF1 lineitem row count — the width the smoke run drives
N_SF1 = 6_001_215


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described device is written to the persistent cache
    # but cannot be read back without a chip: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes]
    return jax.jit(fn).lower(*args).compile()


def _reduction_shapes(rows, n, dtype):
    return ((rows, n), dtype), ((n,), jnp.int32), ((n,), jnp.bool_)


def test_segmented_sums_f32_sf1_width(one_chip):
    """The accumulate-in-place MXU kernel at the Q1 shape: 8 value rows
    over every SF1 lineitem row."""
    compiled = _compile(
        lambda v, c, m: pk.segmented_sums(v, c, m, 8, interpret=False),
        one_chip, *_reduction_shapes(8, N_SF1, jnp.float32))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("row_classes,n", [
    (("float",), pk.SLAB_EXACT), (("int", "int"), pk.SLAB_EXACT),
    (("unit", "unit"), pk.SLAB_EXACT), (("int", "int"), N_SF1)],
    ids=lambda p: p if isinstance(p, int) else f"{len(p)}x{p[0]}")
def test_segmented_sums_fixedpoint(one_chip, row_classes, n):
    """The f64 limb kernel per row class at one SLAB_EXACT slab, and at
    SF1 width, where the one traced slab body is looped (a dynamic slice
    feeding the kernel inside a scan).  Q1's own rows are the case below
    (``test_segmented_sums_q1_named_rows``)."""
    compiled = _compile(
        lambda v, c, m: pk.segmented_sums_fixedpoint(
            v, c, m, 8, row_classes=list(row_classes), interpret=False),
        one_chip, *_reduction_shapes(len(row_classes), n, jnp.float64))
    # one kernel call at any width: the slab loop is not unrolled
    assert compiled.as_text().count("tpu_custom_call") == 1


def test_exact_pow2_f64(one_chip):
    """No kernel here: the point is that the f64 binary exponentiation
    survives the TPU's X64 rewrite (ldexp/frexp do not)."""
    compiled = _compile(pk._exact_pow2, one_chip, ((4096,), jnp.int32))
    assert "tpu_custom_call" not in compiled.as_text()


def test_segmented_sums_q1_named_rows(one_chip):
    """TPC-H Q1's 17 named rows as the compiled tier hands them over (PR
    39): five f64 value rows and the row mask, a bool, named ten times.
    The limb matrix is 5 x 14 limb rows, the mask's own row and 15
    indicator rows, bools until they are stacked: 86 rows, padded to 88
    for Mosaic's (8, 128) tiles, at SF1 width, looped.  (The parent's 17
    + 51 rows made 160 and compiled here for 70-80 s; these take 35.)"""
    q1_classes = ["unit"] + ["float", "unit"] * 7 + ["unit", "unit"]

    def q1(qty, price, disc_price, charge, disc, codes, keep):
        rows = [keep]
        for value in (qty, price, disc_price, charge, qty, price, disc):
            rows += [value, keep]
        rows += [keep, keep]
        assert pk.limb_row_counts(rows, q1_classes) == {
            "limb_rows_named": 17, "limb_rows_summed": 6,
            "limb_indicator_rows": 15}
        return pk.segmented_sums_fixedpoint(
            rows, codes, keep, 6, row_classes=q1_classes, interpret=False)

    compiled = _compile(
        q1, one_chip, *[((N_SF1,), jnp.float64)] * 5,
        ((N_SF1,), jnp.int32), ((N_SF1,), jnp.bool_))
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "f32[88," in text
