"""The generator: the structure follows the scale factor alone, the measures
the seed."""
import numpy as np
import pytest

from chipbench.data import tpch_gen

SEEDS = (7, 2147483653)  # one beyond 32 signed bits, as the driver's are


@pytest.fixture(scope="module")
def two():
    return [tpch_gen.generate(0.01, seed) for seed in SEEDS]


@pytest.mark.parametrize("table", ["region", "nation", "supplier", "part",
                                   "partsupp", "customer", "orders",
                                   "lineitem"])
def test_two_seeds_give_equal_shapes(two, table):
    a, b = two[0][table], two[1][table]
    assert a.shape == b.shape
    assert list(a.dtypes) == list(b.dtypes)
    assert len(a) == tpch_gen.cardinalities(0.01)[table]


MEASURES = [("lineitem", "l_extendedprice"), ("lineitem", "l_tax"),
            ("orders", "o_totalprice"), ("customer", "c_acctbal"),
            ("supplier", "s_acctbal"), ("partsupp", "ps_supplycost"),
            ("partsupp", "ps_availqty")]


@pytest.mark.parametrize("table,column", MEASURES)
def test_two_seeds_give_different_measures(two, table, column):
    assert not (two[0][table][column].to_numpy()
                == two[1][table][column].to_numpy()).all()


@pytest.mark.parametrize("table", ["supplier", "part", "partsupp", "customer",
                                   "orders", "lineitem"])
def test_two_seeds_give_the_same_structure(two, table):
    """What predicates and joins select on is the same at every seed, so
    every intermediate result has the same size."""
    for column in two[0][table].columns:
        if (table, column) not in MEASURES:
            assert (two[0][table][column].to_numpy()
                    == two[1][table][column].to_numpy()).all(), column


def test_the_same_seed_gives_the_same_tables():
    again = tpch_gen.generate(0.01, SEEDS[1])
    once = tpch_gen.generate(0.01, SEEDS[1])
    for name in once:
        assert once[name].equals(again[name])


def test_sf1_cardinalities_are_dbgens_to_the_order_of_lineitem():
    rows = tpch_gen.cardinalities(1.0)
    assert rows["orders"] == 1_500_000 and rows["part"] == 200_000
    assert rows["customer"] == 150_000 and rows["supplier"] == 10_000
    assert rows["partsupp"] == 800_000
    assert abs(rows["lineitem"] - 6_001_215) < 10_000  # dbgen's own count


def test_dictionary_columns_keep_their_category_sets(two):
    for column in ("l_shipmode", "l_returnflag", "l_linestatus",
                   "l_shipinstruct"):
        sets = [set(np.unique(f["lineitem"][column])) for f in two]
        assert sets[0] == sets[1]
