"""The copied generators of benchmark/world.py against bench.py's: the
same seed gives the same rules, flow pool and packed pairs.  A change
to either shows here."""

import json

import numpy as np
import pytest

import bench
from benchmark import world as W

TINY = dict(
    name="tiny", rules=600, endpoints=8, identities=1024, team_size=16,
    pool=3000, services=16, backends_per_service=2,
    prefilter_cidrs=["203.0.113.0/24"],
)


def canon(rule) -> str:
    return json.dumps(rule, default=lambda o: getattr(o, "__dict__", repr(o)),
                      sort_keys=True)


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11])
def test_rules_same_as_bench(seed):
    mine = W.build_rules(np.random.default_rng(seed), 500, 8, 64)
    theirs = bench.build_rules(np.random.default_rng(seed), 500, 8, 64)
    assert [canon(r) for r in mine[0]] == [canon(r) for r in theirs[0]]
    assert mine[1] == theirs[1] and mine[2] == theirs[2]
    assert len(mine[3]) == 500


@pytest.fixture(scope="module")
def worlds():
    args = bench.build_parser().parse_args([
        "--rules", str(TINY["rules"]), "--endpoints", str(TINY["endpoints"]),
        "--identities", str(TINY["identities"]), "--pool", str(TINY["pool"]),
    ])
    theirs = bench.build_config5(args, np.random.default_rng(5))
    mine = W.build_world(TINY, np.random.default_rng(5))
    return mine, theirs


def test_world_pool_same_as_bench(worlds):
    mine, theirs = worlds
    pool = theirs[3]
    assert sorted(mine.pool) == sorted(pool)
    for k in pool:
        assert np.array_equal(mine.pool[k], pool[k]), k
    assert mine.index == dict(theirs[2])


@pytest.mark.parametrize("seed", [3, 2**33 + 1])
def test_packed_pairs_and_zipf_same_as_bench(worlds, seed):
    pool = worlds[0].pool
    a = W.pack_pool_pairs(pool, np.random.default_rng(seed), 512, 2)
    b = bench.pack_pool_pairs(pool, np.random.default_rng(seed), 512, 2)
    for x, y in zip(a[0], b[0]):
        assert np.array_equal(x, y)
    for x, y in zip(a[1], b[1]):
        assert all(np.array_equal(p, q) for p, q in zip(x, y))
    za = W.zipf_picks(np.random.default_rng(seed), 3000, 4096, 1.1)
    zb = bench.zipf_picks(np.random.default_rng(seed), 3000, 4096, 1.1)
    assert np.array_equal(za, zb)
