"""A configuration file documents the mixes its world draws with; the
world's code holds them as literals.  The two must agree, and the
literals, not the file, decide the draws: shares summed from the file
round differently (0.84 + 0.08 == 0.9199999999999999), which would
move n110's draws."""

import os

import pytest

from benchmark import harness as H
from benchmark import world as W


def test_n110_mixes_are_the_drawn_ones():
    cfg = H.load_json(os.path.join(H.HERE, "configs", "n110.json"))
    assert "world" not in cfg
    assert W.RULE_CUTS == (0.84, 0.92, 0.96, 0.99)
    cuts = (0.0,) + W.RULE_CUTS + (1.0,)
    shares = dict(zip(
        ("l4", "l3_only", "cidr", "http", "kafka"),
        (b - a for a, b in zip(cuts, cuts[1:])),
    ))
    assert shares == pytest.approx(cfg["rule_mix"], abs=1e-12, rel=0)
    assert W.POOL_MIX == dict(
        l7_bound=0.025, junk_ports=0.10, egress_to_vip=0.10,
        prefiltered=0.02, world=0.03, fragments=0.02,
    )
    assert W.POOL_MIX == pytest.approx(cfg["pool_mix"], abs=1e-12, rel=0)
