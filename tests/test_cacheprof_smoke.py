"""tools/cacheprof.py as a tier-1 test: the Zipf hit-rate curve of
the verdict-memo plane at smoke scale — dedup_factor >= 2 at s=1.1,
zero hits across a publish boundary, effective hot-bytes dumped next
to the raw hot_bytes_per_tuple number (fast, not slow)."""

import json


def test_cacheprof_smoke_tool(capsys):
    from tools.cacheprof import main

    assert (
        main(
            [
                "--rules", "60",
                "--endpoints", "4",
                "--identities", "256",
                "--pool", "1200",
                "--batch", "4096",
                "--warm-batches", "2",
                "--measure-batches", "2",
            ]
        )
        == 0
    )
    out = capsys.readouterr().out.strip().splitlines()[-1]
    got = json.loads(out)
    assert got["smoke"] == "ok"
    assert got["publish_boundary_hits"] == 0
    by_s = {r["zipf_s"]: r for r in got["curve"]}
    assert set(by_s) == {0.9, 1.1, 1.3}
    assert by_s[1.1]["dedup_factor"] >= 2.0
    for r in got["curve"]:
        # the effective line is the model divided by measured dedup
        assert r["effective_hot_bytes_per_tuple"] < (
            r["hot_bytes_per_tuple"]
        )
        assert 0.0 <= r["hit_rate"] <= 1.0
