"""tools/trace_smoke.py as a tier-1 test: a traced batch end-to-end
over REST — span tree integrity (every parent exists, the root is
the REST request, one device dispatch per batch span), the
flow↔trace join, /debug/profile agreement, failover attribution and
the tracing-overhead gate (fast, not slow)."""

import json


def test_trace_smoke_tool(capsys):
    from tools.trace_smoke import main

    assert main() == 0
    out = capsys.readouterr().out.strip().splitlines()[-1]
    got = json.loads(out)
    assert got["smoke"] == "ok"
    assert got["spans"] > 0
    assert got["batch_spans"] >= 1
    assert got["flow_records_joined"] == 512
    assert got["hostpath_spans"] >= 1
    assert got["tracing_overhead_pct"] < 3.0
