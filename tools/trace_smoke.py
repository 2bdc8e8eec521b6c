"""Trace-plane smoke: one traced batch end-to-end over REST.

Builds a live daemon world (endpoints, an L3+L4 policy), serves it on
a unix socket, POSTs a flow-record buffer with a caller-supplied
`traceparent` header, and asserts the span plane's contract:

  * span tree integrity — every span of the trace has a parent that
    exists in the trace (the only span whose parent lives outside the
    ring is the root, which parents to OUR injected client span id),
    and the root is the REST request (`http.request` on api.server);
  * each per-batch dispatch span holds one device dispatch, and the
    per-batch dispatch spans fit inside the `daemon.process_flows`
    span;
  * the batch's captured FlowRecords carry the SAME trace id
    (GET /flows?trace-id=...) — the observe↔trace join key;
  * `/debug/profile` SpanStat phase totals agree with the summed
    span durations per phase (StatSpan shares one clock window);
  * a dispatch fault produces an `engine.hostpath` failover span in
    the trace (degraded batches are attributed, not invisible);
  * tracer bookkeeping stays under its gate
    (tracing_overhead_pct < 3% measured over warmed batches).

Runs in tier-1 (tests/test_trace_smoke.py, not slow) and standalone:
python tools/trace_smoke.py
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

import numpy as np  # noqa: E402

# a pinned caller context: the ids every span/record must join on
CLIENT_TRACE_ID = "deadbeefcafe4bada55a0ddba11fee15"
CLIENT_SPAN_ID = "c0ffee0123456789"
CLIENT_TRACEPARENT = f"00-{CLIENT_TRACE_ID}-{CLIENT_SPAN_ID}-01"


def build_world():
    """A live daemon: server/client endpoints, client→server:80/TCP
    plus an L3 peer rule; tables published synchronously."""
    from cilium_tpu.daemon import Daemon
    from cilium_tpu.labels import Label, LabelArray, Labels
    from cilium_tpu.policy.api import (
        EndpointSelector,
        IngressRule,
        PortProtocol,
        PortRule,
        Rule,
    )

    def labels(**kv):
        return Labels(
            {k: Label(k, v, "k8s") for k, v in kv.items()}
        )

    def selector(**kv):
        return EndpointSelector(
            match_labels={f"k8s.{k}": v for k, v in kv.items()}
        )

    d = Daemon()
    d.create_endpoint(
        10, labels(app="server"), ipv4="10.0.0.10", name="server-0"
    )
    client = d.create_endpoint(
        11, labels(app="client"), ipv4="10.0.0.11", name="client-0"
    )
    d.policy_add(
        [
            Rule(
                endpoint_selector=selector(app="server"),
                ingress=[
                    IngressRule(
                        from_endpoints=[selector(app="client")],
                        to_ports=[
                            PortRule(
                                ports=[
                                    PortProtocol(
                                        port="80", protocol="TCP"
                                    )
                                ]
                            )
                        ],
                    )
                ],
                labels=LabelArray.parse("trace-smoke-policy"),
            )
        ]
    )
    d.regenerate_all("trace smoke")
    return d, client.security_identity.id


def make_buf(rng, n, client_identity):
    from cilium_tpu.native import encode_flow_records

    return encode_flow_records(
        ep_id=np.full(n, 10, np.uint32),
        identity=np.full(n, client_identity, np.uint32),
        saddr=np.zeros(n, np.uint32),
        daddr=np.zeros(n, np.uint32),
        sport=np.full(n, 40000, np.uint16),
        dport=rng.choice([80, 443], size=n).astype(np.uint16),
        proto=np.full(n, 6, np.uint8),
        direction=np.zeros(n, np.uint8),
        is_fragment=np.zeros(n, np.uint8),
    )


def span_index(spans):
    return {s["span_id"]: s for s in spans}


def children_of(spans, span_id, name=None):
    return [
        s
        for s in spans
        if s["parent_id"] == span_id
        and (name is None or s["name"] == name)
    ]


def assert_tree(spans):
    """Every span's parent exists in the trace; the one external
    parent is our injected client span; the root is the REST
    request."""
    by_id = span_index(spans)
    roots = [s for s in spans if s["parent_id"] not in by_id]
    assert len(roots) == 1, [
        (s["name"], s["parent_id"]) for s in roots
    ]
    root = roots[0]
    assert root["name"] == "http.request", root
    assert root["site"] == "api.server", root
    assert root["parent_id"] == CLIENT_SPAN_ID, root
    assert root["attrs"]["path"] == "/datapath/flows", root
    for s in spans:
        assert s["trace_id"] == CLIENT_TRACE_ID, s
    return root


def assert_durations(spans, root):
    """Containment + partition invariants of the span tree."""
    proc = children_of(spans, root["span_id"], "daemon.process_flows")
    assert len(proc) == 1, proc
    proc = proc[0]
    assert proc["duration_ms"] <= root["duration_ms"] * 1.001
    batch_spans = children_of(spans, proc["span_id"], "dispatch")
    assert batch_spans, "no per-batch dispatch spans"
    assert (
        sum(b["duration_ms"] for b in batch_spans)
        <= proc["duration_ms"] * 1.001
    )
    # each batch span holds exactly one device dispatch
    for b in batch_spans:
        dev = children_of(spans, b["span_id"], "engine.dispatch")
        assert len(dev) == 1, (b, dev)
    # phase spans exist under the process span
    for phase in ("host_pack", "event_fold", "flow_capture"):
        assert children_of(spans, proc["span_id"], phase), phase
    return proc, batch_spans


def main() -> int:
    from cilium_tpu import tracing
    from cilium_tpu.api.client import APIClient
    from cilium_tpu.api.server import APIServer

    from cilium_tpu import option

    rng = np.random.default_rng(3)
    d, client_identity = build_world()
    tracing.tracer.reset(seed=99, sample_rate=1.0)
    # capture every allow (the monitor fold's aggregation knob): the
    # flow↔trace join below asserts an EXACT record count
    agg_before = option.Config.opts.get(option.MONITOR_AGGREGATION)
    option.Config.opts[option.MONITOR_AGGREGATION] = (
        option.MONITOR_AGG_NONE
    )

    tmp = tempfile.mkdtemp(prefix="trace-smoke-")
    sock = os.path.join(tmp, "agent.sock")
    server = APIServer(d, sock).start()
    try:
        client = APIClient(sock)
        # warm the serving path (jit compiles, device upload) so the
        # overhead measurement below sees steady-state batches
        client.process_flows(make_buf(rng, 256, client_identity))

        # --- the traced request: caller-pinned context ----------------
        buf = make_buf(rng, 512, client_identity)
        reply = client.process_flows(
            buf, traceparent=CLIENT_TRACEPARENT
        )
        assert reply["trace_id"] == CLIENT_TRACE_ID, reply
        assert reply["total"] == 512, reply

        got = client.traces_get({"trace-id": CLIENT_TRACE_ID})
        spans = got["spans"]
        assert got["matched"] == len(spans) > 0
        root = assert_tree(spans)
        proc, batch_spans = assert_durations(spans, root)

        # --- flow records join on the same trace id -------------------
        flows = client.flows_get({"trace-id": CLIENT_TRACE_ID})
        assert flows["matched"] == 512, flows["matched"]
        assert all(
            f["trace_id"] == CLIENT_TRACE_ID
            for f in flows["flows"]
        )

        # --- /debug/profile agrees with span durations ----------------
        # (fresh accumulators via ?reset=1, then ONE traced request:
        # the StatSpan shared clock makes the totals identical)
        client.debug_profile(reset=True)
        reply2 = client.process_flows(
            make_buf(rng, 256, client_identity)
        )
        tid2 = reply2["trace_id"]
        prof = client.debug_profile()
        spans2 = client.traces_get({"trace-id": tid2})["spans"]
        for phase in ("host_pack", "dispatch", "event_fold",
                      "flow_capture"):
            stat = prof["datapath_spans"][phase]
            stat_ms = (
                stat["success_total_s"] + stat["failure_total_s"]
            ) * 1000.0
            span_ms = sum(
                s["duration_ms"]
                for s in spans2
                if s["name"] == phase and s["site"] == "daemon"
            )
            assert abs(stat_ms - span_ms) <= max(
                0.005 * max(stat_ms, span_ms), 1e-3
            ), (phase, stat_ms, span_ms)

        # --- failover attribution: a dispatch fault lands an
        # engine.hostpath span in the trace ----------------------------
        from cilium_tpu import faultinject

        faultinject.arm("engine.dispatch", "raise:every=1")
        try:
            reply3 = client.process_flows(
                make_buf(rng, 64, client_identity)
            )
        finally:
            faultinject.disarm_all()
            d.dispatch_breaker.reset()
        assert reply3["degraded_batches"] >= 1, reply3
        spans3 = client.traces_get(
            {"trace-id": reply3["trace_id"]}
        )["spans"]
        hostpath = [
            s for s in spans3 if s["name"] == "engine.hostpath"
        ]
        assert hostpath, [s["name"] for s in spans3]

        # --- overhead gate over warmed batches ------------------------
        tracing.tracer.reset(seed=1, sample_rate=1.0)
        bench_buf = make_buf(rng, 4096, client_identity)
        t0 = time.perf_counter()
        for _ in range(3):
            client.process_flows(bench_buf)
        wall = time.perf_counter() - t0
        overhead = tracing.tracer.overhead_s
        overhead_pct = overhead / max(wall - overhead, 1e-9) * 100.0
        assert overhead_pct < 3.0, (
            f"tracing overhead {overhead_pct:.3f}% breaches the "
            f"3% gate"
        )
        print(
            json.dumps(
                {
                    "smoke": "ok",
                    "spans": len(spans),
                    "batch_spans": len(batch_spans),
                    "flow_records_joined": flows["matched"],
                    "hostpath_spans": len(hostpath),
                    "tracing_overhead_pct": round(overhead_pct, 4),
                }
            )
        )
        return 0
    finally:
        server.stop()
        if agg_before is None:
            option.Config.opts.pop(option.MONITOR_AGGREGATION, None)
        else:
            option.Config.opts[option.MONITOR_AGGREGATION] = agg_before


if __name__ == "__main__":
    sys.exit(main())
