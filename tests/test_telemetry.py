"""Telemetry plane: on-device stage/drop accounting, host folds,
metric exposition, trace_tuple explain, event-fold consistency."""

import numpy as np
import pytest

from cilium_tpu.engine.verdict import (
    TELEM_COLS,
    TELEM_DENIED,
    TELEM_DROP_FRAG,
    TELEM_DROP_POLICY,
    TELEM_DROP_PREFILTER,
    TELEM_FORWARDED,
    TELEM_MATCH_FRAG,
    TELEM_MATCH_L3,
    TELEM_MATCH_L4,
    TELEM_MATCH_L4_WILD,
    TELEM_MATCH_NONE,
    TELEM_TOTAL,
)
from cilium_tpu.telemetry import (
    fold_telemetry,
    telemetry_consistent,
    telemetry_from_outputs,
    telemetry_summary,
)


def _world_and_flows(seed=7, n=512):
    from tests.test_datapath import _build_world, _random_flows

    from cilium_tpu.engine.datapath import FlowBatch

    (rng, prefilter_map, ipcache_map, ct, mgr, states, tables,
     n_eps) = _build_world(seed)
    f = _random_flows(rng, n, n_eps)
    return tables, f, FlowBatch.from_numpy(**f), states


def test_device_telemetry_matches_host_fold():
    """The carried [2, T] device histogram must equal the numpy fold
    of the same batch's per-tuple outputs bit-for-bit — both derive
    from telemetry_masks, so this pins the device reduction."""
    from cilium_tpu.engine.datapath import datapath_step_telem

    tables, f, flows, _ = _world_and_flows()
    out, trow = datapath_step_telem(tables, flows)
    got = np.asarray(trow).astype(np.uint64)
    want = telemetry_from_outputs(out, np.asarray(f["direction"]))
    assert (got == want).all()
    assert telemetry_consistent(got)
    assert int(got[:, TELEM_TOTAL].sum()) == len(f["direction"])


def test_pair_program_matches_accum_and_telem_equals_host_fold():
    """The fused-pair program returns the same verdicts AND counter
    scatter as the generic streaming step over its two halves in
    turn; its telemetry equals the host fold of its own outputs."""
    from tests.test_datapath import (
        _accum_in_sequence,
        _build_world,
        _pair_program,
        _random_flows,
    )

    (rng, _, _, _, _, _, tables, n_eps) = _build_world(3)
    half = 256
    f_in = _random_flows(rng, half, n_eps)
    f_in["direction"][:] = 0
    f_eg = _random_flows(rng, half, n_eps)
    f_eg["direction"][:] = 1

    (oi1, oe1), acc1 = _accum_in_sequence(tables, f_in, f_eg)
    oi2, oe2, acc2, telem = _pair_program(tables, f_in, f_eg)
    assert (np.asarray(acc1) == np.asarray(acc2)).all()
    for a, b in ((oi1, oi2), (oe1, oe2)):
        assert (np.asarray(a.allowed) == np.asarray(b.allowed)).all()
        assert (
            np.asarray(a.proxy_port) == np.asarray(b.proxy_port)
        ).all()
        assert (
            np.asarray(a.match_kind) == np.asarray(b.match_kind)
        ).all()

    got = np.asarray(telem).astype(np.uint64)
    want = telemetry_from_outputs(
        oi2, np.zeros(half, np.int64)
    ) + telemetry_from_outputs(oe2, np.ones(half, np.int64))
    assert (got == want).all()
    assert telemetry_consistent(got)


def test_counter_fold_event_fold_oracle_consistency():
    """Satellite: for a random batch, the summed DropNotify /
    PolicyVerdictNotify counts from verdicts_to_events equal the
    on-device scatter counters and the oracle's verdict histogram."""
    import jax

    from cilium_tpu.engine.oracle import evaluate_batch_oracle
    from cilium_tpu.engine.verdict import (
        TupleBatch,
        _verdict_kernel_with_counters,
    )
    from cilium_tpu.monitor import MonitorBus, verdicts_to_events
    from cilium_tpu.monitor.events import (
        DropNotify,
        PolicyVerdictNotify,
    )
    from tests.test_verdict_engine import random_map_state

    rng = np.random.default_rng(19)
    ids = [1, 2, 3, 256, 300, 1000]
    states = [
        random_map_state(rng, ids, n_l4=8, n_l3=6) for _ in range(2)
    ]
    from cilium_tpu.compiler.tables import compile_map_states

    tables = compile_map_states(states, ids, 32, 8)
    n = 512
    batch_np = dict(
        ep_index=rng.integers(0, 2, size=n),
        identity=rng.choice(ids + [99999], size=n).astype(np.uint32),
        dport=rng.integers(1, 1024, size=n),
        proto=rng.choice([6, 17], size=n),
        direction=rng.integers(0, 2, size=n),
        is_fragment=rng.random(size=n) < 0.1,
    )
    batch = TupleBatch.from_numpy(**batch_np)
    step = jax.jit(_verdict_kernel_with_counters)
    v, l4c, l3c = step(tables, batch)

    import copy

    want_allow, _, want_kind = evaluate_batch_oracle(
        copy.deepcopy(states), **{
            k: batch_np[k]
            for k in ("ep_index", "identity", "dport", "proto",
                      "direction", "is_fragment")
        }
    )
    assert (np.asarray(v.allowed) == want_allow).all()
    assert (np.asarray(v.match_kind) == want_kind).all()

    bus = MonitorBus()
    q = bus.subscribe_queue()
    n_events = verdicts_to_events(
        bus,
        v,
        ep_ids=batch_np["ep_index"],
        identities=batch_np["identity"],
        dports=batch_np["dport"],
        protos=batch_np["proto"],
        directions=batch_np["direction"],
        emit_allowed=True,
    )
    drops = [e for e in q if isinstance(e, DropNotify)]
    verdict_events = [
        e for e in q if isinstance(e, PolicyVerdictNotify)
    ]
    denied = int((want_allow == 0).sum())
    # event fold == oracle histogram
    assert len(drops) == denied
    assert len(verdict_events) == n
    assert sum(1 for e in verdict_events if e.allowed) == n - denied
    # on-device scatter counters == oracle histogram: each lattice
    # hit (L4/L3/wild) bumps exactly one entry counter
    hits = int(
        np.asarray(l4c).sum() + np.asarray(l3c).sum()
    )
    oracle_hits = int(
        ((want_kind == 1) | (want_kind == 2) | (want_kind == 3)).sum()
    )
    assert hits == oracle_hits == int((want_allow == 1).sum())
    assert n_events == len(q)


def test_verdicts_to_events_sampling_caps_publishes():
    from types import SimpleNamespace

    from cilium_tpu.monitor import MonitorBus, verdicts_to_events

    n = 100
    v = SimpleNamespace(
        allowed=np.zeros(n, np.uint8),
        match_kind=np.zeros(n, np.uint8),
        proxy_port=np.zeros(n, np.int32),
    )
    bus = MonitorBus()
    q = bus.subscribe_queue()
    n_events = verdicts_to_events(
        bus, v,
        ep_ids=np.zeros(n, np.int64),
        identities=np.zeros(n, np.uint32),
        dports=np.zeros(n, np.int64),
        protos=np.full(n, 6),
        directions=np.zeros(n, np.int64),
        sample=7,
    )
    assert n_events == 7 and len(q) == 7
    # the aggregate counters stay exact despite the sampled fan-out
    from cilium_tpu.metrics import registry as metrics

    assert (
        metrics.drop_count.get("Policy denied (L3)", "INGRESS") >= n
    )


def test_fold_telemetry_registry_counters():
    from cilium_tpu.metrics import Registry

    telem = np.zeros((2, TELEM_COLS), np.uint64)
    telem[0, TELEM_TOTAL] = 10
    telem[0, TELEM_FORWARDED] = 6
    telem[0, TELEM_DENIED] = 4
    telem[0, TELEM_DROP_PREFILTER] = 1
    telem[0, TELEM_DROP_POLICY] = 2
    telem[0, TELEM_DROP_FRAG] = 1
    telem[0, TELEM_MATCH_L4] = 5
    telem[0, TELEM_MATCH_L3] = 1
    telem[0, TELEM_MATCH_NONE] = 3
    telem[0, TELEM_MATCH_FRAG] = 1
    r = Registry()
    fold_telemetry(telem, registry=r)
    assert r.forward_count.get("INGRESS") == 6
    assert r.drop_count.get("Policy denied (CIDR)", "INGRESS") == 1
    assert r.drop_count.get("Policy denied (L3)", "INGRESS") == 2
    assert r.drop_count.get("Fragmentation needed", "INGRESS") == 1
    assert (
        r.policy_verdict_total.get("INGRESS", "l4", "allowed") == 5
    )
    assert (
        r.policy_verdict_total.get("INGRESS", "none", "denied") == 3
    )
    summary = telemetry_summary(telem)
    assert summary["ingress"]["forwarded"] == 6
    assert "egress" in summary


def test_prometheus_escaping_and_gauge_signature():
    from cilium_tpu.metrics import Counter, Gauge

    c = Counter("t_total", 'help with "quotes" and \\slash',
                ("reason",))
    c.inc('a "quoted" rea\\son\nwith newline', value=2)
    text = "\n".join(c.expose())
    assert (
        'reason="a \\"quoted\\" rea\\\\son\\nwith newline"' in text
    )
    g = Gauge("t_gauge", "h", ("lbl",))
    g.set("x", value=3.5)
    assert g.get("x") == 3.5
    with pytest.raises(TypeError):
        g.set(3.5, "x")  # the old value-first form must not parse


def test_windowed_histogram_quantiles():
    from cilium_tpu.metrics import WindowedHistogram

    h = WindowedHistogram("t_h", "h", window=100)
    for v in range(1, 101):
        h.observe(v / 100.0)
    assert abs(h.window_quantile(0.5) - 0.51) < 0.02
    assert h.window_quantile(0.99) >= 0.99
    assert h.quantile(0.5) > 0.0  # bucket-interpolated estimate


def test_replay_collect_telemetry_and_spans():
    """replay(collect_telemetry=True): stats.telemetry covers every
    record exactly once (device accumulator for full batches + host
    fold for the padded tail), and the phase spans populate."""
    from cilium_tpu.replay import replay
    from tests.test_datapath import _build_world, _random_flows
    from cilium_tpu.native import encode_flow_records

    (rng, _, _, _, _, _, tables, n_eps) = _build_world(5)
    n = 700  # 2 full batches of 256 + a padded 188 tail
    f = _random_flows(rng, n, n_eps)
    buf = encode_flow_records(
        ep_id=f["ep_index"].astype(np.uint32),
        identity=np.zeros(n, np.uint32),
        saddr=f["saddr"],
        daddr=f["daddr"],
        sport=f["sport"].astype(np.uint16),
        dport=f["dport"].astype(np.uint16),
        proto=f["proto"].astype(np.uint8),
        direction=f["direction"].astype(np.uint8),
        is_fragment=f["is_fragment"].astype(np.uint8),
    )
    stats, l4c, l3c = replay(
        tables, buf, batch_size=256, collect_telemetry=True
    )
    assert stats.total == n
    telem = stats.telemetry
    assert telem is not None
    assert int(telem[:, TELEM_TOTAL].sum()) == n
    assert int(telem[:, TELEM_FORWARDED].sum()) == stats.allowed
    assert int(telem[:, TELEM_DENIED].sum()) == stats.denied
    assert telemetry_consistent(telem)
    assert stats.spans is not None
    report = stats.spans.report()
    assert report.get("dispatch", 0) > 0
    assert report.get("host_pack", 0) > 0


def test_trace_tuple_stages_and_rules():
    from tests.test_replay import _daemon_with_policy

    d, server, client = _daemon_with_policy()
    cid = client.security_identity.id

    got = d.trace_tuple(
        ep_id=10, saddr="10.0.0.11", daddr="10.0.0.10",
        dport=80, proto=6, direction=0, sport=4001,
    )
    assert got["allowed"] and got["verdict"] == "allowed"
    assert got["identity"] == cid
    stages = {s["stage"]: s for s in got["stages"]}
    assert stages["prefilter"]["decision"] == "pass"
    assert stages["conntrack"]["decision"] == "NEW"
    assert "L4 exact" in stages["policy"]["detail"]
    assert got["rules"], "matched rule attribution missing"
    assert "policy1" in got["rules"][0]["labels"]
    assert "Final verdict: ALLOWED" in got["text"]

    # world source → ipcache fallback → deny
    got = d.trace_tuple(
        ep_id=10, saddr="8.8.8.8", daddr="10.0.0.10", dport=80
    )
    assert not got["allowed"]
    stages = {s["stage"]: s for s in got["stages"]}
    assert "WORLD" in stages["ipcache"]["detail"]
    assert got["rules"] == []

    # prefiltered source drops regardless of policy
    d.prefilter.insert(["203.0.113.0/24"])
    got = d.trace_tuple(
        ep_id=10, saddr="203.0.113.7", daddr="10.0.0.10", dport=80
    )
    assert not got["allowed"]
    stages = {s["stage"]: s for s in got["stages"]}
    assert stages["prefilter"]["decision"] == "DROP"
    assert stages["combine"]["decision"] == "DROP"

    with pytest.raises(KeyError):
        d.trace_tuple(
            ep_id=9999, saddr="10.0.0.11", daddr="10.0.0.10", dport=80
        )


def test_trace_tuple_rest_route(tmp_path):
    from cilium_tpu.api.client import APIClient
    from cilium_tpu.api.server import APIServer
    from tests.test_replay import _daemon_with_policy

    d, server_ep, client_ep = _daemon_with_policy()
    sock = str(tmp_path / "trace.sock")
    srv = APIServer(d, sock).start()
    try:
        api = APIClient(sock)
        got = api.trace_tuple(
            {
                "ep_id": 10,
                "saddr": "10.0.0.11",
                "daddr": "10.0.0.10",
                "dport": 80,
                "direction": "ingress",
            }
        )
        assert got["verdict"] == "allowed"
        assert [s["stage"] for s in got["stages"]] == [
            "prefilter", "lb", "conntrack", "ipcache", "policy",
            "combine",
        ]
    finally:
        srv.stop()


def test_metrics_prometheus_text_route(tmp_path):
    import http.client
    import socket as socket_mod

    from cilium_tpu.api.server import APIServer
    from cilium_tpu.daemon import Daemon
    from tools.telemetry_smoke import parse_exposition

    d = Daemon()
    sock = str(tmp_path / "prom.sock")
    srv = APIServer(d, sock).start()
    try:
        conn = http.client.HTTPConnection("localhost")
        conn.sock = socket_mod.socket(
            socket_mod.AF_UNIX, socket_mod.SOCK_STREAM
        )
        conn.sock.connect(sock)
        conn.request("GET", "/metrics/prometheus")
        resp = conn.getresponse()
        assert resp.status == 200
        assert resp.headers["Content-Type"].startswith("text/plain")
        body = resp.read().decode()
        assert parse_exposition(body) > 0
        assert "cilium_policy_count" in body
    finally:
        srv.stop()


def test_daemon_process_flows_applies_prefilter():
    """The daemon-owned deny-by-CIDR set drops flows BEFORE policy
    evaluation (bpf_xdp.c order) and counts them under the canonical
    CIDR reason — so process_flows and trace_tuple agree."""
    from cilium_tpu.metrics import registry as metrics
    from cilium_tpu.native import encode_flow_records
    from tests.test_replay import _daemon_with_policy

    d, server, client = _daemon_with_policy()
    d.prefilter.insert(["203.0.113.0/24"])
    cid = client.security_identity.id
    n = 32
    buf = encode_flow_records(
        ep_id=np.full(n, 10, np.uint32),
        identity=np.full(n, cid, np.uint32),
        saddr=np.full(n, int.from_bytes(b"\xcb\x00\x71\x07", "big"),
                      np.uint32),  # 203.0.113.7 — prefiltered
        daddr=np.zeros(n, np.uint32),
        sport=np.full(n, 4001, np.uint16),
        dport=np.full(n, 80, np.uint16),
        proto=np.full(n, 6, np.uint8),
        direction=np.zeros(n, np.uint8),
        is_fragment=np.zeros(n, np.uint8),
    )
    before = metrics.drop_count.get(
        "Policy denied (CIDR)", "INGRESS"
    )
    stats = d.process_flows(buf, batch_size=16)
    assert stats.total == n and stats.denied == n
    assert (
        metrics.drop_count.get("Policy denied (CIDR)", "INGRESS")
        - before
        == n
    )
    # and trace_tuple reports the same drop for one of those tuples
    got = d.trace_tuple(
        ep_id=10, saddr="203.0.113.7", daddr="10.0.0.10", dport=80
    )
    assert not got["allowed"]
    assert got["stages"][0]["decision"] == "DROP"


def test_daemon_process_flows_fills_datapath_spans():
    from tests.test_replay import _daemon_with_policy, _make_buf

    d, server, client = _daemon_with_policy()
    rng = np.random.default_rng(4)
    cid = client.security_identity.id
    buf = _make_buf(rng, 64, [10], [cid, 999999])
    stats = d.process_flows(buf, batch_size=32)
    report = d.datapath_spans.report()
    assert report.get("host_pack", 0) >= 0
    assert report.get("dispatch", 0) > 0
    assert report.get("event_fold", 0) > 0
    assert stats.spans is d.datapath_spans
