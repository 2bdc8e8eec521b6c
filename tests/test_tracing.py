"""Span plane units: tracer semantics (ids, sampling, propagation,
ring bounds), traceparent parsing, StatSpan's shared clock with
SpanStats, the SpanStat re-entrant-start fix, /debug/profile reset,
/debug/traces over REST with header propagation, the flow-record
trace-id join, device-resource accounting metrics across delta
publishes, and the `cilium-tpu trace` renderings."""

import threading
import time

import numpy as np
import pytest

from cilium_tpu import tracing
from cilium_tpu.tracing import (
    Tracer,
    format_traceparent,
    parse_traceparent,
    render_span_tree,
)


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------


def test_deterministic_ids_under_seed():
    a, b = Tracer(seed=42), Tracer(seed=42)
    with a.span("r") as ra:
        with a.span("c") as ca:
            pass
    with b.span("r") as rb:
        with b.span("c") as cb:
            pass
    assert ra.trace_id == rb.trace_id
    assert ra.span_id == rb.span_id
    assert ca.span_id == cb.span_id
    # different seed → different ids
    with Tracer(seed=43).span("r") as rc:
        pass
    assert rc.trace_id != ra.trace_id


def test_context_propagation_and_status():
    t = Tracer(seed=1)
    with t.span("root", site="api") as root:
        assert tracing.current_span() is root
        with t.span("child", site="daemon") as child:
            assert child.trace_id == root.trace_id
            assert child.parent_id == root.span_id
        # sibling after the child closed parents to the root again
        with t.span("child2") as child2:
            assert child2.parent_id == root.span_id
    assert tracing.current_span() is None
    # exception → error status + error attr, and it propagates
    with pytest.raises(RuntimeError):
        with t.span("boom"):
            raise RuntimeError("x")
    boom = [s for s in t.snapshot() if s.name == "boom"][0]
    assert boom.status == "error"
    assert "error" in boom.attrs
    # children close before parents: durations nest
    child_span = [s for s in t.snapshot() if s.name == "child"][0]
    root_span = [s for s in t.snapshot() if s.name == "root"][0]
    assert 0 < child_span.duration <= root_span.duration


def test_traceparent_roundtrip_and_rejects():
    t = Tracer(seed=2)
    with t.span("r") as r:
        header = format_traceparent(r)
    ctx = parse_traceparent(header)
    assert ctx.trace_id == r.trace_id
    assert ctx.span_id == r.span_id
    assert ctx.sampled
    for bad in (
        None, "", "junk", "00-abc-def-01",
        "00-" + "g" * 32 + "-" + "1" * 16 + "-01",
        "00-" + "0" * 32 + "-" + "1" * 16 + "-01",  # all-zero trace
        "00-" + "1" * 32 + "-" + "0" * 16 + "-01",  # all-zero span
    ):
        assert parse_traceparent(bad) is None, bad
    # an explicit remote parent adopts the caller's ids
    with t.span("served", parent=ctx) as sp:
        assert sp.trace_id == r.trace_id
        assert sp.parent_id == r.span_id
    # unsampled flags (…-00) suppress recording entirely
    unsampled = parse_traceparent(header[:-2] + "00")
    assert unsampled is not None and not unsampled.sampled
    n_before = len(t.snapshot())
    with t.span("shed", parent=unsampled) as shed:
        assert shed.trace_id == ""
    assert len(t.snapshot()) == n_before


def test_head_sampling_inherited_by_children():
    t = Tracer(seed=3, sample_rate=0.0)
    with t.span("root") as root:
        assert root.trace_id == ""
        assert tracing.current_trace_id() == ""
        with t.span("child") as child:
            assert child.trace_id == ""
        tracing.add_event("ignored")  # must not blow up
        # record() under an unsampled context must not leak spans
        # either (the head decision covers jit.compile etc.)
        assert t.record("jit.compile", "x", 0.1) is None
    assert t.snapshot() == []
    # rate back to 1: spans record again
    t.sample_rate = 1.0
    with t.span("root2"):
        pass
    assert [s.name for s in t.snapshot()] == ["root2"]


def test_ring_bound_and_dropped():
    t = Tracer(seed=4, capacity=4)
    for i in range(7):
        with t.span(f"s{i}"):
            pass
    assert len(t.snapshot()) == 4
    assert t.dropped == 3
    assert t.finished_total == 7
    assert [s.name for s in t.snapshot()] == ["s3", "s4", "s5", "s6"]


def test_query_and_slowest():
    t = Tracer(seed=5)
    with t.span("slow", site="a"):
        time.sleep(0.02)
    with t.span("fast", site="b"):
        pass
    spans = t.query(site="a")
    assert [s.name for s in spans] == ["slow"]
    assert t.query(min_duration_ms=10.0)[0].name == "slow"
    rows = t.slowest_traces(5)
    assert rows[0]["root"] == "slow"
    assert rows[0]["duration_ms"] >= rows[1]["duration_ms"]
    # get_trace returns only that trace's spans
    tid = rows[0]["trace_id"]
    assert {s.trace_id for s in t.get_trace(tid)} == {tid}


def _profiled(tmp_path, body):
    """Run `body` under the JAX profiler; {event name: [stats dict]}
    of every host event in the recorded trace."""
    jax = pytest.importorskip("jax")
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    events = {}
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                events.setdefault(ev.name, []).append(dict(ev.stats))
    return events


def test_spans_mirror_into_profiler_trace(tmp_path):
    """A begun-and-finished span opens a profiler annotation under its
    name: sampled spans carry their ids as stats (the ring span and
    the trace event join by id), unsampled ones appear with none, and
    record() adds no event."""
    t = Tracer(seed=41)
    quiet = Tracer(seed=42, sample_rate=0.0)
    spans = {}

    def body():
        with t.span("mirror.sampled") as sp:
            spans["sampled"] = sp
        with quiet.span("mirror.unsampled"):
            pass
        t.record("mirror.recorded", "x", 0.001)

    events = _profiled(tmp_path, body)
    sp = spans["sampled"]
    assert events["mirror.sampled"] == [
        {"trace_id": sp.trace_id, "span_id": sp.span_id}
    ]
    assert events["mirror.unsampled"] == [{}]
    assert "mirror.recorded" not in events
    assert quiet.snapshot() == []


def test_span_finished_on_another_thread(tmp_path):
    """A span begun on one thread and finished on another closes its
    annotation and exports without error, profiler on or off."""
    t = Tracer(seed=43)
    errors = []

    def cross():
        span, token = t.begin("cross.thread")

        def finish():
            try:
                t.finish(span, token)
            except Exception as exc:  # noqa: BLE001 — surfaced below
                errors.append(exc)

        worker = threading.Thread(target=finish)
        worker.start()
        worker.join(timeout=10)
        assert not worker.is_alive()
        tracing._current.set(None)

    tok = tracing._current.set(None)
    try:
        cross()
        events = _profiled(tmp_path, cross)
    finally:
        tracing._current.reset(tok)
    assert errors == []
    names = [s.name for s in t.snapshot()]
    assert names == ["cross.thread", "cross.thread"]
    assert len(events["cross.thread"]) == 1
    assert events["cross.thread"][0]["span_id"] == t.snapshot()[1].span_id


def test_add_event_lands_on_active_span():
    t = Tracer(seed=7)
    tok = tracing._current.set(None)  # isolate from ambient context
    try:
        with t.span("op") as sp:
            tracing.add_event("breaker.decision", allowed=False)
        assert sp.events[0]["name"] == "breaker.decision"
        assert sp.events[0]["allowed"] is False
        assert sp.events[0]["offset_ms"] >= 0
    finally:
        tracing._current.reset(tok)


def test_render_span_tree_shapes():
    t = Tracer(seed=8)
    with t.span("root", site="api") as r:
        with t.span("child", site="daemon", attrs={"batch": 0}):
            tracing.add_event("shed", flows=3)
    text = render_span_tree(
        [s.to_dict() for s in t.get_trace(r.trace_id)]
    )
    lines = text.splitlines()
    assert lines[0].startswith("root (api)")
    assert lines[1].startswith("  child (daemon)")
    assert "batch=0" in lines[1]
    assert any("@" in line and "shed" in line for line in lines)
    assert render_span_tree([]) == "(no spans)\n"
    # an orphan (parent evicted from the ring) renders as a root
    orphan = [s.to_dict() for s in t.get_trace(r.trace_id)][1:]
    assert render_span_tree(orphan).startswith("child")


def test_track_jit_counts_hits_misses_and_compile_seconds():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from cilium_tpu.metrics import registry as metrics

    site = "test.trackjit"
    fn = tracing.track_jit(jax.jit(lambda x: x * 2), site)
    h0 = metrics.jit_cache_hits.get(site)
    m0 = metrics.jit_cache_misses.get(site)
    c0 = metrics.jit_compile_seconds.get(site)
    fn(jnp.ones(8))  # compile
    fn(jnp.ones(8))  # cached
    fn(jnp.ones(16))  # new shape class → compile
    assert metrics.jit_cache_misses.get(site) == m0 + 2
    assert metrics.jit_cache_hits.get(site) == h0 + 1
    assert metrics.jit_compile_seconds.get(site) > c0


# ---------------------------------------------------------------------------
# StatSpan: one clock window for spans AND SpanStats
# ---------------------------------------------------------------------------


def test_stat_span_shares_clock_with_spanstats():
    from cilium_tpu.spanstat import SpanStats

    t = Tracer(seed=9)
    stats = SpanStats()
    ss = tracing.stat_span(stats, "dispatch", site="daemon", trc=t)
    ss.start()
    time.sleep(0.002)
    ss.end()
    span = t.snapshot()[-1]
    assert span.name == "dispatch"
    # EXACT agreement: /debug/profile and /debug/traces report the
    # same number for the phase
    assert stats.span("dispatch").total() == span.duration
    assert stats.span("dispatch").num_success == 1
    # failure accounting
    ss2 = tracing.stat_span(stats, "dispatch", trc=t).start()
    ss2.end(success=False)
    assert stats.span("dispatch").num_failure == 1
    assert t.snapshot()[-1].status == "error"
    # unsampled tracer still feeds the SpanStat
    t0 = Tracer(seed=9, sample_rate=0.0)
    ss3 = tracing.stat_span(stats, "other", trc=t0).start()
    ss3.end()
    assert stats.span("other").num_success == 1
    assert t0.snapshot() == []


def test_stat_span_abandoned_window_does_not_poison_stats():
    """A StatSpan abandoned by an exception (start() without end(),
    e.g. a malformed buffer raising mid-phase) must not fold the
    inter-request gap into the accumulator on the next start()."""
    from cilium_tpu.spanstat import SpanStats

    t = Tracer(seed=10)
    stats = SpanStats()
    tok = tracing._current.set(None)
    try:
        tracing.stat_span(stats, "host_pack", trc=t).start()
        # abandoned: no end().  The stat's running state is untouched…
        assert stats.span("host_pack")._start is None
        time.sleep(0.005)
        ss = tracing.stat_span(stats, "host_pack", trc=t).start()
        ss.end()
        # …so the gap never lands in the totals
        assert stats.span("host_pack").total() < 0.004
        assert stats.span("host_pack").num_success == 1
        # the UNSAMPLED path has the same guarantee: the stat's own
        # running state is never engaged, so an abandoned noop
        # window costs nothing either
        t0 = Tracer(seed=10, sample_rate=0.0)
        tracing.stat_span(stats, "noop_phase", trc=t0).start()
        time.sleep(0.005)
        ss2 = tracing.stat_span(stats, "noop_phase", trc=t0).start()
        ss2.end()
        assert stats.span("noop_phase").total() < 0.004
        assert stats.span("noop_phase").num_success == 1
    finally:
        tracing._current.reset(tok)


def test_spanstat_reentrant_start_accumulates():
    """Satellite: start() while running folds the in-flight elapsed
    time instead of silently discarding it."""
    from cilium_tpu.spanstat import SpanStat

    s = SpanStat()
    s.start()
    time.sleep(0.002)
    s.start()  # re-entrant: the first window must be accounted
    time.sleep(0.001)
    s.end()
    assert s.num_success == 2
    assert s.total() >= 0.003 - 1e-4
    # end without start is still a no-op
    assert SpanStat().end().total() == 0.0


# ---------------------------------------------------------------------------
# daemon + REST integration
# ---------------------------------------------------------------------------


def _world():
    from tests.test_replay import _daemon_with_policy

    return _daemon_with_policy()


def _buf(rng, n, identities):
    from tests.test_replay import _make_buf

    return _make_buf(rng, n, [10], identities)


def test_debug_profile_reset_param():
    from cilium_tpu.api.server import DaemonAPI
    from cilium_tpu.metrics import registry as metrics

    d, server, client = _world()
    api = DaemonAPI(d)
    rng = np.random.default_rng(1)
    d.process_flows(
        _buf(rng, 32, [client.security_identity.id]), batch_size=16
    )
    prof = api.debug_profile(reset=True)
    # the reply shows the PRE-reset totals…
    assert prof["reset"] is True
    assert prof["cumulative_since_reset"] is True
    assert prof["datapath_spans"]["dispatch"]["num_success"] > 0
    # …and the accumulators (plus their mirrored gauges) are zeroed
    assert d.datapath_spans == {}
    assert d.regen_spans == {}
    assert metrics.spanstat_seconds.get("datapath", "dispatch") == 0.0
    prof2 = api.debug_profile()
    assert prof2["datapath_spans"] == {}
    assert "reset" not in prof2
    # the next stream repopulates from zero
    d.process_flows(
        _buf(rng, 32, [client.security_identity.id]), batch_size=16
    )
    assert api.debug_profile()["datapath_spans"]["dispatch"][
        "num_success"
    ] == 2


def test_rest_traceparent_propagation_and_traces_route(tmp_path):
    """The REST seam: an inbound traceparent is adopted (client ids on
    every span + flow record), the reply carries traceparent/
    X-Trace-Id headers, and /debug/traces serves the span tree."""
    import http.client
    import socket as _socket

    from cilium_tpu.api.client import APIClient
    from cilium_tpu.api.server import APIServer

    d, server_ep, client_ep = _world()
    tracing.tracer.reset(seed=11, sample_rate=1.0)
    sock = str(tmp_path / "trace.sock")
    srv = APIServer(d, sock).start()
    try:
        client = APIClient(sock)
        tid = "ab" * 16
        psid = "cd" * 8
        rng = np.random.default_rng(2)
        reply = client.process_flows(
            _buf(rng, 48, [client_ep.security_identity.id]),
            traceparent=f"00-{tid}-{psid}-01",
        )
        assert reply["trace_id"] == tid

        got = client.traces_get({"trace-id": tid})
        spans = got["spans"]
        by_id = {s["span_id"]: s for s in spans}
        roots = [s for s in spans if s["parent_id"] not in by_id]
        assert len(roots) == 1
        assert roots[0]["name"] == "http.request"
        assert roots[0]["parent_id"] == psid
        assert {s["name"] for s in spans} >= {
            "daemon.process_flows", "host_pack", "dispatch",
            "engine.dispatch",
        }

        # min-ms / site / slowest filters
        assert all(
            s["site"] == "engine.dispatch"
            for s in client.traces_get(
                {"trace-id": tid, "site": "engine.dispatch"}
            )["spans"]
        )
        slow = client.traces_get({"slowest": 3})
        assert slow["traces"][0]["duration_ms"] > 0
        from cilium_tpu.api.client import APIError

        with pytest.raises(APIError):
            client.traces_get({"bogus": "1"})

        # flow records joined by the same id over /flows
        flows = client.flows_get({"trace-id": tid})
        assert flows["matched"] > 0
        assert all(f["trace_id"] == tid for f in flows["flows"])

        # long-poll routes are NOT traced: an idle follow wait must
        # not dominate --slowest or churn the ring
        before = tracing.tracer.started_total
        client.flows_get(
            {"follow": "1", "since-seq": "0", "timeout": "0.1",
             "last": "0"}
        )
        assert tracing.tracer.started_total == before

        # raw response headers carry the span context back
        conn = http.client.HTTPConnection("localhost")
        conn.sock = _socket.socket(
            _socket.AF_UNIX, _socket.SOCK_STREAM
        )
        conn.sock.connect(sock)
        conn.request("GET", "/status")
        resp = conn.getresponse()
        resp.read()
        assert resp.getheader("X-Trace-Id")
        tp = parse_traceparent(resp.getheader("traceparent"))
        assert tp is not None
        assert tp.trace_id == resp.getheader("X-Trace-Id")
        conn.close()
    finally:
        srv.stop()
        tracing.tracer.reset(seed=None)


def test_trace_cli_renderings(capsys):
    """`cilium-tpu trace <id>` renders the tree; `--slowest N` ranks
    traces — driven through the in-process DaemonAPI fallback."""
    from cilium_tpu.api.server import DaemonAPI
    from cilium_tpu.cli import main as cli_main

    d, server_ep, client_ep = _world()
    tracing.tracer.reset(seed=13, sample_rate=1.0)
    rng = np.random.default_rng(3)
    d.process_flows(
        _buf(rng, 32, [client_ep.security_identity.id]),
        batch_size=16,
    )
    api = DaemonAPI(d)
    assert cli_main(["trace", "--slowest", "3"], api=api) == 0
    out = capsys.readouterr().out
    tid = out.split()[0]
    assert len(tid) == 32
    assert cli_main(["trace", tid], api=api) == 0
    tree = capsys.readouterr().out
    assert "daemon.process_flows (daemon)" in tree
    # unknown trace id → exit 1, no trace id at all → usage error
    assert cli_main(["trace", "f" * 32], api=api) == 1
    assert cli_main(["trace"], api=api) == 2
    tracing.tracer.reset(seed=None)


# ---------------------------------------------------------------------------
# device-resource accounting (publish layer + jit cache)
# ---------------------------------------------------------------------------


def test_device_table_bytes_and_jit_cache_across_publishes():
    """cilium_device_table_bytes{epoch} tracks the live/standby slots
    across full upload → delta scatter → full fallback, the donation
    counter charges delta publishes, and the scatter entry point
    counts jit compiles (miss then hit for a repeated shape class)."""
    pytest.importorskip("jax")
    from cilium_tpu.compiler.delta import tables_nbytes
    from cilium_tpu.compiler.tables import FleetCompiler
    from cilium_tpu.engine.publish import DeviceTableStore
    from cilium_tpu.maps.policymap import (
        INGRESS,
        PolicyKey,
        PolicyMapStateEntry,
    )
    from cilium_tpu.metrics import registry as metrics

    comp = FleetCompiler(identity_pad=32, filter_pad=4)
    ids = [256, 257, 258]
    store = DeviceTableStore()
    state = {PolicyKey(256, 80, 6, INGRESS): PolicyMapStateEntry()}

    def publish(token, with_delta):
        tables, _ = comp.compile([(1, dict(state), token)], ids)
        delta = (
            comp.delta_for(store.spare_stamp(), tables)
            if with_delta
            else None
        )
        dev, stats = store.publish(tables, delta)
        return tables, stats

    retired0 = metrics.device_table_retired_bytes.get()
    hits0 = metrics.jit_cache_hits.get("publish.scatter")
    miss0 = metrics.jit_cache_misses.get("publish.scatter")

    t1, s1 = publish(0, with_delta=False)
    assert s1.mode == "full"
    assert metrics.device_table_bytes.get("live") == tables_nbytes(t1)
    assert metrics.device_table_bytes.get("standby") == 0

    # second full (spare slot empty → no delta possible)
    state[PolicyKey(257, 443, 6, INGRESS)] = PolicyMapStateEntry()
    t2, s2 = publish(1, with_delta=True)
    assert s2.mode == "full"
    assert metrics.device_table_bytes.get("live") == tables_nbytes(t2)
    assert metrics.device_table_bytes.get("standby") == tables_nbytes(t1)

    # real delta: the standby (t1's epoch) is donated and rewritten
    state[PolicyKey(258, 8080, 6, INGRESS)] = PolicyMapStateEntry()
    t3, s3 = publish(2, with_delta=True)
    assert s3.mode == "delta"
    assert s3.scatter_leaves > 0
    assert metrics.device_table_bytes.get("live") == tables_nbytes(t3)
    assert metrics.device_table_bytes.get("standby") == tables_nbytes(t2)
    assert (
        metrics.device_table_retired_bytes.get()
        == retired0 + tables_nbytes(t1)
    )
    assert metrics.jit_cache_misses.get("publish.scatter") > miss0

    # same-shaped delta again → the scatter program is cache-served
    del state[PolicyKey(258, 8080, 6, INGRESS)]
    state[PolicyKey(258, 8081, 6, INGRESS)] = PolicyMapStateEntry()
    t4, s4 = publish(3, with_delta=True)
    assert s4.mode == "delta"
    assert metrics.jit_cache_hits.get("publish.scatter") > hits0

    # shape-class fallback: a delta=None publish reverts to full and
    # the gauges follow
    t5, s5 = publish(4, with_delta=False)
    assert s5.mode == "full"
    assert metrics.device_table_bytes.get("live") == tables_nbytes(t5)


def test_publish_span_exported():
    """DeviceTableStore.publish lands a publish.epoch span with mode
    and byte attribution."""
    pytest.importorskip("jax")
    from cilium_tpu.compiler.tables import FleetCompiler
    from cilium_tpu.engine.publish import DeviceTableStore
    from cilium_tpu.maps.policymap import (
        INGRESS,
        PolicyKey,
        PolicyMapStateEntry,
    )

    tracing.tracer.reset(seed=21, sample_rate=1.0)
    comp = FleetCompiler(identity_pad=32, filter_pad=4)
    tables, _ = comp.compile(
        [(1, {PolicyKey(256, 80, 6, INGRESS): PolicyMapStateEntry()}, 0)],
        [256],
    )
    DeviceTableStore().publish(tables, None)
    spans = [
        s for s in tracing.tracer.snapshot()
        if s.name == "publish.epoch"
    ]
    assert spans
    assert spans[-1].attrs["mode"] == "full"
    assert spans[-1].attrs["bytes_h2d"] > 0
    tracing.tracer.reset(seed=None)


# ---------------------------------------------------------------------------
# resilience attribution
# ---------------------------------------------------------------------------


def test_breaker_and_admission_events_on_spans():
    from cilium_tpu.resilience import AdmissionGate, CircuitBreaker

    t = Tracer(seed=31)
    breaker = CircuitBreaker(name="x", failure_threshold=1)
    gate = AdmissionGate(limit=4)
    with t.span("batch") as sp:
        assert breaker.allow()
        breaker.record_failure("boom")
        assert not breaker.allow()  # open → shed
        assert gate.reserve(3)
        assert not gate.reserve(3)  # over the limit → shed event
    names = [e["name"] for e in sp.events]
    assert names.count("breaker.decision") == 2
    assert "breaker.failure" in names
    assert "admission.shed" in names
    decisions = [
        e for e in sp.events if e["name"] == "breaker.decision"
    ]
    assert decisions[0]["allowed"] is True
    assert decisions[1]["allowed"] is False
    shed = [e for e in sp.events if e["name"] == "admission.shed"][0]
    assert shed["flows"] == 3 and shed["limit"] == 4


def test_watchdog_propagates_trace_context():
    """Spans opened inside a watchdogged call parent to the caller's
    active span (contextvars snapshot crosses the worker thread)."""
    from cilium_tpu.resilience import DispatchWatchdog

    t = Tracer(seed=32)
    wd = DispatchWatchdog(timeout=5.0)

    def work():
        with t.span("inner"):
            return tracing.current_trace_id()

    with t.span("outer") as outer:
        inner_tid = wd.run(work)
    assert inner_tid == outer.trace_id
    inner = [s for s in t.snapshot() if s.name == "inner"][0]
    assert inner.parent_id == outer.span_id


# ---------------------------------------------------------------------------
# flow plane join
# ---------------------------------------------------------------------------


def test_flow_records_carry_trace_id_and_filter():
    from cilium_tpu.flow import FlowFilter, FlowStore, capture_batch

    store = FlowStore()
    n = 6
    capture_batch(
        store,
        ep_ids=np.full(n, 10),
        src_identities=np.full(n, 256),
        dst_identities=np.full(n, 300),
        dports=np.full(n, 80),
        protos=np.full(n, 6),
        directions=np.zeros(n, np.int64),
        allowed=np.asarray([1, 0, 1, 0, 1, 0], bool),
        match_kind=np.ones(n, np.int32),
        trace_id="ab" * 16,
    )
    capture_batch(
        store,
        ep_ids=np.full(2, 10),
        src_identities=np.full(2, 256),
        dst_identities=np.full(2, 300),
        dports=np.full(2, 80),
        protos=np.full(2, 6),
        directions=np.zeros(2, np.int64),
        allowed=np.zeros(2, bool),
        match_kind=np.ones(2, np.int32),
    )
    flt = FlowFilter.from_params({"trace-id": "AB" * 16})
    got = store.query(flt)
    assert len(got) == n
    assert all(r.trace_id == "ab" * 16 for r in got)
    # untraced records have no id and don't match
    assert all(
        r.trace_id == "" for r in store.query() if r not in got
    )
    assert "trace_id" in got[0].to_dict()
