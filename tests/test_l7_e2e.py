"""L7 end-to-end: wide rule sets, host fallback, wire parsing, and the
proxy request-verdict entry point.

Reference semantics covered:
  * pkg/envoy/server.go:316,448 — header-carrying rules participate in
    the OR-across-rules verdict (HeaderMatcher path);
  * envoy/cilium_l7policy.cc — allow = any rule matches; deny → 403 +
    access log;
  * pkg/kafka/request.go:88 — wire-frame parsing feeds the matcher;
  * pkg/kafka/correlation_cache.go:97 — response pairing;
  * no silent truncation: over-length fields route to the host matcher.
"""

import numpy as np
import pytest

from cilium_tpu.l7.http import (
    HTTPRuleSpec,
    compile_http_rules,
    evaluate_http_batch,
    evaluate_with_host_fallback,
    http_rule_matches_host,
    pad_headers,
    pad_requests,
)
from cilium_tpu.l7.kafka import (
    KafkaRequest,
    KafkaRuleSpec,
    MAX_TOPICS,
    compile_kafka_rules,
    evaluate_with_host_fallback as kafka_host_fallback,
    matches_rules_host,
)
from cilium_tpu.l7.kafka_wire import (
    CorrelationCache,
    KafkaParseError,
    decode_request,
    decode_stream,
    encode_request,
)


# ---------------------------------------------------------------------------
# wide rule sets (multi-word accept masks)
# ---------------------------------------------------------------------------


def test_http_200_rules_multiword():
    """R≈200 device rules per filter — far beyond one u32 accept word;
    device verdicts must stay bit-identical to the host matcher."""
    rng = np.random.default_rng(3)
    n_ident = 64
    specs = []
    for i in range(200):
        specs.append(
            HTTPRuleSpec(
                identity_indices=[int(x) for x in rng.integers(0, n_ident, 4)],
                method="GET" if i % 2 else "POST",
                path=f"/svc{i}/[a-z]+",
            )
        )
    policy = compile_http_rules(specs, n_ident)
    assert policy.tables.n_rules == 200
    assert policy.tables.n_words == 7
    assert policy.tables.ident_rules.shape == (n_ident, 7)

    requests = []
    for i in range(512):
        r = int(rng.integers(0, 220))
        requests.append(
            (
                b"GET" if r % 2 else b"POST",
                f"/svc{r}/abc".encode(),
                b"",
            )
        )
    ident = rng.integers(0, n_ident, size=len(requests)).astype(np.int32)
    known = np.ones(len(requests), dtype=bool)
    m, ml, p, pl, h, hl, overflow = pad_requests(requests)
    assert not overflow.any()
    allowed, _ = evaluate_http_batch(
        policy.tables, m, ml, p, pl, h, hl, ident, known
    )
    allowed = np.asarray(allowed)
    for i, (mm, pp, hh) in enumerate(requests):
        want = any(
            int(ident[i]) in s.identity_indices
            and http_rule_matches_host(s, mm, pp, hh)
            for s in specs
        )
        assert bool(allowed[i]) == want, (i, requests[i])


def test_kafka_200_rules_multiword():
    rng = np.random.default_rng(5)
    n_ident = 32
    specs = [
        KafkaRuleSpec(
            identity_indices=[int(x) for x in rng.integers(0, n_ident, 3)],
            api_keys=(int(i % 4),),
            topic=f"t{i}",
        )
        for i in range(200)
    ]
    tables = compile_kafka_rules(specs, n_ident)
    assert tables.n_rules == 200
    assert tables.ident_rules.shape == (n_ident, 7)

    requests = [
        KafkaRequest(kind=int(i % 4), version=0, topics=(f"t{int(t)}",))
        for i, t in enumerate(rng.integers(0, 220, size=256))
    ]
    ident = rng.integers(0, n_ident, size=len(requests)).astype(np.int32)
    got = kafka_host_fallback(
        tables, requests, ident, np.ones(len(requests), dtype=bool)
    )
    for i, req in enumerate(requests):
        want = matches_rules_host(req, specs, int(ident[i]))
        assert bool(got[i]) == want, (i, req)


# ---------------------------------------------------------------------------
# headers + overflow
# ---------------------------------------------------------------------------


def test_header_rule_reaches_verdict():
    """Traffic allowed ONLY by a header-carrying rule must be allowed —
    the round-1/2 advisor finding (header rules were split out and
    never evaluated)."""
    specs = [
        HTTPRuleSpec(
            identity_indices=[0],
            method="GET",
            path="/public",
        ),
        HTTPRuleSpec(
            identity_indices=[0],
            method="GET",
            path="/secret",
            headers=("X-Token: abc",),
        ),
    ]
    policy = compile_http_rules(specs, 4)
    # the header rule is decided on the device: its verdicts equal the
    # host matcher's
    assert len(policy.device_rules) == 2
    assert policy.tables.hdr_rules.shape[0] == 1

    requests = [
        (b"GET", b"/secret", b""),
        (b"GET", b"/secret", b""),
        (b"GET", b"/public", b""),
    ]
    headers = [{"x-token": "abc"}, {"x-token": "nope"}, None]
    ident = np.zeros(3, dtype=np.int32)
    known = np.ones(3, dtype=bool)
    host = [
        any(http_rule_matches_host(s, *r, hd) for s in policy.device_rules)
        for r, hd in zip(requests, headers)
    ]
    m, ml, p, pl, h, hl, _ = pad_requests(requests)
    names, pairs, _ = pad_headers(policy.tables, headers)
    device, _ = evaluate_http_batch(
        policy.tables, m, ml, p, pl, h, hl, ident, known,
        headers=(names, pairs),
    )
    assert np.asarray(device).tolist() == host
    got = evaluate_with_host_fallback(
        policy, requests, ident, known, headers
    )
    assert got.tolist() == [True, False, True]


def test_header_only_policy_no_device_rules():
    """A filter whose ONLY rules carry headers: the header tables
    alone decide it, on the device."""
    specs = [
        HTTPRuleSpec(
            identity_indices=[1], headers=("X-Allow",)
        )
    ]
    policy = compile_http_rules(specs, 4)
    requests = [(b"GET", b"/a", b""), (b"GET", b"/a", b"")]
    got = evaluate_with_host_fallback(
        policy,
        requests,
        np.array([1, 1], dtype=np.int32),
        np.ones(2, dtype=bool),
        [{"x-allow": ""}, None],
    )
    assert got.tolist() == [True, False]


def test_overflow_path_never_truncated():
    """Fields beyond the padded budgets must not be decided from
    truncated bytes, in either direction."""
    long_path = "/deep/" + "a" * 200  # > default 128-byte budget
    specs = [
        HTTPRuleSpec(identity_indices=[0], path=long_path),
    ]
    policy = compile_http_rules(specs, 2)
    requests = [
        (b"GET", long_path.encode(), b""),  # exact match, overflows
        (b"GET", long_path.encode() + b"x", b""),  # overflow, no match
        (b"GET", b"/deep/aaa", b""),  # fits, no match
    ]
    ident = np.zeros(3, dtype=np.int32)
    known = np.ones(3, dtype=bool)
    m, ml, p, pl, h, hl, overflow = pad_requests(requests)
    assert overflow.tolist() == [True, True, False]
    got = evaluate_with_host_fallback(policy, requests, ident, known)
    assert got.tolist() == [True, False, False]


def test_kafka_topic_overflow_host_path():
    """A request naming more topics than the tensor row holds is
    re-run host-side: 'all topics must be allowed' has to see every
    topic, not the first MAX_TOPICS."""
    n = MAX_TOPICS + 3
    specs = [
        KafkaRuleSpec(identity_indices=[0], topic=f"t{i}")
        for i in range(n - 1)  # t{n-1} NOT allowed
    ]
    tables = compile_kafka_rules(specs, 2)
    ok = KafkaRequest(
        kind=0, version=0, topics=tuple(f"t{i}" for i in range(n - 1))
    )
    bad = KafkaRequest(
        kind=0, version=0, topics=tuple(f"t{i}" for i in range(n))
    )
    got = kafka_host_fallback(
        tables, [ok, bad], np.zeros(2, np.int32), np.ones(2, bool)
    )
    assert got.tolist() == [True, False]
    assert matches_rules_host(bad, specs, 0) is False


# ---------------------------------------------------------------------------
# kafka wire format
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", [0, 1, 2, 3, 8, 9])
def test_kafka_wire_roundtrip(kind):
    req = KafkaRequest(
        kind=kind,
        version=0,
        client_id="client-7",
        topics=("alpha", "beta"),
        parsed=True,
    )
    frame = encode_request(req, correlation_id=42)
    got, cid, consumed = decode_request(frame)
    assert consumed == len(frame)
    assert cid == 42
    assert got.parsed is True
    assert got.kind == kind and got.version == 0
    assert got.client_id == "client-7"
    assert got.topics == ("alpha", "beta")


def test_kafka_wire_unknown_key_degrades():
    """Unknown API key: header parses, payload doesn't → parsed=False
    (the matchNonTopicRequests degraded mode)."""
    req = KafkaRequest(kind=18, version=0, client_id="c", topics=())
    frame = encode_request(req, correlation_id=7)
    got, cid, _ = decode_request(frame)
    assert got.parsed is False
    assert got.kind == 18
    assert got.client_id == "c"


def test_kafka_wire_unsupported_version_degrades():
    req = KafkaRequest(kind=1, version=5, client_id="c", topics=("t",))
    frame = encode_request(req, correlation_id=7)
    got, _, _ = decode_request(frame)
    assert got.parsed is False and got.topics == ()


def test_kafka_wire_malformed_raises():
    with pytest.raises(KafkaParseError):
        decode_request(b"\x00\x00\x00\x02\x00\x00")  # size < header
    with pytest.raises(KafkaParseError):
        decode_request(b"\x00\x00")  # not even a size


def test_kafka_wire_stream_and_correlation():
    reqs = [
        KafkaRequest(kind=0, version=0, topics=("a",)),
        KafkaRequest(kind=3, version=0, topics=("b", "c")),
    ]
    buf = b"".join(
        encode_request(r, correlation_id=i) for i, r in enumerate(reqs)
    )
    got = decode_stream(buf + b"\x00\x00")  # trailing partial ignored
    assert [r.kind for r, _ in got] == [0, 3]

    cache = CorrelationCache()
    for r, cid in got:
        cache.record(cid, r)
    assert cache.match(1).topics == ("b", "c")
    assert cache.match(1) is None
    assert len(cache) == 1


# ---------------------------------------------------------------------------
# proxy entry point: proxy_port>0 flow → L7 verdict + access log
# ---------------------------------------------------------------------------


def _mk_daemon_with_http_redirect():
    from tests.test_daemon import (
        Daemon,
        IngressRule,
        L7Rules,
        LabelArray,
        PortProtocol,
        PortRule,
        PortRuleHTTP,
        Rule,
        es_k8s,
        k8s_labels,
        wait_trigger,
    )

    d = Daemon()
    server = d.create_endpoint(5, k8s_labels(app="api"))
    client = d.create_endpoint(6, k8s_labels(app="ui"))
    rule = Rule(
        endpoint_selector=es_k8s(app="api"),
        ingress=[
            IngressRule(
                from_endpoints=[es_k8s(app="ui")],
                to_ports=[
                    PortRule(
                        ports=[PortProtocol(port="80", protocol="TCP")],
                        rules=L7Rules(
                            http=[
                                PortRuleHTTP(method="GET", path="/v1/.*"),
                                PortRuleHTTP(
                                    method="POST",
                                    path="/admin",
                                    headers=["X-Admin: yes"],
                                ),
                            ]
                        ),
                    )
                ],
            )
        ],
        labels=LabelArray.parse("l7e2e"),
    )
    d.policy_add([rule])
    wait_trigger(d)
    return d, server, client


def test_proxied_flow_produces_verdict_and_log():
    """The full circuit: datapath marks proxy_port>0 → redirect lookup
    by port → batched verdicts → access-log records on the monitor."""
    d, server, client = _mk_daemon_with_http_redirect()
    redirect = d.proxy.redirect_for(5, True, "TCP", 80)
    assert redirect is not None

    # flow carrying the datapath's proxy_port verdict
    from cilium_tpu.maps.policymap import INGRESS, PolicyKey

    cid = client.security_identity.id
    entry = server.realized_map_state[PolicyKey(cid, 80, 6, INGRESS)]
    assert entry.proxy_port == redirect.proxy_port
    assert d.proxy.redirect_by_port(entry.proxy_port) is redirect

    from cilium_tpu.compiler.tables import PAD_ID, build_id_table

    id_table = build_id_table(list(d.identity_cache()))
    idx = {int(v): i for i, v in enumerate(id_table) if v != int(PAD_ID)}

    records = []
    d.monitor.subscribe(records.append)
    requests = [
        (b"GET", b"/v1/x", b""),
        (b"DELETE", b"/v1/x", b""),
        (b"POST", b"/admin", b""),
        (b"POST", b"/admin", b""),
    ]
    headers = [None, None, {"x-admin": "yes"}, {"x-admin": "no"}]
    allowed = d.proxy.verdict_http(
        redirect,
        requests,
        np.array([idx[cid]] * 4, dtype=np.int32),
        headers=headers,
    )
    assert allowed.tolist() == [True, False, True, False]

    from cilium_tpu.monitor.events import LogRecordNotify

    logs = [r for r in records if isinstance(r, LogRecordNotify)]
    assert len(logs) == 4
    assert [r.verdict for r in logs] == [
        "Forwarded", "Denied", "Forwarded", "Denied",
    ]
    assert all(r.l7_proto == "http" for r in logs)
    assert logs[0].endpoint_id == 5


def test_kafka_wire_negative_api_key_fatal():
    """A negative api_key would alias into the device matcher's
    clipped key range (api key 0 = Produce) and false-allow; the wire
    parser must treat it as a malformed header (ADVICE r3)."""
    import struct

    from cilium_tpu.l7.kafka_wire import KafkaParseError

    body = struct.pack(">hhi", -1, 0, 99) + struct.pack(">h", -1)
    frame = struct.pack(">i", len(body)) + body
    with pytest.raises(KafkaParseError):
        decode_request(frame)


def test_kafka_stream_partial_vs_malformed():
    """Trailing partial frame → keep what parsed; structurally
    malformed frame → connection-fatal KafkaParseError, not a silent
    skip (request.go: unparseable header kills the connection)."""
    import struct

    from cilium_tpu.l7.kafka_wire import KafkaParseError, decode_stream

    good = encode_request(
        KafkaRequest(kind=3, version=0, client_id="c", topics=("t",),
                     parsed=True),
        correlation_id=1,
    )
    # partial: first 6 bytes of a second frame
    out = decode_stream(good + good[:6])
    assert len(out) == 1 and out[0][1] == 1

    # malformed: negative frame size
    bad = struct.pack(">i", -5)
    with pytest.raises(KafkaParseError):
        decode_stream(good + bad)


def test_kafka_correlation_duplicate_rejected():
    from cilium_tpu.l7.kafka_wire import CorrelationCache, KafkaParseError

    cache = CorrelationCache()
    req = KafkaRequest(kind=0, version=0, client_id="c", topics=("t",),
                       parsed=True)
    cache.record(5, req)
    with pytest.raises(KafkaParseError):
        cache.record(5, req)
    assert cache.match(5) is req
    assert cache.match(5) is None


def test_kafka_overflow_rows_force_denied_on_device():
    """pad_kafka_requests truncates >MAX_TOPICS rows; the device
    matcher must deny them outright so only the host-fallback path
    (which re-runs the full topic list) can allow them."""
    import numpy as np

    from cilium_tpu.l7.kafka import (
        MAX_TOPICS,
        KafkaRuleSpec,
        compile_kafka_rules,
        evaluate_kafka_batch,
        evaluate_with_host_fallback,
        pad_kafka_requests,
    )

    # rule allows ALL topics for identity 0 → host verdict is allow
    specs = [KafkaRuleSpec(identity_indices=[0], api_keys=(0,), topic="")]
    tables = compile_kafka_rules(specs, 4)
    big = KafkaRequest(
        kind=0, version=0, client_id="c",
        topics=tuple(f"t{i}" for i in range(MAX_TOPICS + 2)),
        parsed=True,
    )
    packed = pad_kafka_requests(tables, [big])
    assert bool(packed[-1][0])  # overflow flagged
    ident = np.zeros(1, np.int32)
    known = np.ones(1, bool)
    dev = np.asarray(evaluate_kafka_batch(tables, *packed, ident, known))
    assert not bool(dev[0])  # device alone: deny
    full = evaluate_with_host_fallback(tables, [big], ident, known)
    assert bool(full[0])  # host fallback restores the true allow


def test_ack_gated_publish_timeout_keeps_old_state(monkeypatch):
    """pkg/completion + pkg/envoy/xds/ack.go wiring: a redirect
    matcher compile that never ACKs fails the regeneration within
    EndpointGenerationTimeout — realized redirect state rolls back,
    the OLD redirect tables keep serving, the fail metric increments
    — and unblocking lets the next trigger succeed with the new
    tables."""
    import threading
    import time

    from cilium_tpu import option
    from cilium_tpu.daemon import Daemon
    from cilium_tpu.metrics import registry as metrics
    from cilium_tpu.proxy.proxy import Proxy

    from tests.test_daemon import es_k8s, k8s_labels, wait_trigger
    from cilium_tpu.labels import LabelArray
    from cilium_tpu.policy.api import (
        IngressRule,
        PortProtocol,
        PortRule,
        Rule,
    )
    from cilium_tpu.policy.api.rule import L7Rules, PortRuleHTTP

    monkeypatch.setattr(option.Config, "redirect_ack_timeout", 0.3)

    d = Daemon()
    d.create_endpoint(1, k8s_labels(app="api"), ipv4="10.5.0.1")
    d.create_endpoint(2, k8s_labels(app="ui"), ipv4="10.5.0.2")

    def http_rule(path):
        return Rule(
            endpoint_selector=es_k8s(app="api"),
            ingress=[
                IngressRule(
                    from_endpoints=[es_k8s(app="ui")],
                    to_ports=[
                        PortRule(
                            ports=[
                                PortProtocol(port="80", protocol="TCP")
                            ],
                            rules=L7Rules(
                                http=[PortRuleHTTP(path=path)]
                            ),
                        )
                    ],
                )
            ],
            labels=LabelArray.parse("ack-rule"),
        )

    # first revision compiles and ACKs normally
    d.policy_add([http_rule("/v1/.*")], replace=True)
    wait_trigger(d)
    redirect = d.proxy.redirect_for(1, True, "TCP", 80)
    assert redirect is not None
    old_policy = redirect.http_policy
    before_realized = dict(
        d.endpoint_manager.lookup(1).realized_redirects
    )
    assert before_realized  # the port map is realized

    # block the NEXT tensor compile: the ACK never arrives
    gate = threading.Event()
    orig = Proxy._compile_tables

    def blocking(self, *a, **kw):
        gate.wait()
        return orig(self, *a, **kw)

    monkeypatch.setattr(Proxy, "_compile_tables", blocking)
    fails_before = metrics.endpoint_regenerations.get("fail")
    d.policy_add([http_rule("/v2/.*")], replace=True)
    t0 = time.monotonic()
    d.regenerate_all("ack test")
    elapsed = time.monotonic() - t0
    # the gate actually fired: we waited out the (shortened) timeout
    assert elapsed >= 0.3
    assert metrics.endpoint_regenerations.get("fail") == fails_before + 1
    # old state keeps serving: same redirect tables, rolled-back map
    stuck = d.proxy.redirect_for(1, True, "TCP", 80)
    assert stuck is not None
    assert stuck.http_policy is old_policy
    assert (
        d.endpoint_manager.lookup(1).realized_redirects
        == before_realized
    )

    # unblock; the retry succeeds and swaps the new tables in
    monkeypatch.setattr(Proxy, "_compile_tables", orig)
    gate.set()
    d.regenerate_all("retry")
    # drain the async compiler queue (the blocked job + the retry)
    d.proxy._compiler.submit(lambda: None).result(timeout=5)
    fresh = d.proxy.redirect_for(1, True, "TCP", 80)
    assert fresh.http_policy is not old_policy
