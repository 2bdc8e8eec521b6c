"""The L7 stage on the persistent launch path
(PersistentPairDispatcher(l7=...), l7.fleet.L7Stage), at a small
seeded world on the CPU: each redirected tuple's L7 verdict equals
the host matchers over the compiled fleet's rules of its scope (HTTP
with method, path, Host and exact or presence headers; Kafka), a
scope with no parser denies, a request over the field budgets is
flagged and decided exactly all the same, the counts equal the fold
of the verdicts and reach metrics.policy_l7_total and
metrics.policy_l7_matcher_tuples_total at flush, the matchers run
over their own parser's tuples alone and decide what an evaluation
of every tuple by both matchers decides, a second launch compiles
nothing, and without `l7` the dispatcher is what it was."""

import ipaddress

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cilium_tpu import tracing
from cilium_tpu.ct.device import compile_ct
from cilium_tpu.ct.table import CTMap
from cilium_tpu.daemon import Daemon
from cilium_tpu.engine.datapath import (
    DatapathTables,
    PersistentPairDispatcher,
    pack_flow_records4,
    persistent_pair_program,
)
from cilium_tpu.engine.verdict import (
    make_counter_buffers,
    make_telemetry_buffers,
)
from cilium_tpu.ipcache.ipcache import IPIdentity
from cilium_tpu.ipcache.lpm import specialize_ipcache_to_idx
from cilium_tpu.l7 import memcached as mc
from cilium_tpu.l7.fleet import (
    _HTTP_COLS,
    _KAFKA_COLS,
    L7_COUNTS,
    L7_MATCHERS,
    PARSER_HTTP_ID,
    PARSER_KAFKA_ID,
    L7Stage,
    compile_fleet_l7,
    evaluate_fleet_l7,
    pack_requests,
)
from cilium_tpu.l7.http import http_rule_matches_host
from cilium_tpu.l7.kafka import KafkaRequest, matches_rules_host
from cilium_tpu.labels import Label, LabelArray, Labels
from cilium_tpu.lb.device import compile_lb
from cilium_tpu.lb.service import ServiceManager
from cilium_tpu.metrics import registry as metrics
from cilium_tpu.policy.api import (
    EndpointSelector,
    IngressRule,
    PortProtocol,
    PortRule,
    Rule,
)
from cilium_tpu.policy.api.rule import (
    L7Rules,
    PortRuleHTTP,
    PortRuleKafka,
    PortRuleL7,
)
from cilium_tpu.prefilter import build_prefilter

HALF = 64
EPS = {"web": (100, "10.7.0.1", 8080), "kafka": (101, "10.7.0.2", 9092),
       "cache": (102, "10.7.0.3", 11211)}
TEAMS = ("alpha", "beta")


def _rule(app, team, port, rules):
    return Rule(
        endpoint_selector=EndpointSelector(match_labels={"k8s.app": app}),
        ingress=[IngressRule(
            from_endpoints=[EndpointSelector(match_labels={"k8s.team": team})],
            to_ports=[PortRule(
                ports=[PortProtocol(port=str(port), protocol="TCP")],
                rules=rules,
            )],
        )],
        labels=LabelArray.parse(f"l7-{app}-{team}"),
    )


def build_world():
    """(daemon, device tables, endpoint index, compiled fleet, peers):
    three endpoints with HTTP (Host and header rules among them),
    Kafka and memcached filters, six peers in two teams."""
    d = Daemon(num_workers=2)
    d.policy_trigger.close(wait=True)
    for app, (ep_id, ip, _) in EPS.items():
        d.create_endpoint(ep_id, Labels({"app": Label("app", app, "k8s")}),
                          ipv4=ip, name=app)
    peers = []  # (ip u32, team)
    for i in range(6):
        team = TEAMS[i % 2]
        ident, _ = d.identity_allocator.allocate(Labels({
            "team": Label("team", team, "k8s"),
            "svc": Label("svc", f"s{i}", "k8s"),
        }))
        ip = f"10.9.0.{i + 1}"
        d.ipcache.upsert(ip, IPIdentity(ident.id, "kvstore"))
        peers.append((int(ipaddress.ip_address(ip)), team))
    http = PortRuleHTTP
    d.policy_add([
        _rule("web", "alpha", 8080, L7Rules(http=[
            http(method="GET", path="/api/v1/users(/[0-9]+)?"),
            http(method="POST", path="/api/v1/orders",
                 headers=["X-Tenant: alpha"]),
            http(method="GET", path="/admin", headers=["Authorization"]),
            http(method="GET", path="/h", host="web\\.default\\.svc"),
        ])),
        _rule("web", "beta", 8080, L7Rules(http=[
            http(method="PUT", path="/api/v2/items"),
            # nine named headers: more than a request stages
            http(method="GET", path="/many",
                 headers=[f"X-H{i}" for i in range(9)]),
        ])),
        _rule("kafka", "alpha", 9092, L7Rules(kafka=[
            PortRuleKafka(role="produce", topic="orders"),
            PortRuleKafka(api_key="produce", client_id="c9"),
        ])),
        _rule("kafka", "beta", 9092, L7Rules(kafka=[
            PortRuleKafka(topic="audit", client_id="c1"),
        ])),
        _rule("cache", "alpha", 11211, L7Rules(
            l7proto=mc.PARSER_NAME,
            l7=[PortRuleL7(opCode="readGroup", keyExact="sessions")],
        )),
    ])
    d.regenerate_all("l7 datapath test")
    _, policy, index = d.endpoint_manager.published()
    tables = jax.device_put(DatapathTables(
        prefilter=build_prefilter({}),
        ipcache=specialize_ipcache_to_idx(d.lpm_builder.tables(), policy),
        ct=compile_ct(CTMap()),
        lb=compile_lb(ServiceManager()),
        policy=policy,
    ))
    fleet = compile_fleet_l7(d)
    return d, tables, index, fleet, peers


@pytest.fixture(scope="module")
def world():
    return build_world()


# the request of each kind a tuple may carry: (HTTP triple, headers,
# Kafka request)
NO_KAFKA = KafkaRequest(kind=0, version=0)
REQUESTS = [
    ((b"GET", b"/api/v1/users/42", b""), {"accept": "*/*"}, NO_KAFKA),
    ((b"GET", b"/api/v1/userz", b""), None, NO_KAFKA),
    # exact header: right value, wrong value, absent
    ((b"POST", b"/api/v1/orders", b""), {"x-tenant": "alpha"}, NO_KAFKA),
    ((b"POST", b"/api/v1/orders", b""), {"x-tenant": "beta"}, NO_KAFKA),
    ((b"POST", b"/api/v1/orders", b""), {"user-agent": "x"}, NO_KAFKA),
    # a value no rule names (interned as 0)
    ((b"POST", b"/api/v1/orders", b""), {"x-tenant": "zeta"}, NO_KAFKA),
    # presence header
    ((b"GET", b"/admin", b""), {"authorization": "Bearer 9"}, NO_KAFKA),
    ((b"GET", b"/admin", b""), None, NO_KAFKA),
    # Host: right and wrong
    ((b"GET", b"/h", b"web.default.svc"), None, NO_KAFKA),
    ((b"GET", b"/h", b"web.other.svc"), None, NO_KAFKA),
    ((b"PUT", b"/api/v2/items", b""), None, NO_KAFKA),
    # over budget: a path past 128 bytes, nine headers the policy
    # names; nine it does not name are not staged and fit
    ((b"GET", b"/api/v1/users/" + b"1" * 140, b""), None, NO_KAFKA),
    ((b"GET", b"/many", b""), {f"x-h{i}": "v" for i in range(9)},
     NO_KAFKA),
    ((b"GET", b"/api/v1/users", b""),
     {f"x-z{i}": "v" for i in range(9)}, NO_KAFKA),
    ((b"", b"", b""), None,
     KafkaRequest(kind=0, version=1, client_id="c1", topics=("orders",))),
    ((b"", b"", b""), None,
     KafkaRequest(kind=1, version=1, client_id="c1", topics=("orders",))),
    ((b"", b"", b""), None,
     KafkaRequest(kind=1, version=1, client_id="c1", topics=("audit",))),
    ((b"", b"", b""), None,
     KafkaRequest(kind=1, version=1, client_id="c2", topics=("audit",))),
    # over budget: nine topics
    ((b"", b"", b""), None, KafkaRequest(
        kind=0, version=1, client_id="c9",
        topics=tuple(f"t{i}" for i in range(9)))),
]
OVER_BUDGET = {11, 12, 18}
HTTP_REQUESTS = {i for i, r in enumerate(REQUESTS) if r[2] is NO_KAFKA}
KAFKA_REQUESTS = set(range(len(REQUESTS))) - HTTP_REQUESTS


def request_table(fleet):
    return pack_requests(
        fleet, [r[0] for r in REQUESTS], [r[1] for r in REQUESTS],
        [r[2] for r in REQUESTS],
    )


def _stage(fleet):
    return L7Stage(fleet, request_table(fleet))


def _pair(world, rng, app, peer):
    """One [2, 4, HALF] pair: tuple t ingress to endpoint app[t]'s L7
    port from peers[peer[t]] (and egress back)."""
    _, _, index, _, peers = world
    pair = np.empty((2, 4, HALF), np.uint32)
    ep_ip = np.asarray(
        [int(ipaddress.ip_address(EPS[a][1])) for a in app], np.uint32
    )
    peer_ip = np.asarray([peers[p][0] for p in peer], np.uint32)
    for d in (0, 1):
        pair[d] = pack_flow_records4(
            ep_index=[index[EPS[a][0]] for a in app],
            saddr=peer_ip if d == 0 else ep_ip,
            daddr=ep_ip if d == 0 else peer_ip,
            sport=rng.integers(1024, 65535, HALF),
            dport=[EPS[a][2] for a in app],
            proto=np.full(HALF, 6), direction=np.full(HALF, d),
        )
    return pair


def _traffic(world, rng, n_pairs):
    """n_pairs [2, 4, HALF] pairs, ingress to the three L7 ports from
    the six peers (and egress back), with their request-id planes."""
    apps = list(EPS)
    pairs, reqs = [], []
    for _ in range(n_pairs):
        app = [apps[i] for i in rng.integers(0, 3, HALF)]
        peer = rng.integers(0, len(world[4]), HALF)
        pairs.append(_pair(world, rng, app, peer))
        reqs.append(rng.integers(0, len(REQUESTS), (2, HALF)).astype(
            np.uint32))
    return pairs, reqs


def _carry(tables):
    return (
        jax.device_put(make_counter_buffers(tables.policy)),
        jax.device_put(make_telemetry_buffers()),
    )


def _expected(fleet, pair, out, req):
    """Per tuple of one direction of a pair: (redirected, L7 verdict,
    flagged) by the host matchers over the scope's compiled rules."""
    red = np.asarray(out.proxy_port) > 0
    ep = pair[3] >> 16
    slot = np.asarray(out.l4_slot)
    ident = np.asarray(out.sec_id)
    want = np.zeros(HALF, bool)
    for t in np.nonzero(red)[0]:
        scope = (int(ep[t]), 0, int(slot[t]))
        (m, p, h), hdrs, kreq = REQUESTS[int(req[t])]
        kind = fleet.parser_kind[scope]
        if kind == PARSER_HTTP_ID:
            want[t] = any(
                s.scope_key == scope and int(ident[t]) in s.identity_indices
                and http_rule_matches_host(s, m, p, h, hdrs)
                for s in fleet.http.device_rules
            )
        elif kind == PARSER_KAFKA_ID:
            specs = [s for s in fleet.kafka.specs if s.scope_key == scope]
            want[t] = matches_rules_host(kreq, specs, int(ident[t]))
    flagged = red & np.isin(req, sorted(OVER_BUDGET))
    return red, want, flagged


def test_l7_verdicts_equal_host_matchers(world):
    """Every tuple of two K=2 launches: the L7 verdict equals the host
    matchers (so exact and presence headers, an absent header, an
    uninterned value and a wrong Host are decided as the host
    decides them), a redirect to the memcached port (no fleet parser)
    is denied, a request over the budgets is flagged and decided as the
    host decides it, the final verdict is the L3/L4 verdict and the L7
    one, and the counts equal the fold.  The fused program's outputs equal a dispatcher's
    without `l7`."""
    d, tables, index, fleet, _ = world
    assert fleet.http.tables.hdr_rules.shape[0] == 11
    table = request_table(fleet)
    np.testing.assert_array_equal(
        np.nonzero(table["overflow"])[0], sorted(OVER_BUDGET))
    assert table["wide"]["path"].shape[1] == 256
    assert table["wide"]["hname"].shape[1] == 16
    assert table["wide"]["topics"].shape[1] == 16
    pairs, reqs = _traffic(world, np.random.default_rng(3), 4)
    disp = PersistentPairDispatcher(tables, 2, *_carry(tables),
                                    l7=_stage(fleet))
    plain = PersistentPairDispatcher(tables, 2, *_carry(tables))
    got, ref = [], []
    for pair, req in zip(pairs, reqs):
        got.extend(disp.submit(pair, req))
        ref.extend(plain.submit(pair))
    counts = np.asarray(disp.l7_counts).astype(np.int64)
    want_counts = np.zeros(len(L7_COUNTS), np.int64)
    want_decided = np.zeros(len(L7_MATCHERS), np.int64)
    cases = {"http": set(), "kafka": set(), "none": set(),
             "flagged": set()}
    mc_slot = int(d.endpoint_manager.published()[1].port_slot[6, 11211])
    for (oi, oe, l7v), (ri, re_), pair, req in zip(got, ref, pairs, reqs):
        for a, b in zip(jax.tree.leaves((oi, oe)), jax.tree.leaves((ri, re_))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        red, want, flagged = _expected(fleet, pair[0], oi, req[0])
        l7_allowed = np.asarray(l7v.l7_allowed)
        allowed = np.asarray(l7v.allowed)
        np.testing.assert_array_equal(l7_allowed[0].astype(bool), want)
        # egress: no L7 filter, nothing redirected
        assert not (np.asarray(oe.proxy_port) > 0).any()
        assert not l7_allowed[1].any()
        for dirn, out in ((0, oi), (1, oe)):
            base = np.asarray(out.allowed).astype(bool)
            r = np.asarray(out.proxy_port) > 0
            np.testing.assert_array_equal(
                allowed[dirn].astype(bool), base & (~r | l7_allowed[dirn]))
        slot = np.asarray(oi.l4_slot)
        decided = {"http": 0, "kafka": 0, "none": 0}
        for t in np.nonzero(red)[0]:
            kind = fleet.parser_kind[int(pair[0][3][t] >> 16), 0, slot[t]]
            name = {PARSER_HTTP_ID: "http", PARSER_KAFKA_ID: "kafka"}.get(
                int(kind), "none")
            decided[name] += 1
            cases[name].add((int(req[0][t]), bool(want[t])))
            if name == "none":
                assert slot[t] == mc_slot and not l7_allowed[0][t]
        cases["flagged"] |= {(int(req[0][t]), bool(want[t]))
                             for t in np.nonzero(flagged)[0]}
        want_counts += [red.sum(), want.sum(), (red & ~want).sum(),
                        flagged.sum()]
        # each matcher decided exactly the redirected tuples of its
        # parser's scopes (a count of tuples, not of chunk slots)
        want_decided += [decided[m] for m in L7_MATCHERS]
    np.testing.assert_array_equal(counts, want_counts)
    np.testing.assert_array_equal(_tally(disp), want_decided)
    # the traffic reached every case the test names
    http_seen = {r for r, _ in cases["http"]}
    assert HTTP_REQUESTS <= http_seen, sorted(http_seen)
    assert {True, False} <= {ok for _, ok in cases["http"]}
    assert {True, False} <= {ok for _, ok in cases["kafka"]}
    assert cases["none"]
    # every over-budget request reached the stage, and some of them
    # are allowed: decided, not denied for their size
    assert OVER_BUDGET == {r for r, _ in cases["flagged"]}
    assert {True, False} <= {ok for _, ok in cases["flagged"]}


def _matcher_tuples():
    return np.asarray([metrics.policy_l7_matcher_tuples_total.get(p)
                       for p in L7_MATCHERS])


def _tally(disp):
    """The tuples each matcher decided since the last flush (the sum
    of the L7 program's per-call `decided`, L7_MATCHERS order)."""
    return sum((np.asarray(d).astype(np.int64) for d in disp.l7_decided),
               np.zeros(len(L7_MATCHERS), np.int64))


def test_counts_fold_into_metrics_at_flush(world):
    """flush() moves policy_l7_total{rule} by the drained received,
    forwarded and denied counts, the stage's `overflowed` by the
    drained overflow count and policy_l7_matcher_tuples_total{parser}
    by the tuples each matcher decided (a staged remainder pair
    included, through the L7 program), and restarts the device counts
    and the matcher tally from zero."""
    _, tables, _, fleet, _ = world
    pairs, reqs = _traffic(world, np.random.default_rng(4), 3)
    stage = _stage(fleet)
    disp = PersistentPairDispatcher(tables, 2, *_carry(tables), l7=stage)
    outs = []
    for pair, req in zip(pairs, reqs):
        outs.extend(disp.submit(pair, req))
    drained = np.asarray(disp.l7_counts).astype(np.int64)
    assert drained.shape == (len(L7_COUNTS),)
    assert drained[3] > 0
    tally = _tally(disp)
    assert (tally > 0).all()
    # every tuple an HTTP or Kafka matcher decided was received
    assert tally.sum() <= drained[0]
    before = [metrics.policy_l7_total.get(r) for r in L7_COUNTS[:3]]
    matcher_before = _matcher_tuples()
    rest, _, _ = disp.flush()
    assert len(outs) == 2 and len(rest) == 1 and len(rest[0]) == 3
    red = np.asarray(rest[0][0].proxy_port) > 0
    l7 = np.asarray(rest[0][2].l7_allowed[0]).astype(bool)
    assert not (l7 & ~red).any()
    after = [metrics.policy_l7_total.get(r) for r in L7_COUNTS[:3]]
    moved = np.asarray(after) - np.asarray(before)
    assert moved[0] == drained[0] + red.sum()
    assert moved[1] == drained[1] + l7.sum()
    assert moved[0] == moved[1] + moved[2]
    flagged = red & np.isin(reqs[2][0],
                            sorted(OVER_BUDGET))
    assert stage.overflowed == drained[3] + flagged.sum()
    kind = fleet.parser_kind[
        pairs[2][0][3] >> 16, 0,
        np.minimum(rest[0][0].l4_slot, fleet.parser_kind.shape[2] - 1)]
    np.testing.assert_array_equal(
        _matcher_tuples() - matcher_before,
        tally + [(red & (kind == PARSER_HTTP_ID)).sum(),
                 (red & (kind == PARSER_KAFKA_ID)).sum()])
    assert int(np.asarray(disp.l7_counts).sum()) == 0
    assert disp.l7_decided == []


# the matchers' loops run in steps of CHUNK tuples in the equality
# cases below, so a class of 16 or 17 tuples takes one or two steps
CHUNK = 16


@pytest.fixture(scope="module")
def chunked(world):
    """An L7 stage stepping CHUNK tuples at a time, and its request
    table on the host."""
    fleet = world[3]
    table = request_table(fleet)
    return L7Stage(fleet, table, chunk=CHUNK), table


def _every_tuple(fleet, table, pair, out, req):
    """(L7 verdict, final verdict, counts) of one direction of a
    pair as the L7 program decided before its matchers were split by
    parser: both matchers over every tuple at once, then each flagged
    tuple again from the request table's wide columns."""
    n = req.shape[0]
    red = np.asarray(out.proxy_port) > 0
    ep = jnp.asarray(pair[3] >> 16, jnp.int32)
    slot = jnp.asarray(out.l4_slot, jnp.int32)
    ident = jnp.asarray(out.sec_id, jnp.int32)

    def decide(cols, rows):
        return np.asarray(evaluate_fleet_l7(
            fleet, ep, jnp.zeros(n, jnp.int32), slot, ident,
            jnp.ones(n, bool),
            http_fields=tuple(jnp.asarray(cols[k])[rows]
                              for k in _HTTP_COLS),
            kafka_fields=tuple(jnp.asarray(cols[k])[rows]
                               for k in _KAFKA_COLS),
            http_headers=(jnp.asarray(cols["hname"])[rows],
                          jnp.asarray(cols["hpair"])[rows]),
        ))

    ok = decide(table, req)
    flagged = red & table["overflow"][req]
    if "wide" in table:
        ok = np.where(flagged, decide(table["wide"], table["wide_row"][req]),
                      ok)
    l7 = red & ok
    allowed = np.asarray(out.allowed).astype(bool) & (~red | l7)
    return l7, allowed, [red.sum(), l7.sum(), (red & ~l7).sum(),
                         flagged.sum()]


ALPHA = (0, 2, 4)  # peers of the team every endpoint's rules allow
BETA = (1, 3, 5)  # no rule of the cache endpoint allows this team
# (web, kafka, cache from alpha, cache from beta) tuples of a pair, each
# case's premise on its class counts (HTTP, Kafka, redirected with no
# parser), and whether it carries requests over the field budgets
MIXES = {
    "no_http": ((0, 40, 12, 12), (0, 40, 12), False),
    "no_kafka": ((40, 0, 12, 12), (40, 0, 12), False),
    "all_redirected": ((28, 28, 8, 0), (28, 28, 8), False),
    "http_kafka_one_chunk": ((CHUNK, CHUNK, 16, 16), (16, 16, 16), False),
    "http_kafka_chunk_plus_one": ((CHUNK + 1, CHUNK + 1, 15, 15),
                                  (17, 17, 15), False),
    "no_parser_only": ((0, 0, 48, 16), (0, 0, 48), False),
    "over_budget": ((24, 24, 8, 8), (24, 24, 8), True),
}


@pytest.mark.parametrize("mix", list(MIXES))
def test_matchers_by_parser_equal_every_tuple(world, chunked, mix):
    """The L7 program, whose HTTP and Kafka matchers each step only
    over the redirected tuples of their parser's scopes (CHUNK at a
    time), gives the L7 verdicts, final verdicts and counts that both
    matchers over every tuple give, for a pair with
    no HTTP tuple, with no Kafka tuple, with every tuple redirected,
    with classes of exactly one chunk and of one chunk and one tuple,
    with only redirects to a scope with no parser (all denied), and
    with requests over the field budgets (the wide pass).
    The matcher tally (L7Verdicts.decided) is the class counts:
    tuples, not chunk slots."""
    _, tables, _, fleet, _ = world
    stage, table = chunked
    (web, kafka, cache_a, cache_b), premise, over = MIXES[mix]
    rng = np.random.default_rng(list(MIXES).index(mix))
    app = (["web"] * web + ["kafka"] * kafka
           + ["cache"] * (cache_a + cache_b))
    peer = np.concatenate([
        rng.choice(6, web + kafka), rng.choice(ALPHA, cache_a),
        rng.choice(BETA, cache_b)]).astype(np.int64)
    shuffle = rng.permutation(HALF)
    app, peer = [app[i] for i in shuffle], peer[shuffle]
    pool = set(range(len(REQUESTS))) - (set() if over else OVER_BUDGET)
    kafka_ids = sorted(KAFKA_REQUESTS & pool)
    http_ids = sorted(HTTP_REQUESTS & pool)
    req = np.asarray([
        [rng.choice(kafka_ids if a == "kafka" else http_ids) for a in app],
        rng.choice(sorted(pool), HALF),
    ], np.uint32)
    pair = _pair(world, rng, app, peer)
    disp = PersistentPairDispatcher(tables, 1, *_carry(tables), l7=stage)
    (oi, oe, l7v), = disp.submit(pair, req)
    counts = np.asarray(disp.l7_counts).astype(np.int64)

    l7, allowed, want4 = _every_tuple(fleet, table, pair[0], oi, req[0])
    np.testing.assert_array_equal(np.asarray(l7v.l7_allowed[0]), l7)
    np.testing.assert_array_equal(np.asarray(l7v.allowed[0]), allowed)
    np.testing.assert_array_equal(counts, want4)
    # egress: nothing redirected, the L3/L4 verdict
    assert not np.asarray(l7v.l7_allowed[1]).any()
    np.testing.assert_array_equal(np.asarray(l7v.allowed[1]),
                                  np.asarray(oe.allowed))

    red = np.asarray(oi.proxy_port) > 0
    kind = fleet.parser_kind[
        pair[0][3] >> 16, 0,
        np.minimum(oi.l4_slot, fleet.parser_kind.shape[2] - 1)]
    classes = [int((red & (kind == k)).sum())
               for k in (PARSER_HTTP_ID, PARSER_KAFKA_ID, 0)]
    assert classes == list(premise)
    np.testing.assert_array_equal(_tally(disp), classes[:2])
    # a redirect with no parser is denied
    assert not l7[red & (kind == 0)].any()
    if mix == "all_redirected":
        assert red.all()
    # the case decides some tuples each way, and the over-budget case
    # takes the wide pass
    if classes[0] + classes[1]:
        assert l7.any() and (red & ~l7).any()
    assert bool(want4[3]) == over


def _spans(fn):
    tracer = tracing.Tracer(seed=71)
    tok = tracing._current.set(None)
    old, tracing.tracer = tracing.tracer, tracer
    try:
        out = fn()
        jax.block_until_ready(out)
    finally:
        tracing.tracer = old
        tracing._current.reset(tok)
    return [s for s in tracer.snapshot() if s.name.startswith("datapath.")]


def test_l7_span_after_enqueue_and_no_second_compile(world):
    """With `l7` each launch's children are upload, stack, enqueue,
    l7, outputs, in order; the pair's request ids ride in the upload
    (the launch's bytes); a second launch of the same shapes traces
    and compiles nothing at any of the dispatcher's sites."""
    _, tables, _, fleet, _ = world
    pairs, reqs = _traffic(world, np.random.default_rng(5), 4)
    site = "test.l7.datapath"
    disp = PersistentPairDispatcher(tables, 2, *_carry(tables), site=site,
                                    l7=_stage(fleet))
    spans = _spans(lambda: disp.submit(pairs[0], reqs[0])
                   + disp.submit(pairs[1], reqs[1]))
    launch = next(s for s in spans if s.name == "datapath.launch")
    kids = sorted((s for s in spans if s.parent_id == launch.span_id),
                  key=lambda s: s.start)
    assert [s.name for s in kids] == [
        "datapath.upload", "datapath.stack", "datapath.enqueue",
        "datapath.l7", "datapath.outputs",
    ]
    assert launch.attrs["bytes"] == 2 * (pairs[0].nbytes + reqs[0].nbytes)
    sites = (site, site + ".stack", site + ".l7")

    def reading():
        return [(metrics.jit_cache_misses.get(s),
                 metrics.jit_compile_seconds.get(s)) for s in sites]

    events = []

    def on_event(key, duration, **kw):
        if key.startswith("/jax/core/compile/"):
            events.append(key)

    before = reading()
    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        jax.block_until_ready(disp.submit(pairs[2], reqs[2])
                              + disp.submit(pairs[3], reqs[3]))
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert events == [] and reading() == before
    assert metrics.jit_cache_misses.get(site + ".l7") == 1


def test_without_l7_dispatcher_unchanged(world):
    """Without `l7`: the launch's spans are the four children of
    before, only the program's and the stack's jit sites are called,
    each drained result is the (out_i, out_e) pair of the persistent
    program on the stacked pairs, and submit refuses nothing."""
    _, tables, _, _, _ = world
    pairs, _ = _traffic(world, np.random.default_rng(6), 2)
    site = "test.l7.plain"
    disp = PersistentPairDispatcher(tables, 2, *_carry(tables), site=site)
    assert disp.l7 is None
    got = []
    spans = _spans(lambda: got.extend(disp.submit(pairs[0])
                                      + disp.submit(pairs[1])) or got)
    launch = next(s for s in spans if s.name == "datapath.launch")
    kids = sorted((s for s in spans if s.parent_id == launch.span_id),
                  key=lambda s: s.start)
    assert [s.name for s in kids] == [
        "datapath.upload", "datapath.stack", "datapath.enqueue",
        "datapath.outputs",
    ]
    assert launch.attrs["bytes"] == 2 * pairs[0].nbytes
    assert metrics.jit_cache_misses.get(site + ".l7") == 0
    assert metrics.jit_cache_hits.get(site + ".l7") == 0
    assert metrics.jit_cache_misses.get(site + ".remainder") == 0
    acc, telem = _carry(tables)
    outs_i, outs_e, _, _ = persistent_pair_program(2)(
        tables, jax.device_put(np.stack(pairs)), acc, telem)
    assert all(len(g) == 2 for g in got)
    for i, (gi, ge) in enumerate(got):
        want = jax.tree.map(lambda a: a[i], (outs_i, outs_e))
        for a, b in zip(jax.tree.leaves((gi, ge)), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_l7_dispatcher_needs_request_ids(world):
    _, tables, _, fleet, _ = world
    pairs, _ = _traffic(world, np.random.default_rng(7), 1)
    disp = PersistentPairDispatcher(tables, 2, *_carry(tables),
                                    l7=_stage(fleet))
    with pytest.raises(ValueError, match="req_ids"):
        disp.submit(pairs[0])
