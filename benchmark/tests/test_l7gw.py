"""The cell l7gw.replay's faults, on the CPU at its world's TINY sizes
(benchmark/tests/test_correct.py runs its sound run and its control):
each way the fused program (test_correct's faults) or the L7 stage
can break underneath makes `correct` false.  Then
benchmark/l7gw_reference.py on hand-written cases, and the
configuration file against the world's literals."""

import os
from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness as H
from cilium_tpu.l7 import fleet
from cilium_tpu.l7.fleet import L7Verdicts
from benchmark.l7gw_reference import L7Reference, over_budget
from benchmark.tests import test_correct as TC
from benchmark.tests.test_correct import run

CELL = "l7gw.replay"


def _wrap_l7_program(monkeypatch, fault):

    orig = fleet.fleet_l7_program

    def patched(f, chunk=fleet.L7_CHUNK):
        fn, args = orig(f, chunk)
        return (lambda *a: fault(fn, *a)), args

    monkeypatch.setattr(fleet, "fleet_l7_program", patched)


def _verdict_flipped(fn, tables, requests, pairs, outs_i, outs_e, counts,
                     *req_ids):
    v, counts = fn(tables, requests, pairs, outs_i, outs_e, counts, *req_ids)
    l7 = v.l7_allowed
    return v._replace(l7_allowed=l7.at[0, 0, 0].set(1 - l7[0, 0, 0])), counts


def _stage_skipped(fn, tables, requests, pairs, outs_i, outs_e, counts,
                   *req_ids):
    """No L7 decision: every redirected tuple forwarded."""
    red = jnp.stack([outs_i.proxy_port > 0, outs_e.proxy_port > 0], axis=1)
    allowed = jnp.stack([outs_i.allowed, outs_e.allowed], axis=1)
    n = jnp.sum(red, dtype=jnp.uint32)
    counts = counts + jnp.stack([n, n, jnp.uint32(0), jnp.uint32(0)])
    return L7Verdicts(red.astype(jnp.uint8), allowed), counts


def _counts_unchanged(fn, tables, requests, pairs, outs_i, outs_e, counts,
                      *req_ids):
    """The L7 counts returned as they came in."""
    keep = jnp.array(counts, copy=True)
    v, _ = fn(tables, requests, pairs, outs_i, outs_e, counts, *req_ids)
    return v, keep


@pytest.mark.parametrize(
    "fault", [_verdict_flipped, _stage_skipped, _counts_unchanged],
    ids=["verdict_flipped", "stage_skipped", "counts_unchanged"],
)
def test_l7_program_fault_fails(monkeypatch, fault):
    _wrap_l7_program(monkeypatch, fault)
    out = run(CELL)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize(
    "fault", [TC._answer_altered, TC._half_left_out, TC._state_unchanged],
    ids=["answer_altered", "half_left_out", "state_unchanged"],
)
def test_fused_fault_fails(monkeypatch, fault):
    """test_correct's faults of the fused program, under the L7 stage."""
    TC._wrap_persistent(monkeypatch, fault)
    out = run(CELL)
    assert not out["correct"], out["checks"]


def test_headers_ignored_fails(monkeypatch):
    """Header constraints dropped on the device: no header rule fails."""
    from cilium_tpu.l7 import http

    def no_fail(tables, headers):
        rows = 1 if headers is None else headers[0].shape[0]
        return jnp.zeros((rows, tables.hdr_rules.shape[1]), jnp.uint32)

    monkeypatch.setattr(http, "_header_fail", no_fail)
    out = run(CELL)
    assert not out["correct"], out["checks"]
    assert out["checks"]["rows_wrong"]["value"] > 0


def test_scope_mask_dropped_fails(monkeypatch):
    """Every rule of the fleet in every scope: another endpoint's
    rules leak into a flow's verdict."""
    def every_rule(table, lin):
        return jnp.full((lin.shape[0], table.shape[-1]), 0xFFFFFFFF,
                        jnp.uint32)

    monkeypatch.setattr(fleet, "scope_rows", every_rule)
    out = run(CELL)
    assert not out["correct"], out["checks"]
    assert out["checks"]["rows_wrong"]["value"] > 0


# -- the reference on hand-written cases -----------------------------------

APP, HTTP_PORT, KAFKA_PORT = 0, 8080, 9092
TEAM, OTHER, L3_TEAM = 5, 6, 7


def _http(method, path, host="", headers=(), **kw):
    return dict(app=APP, port=HTTP_PORT, team=TEAM, kind="http",
                method=method, path=path, host=host, headers=headers, **kw)


def _kafka(role="", api_key="", topic="", client_id=""):
    return dict(app=APP, port=KAFKA_PORT, team=TEAM, kind="kafka",
                role=role, api_key=api_key, topic=topic, client_id=client_id)


@pytest.fixture(scope="module")
def ref():
    desc = SimpleNamespace(
        specs=[(APP, "http", TEAM, HTTP_PORT, 6, -1),
               (APP, "kafka", TEAM, KAFKA_PORT, 6, -1),
               (APP, "l3", L3_TEAM, 0, 0, -1)],
        ep_ip={100: 1}, id_ips=np.arange(10, 30, dtype=np.uint32), n_teams=8,
        services=[], prefilter_cidrs=[], index={100: 0},
        l7_rules=[
            _http("GET|HEAD", "/api/v[1-3]/users(/[0-9]+)?"),
            _http("POST", "/api/v1/orders", host="svc0\\.default\\.svc"),
            _http("PUT", "/t", headers=("X-Tenant: t5",)),
            _http("DELETE", "/a", headers=("Authorization",)),
            _kafka(role="produce", topic="a"),
            _kafka(api_key="fetch", topic="b", client_id="c1"),
            _kafka(role="consume", topic="b"),
        ],
        requests=[],
    )
    return L7Reference(desc)


def req(method="", path="", host="", headers=(), kafka=None):
    return (method, path, host, tuple(headers), kafka)


@pytest.mark.parametrize("request_, want", [
    (req("GET", "/api/v2/users/7"), True),
    (req("HEAD", "/api/v3/users"), True),
    (req("GETX", "/api/v2/users"), False),  # method full match
    (req("GET", "/api/v2/users/7/x"), False),  # path full match
    (req("GET", "/api/v4/users"), False),
    (req("POST", "/api/v1/orders", "svc0.default.svc"), True),
    (req("POST", "/api/v1/orders", "svc0Xdefault.svc"), False),  # escaped dot
    (req("POST", "/api/v1/orders", ""), False),  # Host present in the rule
    (req("PUT", "/t", "", [("x-tenant", "t5")]), True),  # exact
    (req("PUT", "/t", "", [("X-TENANT", "t5")]), True),  # name any case
    (req("PUT", "/t", "", [("x-tenant", "t6")]), False),
    (req("PUT", "/t", "", [("accept", "*/*")]), False),  # absent
    (req("DELETE", "/a", "", [("authorization", "Bearer 1")]), True),
    (req("DELETE", "/a", "", [("authorization", "")]), True),  # presence
    (req("DELETE", "/a"), False),
    # no budget decides a verdict: a path of 129 bytes, nine headers
    (req("GET", "/api/v1/users/" + "1" * 115), True),
    (req("GET", "/api/v1/users/" + "1" * 115 + "x"), False),
    (req("GET", "/api/v1/users", "",
         [(f"x-h{i}", "v") for i in range(9)]), True),
    (req("PUT", "/t", "",
         [(f"x-h{i}", "v") for i in range(9)] + [("x-tenant", "t5")]), True),
])
def test_reference_http(ref, request_, want):
    assert ref.decide(APP, HTTP_PORT, TEAM, request_) is want
    # a team with no rule of the filter: denied; an L3-only rule's team:
    # allowed every request
    assert ref.decide(APP, HTTP_PORT, OTHER, request_) is False
    assert ref.decide(APP, HTTP_PORT, L3_TEAM, request_) is True


@pytest.mark.parametrize("request_, flagged", [
    (req("GET", "/api/v1/users/" + "1" * 114), False),  # 128 bytes
    (req("GET", "/api/v1/users/" + "1" * 115), True),  # 129 bytes
    (req("G" * 17, "/"), True),
    (req("GET", "/", "h" * 65), True),
    # headers count only where some rule names them
    (req("GET", "/", "", [(f"x-h{i}", "v") for i in range(9)]), False),
    (req("GET", "/", "", [(f"x-h{i}", "v") for i in range(7)]
         + [("X-Tenant", "t5"), ("authorization", "1")]), True),
    (req(kafka=(1, 0, "c", tuple(f"t{i}" for i in range(8)))), False),
    (req(kafka=(1, 0, "c", tuple(f"t{i}" for i in range(9)))), True),
])
def test_reference_over_budget(ref, request_, flagged):
    """The count of requests over the program's budgets, which decide
    no verdict."""
    named = ref.named | {f"x-h{i}" for i in range(7)}
    assert ref.named == {"x-tenant", "authorization"}
    assert over_budget(request_, named) is flagged


@pytest.mark.parametrize("kafka, want", [
    ((0, 0, "x", ("a",)), True),  # produce role: Produce
    ((3, 1, "x", ("a",)), True),  # produce role: Metadata
    ((1, 0, "x", ("a",)), False),  # Fetch of a: no rule names it
    ((1, 0, "c1", ("b",)), True),  # apiKey fetch, client id, or consume
    ((1, 0, "c2", ("b",)), True),  # the consume rule has no client id
    ((0, 0, "c1", ("b",)), False),  # Produce of b
    ((19, 0, "x", ("a",)), False),  # CreateTopics: in no rule
    ((0, 0, "x", ("a", "b")), False),  # b not produced by any rule
    ((1, 0, "c1", ("b", "b")), True),  # topics as a set
    ((18, 0, "x", ()), True),  # ApiVersions, no topics
])
def test_reference_kafka(ref, kafka, want):
    assert ref.decide(APP, KAFKA_PORT, TEAM, req(kafka=kafka)) is want


def test_reference_client_id():
    """A client id is checked only for request kinds that carry one."""
    rules = [_kafka(api_key="fetch", topic="b", client_id="c1")]
    assert L7Reference.kafka_allowed(rules, (1, 0, "c1", ("b",)))
    assert not L7Reference.kafka_allowed(rules, (1, 0, "c2", ("b",)))
    for key, kind, checked in (("findcoordinator", 10, False),
                               ("offsetcommit", 8, True),
                               ("metadata", 3, True)):
        rules = [_kafka(api_key=key, topic="b", client_id="c1")]
        assert L7Reference.kafka_allowed(
            rules, (kind, 0, "c2", ("b",))) is not checked


def test_reference_no_parser(ref):
    assert ref.decide(APP, 11211, TEAM, req("GET", "/")) is False


# -- the configuration file against the world's literals -------------------


def test_l7gw_config_states_the_drawn_mixes():
    from benchmark.worlds import l7gw as G

    cfg = H.load_json(os.path.join(H.HERE, "configs", "l7gw.json"))
    per_ep = cfg["rules"] // cfg["endpoints"]
    assert cfg["world"] == "l7gw"
    assert cfg["rules_per_endpoint"] == {
        k: round(v * per_ep) for k, v in G.RULE_MIX.items()}
    assert sum(cfg["rules_per_endpoint"].values()) == per_ep
    assert G.POOL_MIX == pytest.approx(cfg["pool_mix"], abs=1e-12, rel=0)
    assert cfg["l7_bound_denied_share"] == G.L7_DENIED_SHARE
    assert cfg["l7_ports"]["kafka"]["pods"] == round(
        G.KAFKA_POD_SHARE * cfg["endpoints"])
    assert cfg["l7_ports"]["http"]["port"] == G.HTTP_PORT
    assert cfg["l7_ports"]["kafka"]["port"] == G.KAFKA_PORT
    assert len(G.ROUTES) == 128 and len(G.TOPICS) == 64
    assert cfg["http_rules"]["methods"] == list(G.METHODS)
    assert cfg["http_rules"]["host_share"] == G.HOST_SHARE
    assert cfg["http_rules"]["headers_share"] == G.HEADER_SHARE
    assert cfg["kafka_rules"]["client_id_share"] == G.CLIENT_SHARE
    assert cfg["kafka_rules"]["api_keys"] == list(G.KAFKA_API_KEYS)
    assert cfg["request_mix"]["match"] == G.MATCH_SHARE
