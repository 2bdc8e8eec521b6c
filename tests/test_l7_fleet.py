"""Fleet-scoped L7: one compiled matcher set for every redirect in the
fleet, gated per flow by (endpoint, direction, L4 slot) — the inline
analog of per-listener proxy policies (envoy/cilium_l7policy.cc:193).

Scope isolation is the property under test: the same request that one
endpoint's filter allows must be denied through another endpoint's
filter whose rules differ, even though both compile into ONE union
DFA."""

import numpy as np
import jax.numpy as jnp

from cilium_tpu.daemon import Daemon
from cilium_tpu.labels import Label, Labels
from cilium_tpu.policy.api import (
    EndpointSelector,
    IngressRule,
    PortProtocol,
    PortRule,
    Rule,
)
from cilium_tpu.policy.api.rule import L7Rules, PortRuleHTTP, PortRuleKafka
from cilium_tpu.l7.fleet import (
    PARSER_HTTP_ID,
    PARSER_KAFKA_ID,
    compile_fleet_l7,
    evaluate_fleet_l7,
)
from cilium_tpu.l7.http import http_rule_matches_host, pad_requests
from cilium_tpu.l7.kafka import (
    KafkaRequest,
    matches_rules_host,
    pad_kafka_requests,
)


def _http_rule(app, team, port, path):
    return Rule(
        endpoint_selector=EndpointSelector(
            match_labels={"k8s.app": app}
        ),
        ingress=[
            IngressRule(
                from_endpoints=[
                    EndpointSelector(match_labels={"k8s.team": team})
                ],
                to_ports=[
                    PortRule(
                        ports=[
                            PortProtocol(port=str(port), protocol="TCP")
                        ],
                        rules=L7Rules(
                            http=[PortRuleHTTP(method="GET", path=path)]
                        ),
                    )
                ],
            )
        ],
    )


def _kafka_rule(app, team, port, topic):
    return Rule(
        endpoint_selector=EndpointSelector(
            match_labels={"k8s.app": app}
        ),
        ingress=[
            IngressRule(
                from_endpoints=[
                    EndpointSelector(match_labels={"k8s.team": team})
                ],
                to_ports=[
                    PortRule(
                        ports=[
                            PortProtocol(port=str(port), protocol="TCP")
                        ],
                        rules=L7Rules(
                            kafka=[PortRuleKafka(topic=topic)]
                        ),
                    )
                ],
            )
        ],
    )


def test_fleet_l7_scope_isolation():
    d = Daemon(num_workers=2)
    d.policy_trigger.close(wait=True)
    for i, app in enumerate(("web", "api")):
        d.create_endpoint(
            100 + i,
            Labels({"app": Label("app", app, "k8s")}),
            ipv4=f"10.7.0.{i + 1}",
            name=app,
        )
    ident_a, _ = d.identity_allocator.allocate(
        Labels({"team": Label("team", "alpha", "k8s")})
    )
    ident_b, _ = d.identity_allocator.allocate(
        Labels({"team": Label("team", "beta", "k8s")})
    )
    d.policy_add(
        [
            _http_rule("web", "alpha", 8080, "/web/[a-z]+"),
            _http_rule("api", "alpha", 8080, "/api/[0-9]+"),
            _kafka_rule("web", "beta", 9092, "orders"),
        ]
    )
    d.regenerate_all("fleet l7 test")

    fleet = compile_fleet_l7(d)
    assert fleet.http is not None and fleet.kafka is not None
    # the stride gauge reads each field's bytes per scan step
    from cilium_tpu.metrics import registry as metrics

    for f in ("method", "path", "host"):
        sdfa = getattr(fleet.http.tables, f + "_sdfa")
        assert sdfa is not None and sdfa.stride > 1
        assert metrics.policy_l7_dfa_stride.get(f) == sdfa.stride

    _, tables, ep_index = d.endpoint_manager.published()
    id_index, _ = d.endpoint_manager.identity_index()
    e_web = ep_index[100]
    e_api = ep_index[101]
    idx_a = id_index[ident_a.id]
    idx_b = id_index[ident_b.id]

    # the slot of (8080, TCP) and (9092, TCP)
    j_http = int(tables.port_slot[6, 8080])
    j_kafka = int(tables.port_slot[6, 9092])
    assert fleet.parser_kind[e_web, 0, j_http] == PARSER_HTTP_ID
    assert fleet.parser_kind[e_web, 0, j_kafka] == PARSER_KAFKA_ID
    assert fleet.parser_kind[e_api, 0, j_http] == PARSER_HTTP_ID

    # four probes: (ep, path) — same request через both endpoints'
    # scopes must differ per their own rules
    reqs = [
        (b"GET", b"/web/hello", b""),
        (b"GET", b"/api/123", b""),
        (b"GET", b"/web/hello", b""),
        (b"GET", b"/api/123", b""),
    ]
    m, ml, p, pl, h, hl, overflow = pad_requests(reqs)
    assert not overflow.any()
    kreqs = [
        KafkaRequest(kind=0, version=0, client_id="c", topics=("orders",),
                     parsed=True)
    ] * 4
    kf = pad_kafka_requests(fleet.kafka, kreqs)

    ep = np.asarray([e_web, e_web, e_api, e_api], np.int32)
    dirn = np.zeros(4, np.int32)
    slot = np.full(4, j_http, np.int32)
    ident = np.asarray([idx_a] * 4, np.int32)
    known = np.ones(4, bool)

    allowed = np.asarray(
        evaluate_fleet_l7(
            fleet,
            jnp.asarray(ep), jnp.asarray(dirn), jnp.asarray(slot),
            jnp.asarray(ident), jnp.asarray(known),
            http_fields=tuple(jnp.asarray(x) for x in (m, ml, p, pl, h, hl)),
            kafka_fields=tuple(jnp.asarray(np.asarray(x)) for x in kf),
        )
    )
    # web allows /web/*, api allows /api/[0-9]+ — cross requests deny
    assert allowed.tolist() == [True, False, False, True]

    # kafka scope: beta may produce to "orders" on web:9092; alpha not
    slot_k = np.full(4, j_kafka, np.int32)
    ep_k = np.asarray([e_web, e_web, e_api, e_api], np.int32)
    ident_k = np.asarray([idx_b, idx_a, idx_b, idx_b], np.int32)
    allowed_k = np.asarray(
        evaluate_fleet_l7(
            fleet,
            jnp.asarray(ep_k), jnp.asarray(dirn), jnp.asarray(slot_k),
            jnp.asarray(ident_k), jnp.asarray(known),
            http_fields=tuple(jnp.asarray(x) for x in (m, ml, p, pl, h, hl)),
            kafka_fields=tuple(jnp.asarray(np.asarray(x)) for x in kf),
        )
    )
    # api has no kafka filter at 9092 → parser NONE → deny (fail closed)
    assert allowed_k.tolist() == [True, False, False, False]

    # host-oracle spot check through the compiled device_rules
    for spec in fleet.http.device_rules:
        if spec.scope_key == (e_web, 0, j_http):
            if spec.path:
                assert http_rule_matches_host(
                    spec, b"GET", b"/web/hello", b""
                )
