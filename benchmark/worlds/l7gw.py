"""The world of configuration `l7gw`: one Cilium v1.2 node agent on a
110-pod node that runs an API tier.  Most pods expose HTTP on 8080,
the rest Kafka on 9092, each under L7 policy, so most of the node's
flows are redirected to the proxy.

Built through the program's control plane as benchmark/world.py builds
`n110` (its helpers: endpoints, identities and ipcache, services,
prefilter, conntrack; its flow pool with the L7 flows overlaid here),
with a policy of its own shape: per endpoint, L7 rules on the pod's L7
port (HTTP: method, path, Host, Headers; Kafka: role or apiKey, topic,
clientID), L4 rules on other ports, L3-only and CIDR rules.  Every
rule allows one team (one identity group), as in `n110`.

Beside `world.py`'s plain description (`specs`, which
benchmark/reference.py reads for the L3/L4 half) the world keeps
`l7_rules`, each L7 rule as plain values (the HTTP rule's method,
path and host as the regexes the policy states), and `requests`, each pool
flow's requests as plain values, which benchmark/l7gw_reference.py
reads.  A request is (method, path, host, headers, kafka): `headers` a
tuple of (lower-cased name, value), `kafka` a tuple (api key, version,
client id, topics) or None for an HTTP request.

The shares are literals here; configs/l7gw.json states the same
numbers (benchmark/tests/test_l7gw.py holds the two together).
"""

from __future__ import annotations

import time
from types import SimpleNamespace

import numpy as np

from benchmark import world as W

HTTP_PORT, KAFKA_PORT = 8080, 9092
# rules per endpoint, by kind, as shares of `rules` / `endpoints`
RULE_MIX = {"l7": 0.40, "l4": 0.45, "l3_only": 0.08, "cidr": 0.07}
KAFKA_POD_SHARE = 0.2  # 22 of 110 pods serve Kafka, 88 HTTP
POOL_MIX = {
    "l7_bound": 0.70,
    "junk_ports": 0.05,
    "egress_to_vip": 0.08,
    "prefiltered": 0.02,
    "world": 0.03,
    "fragments": 0.02,
}
L7_DENIED_SHARE = 0.15  # of the L7-bound flows, from a team not allowed
METHODS = ("GET", "POST", "PUT", "DELETE", "GET|HEAD")
RESOURCES = (
    "users", "orders", "items", "carts", "payments", "invoices",
    "accounts", "sessions", "products", "reviews", "tickets", "messages",
    "events", "metrics", "reports", "tokens", "groups", "roles", "teams",
    "projects", "builds", "jobs", "files", "images", "videos", "comments",
    "likes", "tags", "feeds", "alerts", "quotas", "plans",
)
SUBS = ("status", "history", "items", "owner")
# 128 route templates: /api/v[1-3]/<resource>(/[0-9]+)?(/<sub>)?
ROUTES = tuple((r, s) for r in RESOURCES for s in SUBS)
SERVICES = tuple(f"svc{k}" for k in range(16))
NAMESPACES = ("default", "payments", "orders", "identity")
TOPICS = tuple(f"topic-{k}" for k in range(64))
KAFKA_API_KEYS = {"produce": 0, "fetch": 1, "offsets": 2, "metadata": 3,
                  "offsetcommit": 8}
HOST_SHARE = 0.25
HEADER_SHARE = 0.10
CLIENT_SHARE = 0.25
MATCH_SHARE = 0.6  # else a miss on method, path, Host or a header, 0.1 each
TINY = dict(rules=800, endpoints=8, identities=1024, pool=3000)


def route_pattern(route) -> str:
    res, sub = route
    return f"/api/v[1-3]/{res}(/[0-9]+)?(/{sub})?"


def host_name(svc: str, ns: str) -> str:
    return f"{svc}.{ns}.svc.cluster.local"


def _l7_rule(rng, kind, team):
    """One L7 rule as plain values."""
    if kind == "http":
        host = None
        if rng.random() < HOST_SHARE:
            host = (SERVICES[rng.integers(len(SERVICES))],
                    NAMESPACES[rng.integers(len(NAMESPACES))])
        headers = ()
        if rng.random() < HEADER_SHARE:
            headers = ((f"X-Tenant: t{team}",) if rng.random() < 0.5
                       else ("Authorization",))
        route = ROUTES[rng.integers(len(ROUTES))]
        return dict(
            method=METHODS[rng.integers(len(METHODS))],
            path=route_pattern(route),
            host=host_name(*host).replace(".", "\\.") if host else "",
            headers=headers, route=route, host_parts=host,
        )
    pick = rng.random()
    role, api_key = "", ""
    if pick < 0.4:
        role = "produce"
    elif pick < 0.8:
        role = "consume"
    else:
        api_key = list(KAFKA_API_KEYS)[rng.integers(len(KAFKA_API_KEYS))]
    client = (f"client-{rng.integers(8)}" if rng.random() < CLIENT_SHARE
              else "")
    return dict(role=role, api_key=api_key,
                topic=TOPICS[rng.integers(len(TOPICS))], client_id=client)


def build_rules(rng, n_rules, n_endpoints, n_teams, kafka_pods):
    """(rules, all_ports, l7 rules as plain dicts, specs); rule i
    selects endpoint i % n_endpoints, and its place in that endpoint's
    list, i // n_endpoints, gives its kind (RULE_MIX)."""
    from cilium_tpu.labels import LabelArray
    from cilium_tpu.policy.api import (
        EndpointSelector,
        IngressRule,
        PortProtocol,
        PortRule,
        Rule,
    )
    from cilium_tpu.policy.api.rule import (
        CIDRRule,
        L7Rules,
        PortRuleHTTP,
        PortRuleKafka,
    )

    def es(key, value):
        return EndpointSelector(match_labels={f"k8s.{key}": value})

    per_ep = n_rules // n_endpoints
    cuts = np.cumsum([round(RULE_MIX[k] * per_ep)
                      for k in ("l7", "l4", "l3_only")])
    candidates = np.setdiff1d(np.arange(1000, 30000), [HTTP_PORT, KAFKA_PORT])
    plain_ports = rng.choice(candidates, size=224, replace=False)
    rules, specs, l7_rules = [], [], []
    for i in range(n_rules):
        app_idx = i % n_endpoints
        slot = i // n_endpoints
        team_idx = int(rng.integers(0, n_teams))
        sel = es("app", f"app{app_idx}")
        src = es("team", f"t{team_idx}")
        if slot < cuts[0]:
            kind = "kafka" if app_idx in kafka_pods else "http"
            port = KAFKA_PORT if kind == "kafka" else HTTP_PORT
            rule = _l7_rule(rng, kind, team_idx)
            rule.update(app=app_idx, port=port, team=team_idx, kind=kind)
            l7_rules.append(rule)
            specs.append((app_idx, kind, team_idx, port, 6, -1))
            if kind == "http":
                l7 = L7Rules(http=[PortRuleHTTP(
                    method=rule["method"], path=rule["path"],
                    host=rule["host"], headers=list(rule["headers"]),
                )])
            else:
                l7 = L7Rules(kafka=[PortRuleKafka(
                    role=rule["role"], api_key=rule["api_key"],
                    topic=rule["topic"], client_id=rule["client_id"],
                )])
            ingress = IngressRule(from_endpoints=[src], to_ports=[PortRule(
                ports=[PortProtocol(port=str(port), protocol="TCP")],
                rules=l7,
            )])
        elif slot < cuts[1]:
            port = int(plain_ports[int(rng.integers(0, len(plain_ports)))])
            proto = "TCP" if rng.random() < 0.7 else "UDP"
            specs.append((app_idx, "l4", team_idx, port,
                          6 if proto == "TCP" else 17, -1))
            ingress = IngressRule(from_endpoints=[src], to_ports=[PortRule(
                ports=[PortProtocol(port=str(port), protocol=proto)]
            )])
        elif slot < cuts[2]:
            specs.append((app_idx, "l3", team_idx, 0, 0, -1))
            ingress = IngressRule(from_endpoints=[src])
        else:
            block = int(rng.integers(0, 256))
            specs.append((app_idx, "cidr", -1, 0, 0, block))
            ingress = IngressRule(
                from_cidr_set=[CIDRRule(cidr=f"198.18.{block}.0/24")]
            )
        rules.append(Rule(endpoint_selector=sel, ingress=[ingress],
                          labels=LabelArray.parse(f"l7gw-rule-{i}")))
    all_ports = (
        [(int(p), 6) for p in plain_ports]
        + [(int(p), 17) for p in plain_ports]
        + [(HTTP_PORT, 6), (KAFKA_PORT, 6)]
    )
    return rules, all_ports, l7_rules, specs


def _overlay_l7_flows(rng, pool, l7_rules, l3_teams, ep_ip, id_ips,
                      n_teams, index):
    """L7-bound flows over POOL_MIX["l7_bound"] of the pool rows:
    ingress to an L7 rule's endpoint and port, from a member of the
    rule's team, or for L7_DENIED_SHARE of them from a team that no
    rule of that endpoint allows."""
    n = len(pool["saddr"])
    allowed = {}
    for r in l7_rules:
        allowed.setdefault(r["app"], set()).add(r["team"])
    rows = np.nonzero(rng.random(n) < POOL_MIX["l7_bound"])[0]
    pick = rng.integers(0, len(l7_rules), size=len(rows))
    denied = rng.random(len(rows)) < L7_DENIED_SHARE
    for row, r, deny in zip(rows, pick, denied):
        rule = l7_rules[int(r)]
        app = rule["app"]
        team = rule["team"]
        if deny:
            shut = allowed[app] | l3_teams.get(app, set())
            while team in shut:
                team = int(rng.integers(0, n_teams))
        member = int(rng.integers(0, len(id_ips) // n_teams))
        i_id = member * n_teams + team
        if i_id >= len(id_ips):
            i_id = team
        pool["direction"][row] = 0
        pool["ep_index"][row] = index[100 + app]
        pool["saddr"][row] = id_ips[i_id]
        pool["daddr"][row] = ep_ip[100 + app]
        pool["dport"][row] = rule["port"]
        pool["proto"][row] = 6
        pool["is_fragment"][row] = 0


# ---------------------------------------------------------------------------
# requests
# ---------------------------------------------------------------------------


def _path(rng, route, version=None):
    res, sub = route
    v = int(rng.integers(1, 4)) if version is None else version
    path = f"/api/v{v}/{res}"
    if rng.random() < 0.5:
        path += f"/{int(rng.integers(1, 10**6))}"
    if rng.random() < 0.5:
        path += f"/{sub}"
    return path


def _http_request(rng, rule, team):
    """A request aimed at `rule`: it matches with MATCH_SHARE, else it
    misses on the method, the path, the Host or a header (0.1 each;
    on the path where the rule has no Host or no header)."""
    method = rule["method"]
    method = ("GET", "HEAD")[int(rng.integers(2))] if "|" in method else method
    path = _path(rng, rule["route"])
    if rule["host_parts"]:
        host = host_name(*rule["host_parts"])
    else:
        host = host_name(SERVICES[rng.integers(len(SERVICES))],
                         NAMESPACES[rng.integers(len(NAMESPACES))])
    headers = {"user-agent": "curl/7.58.0", "accept": "*/*",
               "x-request-id": f"{int(rng.integers(1 << 62)):016x}"}
    for h in rule["headers"]:
        if h.startswith("X-Tenant"):
            headers["x-tenant"] = f"t{team}"
        else:
            headers["authorization"] = f"Bearer {int(rng.integers(1 << 62)):x}"
    if "x-tenant" not in headers and rng.random() < 0.3:
        headers["x-tenant"] = f"t{team}"
    u = rng.random()
    if u >= MATCH_SHARE:
        miss = ("method", "path", "host", "header")[
            min(int((u - MATCH_SHARE) / 0.1), 3)]
        if miss == "host" and not rule["host_parts"]:
            miss = "path"
        if miss == "header" and not rule["headers"]:
            miss = "path"
        if miss == "method":
            method = "POST" if method in ("GET", "HEAD") else "PATCH"
        elif miss == "path":
            path = (_path(rng, rule["route"], version=4)
                    if rng.random() < 0.5 else path + "x")
        elif miss == "host":
            svc, ns = rule["host_parts"]
            host = host_name(svc, NAMESPACES[(NAMESPACES.index(ns) + 1)
                                             % len(NAMESPACES)])
        elif "x-tenant" in headers and rule["headers"][0].startswith(
                "X-Tenant"):
            headers["x-tenant"] = f"t{team + 1}"
        else:
            headers.pop("authorization", None)
    return (method, path, host, tuple(headers.items()), None)


# api keys each role expands to (v1.2 api/kafka.go:274)
ROLE_KEYS = {"produce": (0, 3, 18),
             "consume": (1, 2, 3, 8, 9, 10, 11, 12, 13, 14, 18)}


def _kafka_request(rng, rule):
    """A request aimed at `rule`: it matches with MATCH_SHARE, else it
    misses on the api key, the topic or the client id (the topic where
    the rule names no client id)."""
    keys = (ROLE_KEYS[rule["role"]] if rule["role"]
            else (KAFKA_API_KEYS[rule["api_key"]],))
    kind = int(keys[int(rng.integers(len(keys)))])
    topic = rule["topic"]
    client = rule["client_id"] or f"client-{rng.integers(8)}"
    u = rng.random()
    if u >= MATCH_SHARE:
        miss = ("key", "topic", "client")[min(int((u - MATCH_SHARE) / 0.4 * 3),
                                              2)]
        if miss == "client" and not rule["client_id"]:
            miss = "topic"
        if miss == "key":
            kind = 19  # CreateTopics: in no role
        elif miss == "topic":
            topic = TOPICS[(TOPICS.index(topic) + 1) % len(TOPICS)]
        else:
            client = client + "-x"
    return ("", "", "", (), (kind, int(rng.integers(0, 3)), client, (topic,)))


NO_REQUEST = ("", "", "", (), None)


def make_requests(rng, pool, l7_rules, n_per_flow, n_teams, id_ips,
                  app_of_axis):
    """`n_per_flow` requests per pool flow: a flow to an endpoint's L7
    port carries requests aimed at the rules of that port for its
    team (any rule of the port where its team has none); any other
    flow carries empty ones."""
    by_scope, by_team = {}, {}
    for r in l7_rules:
        by_scope.setdefault((r["app"], r["port"]), []).append(r)
        by_team.setdefault((r["app"], r["port"], r["team"]), []).append(r)
    team_of_ip = {int(ip): i % n_teams for i, ip in enumerate(id_ips)}
    out = []
    for row in range(len(pool["saddr"])):
        app = app_of_axis[int(pool["ep_index"][row])]
        scope = (app, int(pool["dport"][row]))
        rules = by_scope.get(scope)
        if rules is None or int(pool["direction"][row]) != 0:
            out.append((NO_REQUEST,) * n_per_flow)
            continue
        team = team_of_ip.get(int(pool["saddr"][row]), -1)
        rules = by_team.get(scope + (team,), rules)
        reqs = []
        for _ in range(n_per_flow):
            rule = rules[int(rng.integers(len(rules)))]
            reqs.append(_http_request(rng, rule, team)
                        if rule["kind"] == "http"
                        else _kafka_request(rng, rule))
        out.append(tuple(reqs))
    return out


# ---------------------------------------------------------------------------
# the world
# ---------------------------------------------------------------------------


def build_world(cfg: dict, rng) -> SimpleNamespace:
    """n110's world build with this configuration's policy, L7 flows
    and requests (benchmark/world.py's contract, plus `l7_rules` and
    `requests`)."""
    n_eps = int(cfg["endpoints"])
    n_kafka = int(round(KAFKA_POD_SHARE * n_eps))
    kafka_pods = set(int(a) for a in rng.choice(n_eps, n_kafka,
                                                replace=False))
    built = {}

    def rules_fn(rng, n_rules, n_endpoints, n_teams, cuts):
        rules, all_ports, l7_rules, specs = build_rules(
            rng, n_rules, n_endpoints, n_teams, kafka_pods
        )
        built["l7_rules"] = l7_rules
        return rules, all_ports, None, specs  # no n110-style overlay

    # world.build_world takes its mixes as arguments but draws its
    # rules with world.build_rules by name: this configuration's rules
    # function stands in for it during the call
    n110_rules, W.build_rules = W.build_rules, rules_fn
    try:
        world = W.build_world(cfg, rng, pool_mix=POOL_MIX)
    finally:
        W.build_rules = n110_rules
    t0 = time.perf_counter()
    l3_teams = {}
    for app, kind, team, *_ in world.specs:
        if kind == "l3":
            l3_teams.setdefault(app, set()).add(team)
    _overlay_l7_flows(rng, world.pool, built["l7_rules"], l3_teams,
                      world.ep_ip, world.id_ips, world.n_teams, world.index)
    app_of_axis = {int(axis): ep - 100 for ep, axis in world.index.items()}
    world.requests = make_requests(
        rng, world.pool, built["l7_rules"], int(cfg["requests_per_flow"]),
        world.n_teams, world.id_ips, app_of_axis,
    )
    world.l7_rules = built["l7_rules"]
    world.kafka_pods = sorted(kafka_pods)
    world.timings["requests_s"] = time.perf_counter() - t0
    return world
