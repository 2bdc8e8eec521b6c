"""The plain reference of configuration `l7gw`: Cilium v1.2 L7 policy
semantics, written from the L7 rules and requests the world asked for
(benchmark/worlds/l7gw.py).  It imports nothing of the program and
reads nothing the program made; the L3/L4 half of each verdict is
benchmark/reference.py's, unchanged.

A redirected flow's requests are decided by the rules of its L4 filter
(the endpoint's L7 port), named by (endpoint, port), for the flow's
peer:

- HTTP (pkg/envoy/server.go:316 getHTTPRule): Path, Method and Host
  are regexes that must match the whole value (Envoy's regex header
  matchers; `re.fullmatch` with DOTALL), each present field must match
  (AND) and any rule may allow (OR); a Headers entry "Name value"
  (split at its first space, the ':' trimmed from the name) is an
  exact match, "Name" a presence match, names case-insensitive
  (server.go:352-366).
- Kafka (pkg/kafka/policy.go): a role stands for its api keys
  (api/kafka.go:274); apiKey, apiVersion and clientID match exactly,
  the client id only for requests that carry one; a request is allowed
  when a rule without a topic matches it, or every topic it names is
  named by some rule that matches it.
- An L3-only rule that allows the peer's team allows every request to
  the endpoint's L7 filters (an empty L7 rule set: repository.go:170).
- A redirect whose filter has no parser is denied.

No verdict depends on the program's field budgets.  Apart from the
verdicts the reference counts the redirected requests over them
(method 16, path 128, host 64 bytes, 8 headers of the names some rule
names, 8 topics), which the program flags and counts.
"""

from __future__ import annotations

import re

import numpy as np

from benchmark import reference as R

BUDGETS = {"method": 16, "path": 128, "host": 64, "headers": 8, "topics": 8}
ROLE_KEYS = {"produce": (0, 3, 18),
             "consume": (1, 2, 3, 8, 9, 10, 11, 12, 13, 14, 18)}
API_KEYS = {"produce": 0, "fetch": 1, "offsets": 2, "metadata": 3,
            "offsetcommit": 8, "offsetfetch": 9, "findcoordinator": 10,
            "joingroup": 11, "heartbeat": 12, "leavegroup": 13,
            "syncgroup": 14, "apiversions": 18}
# api keys whose parsed request carries a checked client id
# (pkg/kafka/policy.go:71-130)
CLIENT_KEYS = frozenset([0, 1, 2, 3, 8, 9])


def header_constraint(entry: str):
    """(lower-cased name, exact value or None for presence)."""
    parts = entry.split(" ", 1)
    name = parts[0].rstrip(":").lower()
    return name, (parts[1] if len(parts) == 2 else None)


def over_budget(request, named=frozenset()) -> bool:
    """Whether `request` is over a field budget; `named` holds the
    lower-cased header names that some rule names."""
    method, path, host, headers, kafka = request
    topics = kafka[3] if kafka else ()
    return (
        len(method.encode()) > BUDGETS["method"]
        or len(path.encode()) > BUDGETS["path"]
        or len(host.encode()) > BUDGETS["host"]
        or len({n.lower() for n, _ in headers} & named) > BUDGETS["headers"]
        or len(set(topics)) > BUDGETS["topics"]
    )


class L7Reference:
    """Per (pool row, request) L7 verdicts of one world.  With
    `drop_headers` every Headers constraint is left out (the control)."""

    def __init__(self, desc, drop_headers: bool = False) -> None:
        self.base = R.Reference(desc)
        self.requests = desc.requests
        self.scopes = {}  # (app, port) -> kind
        self.rules = {}  # (app, port, team) -> [rule]
        for r in desc.l7_rules:
            self.scopes[(r["app"], r["port"])] = r["kind"]
            rule = dict(r)
            if drop_headers and r["kind"] == "http":
                rule["headers"] = ()
            self.rules.setdefault((r["app"], r["port"], r["team"]),
                                  []).append(rule)
        self.named = frozenset(
            header_constraint(h)[0] for r in desc.l7_rules
            for h in r.get("headers", ()))
        self._re = {}

    def _full(self, pattern: str, value: str) -> bool:
        rx = self._re.get(pattern)
        if rx is None:
            rx = self._re[pattern] = re.compile(pattern, re.DOTALL)
        return rx.fullmatch(value) is not None

    def http_rule(self, rule, request) -> bool:
        method, path, host, headers, _ = request
        for field, value in (("method", method), ("path", path),
                             ("host", host)):
            if rule[field] and not self._full(rule[field], value):
                return False
        got = {}
        for name, value in headers:
            got.setdefault(name.lower(), []).append(value)
        for entry in rule["headers"]:
            name, want = header_constraint(entry)
            values = got.get(name)
            if values is None or (want is not None and want not in values):
                return False
        return True

    @staticmethod
    def kafka_rule(rule, kafka) -> bool:
        """ruleMatches (pkg/kafka/policy.go:144) for a parsed request."""
        kind, version, client, _ = kafka
        keys = (ROLE_KEYS[rule["role"]] if rule["role"]
                else (API_KEYS[rule["api_key"]],) if rule["api_key"]
                else ())
        if keys and kind not in keys:
            return False
        if rule.get("api_version") not in (None, "") and int(
                rule["api_version"]) != version:
            return False
        if rule["client_id"] and kind in CLIENT_KEYS:
            return rule["client_id"] == client
        return True

    @staticmethod
    def kafka_allowed(rules, kafka) -> bool:
        """MatchesRule (pkg/kafka/policy.go:200)."""
        match = L7Reference.kafka_rule
        topics = set(kafka[3])
        for rule in rules:
            if (not rule["topic"] or not topics) and match(rule, kafka):
                return True
        covered = {rule["topic"] for rule in rules
                   if rule["topic"] in topics and match(rule, kafka)}
        return bool(topics) and covered == topics

    def decide(self, app, port, team, request) -> bool:
        """The L7 verdict of one request redirected to (app, port)
        from a peer of `team` (-1: not a cluster identity)."""
        kind = self.scopes.get((app, port))
        if kind is None:
            return False
        if team >= 0 and team in self.base.l3_teams.get(app, ()):
            return True
        rules = self.rules.get((app, port, team), [])
        if kind == "http":
            return any(self.http_rule(r, request) for r in rules)
        kafka = request[4] or (0, 0, "", ())
        return self.kafka_allowed(rules, kafka)

    def verdicts(self, pool, cols) -> dict:
        """Per (pool row, request), rows flattened as row * n + j:
        `redirected` (the flow goes to the proxy), `l7_allowed` (its
        L7 verdict; 0 where not redirected), `allowed` (the final
        verdict) and `flagged` (redirected and over a budget, decided all
        the same).  `cols`
        is the L3/L4 reference's Reference.flows()."""
        n_rows = len(pool["saddr"])
        n = len(self.requests[0])
        red = np.repeat(cols["redirect_key"] >= 0, n)
        l7 = np.zeros(n_rows * n, bool)
        flagged = np.zeros(n_rows * n, bool)
        for row in np.nonzero(cols["redirect_key"] >= 0)[0]:
            app = self.base.app_of_axis[int(pool["ep_index"][row])]
            port = int(cols["final_dport"][row])
            team = self.base.team_of(self.base.peer(int(pool["saddr"][row])))
            for j, request in enumerate(self.requests[row]):
                l7[row * n + j] = self.decide(app, port, team, request)
                flagged[row * n + j] = over_budget(request, self.named)
        allowed = np.repeat(cols["allowed"].astype(bool), n) & (~red | l7)
        return {"redirected": red, "l7_allowed": l7, "allowed": allowed,
                "flagged": flagged}

