"""L7 HTTP: regex→DFA compiler and device matcher bit-identity.

The oracle is Python re.fullmatch (≙ Envoy HeaderMatcher regex
full-match, pkg/envoy/server.go:332).
"""

import re

import numpy as np
import pytest

from cilium_tpu.l7.http import (
    HTTPRuleSpec,
    compile_http_rules,
    evaluate_http_batch,
    http_rule_matches_host,
    pad_headers,
    pad_requests,
)
from cilium_tpu.l7.regex_dfa import (
    RegexTooComplex,
    RegexUnsupported,
    compile_union,
    parse,
)


# ---------------------------------------------------------------------------
# DFA compiler vs re.fullmatch
# ---------------------------------------------------------------------------

PATTERNS = [
    "GET",
    "GET|POST",
    "/public/.*",
    "/api/v[0-9]+/users/[^/]+",
    "/a(b|cd)*e",
    "foo.*bar",
    "[a-z]{2,4}x",
    "(?:ab|a)bc",
    "a?b+c*",
    "\\d+\\.\\d+",
    "x{3}",
    "x{2,}y",
    "",
]

INPUTS = [
    b"", b"GET", b"POST", b"PUT", b"GETX",
    b"/public/", b"/public/x/y", b"/public", b"/publicx",
    b"/api/v1/users/jane", b"/api/v12/users/a/b", b"/api/v/users/x",
    b"/ae", b"/abe", b"/acdcde", b"/abcde",
    b"fooAbar", b"foobar", b"fooba",
    b"abx", b"abcdx", b"ax", b"abcdex",
    b"abc", b"aabc", b"abbc",
    b"b", b"abbcc", b"ac", b"a",
    b"1.5", b"12.34", b"1.", b".5",
    b"ab1", b"ab", b"1ab",
    b"xxx", b"xx", b"xxxx",
    b"xxy", b"xy", b"xxxxxy",
]


@pytest.mark.parametrize("pattern", PATTERNS)
def test_dfa_matches_re_fullmatch(pattern):
    dfa = compile_union([pattern])
    for data in INPUTS:
        want = re.fullmatch(pattern.encode(), data, re.DOTALL) is not None
        got = bool(dfa.run(data) & 1)
        assert got == want, (pattern, data)


def test_posix_classes():
    """Python re can't express [[:alpha:]] (Go regexp can) — compare
    against the hand-translated equivalent."""
    dfa = compile_union(["[[:alpha:]]+[[:digit:]]?"])
    for data in [b"ab", b"ab1", b"1ab", b"a", b"7", b"", b"ab12"]:
        want = re.fullmatch(rb"[A-Za-z]+[0-9]?", data) is not None
        assert bool(dfa.run(data) & 1) == want, data


def test_union_bitmask():
    dfa = compile_union(["GET", "G.*", "[A-Z]+"])
    assert dfa.run(b"GET") == 0b111
    assert dfa.run(b"GX") == 0b110
    assert dfa.run(b"POST") == 0b100
    assert dfa.run(b"get") == 0


def test_unsupported_constructs():
    for pattern in ["a(?=b)", "(a)\\1", "a|^b", "a$b", "a*?"]:
        with pytest.raises(RegexUnsupported):
            compile_union([pattern])


def test_complexity_cap():
    # classic exponential-blowup pattern
    with pytest.raises((RegexTooComplex, RegexUnsupported)):
        compile_union(
            [".*a.{20}"], max_states=64
        )


@pytest.mark.parametrize("seed", range(4))
def test_dfa_fuzz(seed):
    """Random regexes from a safe grammar vs re.fullmatch."""
    rng = np.random.default_rng(seed)

    def gen(depth=0):
        kind = rng.choice(
            ["lit", "dot", "class", "alt", "star", "cat", "opt"]
            if depth < 3
            else ["lit", "dot", "class"]
        )
        if kind == "lit":
            return re.escape(chr(rng.integers(97, 103)))
        if kind == "dot":
            return "."
        if kind == "class":
            a, b = sorted(rng.integers(97, 105, size=2))
            neg = "^" if rng.random() < 0.3 else ""
            return f"[{neg}{chr(a)}-{chr(b)}]"
        if kind == "alt":
            return f"(?:{gen(depth+1)}|{gen(depth+1)})"
        if kind == "star":
            return f"(?:{gen(depth+1)})*"
        if kind == "opt":
            return f"(?:{gen(depth+1)})?"
        return gen(depth + 1) + gen(depth + 1)

    patterns = [gen() for _ in range(8)]
    dfa = compile_union(patterns)
    alphabet = b"abcdefghij"
    for _ in range(200):
        n = rng.integers(0, 6)
        data = bytes(rng.choice(list(alphabet), size=n))
        want = 0
        for i, pattern in enumerate(patterns):
            if re.fullmatch(pattern.encode(), data, re.DOTALL):
                want |= 1 << i
        assert dfa.run(data) == want, (patterns, data)


# ---------------------------------------------------------------------------
# device matcher
# ---------------------------------------------------------------------------


def test_http_device_matcher_end_to_end():
    # identities: 0=frontend, 1=backend, 2=other (indices, pre-resolved)
    rules = [
        HTTPRuleSpec(identity_indices=[0], method="GET", path="/public/.*"),
        HTTPRuleSpec(identity_indices=[0, 1], method="POST", path="/api/v1"),
        HTTPRuleSpec(identity_indices=[2]),  # L7 allow-all for id 2
    ]
    policy = compile_http_rules(rules, n_identities=8)
    assert policy.tables.hdr_rules.shape[0] == 0  # no header constraint

    requests = [
        (b"GET", b"/public/index.html", b""),   # rule 0
        (b"GET", b"/private", b""),             # no rule
        (b"POST", b"/api/v1", b""),             # rule 1
        (b"POST", b"/api/v12", b""),            # no rule (full match!)
        (b"DELETE", b"/x", b""),                # only allow-all
    ]
    m, ml, p, pl, h, hl, _ = pad_requests(requests)

    cases = [
        # (ident_idx, expected allowed per request)
        (0, [1, 0, 1, 0, 0]),
        (1, [0, 0, 1, 0, 0]),
        (2, [1, 1, 1, 1, 1]),  # allow-all pseudo-rule
        (3, [0, 0, 0, 0, 0]),
    ]
    for idx, want in cases:
        allowed, _ = evaluate_http_batch(
            policy.tables,
            m, ml, p, pl, h, hl,
            ident_idx=np.full(len(requests), idx, dtype=np.int32),
            known=np.ones(len(requests), dtype=bool),
        )
        assert np.asarray(allowed).astype(int).tolist() == want, idx


def test_http_host_rule_split_and_headers():
    """A header-carrying rule is a device rule: its exact and presence
    constraints land in the header tables, and the device verdict
    equals the host matcher for a right, a wrong and an absent value,
    a header name in another case and a wrong method."""
    rules = [
        HTTPRuleSpec(
            identity_indices=[0],
            method="GET",
            headers=("X-Token: secret",),
        ),
        HTTPRuleSpec(identity_indices=[0], path="/p", headers=("X-Flag",)),
    ]
    policy = compile_http_rules(rules, n_identities=4)
    assert len(policy.device_rules) == 2
    assert policy.tables.hdr_rules.shape[0] == 2
    cases = [
        (b"GET", b"/", {"x-token": "secret"}),
        (b"GET", b"/", {"x-token": "wrong"}),
        (b"GET", b"/", {}),
        (b"POST", b"/", {"x-token": "secret"}),
        (b"PUT", b"/p", {"x-flag": ""}),
        (b"PUT", b"/p", {"x-other": "1"}),
    ]
    want = [
        any(http_rule_matches_host(r, m, p, b"", h)
            for r in policy.device_rules)
        for m, p, h in cases
    ]
    assert want == [True, False, False, False, True, False]
    m, ml, p, pl, h, hl, _ = pad_requests([(m, p, b"") for m, p, _ in cases])
    names, pairs, overflow = pad_headers(policy.tables, [h for *_, h in cases])
    assert not overflow.any()
    allowed, _ = evaluate_http_batch(
        policy.tables, m, ml, p, pl, h, hl,
        ident_idx=np.zeros(len(cases), dtype=np.int32),
        known=np.ones(len(cases), dtype=bool),
        headers=(names, pairs),
    )
    assert np.asarray(allowed).tolist() == want
    # the rule spelled with the name in another case is the same rule
    upper = compile_http_rules(
        [HTTPRuleSpec(identity_indices=[0], method="GET",
                      headers=("x-TOKEN: secret",))], n_identities=4)
    assert upper.tables.header_pairs == policy.tables.header_pairs


def test_http_unknown_identity_denied():
    rules = [HTTPRuleSpec(identity_indices=[0], method="GET")]
    policy = compile_http_rules(rules, n_identities=4)
    m, ml, p, pl, h, hl, _ = pad_requests([(b"GET", b"/", b"")])
    allowed, _ = evaluate_http_batch(
        policy.tables, m, ml, p, pl, h, hl,
        ident_idx=np.zeros(1, dtype=np.int32),
        known=np.zeros(1, dtype=bool),
    )
    assert not bool(np.asarray(allowed)[0])


def test_specs_from_l4_filter():
    """Rules → L4Filter (with L7DataMap) → device tables end-to-end."""
    from cilium_tpu.l7.http import specs_from_filter
    from cilium_tpu.labels import LabelArray, parse_select_label
    from cilium_tpu.policy.api import (
        EndpointSelector,
        IngressRule,
        PortProtocol,
        PortRule,
        Rule,
    )
    from cilium_tpu.policy.api.rule import L7Rules, PortRuleHTTP
    from cilium_tpu.policy.repository import Repository
    from cilium_tpu.policy.search import SearchContext

    def es(label):
        return EndpointSelector.from_labels(parse_select_label(label))

    repo = Repository()
    repo.add(Rule(
        endpoint_selector=es("app=server"),
        ingress=[IngressRule(
            from_endpoints=[es("app=client")],
            to_ports=[PortRule(
                ports=[PortProtocol(port="80", protocol="TCP")],
                rules=L7Rules(http=[
                    PortRuleHTTP(method="GET", path="/public/.*"),
                ]),
            )],
        )],
    ))
    l4 = repo.resolve_l4_ingress_policy(
        SearchContext(to_labels=LabelArray.parse_select("app=server"))
    )
    f = l4["80/TCP"]
    cache = {
        256: LabelArray.parse_select("app=client"),
        257: LabelArray.parse_select("app=other"),
    }
    id_index = {256: 0, 257: 1}
    specs = specs_from_filter(f, cache, id_index)
    policy = compile_http_rules(specs, n_identities=4)

    m, ml, p, pl, h, hl, _ = pad_requests(
        [(b"GET", b"/public/a", b""), (b"PUT", b"/public/a", b"")]
    )
    allowed, _ = evaluate_http_batch(
        policy.tables, m, ml, p, pl, h, hl,
        ident_idx=np.array([0, 0], dtype=np.int32),
        known=np.ones(2, dtype=bool),
    )
    assert np.asarray(allowed).astype(int).tolist() == [1, 0]
    # identity not selected by the rule: denied
    allowed, _ = evaluate_http_batch(
        policy.tables, m, ml, p, pl, h, hl,
        ident_idx=np.array([1, 1], dtype=np.int32),
        known=np.ones(2, dtype=bool),
    )
    assert np.asarray(allowed).astype(int).tolist() == [0, 0]


@pytest.mark.parametrize("seed", range(2))
def test_http_device_vs_host_oracle_fuzz(seed):
    rng = np.random.default_rng(seed)
    methods = ["GET", "POST", "PUT", "DELETE"]
    paths = ["/a", "/a/b", "/api/v1", "/api/v2/x", "/pub/x.html", "/"]
    rules = []
    for i in range(6):
        rules.append(HTTPRuleSpec(
            identity_indices=list(rng.choice(4, size=2, replace=False)),
            method=str(rng.choice(["GET", "POST", "GET|PUT", ""])),
            path=str(rng.choice(["/a.*", "/api/v[0-9]+.*", "", "/pub/.*"])),
        ))
    policy = compile_http_rules(rules, n_identities=4)

    reqs = []
    idents = []
    for _ in range(128):
        reqs.append((
            str(rng.choice(methods)).encode(),
            str(rng.choice(paths)).encode(),
            b"",
        ))
        idents.append(int(rng.integers(0, 4)))
    m, ml, p, pl, h, hl, _ = pad_requests(reqs)
    allowed, _ = evaluate_http_batch(
        policy.tables, m, ml, p, pl, h, hl,
        ident_idx=np.array(idents, dtype=np.int32),
        known=np.ones(len(reqs), dtype=bool),
    )
    got = np.asarray(allowed)
    for i, ((mm, pp, hh), idx) in enumerate(zip(reqs, idents)):
        want = any(
            idx in r.identity_indices
            and http_rule_matches_host(r, mm, pp, hh)
            for r in rules
        )
        assert bool(got[i]) == want, (i, reqs[i], idents[i])


def test_mxu_lookup_matches_numpy_gather():
    """_mxu_lookup (one-hot × table matmul) must be EXACT for integer
    tables — both the single-dot path (values ≤ 256) and the lo/hi
    byte-plane split (values > 256, where bf16 would round)."""
    import numpy as np
    import jax

    from cilium_tpu.l7.http import _mxu_lookup

    rng = np.random.default_rng(5)
    for k, hi in ((257, 256), (900, 255), (513, 4095), (2048, 60000)):
        table = rng.integers(0, hi + 1, size=k).astype(np.int64)
        table[0] = hi  # pin the extreme value
        idx = rng.integers(0, k, size=(512, 7)).astype(np.int32)
        got = np.asarray(jax.jit(
            lambda i, t=table: _mxu_lookup(i, t)
        )(idx))
        np.testing.assert_array_equal(got, table[idx])
