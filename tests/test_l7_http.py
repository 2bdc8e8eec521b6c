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


# ---------------------------------------------------------------------------
# strided scan: stride chosen by the table the device holds
# ---------------------------------------------------------------------------

# an API-gateway node's HTTP fleet: 128 route templates and 64 service
# Hosts, each union with the never-matching pattern an absent field
# compiles to (compile_http_rules' union_for)
GW_RESOURCES = (
    "users", "orders", "items", "carts", "payments", "invoices",
    "accounts", "sessions", "products", "reviews", "tickets", "messages",
    "events", "metrics", "reports", "tokens", "groups", "roles", "teams",
    "projects", "builds", "jobs", "files", "images", "videos", "comments",
    "likes", "tags", "feeds", "alerts", "quotas", "plans",
)
GW_SUBS = ("status", "history", "items", "owner")
GW_NAMESPACES = ("default", "payments", "orders", "identity")
GW_ROUTES = [
    f"/api/v[1-3]/{res}(/[0-9]+)?(/{sub})?"
    for res in GW_RESOURCES for sub in GW_SUBS
]
GW_HOSTS = [
    f"svc{k}\\.{ns}\\.svc\\.cluster\\.local"
    for k in range(16) for ns in GW_NAMESPACES
]
ABSENT = "[^\\x00-\\xff]"


def gateway_rules():
    """HTTPRuleSpecs whose union DFAs are the gateway's: one per route
    (the first 64 with a Host each) and an allow-all pseudo-rule."""
    return [
        HTTPRuleSpec(
            identity_indices=[i % 16],
            method=("GET", "POST", "PUT", "DELETE", "GET|HEAD")[i % 5],
            path=route,
            host=GW_HOSTS[i] if i < len(GW_HOSTS) else "",
        )
        for i, route in enumerate(GW_ROUTES)
    ] + [HTTPRuleSpec(identity_indices=[0])]


@pytest.fixture(scope="module")
def gw_unions():
    from cilium_tpu.l7.http import build_strided

    out = {}
    for name, patterns in (
        ("path", GW_ROUTES + [ABSENT]),
        ("host", GW_HOSTS + [ABSENT]),
    ):
        dfa = compile_union(patterns)
        out[name] = (patterns, dfa, build_strided(dfa))
    return out


def _gw_request(rng, name) -> bytes:
    """A value for the gateway's `name` field: a match, a near miss
    (version 4, a trailing 'x', the next namespace, svc16) or noise."""
    u = rng.random()
    if name == "path":
        res = GW_RESOURCES[rng.integers(len(GW_RESOURCES))]
        v = 4 if u < 0.2 else int(rng.integers(1, 4))
        s = f"/api/v{v}/{res}"
        if rng.random() < 0.5:
            s += f"/{int(rng.integers(1, 10**int(rng.integers(1, 8))))}"
        if rng.random() < 0.5:
            s += f"/{GW_SUBS[rng.integers(len(GW_SUBS))]}"
    else:
        k = 16 if u < 0.15 else int(rng.integers(16))
        ns = int(rng.integers(len(GW_NAMESPACES)))
        if 0.15 <= u < 0.3:
            ns = (ns + 1) % len(GW_NAMESPACES) + len(GW_NAMESPACES)
        ns_name = (GW_NAMESPACES * 2 + ("kube-system",))[ns]
        s = f"svc{k}.{ns_name}.svc.cluster.local"
    if 0.3 <= u < 0.45:
        s += "x"
    elif 0.45 <= u < 0.5:
        s = "".join(chr(int(c)) for c in rng.integers(32, 127, size=9))
    return s.encode()


def _fullmatch_bits(patterns, value: bytes, words: int):
    bits = [0] * words
    for i, pattern in enumerate(patterns):
        if re.fullmatch(pattern.encode(), value, re.DOTALL):
            bits[i // 32] |= 1 << (i % 32)
    return bits


@pytest.mark.parametrize("name", ["path", "host"])
def test_gateway_union_strides_once_within_budget(gw_unions, name):
    """The gateway's path (1,023 states) and Host (1,653) unions get
    exactly one level: its deduplicated table fits the byte budget
    though the squaring's pre-dedup transient does not, and the next
    level's pair count would exceed the MXU fold's K, so it is not
    built."""
    from cilium_tpu.l7.http import MXU_LOOKUP_MAX_K, build_strided

    _, dfa, sdfa = gw_unions[name]
    budget = 1 << 22  # build_strided's default
    assert sdfa is not None and sdfa.stride == 2
    assert sdfa.trans.dtype == np.int32
    assert sdfa.trans.shape[0] == dfa.n_states
    assert sdfa.trans.nbytes <= budget
    nc0 = dfa.trans.shape[1] + 1  # with the identity class
    assert sdfa.level_ncs == [nc0]
    assert dfa.n_states * nc0 * nc0 * 8 > budget  # the int64 transient
    assert all(nc * nc <= MXU_LOOKUP_MAX_K for nc in sdfa.level_ncs)
    assert sdfa.trans.shape[1] ** 2 > MXU_LOOKUP_MAX_K
    # the budget bounds the deduplicated table: one byte under it, no
    # level, hence no strided form
    assert build_strided(dfa, max_table_bytes=sdfa.trans.nbytes - 1) is None
    again = build_strided(dfa, max_table_bytes=sdfa.trans.nbytes)
    assert again is not None and again.stride == 2


@pytest.mark.parametrize("seed", range(2))
@pytest.mark.parametrize("name", ["path", "host"])
def test_gateway_strided_scan_matches_bytewise_and_re(gw_unions, name, seed):
    """_dfa_scan_strided == _dfa_scan == re.fullmatch on the gateway's
    union, over values of lengths 0, 1, odd, 31 and 32 (prefixes of
    matches and near misses cut to those lengths) and whole ones."""
    import jax

    from cilium_tpu.l7.http import _dfa_scan, _dfa_scan_strided

    patterns, dfa, sdfa = gw_unions[name]
    rng = np.random.default_rng(seed)
    width = 32
    values = []
    while len(values) < 192:
        v = _gw_request(rng, name)
        if len(v) <= width:
            values.append(v)
    for n in (0, 1, 7, 13, 31, 32):
        for _ in range(8):
            v = _gw_request(rng, name) + b"/0123456789abcdefghijklmnopqrst"
            values.append(v[:n])
    data = np.zeros((len(values), width), np.uint8)
    lens = np.zeros(len(values), np.int32)
    for i, v in enumerate(values):
        data[i, : len(v)] = np.frombuffer(v, np.uint8)
        lens[i] = len(v)
    assert {0, 1, 31, 32} <= set(lens.tolist())

    strided = np.asarray(jax.jit(
        lambda d, n: _dfa_scan_strided(sdfa, d, n)
    )(data, lens))
    bytewise = np.asarray(jax.jit(lambda d, n: _dfa_scan(dfa, d, n))(
        data, lens
    ))
    np.testing.assert_array_equal(strided, bytewise)
    words = strided.shape[1]
    want = np.asarray(
        [_fullmatch_bits(patterns, v, words) for v in values], np.uint32
    )
    np.testing.assert_array_equal(strided, want)
    assert want.any() and not want.all()  # matches and misses both


def test_union_past_mxu_fold_keeps_bytewise_scan():
    """A union with more than 45 byte classes (identity class
    included) has a first pair fold past MXU_LOOKUP_MAX_K: it gets no
    strided form and scans byte by byte, as before (its squared table
    never fit the old budget either), with the same verdicts."""
    from cilium_tpu.l7.http import MXU_LOOKUP_MAX_K, build_strided

    rng = np.random.default_rng(11)
    alphabet = "abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ0123"
    words = [
        "".join(alphabet[j] for j in rng.integers(len(alphabet), size=6))
        for _ in range(64)
    ]
    rules = [
        HTTPRuleSpec(identity_indices=[i % 4], path=f"/{w}(/[0-9]+)?")
        for i, w in enumerate(words)
    ]
    policy = compile_http_rules(rules, n_identities=4)
    dfa = policy.tables.path_dfa
    nc0 = dfa.trans.shape[1] + 1
    assert nc0 * nc0 > MXU_LOOKUP_MAX_K
    assert build_strided(dfa) is None
    assert policy.tables.path_sdfa is None

    reqs, idents = [], []
    for i in range(160):
        w = words[i % len(words)]
        path = (f"/{w}", f"/{w}/{i}", f"/{w}x", f"/{w[:-1]}", "/")[i % 5]
        reqs.append((b"GET", path.encode(), b""))
        idents.append(int(rng.integers(0, 4)))
    m, ml, p, pl, h, hl, _ = pad_requests(reqs)
    allowed, _ = evaluate_http_batch(
        policy.tables, m, ml, p, pl, h, hl,
        ident_idx=np.array(idents, dtype=np.int32),
        known=np.ones(len(reqs), dtype=bool),
    )
    want = [
        any(idx in r.identity_indices
            and http_rule_matches_host(r, mm, pp, hh) for r in rules)
        for (mm, pp, hh), idx in zip(reqs, idents)
    ]
    assert np.asarray(allowed).tolist() == want
    assert any(want) and not all(want)
