"""HTTP L7 policy: rules → DFA tables → batched device matching.

Reference semantics being reproduced (bit-identically):
  * pkg/envoy/server.go:316 getHTTPRule — Path/Method/Host become
    Envoy regex HeaderMatchers, which FULL-match the value; all fields
    of one PortRuleHTTP must match (AND); a request is allowed if ANY
    rule of the relevant L7Rules matches (OR) — envoy route semantics
    in cilium_l7policy.cc (deny → 403).
  * pkg/policy/l4.go:118 GetRelevantRules — rules apply per remote
    identity through their selector; an entry with EMPTY L7Rules is an
    L7 allow-all for the selected identities (wildcardL3L4Rules,
    repository.go:170).
  * Header constraints (PortRuleHTTP.Headers, server.go:352-366):
    "Name: value" is an exact match, "Name" a presence match, names
    case-insensitive.  They compile to device tables over header
    names and (name, value) pairs interned against the policy's own
    literals; a request stages the headers of the names the policy
    names as those ids (`pad_headers`: a value no rule names is 0,
    which equals no constraint), so the device decides every header
    rule and the host only interns strings.

Device layout (R rules per port filter, W = ceil(R/32) mask words —
rule r lives in bit r%32 of word r//32; no 32-rule cap):
  method/path/host DFAs — union DFAs with per-rule accept bits
                           (accept u32 [S, W]);
  absent_<field> u32 [W] — rules that omit the field (auto-match);
  ident_rules u32 [N, W] — bit r set ⟺ rule r's selector admits
                           identity index n (includes allow-all
                           pseudo-rules, which also have all fields
                           absent);
  hdr_name/hdr_pair u32 [C], hdr_rules u32 [C, Wh] — header
                           constraint c (a presence match on name id
                           hdr_name[c] when hdr_pair[c] is 0, else an
                           exact match on pair id hdr_pair[c]) and the
                           rules that require it.  Rules with header
                           constraints are numbered first, so their
                           bits fit the leading Wh words.

Requests whose method/path/host exceed the padded field budgets, or
that carry more headers of the names the policy names than
`MAX_HEADER_PAIRS`, are FLAGGED (`overflow`) and decided again whole:
host-side by `evaluate_with_host_fallback`, or on the device from the
fleet request table's wide columns (l7/fleet.py) — never silently
truncated: a truncated byte tensor could both falsely full-match a
prefix-shaped pattern and miss a long-match, in either direction.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from cilium_tpu.l7.regex_dfa import DFA, RegexTooComplex, compile_union

# Sanity ceiling only (accept masks are multi-word): guards against a
# pathological compile blowing up accept-table width, not a semantic
# limit — the reference's per-filter rule count is bounded by policy
# size, not a constant.
MAX_RULES = 4096
# headers of the policy's names a request stages for the device
# (excess → the request is flagged `overflow`, never decided from a
# prefix)
MAX_HEADER_PAIRS = 8


@dataclass
class HTTPRuleSpec:
    """One (selector-scope, PortRuleHTTP) pair, pre-resolved: the
    identity indices the selector admits over the current universe."""

    identity_indices: Sequence[int]  # indices into the padded universe
    path: str = ""
    method: str = ""
    host: str = ""
    headers: Tuple[str, ...] = ()
    # fleet-scoped compiles (l7/fleet.py) key each rule to its
    # (endpoint, direction, L4 slot); None = filter-local rule.
    # Participates in dedupe: rules only merge within one scope.
    scope_key: "object" = None


@dataclass
class HTTPTables:
    """Device tables for one (endpoint, port, direction) HTTP filter."""

    # DFAs (trans u16 [S,C], accept u32 [S,W], classes u8 [256], start)
    method_dfa: DFA
    path_dfa: DFA
    host_dfa: DFA
    absent_method: np.ndarray  # u32 [W] bitmask
    absent_path: np.ndarray
    absent_host: np.ndarray
    ident_rules: np.ndarray  # u32 [N, W] per-identity rule bits
    n_rules: int
    n_words: int
    # header constraints (module docstring); C may be 0
    hdr_name: np.ndarray = field(
        default_factory=lambda: np.zeros(0, np.uint32)
    )
    hdr_pair: np.ndarray = field(
        default_factory=lambda: np.zeros(0, np.uint32)
    )
    hdr_rules: np.ndarray = field(
        default_factory=lambda: np.zeros((0, 1), np.uint32)
    )
    # the policy's header literals: lower-cased name → id, (lower-cased
    # name, value) → id; ids start at 1 (0 = unseen)
    header_names: Dict[str, int] = field(default_factory=dict)
    header_pairs: Dict[Tuple[str, str], int] = field(default_factory=dict)
    # strided forms (None = fall back to the byte-at-a-time scan)
    method_sdfa: "Optional[StridedDFA]" = None
    path_sdfa: "Optional[StridedDFA]" = None
    host_sdfa: "Optional[StridedDFA]" = None


@dataclass
class HTTPPolicy:
    """Compiled HTTP policy."""

    tables: HTTPTables
    # Deduped device rules (rule r is bit r of the tables), retained
    # for the host path: overflowed requests (fields beyond the padded
    # budgets) re-evaluate against these with re.fullmatch instead of
    # the truncated tensors.
    device_rules: List[HTTPRuleSpec] = field(default_factory=list)


def resolve_selector_indices(
    selector, identity_cache, id_index, selector_cache=None
) -> List[int]:
    """selector → dense identity indices.  With a SelectorCache the
    resolution is one memoized set lookup (O(matched)); without, it
    falls back to the per-identity matches() walk — identical result,
    O(identities) (compiler/selectorcache.py docstring derivation)."""
    if selector_cache is not None:
        return [
            id_index[num_id]
            for num_id in selector_cache.matches(selector)
            if num_id in id_index
        ]
    return [
        id_index[num_id]
        for num_id, labels in identity_cache.items()
        if selector.matches(labels) and num_id in id_index
    ]


def specs_from_filter(
    l4_filter, identity_cache, id_index, selector_cache=None
) -> List["HTTPRuleSpec"]:
    """L4Filter.l7_rules_per_ep (selector → L7Rules, pkg/policy/l4.go:31)
    → flat HTTPRuleSpec list over the identity universe.

    A selector entry with EMPTY L7Rules becomes an allow-all
    pseudo-rule (all fields absent ⇒ matches every request) — the
    L3-override / wildcard entries of createL4IngressFilter
    (l4.go:209) and wildcardL3L4Rules (repository.go:170).
    """
    specs: List[HTTPRuleSpec] = []
    for selector, l7 in l4_filter.l7_rules_per_ep.items():
        indices = resolve_selector_indices(
            selector, identity_cache, id_index, selector_cache
        )
        http_rules = l7.http or []
        if not http_rules:
            specs.append(HTTPRuleSpec(identity_indices=indices))
            continue
        for rule in http_rules:
            specs.append(
                HTTPRuleSpec(
                    identity_indices=indices,
                    path=rule.path or "",
                    method=rule.method or "",
                    host=rule.host or "",
                    headers=tuple(rule.headers or ()),
                )
            )
    return specs


def parse_header(header: str) -> Tuple[str, Optional[str]]:
    """A PortRuleHTTP.Headers entry → (lower-cased name, exact value
    or None for a presence match), as server.go:352-366 splits it: at
    the first space, the ':' trimmed from the name ("Name: value"), or
    a name alone ("Name").  Envoy matches names case-insensitively."""
    name, space, value = header.partition(" ")
    return name.rstrip(":").lower(), (value if space else None)


def _dedupe_specs(rules: List[HTTPRuleSpec]) -> List[HTTPRuleSpec]:
    """Rules with identical patterns are one device rule with the
    union of their identity sets — allowed = OR over rules, so this
    is semantics-preserving.  The dominant case is the allow-all
    pseudo-rules that every L3-only rule wildcards into each L7
    filter (repository.go:170): they all collapse to one.  Rules with
    header constraints come first (HTTPTables.hdr_rules' words)."""
    merged: Dict[tuple, set] = {}
    order: List[tuple] = []
    for rule in rules:
        headers = tuple(sorted(
            set(map(parse_header, rule.headers)),
            key=lambda h: (h[0], h[1] is None, h[1] or ""),
        ))
        key = (rule.method, rule.path, rule.host, rule.scope_key, headers)
        if key not in merged:
            merged[key] = set()
            order.append(key)
        merged[key].update(rule.identity_indices)
    order.sort(key=lambda key: not key[4])  # stable
    return [
        HTTPRuleSpec(
            identity_indices=sorted(merged[key]),
            method=key[0],
            path=key[1],
            host=key[2],
            scope_key=key[3],
            headers=tuple(
                name if value is None else f"{name}: {value}"
                for name, value in key[4]
            ),
        )
        for key in order
    ]


def _header_tables(device_rules: List[HTTPRuleSpec]) -> dict:
    """The header constraints of the (deduped, header rules first)
    device rules as HTTPTables' hdr_* fields and interned literals."""
    names: Dict[str, int] = {}
    pairs: Dict[Tuple[str, str], int] = {}
    constraints: Dict[Tuple[int, int], int] = {}
    n_hdr = 0
    for i, rule in enumerate(device_rules):
        if not rule.headers:
            continue
        n_hdr = i + 1
        for header in rule.headers:
            name, value = parse_header(header)
            nid = names.setdefault(name, len(names) + 1)
            pid = (
                0 if value is None
                else pairs.setdefault((name, value), len(pairs) + 1)
            )
            constraints[(nid, pid)] = constraints.get((nid, pid), 0) | (
                1 << i
            )
    words = max(1, -(-n_hdr // 32))
    keys = list(constraints)
    rules = np.zeros((len(keys), words), np.uint32)
    for c, key in enumerate(keys):
        mask = constraints[key]
        for w in range(words):
            rules[c, w] = (mask >> (32 * w)) & 0xFFFFFFFF
    return dict(
        hdr_name=np.asarray([k[0] for k in keys], np.uint32),
        hdr_pair=np.asarray([k[1] for k in keys], np.uint32),
        hdr_rules=rules,
        header_names=names,
        header_pairs=pairs,
    )


def compile_http_rules(
    rules: Sequence[HTTPRuleSpec],
    n_identities: int,
    max_states: int = 4096,
) -> HTTPPolicy:
    """Dedupe the rules and build the union DFAs and header tables:
    every rule, header-carrying or not, is decided on the device."""
    device_rules = _dedupe_specs(list(rules))
    if len(device_rules) > MAX_RULES:
        raise RegexTooComplex(
            f"more than {MAX_RULES} device HTTP rules per filter"
        )
    n_words = max(1, -(-len(device_rules) // 32))

    def _to_words(mask: int) -> np.ndarray:
        return np.array(
            [(mask >> (32 * w)) & 0xFFFFFFFF for w in range(n_words)],
            dtype=np.uint32,
        )

    def union_for(field_name: str) -> Tuple[DFA, np.ndarray]:
        """DFA over the present patterns; absent bitmask for the rest.
        Pattern bit positions == rule positions (absent patterns
        compile as never-matching placeholders via the absent mask)."""
        patterns = []
        absent = 0
        for i, rule in enumerate(device_rules):
            pattern = getattr(rule, field_name)
            if pattern == "":
                absent |= 1 << i
                patterns.append("[^\\x00-\\xff]")  # matches nothing
            else:
                patterns.append(pattern)
        dfa = compile_union(patterns, max_states=max_states)
        return dfa, _to_words(absent)

    method_dfa, absent_method = union_for("method")
    path_dfa, absent_path = union_for("path")
    host_dfa, absent_host = union_for("host")

    ident_rules = np.zeros((n_identities, n_words), dtype=np.uint32)
    for i, rule in enumerate(device_rules):
        for idx in rule.identity_indices:
            ident_rules[idx, i // 32] |= np.uint32(1 << (i % 32))

    tables = HTTPTables(
        method_sdfa=build_strided(method_dfa),
        path_sdfa=build_strided(path_dfa),
        host_sdfa=build_strided(host_dfa),
        method_dfa=method_dfa,
        path_dfa=path_dfa,
        host_dfa=host_dfa,
        absent_method=absent_method,
        absent_path=absent_path,
        absent_host=absent_host,
        ident_rules=ident_rules,
        n_rules=len(device_rules),
        n_words=n_words,
        **_header_tables(device_rules),
    )
    return HTTPPolicy(tables=tables, device_rules=device_rules)


# ---------------------------------------------------------------------------
# device kernel
# ---------------------------------------------------------------------------


@dataclass
class StridedDFA:
    """A DFA squared k times: one scan step consumes 2^k bytes.

    The sequential byte-at-a-time scan is the HTTP path's cost center;
    squaring the transition table — with column deduplication between
    rounds and an artificial identity class so padding can never move
    the state — divides the step count by the stride.  The union DFAs
    here are tiny (tens of states), so the tables stay kilobytes.

    Level map l takes a pair of level-(l-1) classes to a level-l
    class.  The device evaluation maps byte values to level-0 classes
    and folds pairs level by level BEFORE the scan — and because every
    lookup's table is small, the folds run as one-hot × table matmuls
    on the MXU (measured 5-35× faster than XLA's gather lowering for
    K ≤ ~2k on v5e; gathers cost ~5-9 ns/element, the systolic array
    ~0.4 ns) — then scans the remaining positions with the transition
    table OF THAT LEVEL (level_trans[d], retained per round)."""

    classes: np.ndarray  # byte → level-0 class (identity class added)
    id_class0: int
    base_trans: np.ndarray  # [S, nc0] incl identity column (level 0)
    level_maps: List[np.ndarray]  # [nc_prev * nc_prev] → class id
    level_ncs: List[int]  # nc INPUT of each level
    level_ids: List[int]  # identity class id at each level OUTPUT
    # transition table AFTER each level (level_trans[k] pairs with a
    # class sequence folded through level_maps[:k+1]); the MXU scan
    # picks its fold depth by table size, so every depth's table is
    # kept (they are kilobytes)
    level_trans: List[np.ndarray]
    trans: np.ndarray  # [S, nc_final] == level_trans[-1]
    start: int
    accept: np.ndarray


# one-hot×table matmul beats XLA's gather lowering up to roughly this
# table size on v5e (measured crossover ~2-4k; gathers win above)
MXU_LOOKUP_MAX_K = 2048


def build_strided(
    dfa: DFA, rounds: int = 4, max_table_bytes: int = 1 << 22
) -> "Optional[StridedDFA]":
    """Square the transition table `rounds` times (stride 2^rounds),
    deduping equivalent columns between rounds and carrying an
    identity class for padding."""
    trans = dfa.trans.astype(np.int64)
    s_count, nc = trans.shape
    # identity column: padding bytes leave the state unchanged, so a
    # stride group that crosses the end of the string is exact
    trans = np.concatenate(
        [trans, np.arange(s_count, dtype=np.int64)[:, None]], axis=1
    )
    id_class = nc
    nc += 1
    base_trans = trans.astype(np.int32)

    level_maps: List[np.ndarray] = []
    level_ncs: List[int] = []
    level_ids: List[int] = []
    level_trans: List[np.ndarray] = []
    cur_id = id_class
    for _ in range(rounds):
        if s_count * nc * nc * 8 > max_table_bytes:
            break
        # T2[s, c1, c2] = trans[trans[s, c1], c2]
        t2 = trans[trans, :]  # t2[s, c1, c2] = trans[trans[s, c1], c2]
        flat = t2.reshape(s_count, nc * nc)
        cols, inverse = np.unique(flat.T, axis=0, return_inverse=True)
        level_maps.append(inverse.astype(np.int32))
        level_ncs.append(nc)
        trans = cols.T.astype(np.int64)  # [S, n_unique]
        cur_id = int(inverse[cur_id * nc + cur_id])
        level_ids.append(cur_id)
        level_trans.append(trans.astype(np.int32))
        nc = trans.shape[1]

    if not level_maps:
        # squaring never fit the budget: no strided form — callers
        # use the byte-at-a-time scan
        return None

    return StridedDFA(
        classes=dfa.classes.astype(np.int32),
        id_class0=id_class,
        base_trans=base_trans,
        level_maps=level_maps,
        level_ncs=level_ncs,
        level_ids=level_ids,
        level_trans=level_trans,
        trans=level_trans[-1],
        start=dfa.start,
        accept=dfa.accept,
    )


def _mxu_lookup(idx, table: np.ndarray):
    """Integer table lookup lowered as one-hot(idx) × table on the
    MXU instead of a gather (the gather lowering on TPU costs ~5-9 ns
    PER ELEMENT; the matmul streams at systolic-array rate).  Exact:
    the one-hot operand is 0/1, bf16 represents integers ≤ 256
    exactly, and tables with larger values split into lo/hi byte
    planes recombined after the f32-accumulated dot."""
    import jax
    import jax.numpy as jnp

    k = table.shape[0]
    iota = jnp.arange(k, dtype=jnp.int32)
    oh = (idx[..., None] == iota).astype(jnp.bfloat16)
    dims = (((oh.ndim - 1,), (0,)), ((), ()))

    def dot(vals: np.ndarray):
        return jax.lax.dot_general(
            oh,
            jnp.asarray(vals.astype(np.float32), jnp.bfloat16),
            dims,
            preferred_element_type=jnp.float32,
        )

    if int(table.max(initial=0)) <= 256:
        out = dot(table)
    else:
        out = dot(table % 256) + 256.0 * dot(table // 256)
    return out.astype(jnp.int32)


def _dfa_scan_strided(sdfa: StridedDFA, data, lengths):
    """[B, L] u8 → accept bitmask.  Positions past the string length
    become the identity class before the level folding, so padding is
    state-neutral by construction.  Byte-classing and the small-table
    pair folds run on the MXU (_mxu_lookup); folding stops at the
    first level whose pair table exceeds MXU_LOOKUP_MAX_K, and the
    remaining positions scan sequentially with that level's
    transition table (scan-step gathers are the one gather shape that
    stays cheap: [B] elements per step)."""
    import jax
    import jax.numpy as jnp

    b, l = data.shape
    pos = jnp.arange(l, dtype=jnp.int32)
    p = jnp.where(
        pos[None, :] < lengths[:, None],
        data.astype(jnp.int32),
        jnp.int32(256),  # pad pseudo-byte
    )
    # byte → level-0 class on the MXU (K = 257)
    classes_e = np.concatenate(
        [sdfa.classes.astype(np.int64), [sdfa.id_class0]]
    )
    c = _mxu_lookup(p, classes_e)  # [B, L]
    pad_id = sdfa.id_class0

    depth = -1
    for k, (pair_map, nc_in, out_id) in enumerate(
        zip(sdfa.level_maps, sdfa.level_ncs, sdfa.level_ids)
    ):
        if nc_in * nc_in > MXU_LOOKUP_MAX_K:
            break
        if c.shape[1] % 2:
            c = jnp.concatenate(
                [c, jnp.full((b, 1), pad_id, jnp.int32)], axis=1
            )
        c = _mxu_lookup(
            c[:, 0::2] * nc_in + c[:, 1::2], pair_map
        )  # [B, L/2]
        pad_id = out_id
        depth = k

    # scan with the transition table of the deepest folded level
    # (base table when even the first pair map exceeded the budget —
    # a pathological byte-class count; the scan is then per-byte)
    trans = jnp.asarray(
        sdfa.base_trans if depth < 0 else sdfa.level_trans[depth]
    )
    nc_final = trans.shape[1]
    flat = trans.reshape(-1)
    state0 = jnp.full((b,), sdfa.start, dtype=jnp.int32)

    def step(state, col):
        return flat[state * nc_final + col], None

    cols = jnp.moveaxis(c, 1, 0)  # [L', B]
    state, _ = jax.lax.scan(step, state0, cols)
    return jnp.asarray(sdfa.accept)[state]


def _dfa_scan(dfa: DFA, data, lengths):
    """Step a [B, L] u8 byte tensor through the DFA; returns accept
    bitmask u32 [B, W].  One [B]-gather per position via lax.scan — the
    'dense take_along_axis stepping' of SURVEY §7 step 3."""
    import jax
    import jax.numpy as jnp

    trans = jnp.asarray(dfa.trans.astype(np.int32))
    classes = jnp.asarray(dfa.classes.astype(np.int32))
    accept = jnp.asarray(dfa.accept)
    n_classes = trans.shape[1]
    flat = trans.reshape(-1)

    b, l = data.shape
    state0 = jnp.full((b,), dfa.start, dtype=jnp.int32)

    def step(state, inputs):
        byte_col, pos = inputs
        c = classes[byte_col.astype(jnp.int32)]
        nxt = flat[state * n_classes + c]
        state = jnp.where(pos < lengths, nxt, state)
        return state, None

    cols = jnp.moveaxis(data, 1, 0)  # [L, B]
    state, _ = jax.lax.scan(
        step, state0, (cols, jnp.arange(l, dtype=jnp.int32))
    )
    return accept[state]


def _header_fail(tables: HTTPTables, headers):
    """u32 [B, Wh]: the header rules each request fails — some header
    constraint of the rule has no staged pair that satisfies it.
    `headers` = (name ids, pair ids) u32 [B, H] (pad_headers); None
    means no request carries a header."""
    import jax
    import jax.numpy as jnp

    want_name = jnp.asarray(tables.hdr_name)[None, None, :]  # [1,1,C]
    want_pair = jnp.asarray(tables.hdr_pair)[None, None, :]
    if headers is None:
        sat = jnp.zeros((1, want_name.shape[-1]), bool)
    else:
        names, pairs = (jnp.asarray(a)[:, :, None] for a in headers)
        sat = jnp.any(
            jnp.where(want_pair == 0, names == want_name, pairs == want_pair),
            axis=1,
        )  # [B, C]
    need = jnp.where(
        sat[:, :, None], jnp.uint32(0), jnp.asarray(tables.hdr_rules)[None]
    )  # [B, C, Wh]
    return jax.lax.reduce(need, jnp.uint32(0), jax.lax.bitwise_or, (1,))


def evaluate_http_batch(
    tables: HTTPTables,
    method: "np.ndarray",  # u8 [B, Lm]
    method_len: "np.ndarray",  # i32 [B]
    path: "np.ndarray",
    path_len: "np.ndarray",
    host: "np.ndarray",
    host_len: "np.ndarray",
    ident_idx: "np.ndarray",  # i32 [B] identity index (from engine._index)
    known: "np.ndarray",  # bool [B]
    scope_bits=None,  # u32 [B, W] per-flow rule-scope mask (fleet mode)
    headers=None,  # (name ids, pair ids) u32 [B, H] from pad_headers
):
    """Returns (allowed bool [B], matched_rules u32 [B, W])."""
    import jax.numpy as jnp

    def scan(dfa, sdfa, data, lens):
        if sdfa is not None:
            return _dfa_scan_strided(sdfa, data, lens)
        return _dfa_scan(dfa, data, lens)

    acc_m = scan(
        tables.method_dfa, tables.method_sdfa, method, method_len
    )  # [B, W]
    acc_p = scan(tables.path_dfa, tables.path_sdfa, path, path_len)
    acc_h = scan(tables.host_dfa, tables.host_sdfa, host, host_len)

    matched = (
        (acc_m | jnp.asarray(tables.absent_method)[None, :])
        & (acc_p | jnp.asarray(tables.absent_path)[None, :])
        & (acc_h | jnp.asarray(tables.absent_host)[None, :])
    )
    if tables.hdr_rules.shape[0]:
        fail = _header_fail(tables, headers)
        pad = matched.shape[1] - fail.shape[1]
        matched = matched & ~jnp.pad(fail, ((0, 0), (0, pad)))
    ident_bits = jnp.asarray(tables.ident_rules)[
        jnp.clip(ident_idx, 0, tables.ident_rules.shape[0] - 1)
    ]  # [B, W]
    matched = matched & ident_bits & jnp.where(
        known, jnp.uint32(0xFFFFFFFF), jnp.uint32(0)
    )[:, None]
    if scope_bits is not None:
        matched = matched & scope_bits
    return jnp.any(matched != 0, axis=1), matched


# ---------------------------------------------------------------------------
# host oracle + fallback
# ---------------------------------------------------------------------------


def http_rule_matches_host(
    rule: HTTPRuleSpec,
    method: bytes,
    path: bytes,
    host: bytes,
    headers: Optional[Dict[str, str]] = None,
) -> bool:
    """Host reference matcher (Python re.fullmatch ≙ Envoy regex
    HeaderMatcher full-match).  `headers` maps lower-cased names to
    values."""
    import re

    if rule.method and not re.fullmatch(
        rule.method.encode(), method, re.DOTALL
    ):
        return False
    if rule.path and not re.fullmatch(rule.path.encode(), path, re.DOTALL):
        return False
    if rule.host and not re.fullmatch(rule.host.encode(), host, re.DOTALL):
        return False
    for header in rule.headers:
        name, want = parse_header(header)
        got = (headers or {}).get(name)
        if got is None:
            return False
        if want is not None and got != want:
            return False
    return True


def pad_headers(
    tables: HTTPTables,
    headers: Sequence[Optional[Dict[str, str]]],
    max_pairs: int = MAX_HEADER_PAIRS,
):
    """Per-request header dicts (lower-cased name → value; None = no
    headers) → (name ids u32 [B, H], pair ids u32 [B, H], overflow bool
    [B]), interned against the policy's header literals.  Only headers
    whose name the policy names are staged: any other can satisfy no
    constraint.  A named header with a value no rule names stages its
    name id and pair id 0.  A request with more than `max_pairs` named
    headers is flagged `overflow` and its device verdict must be
    discarded."""
    b = len(headers)
    names = np.zeros((b, max_pairs), np.uint32)
    pairs = np.zeros((b, max_pairs), np.uint32)
    overflow = np.zeros(b, bool)
    for i, hdrs in enumerate(headers):
        items = [
            (tables.header_names[name],
             tables.header_pairs.get((name, value), 0))
            for name, value in (hdrs or {}).items()
            if name in tables.header_names
        ]
        overflow[i] = len(items) > max_pairs
        for j, (nid, pid) in enumerate(items[:max_pairs]):
            names[i, j], pairs[i, j] = nid, pid
    return names, pairs, overflow


def pad_requests(
    requests: Sequence[Tuple[bytes, bytes, bytes]],
    lm: int = 16,
    lp: int = 128,
    lh: int = 64,
):
    """(method, path, host) bytes → padded u8 tensors + lengths +
    overflow flags.

    A field longer than its budget is NOT silently truncated into the
    tensors-with-shorter-length (that would corrupt full-match
    semantics in both directions); the row is flagged `overflow` and
    must be decided again whole (evaluate_with_host_fallback on the
    host, l7.fleet's wide pass on the device).  The tensor row still carries the truncated prefix so
    shapes stay static, but its device verdict is discarded."""
    b = len(requests)
    method = np.zeros((b, lm), dtype=np.uint8)
    path = np.zeros((b, lp), dtype=np.uint8)
    host = np.zeros((b, lh), dtype=np.uint8)
    lens = np.zeros((3, b), dtype=np.int32)
    overflow = np.zeros(b, dtype=bool)
    for i, (m, p, h) in enumerate(requests):
        overflow[i] = len(m) > lm or len(p) > lp or len(h) > lh
        m, p, h = m[:lm], p[:lp], h[:lh]
        method[i, : len(m)] = np.frombuffer(m, dtype=np.uint8)
        path[i, : len(p)] = np.frombuffer(p, dtype=np.uint8)
        host[i, : len(h)] = np.frombuffer(h, dtype=np.uint8)
        lens[0, i], lens[1, i], lens[2, i] = len(m), len(p), len(h)
    return method, lens[0], path, lens[1], host, lens[2], overflow


def trim_packed(
    data: "np.ndarray", lengths: "np.ndarray", min_width: int = 8
) -> "np.ndarray":
    """Slice a padded [B, L] byte tensor down to the smallest
    power-of-two column count covering every row's actual length.
    The DFA scans cost per PROCESSED byte (pad positions fold through
    the identity class but still pay their gathers/matmuls), so a
    batch of short requests should not pay the full field budget.
    Pow2 buckets keep the jit cache small."""
    data = np.asarray(data)
    need = int(np.max(lengths, initial=0))
    width = min_width
    while width < need:
        width *= 2
    return data[:, : min(width, data.shape[1])]


def evaluate_with_host_fallback(
    policy: HTTPPolicy,
    requests: Sequence[Tuple[bytes, bytes, bytes]],
    ident_idx: "np.ndarray",  # i32 [B] identity index
    known: "np.ndarray",  # bool [B]
    headers: Optional[Sequence[Optional[Dict[str, str]]]] = None,
    lm: int = 16,
    lp: int = 128,
    lh: int = 64,
) -> np.ndarray:
    """Full HTTP policy verdict: device DFAs and header tables, with
    overflow rows (fields beyond the padded budgets, or more header
    pairs than staged) re-decided host-side from policy.device_rules
    by re.fullmatch and the header checks — never from truncated
    bytes.  Unknown identities stay denied.  Returns allowed bool [B].
    """
    packed = pad_requests(requests, lm=lm, lp=lp, lh=lh)
    m, mlen, p, plen, h, hlen, overflow = packed
    hdrs = list(headers) if headers is not None else [None] * len(requests)
    names, pairs, hdr_overflow = pad_headers(policy.tables, hdrs)
    allowed_dev, _ = evaluate_http_batch(
        policy.tables,
        trim_packed(m, mlen), mlen,
        trim_packed(p, plen), plen,
        trim_packed(h, hlen), hlen,
        ident_idx, known,
        headers=(names, pairs),
    )
    allowed = np.asarray(allowed_dev).copy()
    ident_idx = np.asarray(ident_idx)
    known = np.asarray(known)
    for i in np.nonzero(overflow | hdr_overflow)[0]:
        mm, pp, hh = requests[i]
        allowed[i] = bool(known[i]) and any(
            int(ident_idx[i]) in spec.identity_indices
            and http_rule_matches_host(spec, mm, pp, hh, hdrs[i])
            for spec in policy.device_rules
        )
    return allowed
