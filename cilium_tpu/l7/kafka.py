"""Kafka L7 policy: field-equality rule matching as tensor ops.

Behavioral port of /root/reference/pkg/kafka/policy.go:
  - RequestMessage.MatchesRule (policy.go:200): a request is allowed
    if a topic-less (or topic-free-request) rule matches, OR if every
    topic of the request is covered by some matching rule naming it —
    "all topics must be allowed";
  - ruleMatches (policy.go:144): APIKey/Role set membership, exact
    APIVersion (wildcard when unset), ClientID exact (only for the
    request structs that carry one — ConsumerMetadata and unknown
    kinds skip the check, policy.go:182-195);
  - matchNonTopicRequests (policy.go:54): an unparsed request can
    never satisfy a topic rule if its API key is topic-typed; its
    ClientID is NOT checked (reference TODO GH-3097 — reproduced).

Strings (client ids, topics) are interned host-side to u32 ids, so the
device work is pure integer equality over [B, R] / [B, T, R] tensors
— the "easy tensor case" of SURVEY.md §7 step 7.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

# Sanity ceiling only (ident_rules masks are multi-word; base matrix
# is [B, R] regardless) — not a semantic limit.
MAX_RULES = 4096
MAX_TOPICS = 8  # topics per request tensor row (excess → host path)

# api/kafka.go:110-133 — API keys whose REQUEST carries topics.
TOPIC_API_KEYS = frozenset(
    [0, 1, 2, 3, 4, 5, 6, 8, 9, 10, 11, 19, 20, 21, 23, 24, 27, 28,
     34, 35, 37]
)

# Request kinds whose parsed struct carries a checked ClientID
# (policy.go:71-130: Produce/Fetch/Offset/Metadata/OffsetCommit/
# OffsetFetch).
CLIENT_CHECKED_KINDS = frozenset([0, 1, 2, 3, 8, 9])


class Interner:
    """Host-side string → dense u32 id (0 reserved for 'absent')."""

    def __init__(self) -> None:
        self._ids: Dict[str, int] = {}

    def intern(self, s: str) -> int:
        if s == "":
            return 0
        got = self._ids.get(s)
        if got is None:
            got = len(self._ids) + 1
            self._ids[s] = got
        return got

    def lookup(self, s: str) -> int:
        """0 when unseen — an unseen string can never equal a rule's."""
        return self._ids.get(s, 0) if s else 0


@dataclass
class KafkaRequest:
    """A parsed request (pkg/kafka/request.go RequestMessage)."""

    kind: int  # api key int16
    version: int
    client_id: str = ""
    topics: Tuple[str, ...] = ()
    parsed: bool = True  # False ⇒ only the generic header was parsed


@dataclass
class KafkaRuleSpec:
    """One (selector-scope, PortRuleKafka) pair, pre-resolved."""

    identity_indices: Sequence[int]
    api_keys: Tuple[int, ...] = ()  # empty = wildcard (post Role expand)
    api_version: Optional[int] = None  # None = wildcard
    client_id: str = ""
    topic: str = ""
    # fleet-scoped compiles (l7/fleet.py): rules merge only within
    # one (endpoint, direction, L4 slot) scope
    scope_key: "object" = None


@dataclass
class KafkaTables:
    """Device tables for one (endpoint, port, direction) Kafka filter."""

    rule_keys_lo: np.ndarray  # u32 [R] api keys 0-31 bitmask
    rule_keys_hi: np.ndarray  # u32 [R] api keys 32-63
    rule_keys_any: np.ndarray  # u8 [R] wildcard
    rule_version: np.ndarray  # i32 [R]; -1 = wildcard
    rule_client: np.ndarray  # u32 [R]; 0 = wildcard
    rule_topic: np.ndarray  # u32 [R]; 0 = wildcard
    ident_rules: np.ndarray  # u32 [N, W] per-identity rule bits
    n_rules: int
    interner: Interner = field(default_factory=Interner)
    # Deduped specs retained for the host path (requests with more
    # topics than the tensor rows hold re-run MatchesRule host-side).
    specs: List[KafkaRuleSpec] = field(default_factory=list)


def rule_spec_from_port_rule(rule, identity_indices) -> KafkaRuleSpec:
    """PortRuleKafka (sanitized) → spec."""
    return KafkaRuleSpec(
        identity_indices=identity_indices,
        api_keys=tuple(rule.api_key_int),
        api_version=rule.api_version_int,
        client_id=rule.client_id,
        topic=rule.topic,
    )


def _dedupe_specs(specs: Sequence[KafkaRuleSpec]) -> List[KafkaRuleSpec]:
    """Specs with identical match fields are one device rule with the
    union of their identity sets (allowed = OR over rules) — collapses
    the per-selector allow-all pseudo-rules that L3-only rules
    wildcard into every L7 filter (repository.go:170)."""
    merged: Dict[tuple, set] = {}
    order: List[tuple] = []
    for spec in specs:
        key = (
            tuple(sorted(spec.api_keys)),
            spec.api_version,
            spec.client_id,
            spec.topic,
            spec.scope_key,
        )
        if key not in merged:
            merged[key] = set()
            order.append(key)
        merged[key].update(spec.identity_indices)
    return [
        KafkaRuleSpec(
            identity_indices=sorted(merged[key]),
            api_keys=key[0],
            api_version=key[1],
            client_id=key[2],
            topic=key[3],
            scope_key=key[4],
        )
        for key in order
    ]


def compile_kafka_rules(
    specs: Sequence[KafkaRuleSpec], n_identities: int
) -> KafkaTables:
    specs = _dedupe_specs(specs)
    if len(specs) > MAX_RULES:
        raise ValueError(f"more than {MAX_RULES} Kafka rules per filter")
    r = max(len(specs), 1)
    n_words = max(1, -(-r // 32))
    interner = Interner()
    keys_lo = np.zeros(r, dtype=np.uint32)
    keys_hi = np.zeros(r, dtype=np.uint32)
    keys_any = np.zeros(r, dtype=np.uint8)
    version = np.full(r, -1, dtype=np.int32)
    client = np.zeros(r, dtype=np.uint32)
    topic = np.zeros(r, dtype=np.uint32)
    ident = np.zeros((n_identities, n_words), dtype=np.uint32)

    for i, spec in enumerate(specs):
        if not spec.api_keys:
            keys_any[i] = 1
        for k in spec.api_keys:
            if k < 32:
                keys_lo[i] |= np.uint32(1 << k)
            elif k < 64:
                keys_hi[i] |= np.uint32(1 << (k - 32))
            else:
                raise ValueError(f"api key {k} out of range")
        if spec.api_version is not None:
            version[i] = spec.api_version
        client[i] = interner.intern(spec.client_id)
        topic[i] = interner.intern(spec.topic)
        for idx in spec.identity_indices:
            ident[idx, i // 32] |= np.uint32(1 << (i % 32))

    return KafkaTables(
        rule_keys_lo=keys_lo,
        rule_keys_hi=keys_hi,
        rule_keys_any=keys_any,
        rule_version=version,
        rule_client=client,
        rule_topic=topic,
        ident_rules=ident,
        n_rules=len(specs),
        interner=interner,
        specs=list(specs),
    )


def pad_kafka_requests(
    tables: KafkaTables,
    requests: Sequence[KafkaRequest],
    max_topics: int = MAX_TOPICS,
):
    """Requests → integer tensors (strings resolved via the tables'
    interner; unseen strings become 0 ≠ any rule value).

    A request with more unique topics than the tensor row holds
    (`max_topics`) is
    FLAGGED `overflow` (last return) — its device verdict must be
    discarded and the request re-run through matches_rules_host
    (evaluate_with_host_fallback does this)."""
    b = len(requests)
    kind = np.zeros(b, dtype=np.int32)
    version = np.zeros(b, dtype=np.int32)
    client = np.zeros(b, dtype=np.uint32)
    topics = np.zeros((b, max_topics), dtype=np.uint32)
    # Sentinel for "no topic in this slot": topic ids are ≥1, and
    # 0xFFFFFFFF never equals an interned id.
    topics[:] = 0xFFFFFFFF
    topic_count = np.zeros(b, dtype=np.int32)
    parsed = np.zeros(b, dtype=bool)
    checks_client = np.zeros(b, dtype=bool)
    overflow = np.zeros(b, dtype=bool)
    for i, request in enumerate(requests):
        kind[i] = request.kind
        version[i] = request.version
        client[i] = tables.interner.lookup(request.client_id)
        # MatchesRule dedupes topics via reqTopicsMap (policy.go:205)
        uniq = list(dict.fromkeys(request.topics))
        if len(uniq) > max_topics:
            overflow[i] = True
            uniq = uniq[:max_topics]
        for j, t in enumerate(uniq):
            topics[i, j] = tables.interner.lookup(t)
        topic_count[i] = len(uniq)
        parsed[i] = request.parsed
        checks_client[i] = request.parsed and (
            request.kind in CLIENT_CHECKED_KINDS
        )
    return (
        kind, version, client, topics, topic_count, parsed,
        checks_client, overflow,
    )


def evaluate_with_host_fallback(
    tables: KafkaTables,
    requests: Sequence[KafkaRequest],
    ident_idx,
    known,
) -> np.ndarray:
    """Full Kafka verdict: device tensors + host re-run for requests
    whose topic list exceeds the tensor rows.  Returns allowed bool [B]."""
    packed = pad_kafka_requests(tables, requests)
    overflow = packed[-1]
    allowed = np.asarray(
        evaluate_kafka_batch(tables, *packed, ident_idx, known)
    ).copy()
    ident_idx = np.asarray(ident_idx)
    known = np.asarray(known)
    for i in np.nonzero(overflow)[0]:
        allowed[i] = bool(known[i]) and matches_rules_host(
            requests[i], tables.specs, int(ident_idx[i])
        )
    return allowed


def evaluate_kafka_batch(
    tables: KafkaTables,
    kind,
    version,
    client,
    topics,
    topic_count,
    parsed,
    checks_client,
    overflow,
    ident_idx,
    known,
    scope_bits=None,  # u32 [B, W] per-flow rule-scope mask (fleet mode)
):
    """Returns allowed bool [B].  Pure integer [B,R]/[B,T,R] compares.

    Rows flagged `overflow` (topic list truncated by
    pad_kafka_requests) are force-DENIED — only
    evaluate_with_host_fallback may re-run them with the full topic
    list; a direct caller dropping the flag must never see a
    truncated row allowed."""
    import jax.numpy as jnp

    keys_lo = jnp.asarray(tables.rule_keys_lo)
    keys_hi = jnp.asarray(tables.rule_keys_hi)
    keys_any = jnp.asarray(tables.rule_keys_any).astype(bool)
    rule_version = jnp.asarray(tables.rule_version)
    rule_client = jnp.asarray(tables.rule_client)
    rule_topic = jnp.asarray(tables.rule_topic)

    kind = jnp.asarray(kind)[:, None]  # [B,1]
    version = jnp.asarray(version)[:, None]
    client = jnp.asarray(client)[:, None]
    parsed_b = jnp.asarray(parsed)[:, None]
    checks_client_b = jnp.asarray(checks_client)[:, None]

    # api-key membership (CheckAPIKeyRole, kafka.go:247); negative
    # keys (structurally invalid, rejected at the wire parser) must
    # not alias into the clipped shift range — gate them out here too
    in_lo = (keys_lo[None, :] >> jnp.clip(kind, 0, 31).astype(jnp.uint32)) & 1
    in_hi = (keys_hi[None, :] >> jnp.clip(kind - 32, 0, 31).astype(jnp.uint32)) & 1
    key_ok = (kind >= 0) & (
        keys_any[None, :]
        | jnp.where(
            kind < 32, in_lo, jnp.where(kind < 64, in_hi, 0)
        ).astype(bool)
    )

    ver_ok = (rule_version[None, :] < 0) | (rule_version[None, :] == version)

    client_ok = (rule_client[None, :] == 0) | (
        rule_client[None, :] == client
    )
    # ClientID only checked for parsed structs that carry it
    # (policy.go switch); unparsed requests skip it (GH-3097 TODO).
    client_ok = client_ok | ~checks_client_b

    # matchNonTopicRequests: unparsed + topic rule + topic-typed kind
    # → rule can't match.
    is_topic_kind = jnp.isin(
        kind, jnp.asarray(sorted(TOPIC_API_KEYS), dtype=kind.dtype)
    )
    nontopic_ok = ~(
        (rule_topic[None, :] != 0) & is_topic_kind & ~parsed_b
    )

    base = key_ok & ver_ok & client_ok & nontopic_ok  # [B, R]

    ident_bits = jnp.asarray(tables.ident_rules)[
        jnp.clip(jnp.asarray(ident_idx), 0, tables.ident_rules.shape[0] - 1)
    ]  # [B, W]
    r = base.shape[1]
    word_of_rule = jnp.arange(r) // 32
    bit_of_rule = (jnp.arange(r) % 32).astype(jnp.uint32)
    rule_bit = (ident_bits[:, word_of_rule] >> bit_of_rule[None, :]) & 1
    base = base & rule_bit.astype(bool) & jnp.asarray(known)[:, None]
    if scope_bits is not None:
        scope_bit = (
            scope_bits[:, word_of_rule] >> bit_of_rule[None, :]
        ) & 1
        base = base & scope_bit.astype(bool)

    # MatchesRule: topic-less rule (or topic-less request) matching →
    # allow everything...
    topic_count_b = jnp.asarray(topic_count)[:, None]
    allow_all = jnp.any(
        base & ((rule_topic[None, :] == 0) | (topic_count_b == 0)), axis=1
    )
    # ...else every request topic must be covered by a matching rule
    # naming it.
    topics_b = jnp.asarray(topics)  # [B, T]
    covered = jnp.any(
        base[:, None, :] & (rule_topic[None, None, :] == topics_b[:, :, None]),
        axis=2,
    )  # [B, T]
    slot_active = (
        jnp.arange(topics_b.shape[1])[None, :]
        < jnp.asarray(topic_count)[:, None]
    )
    all_covered = (jnp.asarray(topic_count) > 0) & jnp.all(
        covered | ~slot_active, axis=1
    )
    return (allow_all | all_covered) & ~jnp.asarray(overflow)


# ---------------------------------------------------------------------------
# host oracle (exact MatchesRule port)
# ---------------------------------------------------------------------------


def rule_matches_host(request: KafkaRequest, spec: KafkaRuleSpec) -> bool:
    """ruleMatches (policy.go:144)."""
    if spec.api_keys and request.kind not in spec.api_keys:
        return False
    if spec.api_version is not None and spec.api_version != request.version:
        return False
    if spec.topic == "" and spec.client_id == "":
        return True
    if not request.parsed:
        # matchNonTopicRequests (policy.go:54)
        if spec.topic != "" and request.kind in TOPIC_API_KEYS:
            return False
        return True
    if request.kind in CLIENT_CHECKED_KINDS:
        if spec.client_id != "" and spec.client_id != request.client_id:
            return False
        return True
    # ConsumerMetadataReq / default: no further checks (policy.go:183,195)
    return True


def matches_rules_host(
    request: KafkaRequest, specs: Sequence[KafkaRuleSpec],
    identity_index: Optional[int] = None,
) -> bool:
    """MatchesRule (policy.go:200), optionally identity-scoped."""
    scoped = [
        s
        for s in specs
        if identity_index is None or identity_index in s.identity_indices
    ]
    remaining = dict.fromkeys(request.topics, True)
    for spec in scoped:
        if spec.topic == "" or len(request.topics) == 0:
            if rule_matches_host(request, spec):
                return True
        elif remaining.get(spec.topic):
            if rule_matches_host(request, spec):
                del remaining[spec.topic]
                if not remaining:
                    return True
    return False
