"""Fleet-scoped L7 policy: ONE compiled matcher set for every redirect.

The reference hands each redirected flow to its proxy listener, which
enforces the L7 rules of the L4Filter that redirected it
(/root/reference/envoy/cilium_l7policy.cc:193 for HTTP,
/root/reference/pkg/proxy/kafka.go:116 for Kafka).  A per-redirect
device dispatch would cost one program launch per (endpoint, port);
instead the union DFA / field tensors span the WHOLE fleet and a
per-flow scope mask — each compiled rule lives in exactly one
(endpoint, direction, L4 slot) scope — restricts matching to the
redirecting filter's rules.  One jitted program then evaluates any mix
of redirected flows, which is what lets the replay loop run L7
verdicts inline with the fused datapath step (the combined
datapath+proxy number of BASELINE config 5).

Scope tables are indexed by the datapath's own outputs: the fused
verdict exposes the matched L4 slot (`DatapathVerdicts.l4_slot`), so a
redirected flow's scope is (ep_index, direction, l4_slot) with no
extra probes.  Every HTTP rule, header constraints included
(l7/http.py), and every Kafka rule is decided on the device.

`L7Stage` is the L7 half of the persistent launch path
(engine.datapath.PersistentPairDispatcher(l7=...)): a program of its
own, XLA module `jit_l7_program`, chained after the fused L3/L4
program in the same launch.  It reads that program's stacked outputs
on the device, takes each tuple's request from a device request table
(`pack_requests`: one row per request, addressed by a u32 request-id
plane staged beside the pairs), and returns each tuple's L7 verdict
and final verdict while it accumulates the L7 counts
(`L7_COUNTS`).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, replace
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from cilium_tpu.l7.http import (
    MAX_HEADER_PAIRS,
    HTTPPolicy,
    compile_http_rules,
    evaluate_http_batch,
    pad_headers,
    pad_requests,
    resolve_selector_indices,
    specs_from_filter,
    trim_packed,
)
from cilium_tpu.l7.kafka import (
    MAX_TOPICS,
    KafkaRequest,
    KafkaRuleSpec,
    KafkaTables,
    compile_kafka_rules,
    evaluate_kafka_batch,
    pad_kafka_requests,
    rule_spec_from_port_rule,
)
from cilium_tpu.metrics import registry as metrics
from cilium_tpu.policy.l4 import (
    PARSER_TYPE_HTTP as PARSER_HTTP,
    PARSER_TYPE_KAFKA as PARSER_KAFKA,
)

PARSER_NONE_ID = 0
PARSER_HTTP_ID = 1
PARSER_KAFKA_ID = 2


@dataclass
class FleetL7:
    """Fleet-wide compiled L7 matchers + per-(ep, dir, slot) scoping."""

    http: Optional[HTTPPolicy]
    kafka: Optional[KafkaTables]
    scope_http: np.ndarray  # u32 [E, 2, Kg, Wh] rule-scope bits
    scope_kafka: np.ndarray  # u32 [E, 2, Kg, Wk]
    parser_kind: np.ndarray  # u8 [E, 2, Kg] PARSER_*_ID


def compile_fleet_l7(daemon) -> FleetL7:
    """Walk every endpoint's desired L4 policy, collect redirect
    filters' L7 rules tagged with their (ep, dir, slot) scope, and
    compile one fleet-wide matcher set per parser."""
    id_index, n_identities = daemon.endpoint_manager.identity_index()
    _, tables, ep_index = daemon.endpoint_manager.published()
    if tables is None:
        raise ValueError("no published tables — regenerate first")
    e_count, _, kg = tables.l4_meta.shape
    port_slot = tables.port_slot  # u16 [256, 65536]
    cache = daemon.identity_cache()
    sel_cache = daemon.selector_cache

    http_specs: List = []
    kafka_specs: List[KafkaRuleSpec] = []
    parser_kind = np.zeros((e_count, 2, kg), np.uint8)

    from cilium_tpu.compiler.tables import NO_SLOT

    for ep in daemon.endpoint_manager.endpoints():
        e = ep_index.get(ep.id)
        l4pol = ep.desired_l4_policy
        if e is None or l4pol is None:
            continue
        for dirv, pmap in ((0, l4pol.ingress), (1, l4pol.egress)):
            for l4 in pmap.values():
                if not l4.is_redirect():
                    continue
                j = int(port_slot[l4.u8proto & 0xFF, l4.port])
                if j == int(NO_SLOT):
                    continue  # filter not realized in the slot space
                scope = (e, dirv, j)
                if l4.l7_parser == PARSER_KAFKA:
                    parser_kind[e, dirv, j] = PARSER_KAFKA_ID
                    for selector, l7 in l4.l7_rules_per_ep.items():
                        indices = resolve_selector_indices(
                            selector, cache, id_index, sel_cache
                        )
                        rules = l7.kafka or []
                        if not rules:
                            kafka_specs.append(
                                KafkaRuleSpec(
                                    identity_indices=indices,
                                    scope_key=scope,
                                )
                            )
                        for rule in rules:
                            kafka_specs.append(
                                replace(
                                    rule_spec_from_port_rule(
                                        rule, indices
                                    ),
                                    scope_key=scope,
                                )
                            )
                elif l4.l7_parser == PARSER_HTTP:
                    parser_kind[e, dirv, j] = PARSER_HTTP_ID
                    for spec in specs_from_filter(
                        l4, cache, id_index, sel_cache
                    ):
                        http_specs.append(
                            replace(spec, scope_key=scope)
                        )
                # generic proxylib parsers stay on their per-redirect
                # wire path (l7/proxylib.py); the fleet fast path
                # covers the two tensorized protocols

    http = (
        compile_http_rules(http_specs, n_identities)
        if http_specs
        else None
    )
    kafka = (
        compile_kafka_rules(kafka_specs, n_identities)
        if kafka_specs
        else None
    )

    def scope_table(rules, n_rules) -> np.ndarray:
        w = max(1, -(-max(n_rules, 1) // 32))
        table = np.zeros((e_count, 2, kg, w), np.uint32)
        for r, spec in enumerate(rules):
            if spec.scope_key is None:
                continue
            e, dirv, j = spec.scope_key
            table[e, dirv, j, r // 32] |= np.uint32(1 << (r % 32))
        return table

    scope_http = scope_table(
        http.device_rules if http else [], http.tables.n_rules if http else 0
    )
    scope_kafka = scope_table(
        kafka.specs if kafka else [], kafka.n_rules if kafka else 0
    )
    return FleetL7(
        http=http,
        kafka=kafka,
        scope_http=scope_http,
        scope_kafka=scope_kafka,
        parser_kind=parser_kind,
    )


def evaluate_fleet_l7(
    fleet: FleetL7,
    ep_index,  # i32 [B]
    direction,  # i32 [B]
    l4_slot,  # i32 [B] from DatapathVerdicts.l4_slot
    ident_idx,  # i32 [B]
    known,  # bool [B]
    http_fields: Optional[Tuple] = None,  # (m, ml, p, pl, h, hl)
    kafka_fields: Optional[Tuple] = None,  # pad_kafka_requests order
    http_headers: Optional[Tuple] = None,  # pad_headers (names, pairs)
):
    """L7 verdicts for a batch of redirected flows (traced; call
    inside a jit).  Returns allowed bool [B]: flows whose scope has no
    parser are denied (a redirect with no compiled policy must fail
    closed, as the proxy denies without a NetworkPolicy)."""
    import jax.numpy as jnp

    lin, kind = scope_kind(fleet, ep_index, direction, l4_slot)
    allowed = jnp.zeros(ep_index.shape, bool)
    if fleet.http is not None and http_fields is not None:
        ok, _ = evaluate_http_batch(
            fleet.http.tables, *http_fields, ident_idx, known,
            scope_bits=scope_rows(fleet.scope_http, lin),
            headers=http_headers,
        )
        allowed = jnp.where(kind == PARSER_HTTP_ID, ok, allowed)
    if fleet.kafka is not None and kafka_fields is not None:
        ok = evaluate_kafka_batch(
            fleet.kafka, *kafka_fields, ident_idx, known,
            scope_bits=scope_rows(fleet.scope_kafka, lin),
        )
        allowed = jnp.where(kind == PARSER_KAFKA_ID, ok, allowed)
    return allowed


def scope_kind(fleet, ep_index, direction, l4_slot):
    """(lin, kind): each flow's flat (ep, dir, slot) scope index into
    the [E, 2, Kg, ...] scope tables, and its scope's PARSER_*_ID."""
    import jax.numpy as jnp

    _, _, kg = fleet.parser_kind.shape
    lin = (
        jnp.asarray(ep_index).astype(jnp.int32) * (2 * kg)
        + jnp.asarray(direction).astype(jnp.int32) * kg
        + jnp.clip(l4_slot, 0, kg - 1)
    )
    return lin, jnp.asarray(fleet.parser_kind).reshape(-1)[lin]


def scope_rows(table, lin):
    """The rule-scope bits u32 [B, W] of each flow's (ep, dir, slot)
    scope, `lin` its flat index into the [E, 2, Kg, W] table."""
    import jax.numpy as jnp

    return jnp.asarray(table).reshape(-1, table.shape[-1])[lin]


# ---------------------------------------------------------------------------
# the L7 stage of the persistent launch path
# ---------------------------------------------------------------------------

# the device's L7 counts, in order: the first three fold into
# metrics.policy_l7_total{rule=...} at the dispatcher's flush;
# `overflow` counts the redirected tuples whose request was over the
# field budgets (decided exactly all the same: fleet_l7_program)
L7_COUNTS = ("received", "forwarded", "denied", "overflow")
# the matchers, in the order of L7Verdicts.decided: the tuples each
# decided (the redirected tuples whose scope has its parser), folded
# into metrics.policy_l7_matcher_tuples_total{parser=...} at the
# dispatcher's flush
L7_MATCHERS = ("http", "kafka")
# tuples evaluated per step of a matcher's loop over a launch: the
# per-request tensors of one step ([chunk, field bytes, ...]) stay a
# small share of device memory
L7_CHUNK = 16384
# arrays of the compiled fleet at least this large are arguments of the
# L7 program, not constants folded into it
_ARG_BYTES = 1 << 16
_HTTP_COLS = ("method", "method_len", "path", "path_len", "host",
              "host_len")
_KAFKA_COLS = ("kind", "version", "client", "topics", "topic_count",
               "parsed", "checks_client", "kafka_overflow")


def _cover(n: int, least: int = 1) -> int:
    """The smallest power of two of at least max(n, least)."""
    width = least
    while width < n:
        width *= 2
    return width


def _pack(fleet, http, headers, kafka, lm, lp, lh, pairs, topics):
    """(request table columns, overflow bool [B]) at the given
    widths; the byte fields cut to the power of two that covers the
    longest request within them."""
    m, ml, p, pl, h, hl, overflow = pad_requests(http, lm=lm, lp=lp, lh=lh)
    if fleet.http is not None:
        names, pair_ids, hdr_over = pad_headers(
            fleet.http.tables, headers, max_pairs=pairs)
    else:  # no policy names a header: nothing to stage
        names = pair_ids = np.zeros((len(http), pairs), np.uint32)
        hdr_over = np.zeros(len(http), bool)
    kf = pad_kafka_requests(fleet.kafka or compile_kafka_rules([], 1),
                            kafka, max_topics=topics)
    fits = ~overflow
    cols = dict(zip(_HTTP_COLS, (
        trim_packed(m, ml[fits]), ml, trim_packed(p, pl[fits]), pl,
        trim_packed(h, hl[fits]), hl,
    )))
    cols.update(zip(_KAFKA_COLS, (np.asarray(x) for x in kf)))
    cols.update(hname=names, hpair=pair_ids)
    return cols, overflow | hdr_over | np.asarray(kf[-1])


def pack_requests(
    fleet: FleetL7,
    http: Sequence[Tuple[bytes, bytes, bytes]],
    headers: Sequence[Optional[Dict[str, str]]],
    kafka: Sequence[KafkaRequest],
    lm: int = 16,
    lp: int = 128,
    lh: int = 64,
) -> Dict[str, object]:
    """The request table: row i holds request i's HTTP fields
    (pad_requests), the headers of the names the fleet's policy names,
    interned (pad_headers), and its Kafka fields interned against the
    fleet's Kafka strings (pad_kafka_requests), at the field budgets
    (lm, lp, lh, MAX_HEADER_PAIRS, kafka.MAX_TOPICS).  `overflow` marks
    a request over any of them.  Such a request is never decided from
    truncated fields: `wide` holds the overflowing requests again, at
    widths that cover them whole, and `wide_row` gives each row's
    place there (0 where it fits).  Each byte field is cut to the
    power of two that covers its longest request: positions past a
    request's length leave the DFA state unchanged.  The three lists
    are aligned; a row whose flow is of one protocol carries empty
    fields of the other."""
    cols, overflow = _pack(fleet, http, headers, kafka, lm, lp, lh,
                           MAX_HEADER_PAIRS, MAX_TOPICS)
    out: Dict[str, object] = dict(cols, overflow=overflow)
    rows = np.nonzero(overflow)[0]
    out["wide_row"] = np.zeros(len(http), np.int32)
    if len(rows):
        out["wide_row"][rows] = np.arange(len(rows), dtype=np.int32)
        named = (fleet.http.tables.header_names if fleet.http is not None
                 else {})
        http_w = [http[i] for i in rows]
        headers_w = [headers[i] for i in rows]
        kafka_w = [kafka[i] for i in rows]
        width = [_cover(max(len(r[f]) for r in http_w), 8) for f in range(3)]
        wide, wide_over = _pack(
            fleet, http_w, headers_w, kafka_w, *width,
            _cover(max(sum(n in named for n in (h or {}))
                       for h in headers_w)),
            _cover(max(len(set(k.topics)) for k in kafka_w)),
        )
        assert not wide_over.any()
        out["wide"] = wide
    return out


def _swap(obj, fn):
    """`obj` (a compiled fleet, nested dataclasses) rebuilt with
    fn(value) for every field that is not itself a dataclass."""
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return replace(obj, **{
            f.name: _swap(getattr(obj, f.name), fn)
            for f in dataclasses.fields(obj) if f.init
        })
    return fn(obj)


class _Arg(NamedTuple):
    index: int


class L7Verdicts(NamedTuple):
    """The L7 outcome, u8 [..., 2, B] each (row 0 ingress, 1 egress):
    `l7_allowed` the L7 verdict of a redirected tuple (0 for a tuple
    not redirected), `allowed` the final verdict (the L3/L4 verdict,
    and for a redirected tuple also the L7 one).  `decided`, u32
    [len(L7_MATCHERS)] on the L7 program's output (None on one pair's
    slice): the tuples the HTTP and the Kafka matcher decided in that
    call."""

    l7_allowed: "object"
    allowed: "object"
    decided: "object" = None


def _decide(fleet, cols, direction, ep, slot, ident, rows,
            parsers=(PARSER_HTTP_ID, PARSER_KAFKA_ID)):
    """Each tuple's L7 verdict through its (ep, direction, slot) scope,
    its request row `rows` of the request columns `cols`, by the
    matchers of `parsers` alone (a tuple of another parser is
    denied)."""
    import jax.numpy as jnp

    http = PARSER_HTTP_ID in parsers
    kafka = PARSER_KAFKA_ID in parsers
    return evaluate_fleet_l7(
        fleet, ep, jnp.full(ep.shape, direction, jnp.int32), slot,
        ident, jnp.ones(ep.shape, bool),
        http_fields=(tuple(cols[k][rows] for k in _HTTP_COLS)
                     if http else None),
        kafka_fields=(tuple(cols[k][rows] for k in _KAFKA_COLS)
                      if kafka else None),
        http_headers=((cols["hname"][rows], cols["hpair"][rows])
                      if http else None),
    )


def _l7_direction(fleet, requests, chunk, ep, direction, slot, ident,
                  rid, red):
    """(ok bool [n], decided u32 [2]): each redirected tuple's L7
    verdict, each matcher run only over the tuples it decides.

    The redirected tuples whose scope has the HTTP parser, then those
    whose scope has the Kafka parser, are put in front of the others
    by one stable counting partition (per-class ranks by cumsum, one
    scatter of the tuple positions).  Each matcher then runs over its
    class alone in steps of `chunk` tuples, as many as its count
    needs (a fori_loop with a traced trip count: no step for an empty
    class); a step's slots past
    the count read a real tuple and are dropped.  Every other tuple is
    left False: not redirected, or redirected to a scope with no
    parser (denied, fail closed).  `decided` counts each class
    (http, kafka) whose matcher ran."""
    import jax
    import jax.numpy as jnp

    n = ep.shape[0]
    c = min(chunk, n)
    _, kind = scope_kind(fleet, ep, direction, slot)
    parsers = [p for p, compiled in ((PARSER_HTTP_ID, fleet.http),
                                     (PARSER_KAFKA_ID, fleet.kafka))
               if compiled is not None]
    ok = jnp.zeros(n, bool)
    decided = jnp.zeros(len(L7_MATCHERS), jnp.uint32)
    if not parsers:
        return ok, decided
    member = jnp.stack([red & (kind == p) for p in parsers], axis=1)
    rank = jnp.cumsum(member, axis=0, dtype=jnp.int32) - 1  # [n, P]
    count = rank[-1] + 1  # [P]
    first = jnp.cumsum(count) - count  # each class's first position
    dest = jnp.where(member.any(axis=1),
                     jnp.sum(jnp.where(member, first + rank, 0), axis=1),
                     n + c)  # n + c: dropped
    # the tuple positions class by class; c slots of padding keep the
    # last step's slice from being clamped back over its class
    order = jnp.zeros(n + c, jnp.int32).at[dest].set(
        jnp.arange(n, dtype=jnp.int32), mode="drop")

    for j, parser in enumerate(parsers):
        def step(i, ok, j=j, parser=parser):
            take = jax.lax.dynamic_slice(order, (first[j] + i * c,), (c,))
            got = _decide(fleet, requests, direction, ep[take],
                          slot[take], ident[take], rid[take], (parser,))
            real = i * c + jnp.arange(c) < count[j]
            return ok.at[jnp.where(real, take, n)].set(got, mode="drop")

        ok = jax.lax.fori_loop(0, (count[j] + c - 1) // c, step, ok)
        decided = decided.at[parser - PARSER_HTTP_ID].set(
            count[j].astype(jnp.uint32))
    return ok, decided


def _redecide(fleet, wide, chunk, ep, direction, slot, ident, rows, ok,
              pending):
    """`ok` with the tuples marked `pending` decided again from the
    request table's `wide` columns (their rows `rows`), up to `chunk`
    of them per step, until none is left (no step when none is)."""
    import jax
    import jax.numpy as jnp

    n = ep.shape[0]
    c = min(chunk, n)

    def step(state):
        ok, pending = state
        at = jnp.nonzero(pending, size=c, fill_value=n)[0]  # n: none
        take = jnp.minimum(at, n - 1)
        got = _decide(fleet, wide, direction, ep[take], slot[take],
                      ident[take], rows[take])
        return (ok.at[at].set(got, mode="drop"),
                pending.at[at].set(False, mode="drop"))

    return jax.lax.while_loop(lambda s: jnp.any(s[1]), step,
                              (ok, pending))[0]


def fleet_l7_program(fleet: FleetL7, chunk: int = L7_CHUNK):
    """(jitted program, its table arguments).

    program(tables, requests, pairs [K, 2, 4, B], outs_i, outs_e,
            counts u32 [len(L7_COUNTS)], *req_ids K x u32 [2, B])
        -> (L7Verdicts of u8 [K, 2, B], counts')

    outs_i/outs_e are the fused program's stacked DatapathVerdicts;
    `requests` is pack_requests' table on the device.  A tuple is
    redirected where its proxy_port is set; its scope is (its
    endpoint, its half's direction, its l4_slot) and its identity the
    sec_id index.  The HTTP matcher decides only the redirected tuples
    whose scope has the HTTP parser, the Kafka matcher only those
    whose scope has the Kafka parser (_l7_direction); a redirected
    tuple whose scope has no parser is denied (fail closed); a
    direction with no parser anywhere in the fleet runs no matcher.  A
    tuple whose request is over the field budgets is decided again
    from the table's `wide` columns, so every verdict is exact.
    counts gains (received, forwarded, denied, overflow) =
    (redirected, L7-allowed, redirected and not L7-allowed, redirected
    with a request over the budgets); the verdicts' `decided` holds
    the tuples the HTTP and the Kafka matcher decided in this call.
    `counts` is donated."""
    import jax
    import jax.numpy as jnp

    from cilium_tpu.engine.datapath import flow_batch_from_packed4

    args: list = []

    def lift(value):
        if isinstance(value, np.ndarray) and value.nbytes >= _ARG_BYTES:
            args.append(value)
            return _Arg(len(args) - 1)
        return value

    skeleton = _swap(fleet, lift)
    live = tuple(bool(fleet.parser_kind[:, d].any()) for d in (0, 1))

    def l7_program(tables, requests, pairs, outs_i, outs_e, counts,
                   *req_ids):
        fl = _swap(skeleton, lambda v: (
            tables[v.index] if isinstance(v, _Arg) else v))
        rid_all = jnp.stack(req_ids)  # [K, 2, B]
        l7_cols, allowed_cols = [], []
        decided_all = jnp.zeros(len(L7_MATCHERS), jnp.uint32)
        for d, outs in enumerate((outs_i, outs_e)):
            shape = outs.proxy_port.shape  # [K, B]
            rid = rid_all[:, d].reshape(-1)
            red = outs.proxy_port.reshape(-1) > 0
            flagged = red & requests["overflow"][rid]
            decided = jnp.zeros(len(L7_MATCHERS), jnp.uint32)
            if live[d]:
                ep = flow_batch_from_packed4(
                    jnp.moveaxis(pairs[:, d], 1, 0)
                ).ep_index.reshape(-1)
                slot = outs.l4_slot.reshape(-1).astype(jnp.int32)
                ident = outs.sec_id.reshape(-1).astype(jnp.int32)
                ok, decided = _l7_direction(fl, requests, chunk, ep, d,
                                            slot, ident, rid, red)
                if "wide" in requests:
                    ok = _redecide(fl, requests["wide"], chunk, ep, d,
                                   slot, ident, requests["wide_row"][rid],
                                   ok, flagged)
                l7 = red & ok
            else:
                l7 = jnp.zeros_like(red)
            allowed = outs.allowed.reshape(-1).astype(bool) & (~red | l7)
            counts = counts + jnp.stack([
                jnp.sum(x, dtype=jnp.uint32)
                for x in (red, l7, red & ~l7, flagged)
            ])
            decided_all = decided_all + decided
            l7_cols.append(l7.reshape(shape))
            allowed_cols.append(allowed.reshape(shape))
        return L7Verdicts(
            jnp.stack(l7_cols, axis=1).astype(jnp.uint8),
            jnp.stack(allowed_cols, axis=1).astype(jnp.uint8),
            decided_all,
        ), counts

    return jax.jit(l7_program, donate_argnums=(5,)), args


class L7Stage:
    """What PersistentPairDispatcher(l7=...) runs after the fused
    program: the compiled fleet's tables and the request table on the
    device, and the jitted L7 program (fleet_l7_program), whose HTTP
    matcher decides only the redirected tuples of HTTP scopes and its
    Kafka matcher only those of Kafka scopes.  Its counts are
    L7_COUNTS (received, forwarded, denied, overflow); each call's
    verdicts also carry the tuples each matcher decided
    (L7Verdicts.decided, in L7_MATCHERS order)."""

    def __init__(self, fleet: FleetL7, requests: Dict[str, object],
                 chunk: int = L7_CHUNK) -> None:
        import jax

        self.fleet = fleet
        self.program, tables = fleet_l7_program(fleet, chunk)
        self.tables = jax.device_put(tables)
        self.requests = jax.device_put(requests)
        # redirected tuples with a request over the field budgets, as
        # folded at the dispatcher's flushes
        self.overflowed = 0

    @staticmethod
    def zero_counts():
        import jax

        return jax.device_put(np.zeros(len(L7_COUNTS), np.uint32))

    def fold_counts(self, counts, decided=()) -> None:
        """Add drained counts (L7_COUNTS order) to
        metrics.policy_l7_total{rule} and `overflowed`, and the calls'
        `decided` tallies (L7_MATCHERS order) to
        metrics.policy_l7_matcher_tuples_total{parser}."""
        counts = np.asarray(counts)
        for rule, n in zip(L7_COUNTS[:3], counts):
            metrics.policy_l7_total.inc(rule, value=int(n))
        self.overflowed += int(counts[3])
        total = np.zeros(len(L7_MATCHERS), np.int64)
        for tally in decided:
            total += np.asarray(tally)
        for parser, n in zip(L7_MATCHERS, total):
            metrics.policy_l7_matcher_tuples_total.inc(parser, value=int(n))
