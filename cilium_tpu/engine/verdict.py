"""Jitted batched verdict kernel (the TPU datapath).

Computes, for every (endpoint, identity, dport, proto, direction)
tuple in a batch, the 3-probe lattice of bpf/lib/policy.h:46 against
the compiled PolicyTables — fully vectorized:

  * identity hash-probe  → one direct-table gather (id_direct);
  * L4 key hash-probe    → proto remap + (proto slot, dport) direct
    slot-table gather — O(1) instead of per-endpoint key scans;
  * per-endpoint map selection (the PROG_ARRAY tail call,
    bpf/bpf_lxc.c:1039) → gather along the endpoint axis.

Random 1M-element HBM gathers cost ~20-30 ms on TPU via XLA, so the
kernel is engineered down to 6 gathers total; see compiler/tables.py
for the fused layouts.

Everything is integer (u32/i32) — no floats anywhere near the verdict,
so device results are bit-identical to the host oracle by construction
(SURVEY.md §7 hard part 5).

The batch axis is embarrassingly parallel (packets across nodes in the
reference ≙ tuples across TPU chips): `make_sharded_evaluator` shards
it over a `jax.sharding.Mesh` with the tables replicated, which keeps
all collective traffic at zero during evaluation.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from cilium_tpu.compiler.tables import PolicyTables
from cilium_tpu.engine.oracle import (
    MATCH_FRAG_DROP,
    MATCH_L3,
    MATCH_L4,
    MATCH_L4_WILD,
    MATCH_NONE,
)


@jax.tree_util.register_pytree_node_class
@dataclass
class TupleBatch:
    """A batch of flow tuples (the SearchContext of the datapath)."""

    ep_index: jax.Array  # i32 [B] index into the endpoint axis
    identity: jax.Array  # u32 [B] src id (ingress) / dst id (egress)
    dport: jax.Array  # i32 [B] destination port, host order
    proto: jax.Array  # i32 [B] IP protocol number
    direction: jax.Array  # i32 [B] 0=ingress 1=egress
    is_fragment: jax.Array  # bool [B]

    def tree_flatten(self):
        return (
            (
                self.ep_index,
                self.identity,
                self.dport,
                self.proto,
                self.direction,
                self.is_fragment,
            ),
            None,
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @staticmethod
    def from_numpy(
        ep_index,
        identity,
        dport,
        proto,
        direction,
        is_fragment=None,
    ) -> "TupleBatch":
        """Single-transfer upload: one [6, B] u32 pack instead of six
        device_puts (see FlowBatch.from_numpy)."""
        b = len(ep_index)
        if is_fragment is None:
            is_fragment = np.zeros(b, dtype=bool)
        packed = np.empty((6, b), dtype=np.uint32)
        packed[0] = np.asarray(ep_index).astype(np.uint32, copy=False)
        packed[1] = np.asarray(identity, np.uint32)
        packed[2] = np.asarray(dport).astype(np.uint32, copy=False)
        packed[3] = np.asarray(proto).astype(np.uint32, copy=False)
        packed[4] = np.asarray(direction).astype(
            np.uint32, copy=False
        )
        packed[5] = np.asarray(is_fragment).astype(np.uint32)
        return _unpack_tuple_batch(jnp.asarray(packed))


def _tuple_batch_from_packed(packed) -> "TupleBatch":
    return TupleBatch(
        ep_index=packed[0].astype(jnp.int32),
        identity=packed[1],
        dport=packed[2].astype(jnp.int32),
        proto=packed[3].astype(jnp.int32),
        direction=packed[4].astype(jnp.int32),
        is_fragment=packed[5].astype(bool),
    )


# jitted splitter for TupleBatch.from_numpy's single-transfer pack
_unpack_tuple_batch = jax.jit(_tuple_batch_from_packed)


@jax.tree_util.register_pytree_node_class
@dataclass
class Verdicts:
    """Per-tuple results, dtype-stable for bit-compare with the oracle."""

    allowed: jax.Array  # u8 [B] 0/1
    proxy_port: jax.Array  # u16-valued i32 [B] (0 = plain allow)
    match_kind: jax.Array  # u8 [B] MATCH_* codes

    def tree_flatten(self):
        return ((self.allowed, self.proxy_port, self.match_kind), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def _index_identity(tables: PolicyTables, batch: TupleBatch):
    """Identity half of index resolution: raw u32 id → dense index
    (1 gather from the small direct table).  Returns (idx, known)."""
    from cilium_tpu.compiler.tables import LOCAL_ID_BASE, NO_INDEX

    n = tables.id_table.shape[0]
    direct_sz = tables.id_direct.shape[0]
    lo_len = tables.id_lo_len.astype(jnp.uint32)

    # id_direct is two dense regions: [0, lo_len) for cluster-scope
    # ids, [lo_len, end) for local CIDR ids offset by LOCAL_ID_BASE.
    ident = batch.identity.astype(jnp.uint32)
    is_local = ident >= jnp.uint32(LOCAL_ID_BASE)
    local_off = ident - jnp.uint32(LOCAL_ID_BASE)
    pos = jnp.where(is_local, lo_len + local_off, ident)
    in_range = jnp.where(
        is_local,
        local_off < jnp.uint32(direct_sz) - lo_len,
        ident < lo_len,
    )
    pos = jnp.minimum(pos, jnp.uint32(direct_sz - 1)).astype(jnp.int32)
    v = tables.id_direct[pos]
    known = in_range & (v != jnp.uint32(NO_INDEX))
    idx = jnp.where(known, v, jnp.uint32(n - 1)).astype(jnp.int32)
    return idx, known


def _index(tables: PolicyTables, batch: TupleBatch):
    """Index resolution: O(1) direct-table gathers only.

    Returns (idx, word, bit, known, j, has_port) — the global identity
    index / bit position and the global L4 slot of each tuple, all
    derived from small replicated tables (no touch of the big
    allow-bit tensors, so the identity-sharded path can reuse this and
    offset `word` per shard)."""
    from cilium_tpu.compiler.tables import NO_SLOT

    idx, known = _index_identity(tables, batch)
    word = idx >> 5
    bit = (idx & 31).astype(jnp.uint32)

    # -- L4 key probe: (proto, dport) → global slot (1 gather) --------------
    # port_slot is indexed by the RAW proto byte (one 65536-entry row
    # per proto, 32 MB); only the identity-sharded mesh evaluator
    # still probes through it — the single-chip kernels resolve the
    # slot from the hashed entry table's value word instead.
    proto = jnp.clip(batch.proto, 0, 255).astype(jnp.int32)
    dport = jnp.clip(batch.dport, 0, 65535).astype(jnp.int32)
    slot16 = tables.port_slot[proto, dport]
    has_port = slot16 != jnp.uint16(NO_SLOT)
    j = jnp.where(has_port, slot16, 0).astype(jnp.int32)
    return idx, word, bit, known, j, has_port


def l4hash_probe_keys(entry_words, ep, dirn, idx, dport, proto):
    """(w0, w1) probe key words for either hashed-entry layout —
    build side and probe side MUST stay one implementation.  `idx`
    may carry the L4H_WILD_IDX sentinel; the compact layout remaps it
    to its own 18-bit sentinel."""
    from cilium_tpu.compiler.tables import (
        L4C_WILD_IDX18,
        L4H_WILD_IDX,
        l4c_key0,
        l4c_key1,
        l4h_key0,
        l4h_key1,
    )

    if entry_words == 2:
        idx18 = jnp.where(
            idx == jnp.uint32(L4H_WILD_IDX),
            jnp.uint32(L4C_WILD_IDX18),
            idx.astype(jnp.uint32),
        )
        return l4c_key0(idx18, dport), l4c_key1(dport, proto, ep, dirn)
    return l4h_key0(idx, dirn, ep), l4h_key1(dport, proto, ep)


def l4hash_row_parts(rows, w0, w1, entry_words, owns=None):
    """Lane compares against pre-gathered hashed-entry rows, either
    layout, with an optional ownership mask (the routed mesh kernels
    gather each row on its owning shard only and psum these parts).
    Returns (found [B], val u32 [B]) — val is `j << 16 | proxy` in
    the 3-word layout and the bare slot index `j` in the compact one
    (decode with l4hash_value_decode)."""
    from cilium_tpu.compiler.tables import L4C_CMP_MASK

    e = rows.shape[1] // entry_words
    if entry_words == 2:
        hit = (rows[:, :e] == w0[:, None]) & (
            (rows[:, e : 2 * e] & jnp.uint32(L4C_CMP_MASK))
            == w1[:, None]
        )
        vals = (rows[:, e : 2 * e] >> jnp.uint32(19)) & jnp.uint32(
            0xFFF
        )
    else:
        hit = (rows[:, :e] == w0[:, None]) & (
            rows[:, e : 2 * e] == w1[:, None]
        )
        vals = rows[:, 2 * e : 3 * e]
    if owns is not None:
        hit = hit & owns[:, None]
    val = jnp.sum(jnp.where(hit, vals, 0), axis=1, dtype=jnp.uint32)
    return jnp.any(hit, axis=1), val


def l4hash_stash_parts(stash, w0, w1, entry_words):
    """Broadcast-compare half of the probe (the stash replicates on a
    mesh — added AFTER the row-part psum).  Same value contract as
    l4hash_row_parts."""
    from cilium_tpu.compiler.tables import L4C_CMP_MASK

    stash = jnp.asarray(stash)
    if entry_words == 2:
        s_hit = (stash[None, :, 0] == w0[:, None]) & (
            (stash[None, :, 1] & jnp.uint32(L4C_CMP_MASK))
            == w1[:, None]
        )
        vals = (stash[None, :, 1] >> jnp.uint32(19)) & jnp.uint32(
            0xFFF
        )
    else:
        s_hit = (stash[None, :, 0] == w0[:, None]) & (
            stash[None, :, 1] == w1[:, None]
        )
        vals = stash[None, :, 2]
    val = jnp.sum(
        jnp.where(s_hit, vals, 0), axis=1, dtype=jnp.uint32
    )
    return jnp.any(s_hit, axis=1), val


def l4hash_value_decode(
    tables, ep, dirn, probe1, val1, hit3, val3, entry_words
):
    """Fold the exact/wild probe values into (proxy, j) — the shared
    terminal step of every lattice probe.  The 3-word layout splits
    the matched value word; the compact layout takes the matched slot
    index and reconstructs the proxy port with ONE l4_meta element
    gather (the plane the lowering keeps bit-equal to the dropped
    per-entry copy — gated by repack_l4_subword at pack time)."""
    val = jnp.where(probe1, val1, val3)
    if entry_words == 3:
        return (
            (val & jnp.uint32(0xFFFF)).astype(jnp.int32),
            (val >> jnp.uint32(16)).astype(jnp.int32),
        )
    j = val.astype(jnp.int32)
    meta = tables.l4_meta[ep, dirn, j]
    proxy = jnp.where(
        probe1 | hit3, (meta >> jnp.uint32(1)).astype(jnp.int32), 0
    )
    return proxy, j


def _l4hash_probe(hash_rows, hash_stash, ep, dirn, idx, dport, proto):
    """One probe of a hashed L4 entry table: a single row gather +
    lane compares (+ a small stash broadcast).  Returns (hit bool
    [B], value u32 [B] — `j << 16 | proxy_port` in the 3-word layout,
    the bare slot index in the compact 2-word one).  The entry count
    per bucket derives from the row width and the layout from the
    stash width (compiler.tables.l4_entry_words) — probe and build
    share the layout through the array shapes themselves."""
    from cilium_tpu.compiler.tables import l4_entry_words
    from cilium_tpu.engine.hashtable import fnv1a_device

    entry_words = l4_entry_words(hash_stash)
    w0, w1 = l4hash_probe_keys(
        entry_words, ep, dirn, idx, dport, proto
    )
    h = fnv1a_device(jnp.stack([w0, w1], axis=1))
    n_rows = hash_rows.shape[0]
    b = (h & jnp.uint32(n_rows - 1)).astype(jnp.int32)
    rows = jnp.asarray(hash_rows)[b]  # [B, lanes] — 1 gather
    found, val = l4hash_row_parts(rows, w0, w1, entry_words)
    s_found, s_val = l4hash_stash_parts(
        hash_stash, w0, w1, entry_words
    )
    return found | s_found, val + s_val


def _probes(tables: PolicyTables, batch: TupleBatch, idx_known=None):
    """The three map probes of policy.h:46, vectorized.  Returns
    (probe1, probe2, probe3, proxy, j, idx).

    With the hashed entry table present (the FleetCompiler always
    builds it), the exact probe and the wildcard probe are each ONE
    row gather; the slot index for counters and the proxy port ride
    in the matched entry's value word, so neither port_slot nor the
    dense bitmap is touched.  `idx_known=(idx, known[, l3_bit])`
    supplies a pre-resolved identity index (e.g. from an idx-form
    ipcache) and skips the id_direct gather; with `l3_bit` (the
    identity's per-endpoint L3-allow bit, from an l3-plane ipcache)
    the L3 probe gather disappears too."""
    from cilium_tpu.compiler.tables import L4H_WILD_IDX

    l3_bit = None
    if idx_known is not None:
        idx, known = idx_known[0], idx_known[1]
        if len(idx_known) > 2:
            l3_bit = idx_known[2]
    else:
        idx, known = _index_identity(tables, batch)
    word = idx >> 5
    bit = (idx & 31).astype(jnp.uint32)
    proto = jnp.clip(batch.proto, 0, 255).astype(jnp.int32)
    dport = jnp.clip(batch.dport, 0, 65535).astype(jnp.int32)

    if tables.l4_hash_rows is not None:
        # -- probes 1+3: two row gathers from the hashed entry table ----
        # (an unknown identity resolves to the in-range fallback idx
        # and probe1 is masked by `known`; a real idx never equals the
        # wildcard sentinel — the compilers bound the identity axis
        # below L4H_WILD_IDX)
        from cilium_tpu.compiler.tables import l4_entry_words

        entry_words = l4_entry_words(tables)
        hit1, val1 = _l4hash_probe(
            tables.l4_hash_rows, tables.l4_hash_stash,
            batch.ep_index, batch.direction,
            idx.astype(jnp.uint32), dport, proto,
        )
        wild_idx = jnp.full(
            idx.shape, jnp.uint32(L4H_WILD_IDX), jnp.uint32
        )
        hit3, val3 = _l4hash_probe(
            tables.l4_wild_rows, tables.l4_wild_stash,
            batch.ep_index, batch.direction, wild_idx,
            dport, proto,
        )
        probe1 = known & hit1
        probe3 = hit3
        proxy, j = l4hash_value_decode(
            tables, batch.ep_index, batch.direction,
            probe1, val1, hit3, val3, entry_words,
        )
    else:
        # dense fallback (hand-built tables without the hash)
        from cilium_tpu.compiler.tables import NO_SLOT

        slot16 = tables.port_slot[proto, dport]
        has_port = slot16 != jnp.uint16(NO_SLOT)
        j = jnp.where(has_port, slot16, 0).astype(jnp.int32)
        exact_words = tables.l4_allow_bits[
            batch.ep_index, batch.direction, j, word
        ]
        exact_bit = ((exact_words >> bit) & 1).astype(bool)
        meta = tables.l4_meta[batch.ep_index, batch.direction, j]
        proxy = (meta >> 1).astype(jnp.int32)
        wild = (meta & 1).astype(bool)
        probe1 = known & has_port & exact_bit
        probe3 = has_port & wild

    # -- probe 2: L3-only (identity, 0, 0) ----------------------------------
    if l3_bit is not None:
        probe2 = known & l3_bit
    else:
        l3_words = tables.l3_allow_bits[
            batch.ep_index, batch.direction, word
        ]
        probe2 = known & ((l3_words >> bit) & 1).astype(bool)

    return probe1, probe2, probe3, proxy, j, idx


def _combine(probe1, probe2, probe3, proxy, frag) -> Verdicts:
    """Lattice combine (policy.h:62-109 order; fragments skip L4
    probes)."""
    p1 = probe1 & ~frag
    p3 = probe3 & ~frag
    allowed = p1 | probe2 | p3

    proxy_out = jnp.where(p1 | (~probe2 & p3), proxy, 0)
    proxy_out = jnp.where(allowed, proxy_out, 0)

    kind = jnp.where(
        p1,
        MATCH_L4,
        jnp.where(
            probe2,
            MATCH_L3,
            jnp.where(
                p3,
                MATCH_L4_WILD,
                jnp.where(frag, MATCH_FRAG_DROP, MATCH_NONE),
            ),
        ),
    ).astype(jnp.uint8)

    return Verdicts(
        allowed=allowed.astype(jnp.uint8),
        proxy_port=proxy_out,
        match_kind=kind,
    )


def _verdict_kernel(tables: PolicyTables, batch: TupleBatch) -> Verdicts:
    probe1, probe2, probe3, proxy, _, _ = _probes(tables, batch)
    return _combine(probe1, probe2, probe3, proxy, batch.is_fragment)


def _counter_cols(v, batch, j, idx, kg: int):
    """Scatter ingredients for the per-entry counters: returns
    (ep_index, direction, col, weight) — shared by the in-kernel
    accumulate and the paired-dispatch merged scatter so the two can
    never diverge."""
    hit_l4 = (v.match_kind == MATCH_L4) | (v.match_kind == MATCH_L4_WILD)
    hit_l3 = v.match_kind == MATCH_L3
    col = jnp.where(hit_l4, j, kg + idx)
    weight = (hit_l4 | hit_l3).astype(jnp.uint32)
    return batch.ep_index, batch.direction, col, weight


def _accumulate_counters(v, batch, j, idx, acc, kg: int):
    """Scatter the batch's lattice hits into the carried counter
    buffer (policy_entry packets, policy.h:66-68) — ONE scatter: the
    L4 slot axis and the L3 identity axis share a flat column space
    ([0, Kg) = L4 slots, [Kg, Kg+N) = L3 identities; a tuple matches
    at most one entry, policy.h's single matched policy_entry).
    `kg` is the static slot count (tables.l4_meta.shape[2]).  Callers
    donate the buffer across batches (XLA updates in place) instead of
    materializing fresh [E, 2, N] tensors per batch."""
    ep, d, col, weight = _counter_cols(v, batch, j, idx, kg)
    return acc.at[ep, d, col].add(weight)


# ---------------------------------------------------------------------------
# On-device telemetry: per-direction stage/drop accounting
# ---------------------------------------------------------------------------
# Column space of the [2, TELEM_COLS] u32 telemetry accumulator the
# instrumented datapath kernels carry alongside the per-entry counter
# buffer (row 0 = ingress, row 1 = egress).  The columns partition the
# batch by stage outcome, so the host fold can reconstruct
# cilium_drop_count_total{reason,direction} /
# cilium_policy_verdict_total / cilium_forward_count_total without
# pulling per-tuple verdict columns off the device:
#
#   * TOTAL/FORWARDED/DENIED: final combine outcome;
#   * DROP_*: disjoint drop attribution (prefilter first, then the
#     lattice's frag/policy split — bpf/lib/common.h reason codes);
#   * MATCH_*: the lattice verdict histogram (the per-tuple
#     match_kind, summed);
#   * LB/CT/IPCACHE/PROXY: intermediate stage outcomes (DNAT applied,
#     conntrack state, world fallback, proxy redirect).
TELEM_TOTAL = 0
TELEM_FORWARDED = 1
TELEM_DENIED = 2
TELEM_DROP_PREFILTER = 3
TELEM_DROP_POLICY = 4
TELEM_DROP_FRAG = 5
TELEM_MATCH_L4 = 6
TELEM_MATCH_L3 = 7
TELEM_MATCH_L4_WILD = 8
TELEM_MATCH_NONE = 9
TELEM_MATCH_FRAG = 10
TELEM_LB_DNAT = 11
TELEM_CT_NEW = 12
TELEM_CT_ESTABLISHED = 13
TELEM_CT_REPLY = 14
TELEM_CT_RELATED = 15
TELEM_CT_BYPASS_ALLOW = 16
TELEM_CT_DELETE = 17
TELEM_IPCACHE_WORLD = 18
TELEM_PROXY_REDIRECT = 19
TELEM_COLS = 20

TELEM_NAMES = (
    "total",
    "forwarded",
    "denied",
    "drop_prefilter",
    "drop_policy",
    "drop_frag",
    "match_l4",
    "match_l3",
    "match_l4_wild",
    "match_none",
    "match_frag",
    "lb_dnat",
    "ct_new",
    "ct_established",
    "ct_reply",
    "ct_related",
    "ct_bypass_allow",
    "ct_delete",
    "ipcache_world",
    "proxy_redirect",
)


def make_telemetry_buffers():
    """Zeroed [2, TELEM_COLS] u32 device telemetry accumulator
    (direction-major, TELEM_* columns) — carried and donated across
    batches like the counter buffer; fold host-side with
    cilium_tpu.telemetry.fold_telemetry."""
    return jnp.zeros((2, TELEM_COLS), jnp.uint32)


def telemetry_masks(
    pre_dropped,
    ct_result,
    match_kind,
    allowed,
    ct_delete,
    proxy_port,
    lb_slave,
    ipcache_miss,
    xp=jnp,
):
    """The TELEM_* column masks as a list of bool [B] arrays, in
    column order.  One implementation serves BOTH the traced device
    kernel (xp=jnp) and the numpy host fold (xp=np): the bit-identity
    gate between the on-device accumulator and the host per-stage
    histogram holds by construction.

    All inputs are the DatapathVerdicts columns of the same names
    (any integer/bool dtype)."""
    from cilium_tpu.ct.table import (
        CT_ESTABLISHED,
        CT_NEW,
        CT_RELATED,
        CT_REPLY,
    )

    allowed = allowed.astype(bool)
    pre = pre_dropped.astype(bool)
    kind = match_kind
    denied = ~allowed
    post = denied & ~pre  # lattice-attributed drops
    pass_ct = (ct_result == CT_REPLY) | (ct_result == CT_RELATED)
    pol_allow = (
        (kind == MATCH_L4)
        | (kind == MATCH_L3)
        | (kind == MATCH_L4_WILD)
    )
    return [
        xp.ones(allowed.shape, bool),
        allowed,
        denied,
        pre,
        post & (kind == MATCH_NONE),
        post & (kind == MATCH_FRAG_DROP),
        kind == MATCH_L4,
        kind == MATCH_L3,
        kind == MATCH_L4_WILD,
        kind == MATCH_NONE,
        kind == MATCH_FRAG_DROP,
        lb_slave > 0,
        ct_result == CT_NEW,
        ct_result == CT_ESTABLISHED,
        ct_result == CT_REPLY,
        ct_result == CT_RELATED,
        pass_ct & ~pol_allow & ~pre,
        ct_delete.astype(bool),
        ipcache_miss.astype(bool),
        (proxy_port > 0) & allowed,
    ]


def make_counter_buffers(tables: PolicyTables):
    """Zeroed device counter buffer [E, 2, Kg + N] u32 — L4 slot
    columns first, then L3 identity columns (split with
    split_counters)."""
    e_count, _, k = tables.l4_meta.shape
    n = tables.id_table.shape[0]
    return jnp.zeros((e_count, 2, k + n), jnp.uint32)


def split_counters(acc, tables: PolicyTables):
    """Flat accumulator → (l4 [E, 2, Kg], l3 [E, 2, N]) views."""
    k = tables.l4_meta.shape[2]
    return acc[:, :, :k], acc[:, :, k:]


def _verdict_kernel_with_counters(tables: PolicyTables, batch: TupleBatch):
    """Verdicts + fresh per-batch counters (allocates; for one-shot
    callers and tests — streaming paths use the donated-accumulator
    variants)."""
    probe1, probe2, probe3, proxy, j, idx = _probes(tables, batch)
    v = _combine(probe1, probe2, probe3, proxy, batch.is_fragment)
    acc = make_counter_buffers(tables)
    acc = _accumulate_counters(
        v, batch, j, idx, acc, tables.l4_meta.shape[2]
    )
    l4_counts, l3_counts = split_counters(acc, tables)
    return v, l4_counts, l3_counts


evaluate_batch = jax.jit(_verdict_kernel)


def _verdict_kernel_from_ips(lpm_tables, policy_tables, src_ips, batch):
    """Fused datapath: derive the source identity from the raw IP via
    the DIR-24-8 ipcache (bpf_netdev.c's identity derivation before
    the tail call into the policy program), then run the lattice.
    IPs that miss the ipcache resolve to identity 0 (unknown)."""
    from cilium_tpu.ipcache.lpm import _lookup_kernel

    ids = _lookup_kernel(lpm_tables, src_ips.astype(jnp.uint32))
    resolved = TupleBatch(
        ep_index=batch.ep_index,
        identity=ids,
        dport=batch.dport,
        proto=batch.proto,
        direction=batch.direction,
        is_fragment=batch.is_fragment,
    )
    return _verdict_kernel(policy_tables, resolved)


evaluate_batch_from_ips = jax.jit(_verdict_kernel_from_ips)


def make_sharded_evaluator(mesh: Optional[jax.sharding.Mesh] = None,
                           batch_axis: str = "batch"):
    """Return a jitted evaluator with the batch axis sharded over the
    mesh and tables replicated (SURVEY.md §2.9: flow batches shard like
    packets shard across nodes; tables replicate like BPF maps
    replicate per node).

    With `mesh=None` this degrades to the single-device evaluator.
    """
    if mesh is None:
        return evaluate_batch

    replicated = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec()
    )
    batch_sharded = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(batch_axis)
    )

    table_shardings = PolicyTables(
        id_table=replicated,
        id_direct=replicated,
        id_lo_len=replicated,
        port_slot=replicated,
        l4_meta=replicated,
        l4_allow_bits=replicated,
        l3_allow_bits=replicated,
        generation=replicated,
        l4_hash_rows=replicated,
        l4_hash_stash=replicated,
        l4_wild_rows=replicated,
        l4_wild_stash=replicated,
    )
    batch_shardings = TupleBatch(
        ep_index=batch_sharded,
        identity=batch_sharded,
        dport=batch_sharded,
        proto=batch_sharded,
        direction=batch_sharded,
        is_fragment=batch_sharded,
    )
    out_shardings = Verdicts(
        allowed=batch_sharded,
        proxy_port=batch_sharded,
        match_kind=batch_sharded,
    )
    return jax.jit(
        _verdict_kernel,
        in_shardings=(table_shardings, batch_shardings),
        out_shardings=out_shardings,
    )
