"""Lowering PolicyMapState → dense, padded device tensors.

TPU-first design replacing the per-endpoint BPF hash maps
(pkg/maps/policymap) with direct-indexed integer tensors.  The guiding
constraint is that XLA-TPU executes random HBM gathers at ~100M/s per
chip, so every probe must be O(1) gathers — no device-side binary
search, no per-tuple scans:

  * identity probe — raw u32 security identity → dense index through
    two direct tables: `id_lo` for cluster-scope ids (dense from 0)
    and `id_local` for local CIDR identities (dense from
    LOCAL_ID_BASE).  One 4-byte gather each, both from tables that fit
    VMEM for realistic universes (512k ids = 2 MB; the reference's
    ipcache cap, ipcache.go:36).
  * L4 key probe — (proto, dport) → global filter slot through a
    256-entry proto remap plus a [8, 65536] u16 slot table (1 MB).
    This replaces the reference's per-endpoint hash-map key probe
    (policy.h:54) with two gathers shared by all endpoints.
  * allow sets — bit-packed u32 words over the identity axis, one row
    per (endpoint, direction, slot) plus an L3-only row pair; 32×
    smaller than bool tensors (64k ids × 256 slots × 16 endpoints
    ≈ 64 MB instead of 2 GB).

All axes are padded to configurable buckets so XLA compilation caches
across table updates (SURVEY.md §7 hard part 3).  Identities, ports
and verdict bits are integers end-to-end — no floats (hard part 5).
"""

from __future__ import annotations

import threading
from collections import deque
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from cilium_tpu.identity import IdentityAllocator
from cilium_tpu.maps.policymap import (
    EGRESS,
    INGRESS,
    MapStateArrays,
    PolicyKey,
    PolicyMapState,
    unpack_keys,
)

# Sentinel for padded slots of the sorted identity table: sorts above
# every real identity, so searchsorted never aliases a real id.
PAD_ID = np.uint32(0xFFFFFFFF)
# Absent-entry marker in the direct identity index tables.
NO_INDEX = np.uint32(0xFFFFFFFF)
# Absent-slot marker in the (proto, dport) → L4 slot table.
NO_SLOT = np.uint16(0xFFFF)
# Cap on direct-table sizes (2^22 u32 = 16 MB).  Identity universes
# with non-local ids above this would need a hash-probe fallback; the
# reference caps at 512k ipcache entries (ipcache.go:36), well below.
MAX_DIRECT = 1 << 22

LOCAL_ID_BASE = IdentityAllocator.LOCAL_IDENTITY_BASE

# FleetCompiler instance nonces for generation-stamp scoping.
import itertools as _itertools

_COMPILER_NONCE = _itertools.count(1)

NUM_DIRECTIONS = 2  # INGRESS, EGRESS

# -- hashed L4 entry table ---------------------------------------------------
# The exact and wildcard L4 probes gather from ONE bucketized entry
# table instead of the dense [E, 2, Kg, W] bitmap: measured on v5e,
# element gathers from >=128 MB tables run ~17 ns/flow while 128-lane
# ROW gathers run ~7 ns regardless of row width, and the entry table
# is proportional to the REALIZED map entries (the reference's
# per-endpoint BPF hash maps are entry-proportional too,
# pkg/maps/policymap) rather than E×Kg×identities.
#
# Row = one bucket of `lanes // 3` planar 3-word entries (E below):
#   lanes [0, E)    key0 = idx | dir << 22 | (ep & 0x1FF) << 23
#   lanes [E, 2E)   key1 = dport << 16 | proto << 8 | ep >> 9
#   lanes [2E, 3E)  value = j << 16 | proxy_port
# Wildcard (identity 0) entries store idx = L4H_WILD_IDX.  Empty lanes
# hold key1 = 0xFFFFFFFF, unreachable because ep >> 9 < 128 for any
# endpoint index < 2^16 (the reference's endpoint-id cap).
#
# The lane width is the HOT-PLANE PACK WIDTH: the per-tuple probe
# gathers exactly one `lanes`-wide row and lane-compares E entries, so
# bytes-moved-per-tuple and compare work both scale linearly with it.
# The default is 64 lanes (21 entries, ~8 average load): halving the
# legacy 128-lane rows halves the dominant gather of the fused
# pipeline while the overflow tail (Poisson beyond 21 at lambda=8)
# stays far below the stash.  Build and probe both derive E from the
# row shape — the array IS the layout contract.
L4H_LANES = 64
L4H_WILD_IDX = np.uint32((1 << 22) - 1)
L4H_STASH = 64


def l4h_entries(lanes: int) -> int:
    """Entries per bucket row at a given lane width (3 words each)."""
    return lanes // 3


def l4h_load(lanes: int) -> int:
    """Target average entries per bucket when sizing the row count —
    lanes/8 keeps the overflow tail roughly constant across widths."""
    return max(lanes // 8, 2)


def trim_stash(stash: np.ndarray) -> np.ndarray:
    """Trim a [L4H_STASH, 3 or 2] stash to the pow2 prefix that holds
    its occupied rows (front-filled; empty rows carry w1 = 0xFFFFFFFF
    in the 3-word layout, cw1 = L4C_EMPTY_W1 in the compact one).
    The probe broadcast-compares EVERY stash lane against every tuple,
    so an empty stash shipped at capacity charges the hot path 64
    never-matching compares per table per tuple; verdicts are
    unchanged by construction (trimmed lanes can never match)."""
    from cilium_tpu.engine.hashtable import trim_pow2_prefix

    if stash.shape[-1] == 2:
        used = int((stash[:, 1] != L4C_EMPTY_W1).sum())
    else:
        used = int((stash[:, 1] != np.uint32(0xFFFFFFFF)).sum())
    return trim_pow2_prefix(stash, used)


# -- sub-word (compact, 2-word) L4 entries -----------------------------------
# The 3-word entry spends a full u32 on `value = j << 16 | proxy`, but
# the proxy port is ALREADY resident in the hot l4_meta plane at
# [ep, d, j] (lower_map_state writes it there for every entry, and the
# proxy-consistency check guarantees the two copies agree) — so the
# sub-word form stores only the 12-bit slot index, folded into the
# spare bits of key word 1 ("row metadata" packed beside the key):
#
#   cw0 = idx18            | (dport & 0x3FFF) << 18
#   cw1 = dport >> 14      bits 0-1
#         | proto << 2     bits 2-9
#         | ep << 10       bits 10-17
#         | dir << 18      bit  18
#         | j << 19        bits 19-30   (VALUE, masked out of compares)
#         bit 31 = 0; empty lanes hold cw1 = 0x80000000 (bit 31 set —
#         unreachable for any real entry, the exact-marker discipline
#         of the 3-word layout's key1 trick)
#
# 2 words/entry instead of 3 → the same bucket load fits a 32-lane row
# (16 entries) where the 3-word layout needs 64 lanes: the dominant
# lattice gathers halve again.  The probe reconstructs proxy with ONE
# l4_meta element gather at the combined j (+4 B/tuple, priced by
# autotune.hot_bytes_per_tuple).  Semantics allow it only when
# idx < 2^18-1 (universe ≤ 262142 padded identities), ep < 2^8,
# j < 2^12 — repack_l4_subword verifies and refuses otherwise.  The
# stash ships 2-word entries too:
# its width (2 vs 3) is the LAYOUT MARKER the kernels branch on
# (l4_entry_words: a static jit-cache axis that travels with the
# pytree, no aux-structure change).
L4C_LANES = 32
L4C_WILD_IDX18 = np.uint32((1 << 18) - 1)
L4C_KEY_MASK = np.uint32((1 << 19) - 1)
L4C_EMPTY_W1 = np.uint32(1 << 31)
L4C_CMP_MASK = np.uint32(L4C_KEY_MASK | L4C_EMPTY_W1)


def l4_entry_words(tables_or_stash) -> int:
    """Entry word count of the hashed L4 layout (3 legacy, 2 compact)
    read from the stash width — the shape-borne layout marker shared
    by build, probe and the layout stamp."""
    stash = getattr(tables_or_stash, "l4_hash_stash", tables_or_stash)
    if stash is None:
        return 3
    return 2 if int(stash.shape[-1]) == 2 else 3


def l4c_key0(idx, dport):
    """Compact key word 0 (dtype-generic; build and probe share)."""
    return (
        (idx.astype(np.uint32) & np.uint32(0x3FFFF))
        | ((dport.astype(np.uint32) & np.uint32(0x3FFF)) << np.uint32(18))
    )


def l4c_key1(dport, proto, ep, d):
    """Compact key word 1, KEY BITS ONLY (j is ORed in at build)."""
    return (
        (dport.astype(np.uint32) >> np.uint32(14))
        | ((proto.astype(np.uint32) & np.uint32(0xFF)) << np.uint32(2))
        | ((ep.astype(np.uint32) & np.uint32(0xFF)) << np.uint32(10))
        | ((d.astype(np.uint32) & np.uint32(1)) << np.uint32(18))
    )


def l4h_key0(idx, d, ep):
    """Key word 0 of the hashed L4 probe.  Dtype-generic (np or jnp
    arrays): build side and device probe MUST share this packing."""
    return (
        idx.astype(np.uint32)
        | (d.astype(np.uint32) << np.uint32(22))
        | ((ep.astype(np.uint32) & np.uint32(0x1FF)) << np.uint32(23))
    )


def l4h_key1(dport, proto, ep):
    """Key word 1 (see l4h_key0)."""
    return (
        (dport.astype(np.uint32) << np.uint32(16))
        | (proto.astype(np.uint32) << np.uint32(8))
        | (ep.astype(np.uint32) >> np.uint32(9))
    )


def place_l4_hash(
    w0: np.ndarray,
    w1: np.ndarray,
    value: np.ndarray,
    h: np.ndarray,
    min_rows: int,
    lanes: int = L4H_LANES,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Sizing + bucket placement over precomputed key/hash columns —
    THE layout implementation, shared by build_l4_hash and the
    incremental delta builder (compiler/delta.py), whose bit-identity
    contract depends on there being exactly one copy of this logic.
    Returns (rows, stash, overflow_positions, buckets): the last two
    let the delta builder reconstruct its per-bucket overflow state
    without re-deriving the placement."""
    t = len(w0)
    entries = l4h_entries(lanes)
    n_rows = _pow2_at_least(max(t // l4h_load(lanes), 1), min_rows)
    while True:
        b = (h & np.uint32(n_rows - 1)).astype(np.int64)
        order = np.argsort(b, kind="stable")
        sb = b[order]
        first = np.searchsorted(sb, sb)
        rank = np.arange(t, dtype=np.int64) - first
        main = rank < entries
        if int((~main).sum()) <= L4H_STASH:
            break
        n_rows <<= 1
    rows = np.zeros((n_rows, lanes), dtype=np.uint32)
    rows[:, entries : 2 * entries] = np.uint32(0xFFFFFFFF)
    flat = rows.reshape(-1)
    # `main`/`rank` index SORTED positions; `order` maps them back
    mo = order[main]
    base = sb[main] * lanes + rank[main]
    flat[base] = w0[mo]
    flat[base + entries] = w1[mo]
    flat[base + 2 * entries] = value[mo]
    stash = np.zeros((L4H_STASH, 3), dtype=np.uint32)
    stash[:, 1] = np.uint32(0xFFFFFFFF)
    so = order[~main]
    stash[: len(so), 0] = w0[so]
    stash[: len(so), 1] = w1[so]
    stash[: len(so), 2] = value[so]
    return rows, stash, so, b


def build_l4_hash(
    ep: np.ndarray,
    d: np.ndarray,
    idx: np.ndarray,
    dport: np.ndarray,
    proto: np.ndarray,
    value: np.ndarray,
    min_rows: int = 64,
    lanes: int = L4H_LANES,
) -> Tuple[np.ndarray, np.ndarray]:
    """Vectorized bucket placement of T entries → (rows u32
    [R, lanes], stash u32 [pow2 used, 3]).  R is a power of two sized
    for ~lanes/8 entries per lanes//3-capacity row; rows double until
    the overflow fits the stash (never in practice — the tail is
    Poisson)."""
    t = len(ep)
    if np.any((idx >= L4H_WILD_IDX) & (idx != L4H_WILD_IDX)):
        raise ValueError("identity index exceeds 22-bit hash key space")
    if t and int(ep.max()) >= 65536:
        # the empty-lane marker relies on ep >> 9 < 128; the reference
        # caps endpoint ids at 65535 too (pkg/endpoint/endpoint.go)
        raise ValueError("endpoint axis exceeds the 16-bit key space")
    w0 = l4h_key0(idx, d, ep)
    w1 = l4h_key1(dport, proto, ep)
    h = _fnv1a_host_2(w0, w1)
    rows, stash, _, _ = place_l4_hash(
        w0, w1, value, h, min_rows, lanes=lanes
    )
    return rows, trim_stash(stash)


def build_l4_hash_pair(
    ep: np.ndarray,
    d: np.ndarray,
    idx: np.ndarray,
    dport: np.ndarray,
    proto: np.ndarray,
    value: np.ndarray,
    lanes: int = L4H_LANES,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Partition entries into the main (exact) and wildcard tables:
    (rows, stash, wild_rows, wild_stash)."""
    wild = idx == L4H_WILD_IDX
    keep = ~wild
    rows, stash = build_l4_hash(
        ep[keep], d[keep], idx[keep], dport[keep], proto[keep],
        value[keep], lanes=lanes,
    )
    wrows, wstash = build_l4_hash(
        ep[wild], d[wild], idx[wild], dport[wild], proto[wild],
        value[wild], min_rows=16, lanes=lanes,
    )
    return rows, stash, wrows, wstash


def _fnv1a_host_2(w0: np.ndarray, w1: np.ndarray) -> np.ndarray:
    """FNV-1a over two u32 word columns (avoids the [T, 2] stack)."""
    from cilium_tpu.engine.hashtable import FNV_OFFSET, FNV_PRIME

    h = np.full(len(w0), FNV_OFFSET, dtype=np.uint64)
    prime = np.uint64(int(FNV_PRIME))
    for col in (w0, w1):
        c = col.astype(np.uint64)
        for shift in (0, 8, 16, 24):
            h = ((h ^ ((c >> shift) & 0xFF)) * prime) & 0xFFFFFFFF
    return h.astype(np.uint32)


def _round_up(n: int, mult: int) -> int:
    return max(mult, ((n + mult - 1) // mult) * mult)


def _pow2_at_least(n: int, floor: int) -> int:
    size = floor
    while size < n:
        size <<= 1
    return size


@dataclass
class PolicyTables:
    """Stacked verdict tables for E endpoints — the device-resident
    equivalent of E pinned policy maps plus the tail-call PROG_ARRAY
    dispatch (bpf/bpf_lxc.c:1039: per-tuple gather along the endpoint
    axis replaces the per-endpoint program jump).

    Gather budget: the kernel spends ~20-30 ms per 1M-tuple random
    gather on TPU regardless of table size, so fused layouts matter
    more than compactness — identity index is ONE table (`id_direct` =
    cluster-scope ids dense from 0, then local CIDR ids dense from
    `id_lo_len`), and proxy-port + wildcard-bit are ONE u32 word
    (`l4_meta` = proxy << 1 | wild).

    Shapes (E endpoints, Kg padded global L4 slots, N padded
    identities, W = N // 32 words):
      id_table       u32 [N]            sorted identity universe
      id_direct      u32 [LO+LL]        id → index (two dense regions)
      id_lo_len      i32 scalar         split point of id_direct
      port_slot      u16 [256, 65536]   (proto, dport) → L4 slot; one
                                        row per raw IP proto byte — 32
                                        MB buys one fewer gather/tuple
      l4_meta        u32 [E, 2, Kg]     proxy_port << 1 | wildcard
      l4_allow_bits  u32 [E, 2, Kg, W]  per-identity allow (exact probe)
      l3_allow_bits  u32 [E, 2, W]      L3-only allow (2nd probe)
    """

    id_table: np.ndarray
    id_direct: np.ndarray
    id_lo_len: np.ndarray
    port_slot: np.ndarray
    l4_meta: np.ndarray
    l4_allow_bits: np.ndarray
    l3_allow_bits: np.ndarray
    # publish-generation stamp (FleetCompiler): a pytree CHILD (scalar
    # u64: compiler-instance nonce << 32 | publish counter) so it
    # survives device_put/flatten round trips without becoming a jit
    # cache key; 0 = unstamped (hand-built tables)
    generation: np.ndarray = np.uint64(0)
    # hashed L4 entry tables (see build_l4_hash): the exact and
    # wildcard probes are each ONE 128-lane row gather from an
    # entry-proportional table instead of element gathers from the
    # dense bitmap — on v5e row gathers run ~2x faster than big-table
    # element gathers.  Wildcard (identity 0) entries live in their
    # own SMALL table: they are per-(ep, dir, port, proto), so the
    # table stays a few KB and the second gather per flow hits a hot
    # region instead of paying the big-table random-access cost
    # again.  None → the kernel falls back to the dense
    # l4_allow_bits/l4_meta path (the layout the table-axis-sharded
    # mesh evaluator uses).
    l4_hash_rows: "np.ndarray | None" = None
    l4_hash_stash: "np.ndarray | None" = None
    l4_wild_rows: "np.ndarray | None" = None
    l4_wild_stash: "np.ndarray | None" = None

    @property
    def num_endpoints(self) -> int:
        return self.l4_meta.shape[0]

    @property
    def num_identities(self) -> int:
        return self.id_table.shape[0]

    @property
    def num_l4_slots(self) -> int:
        return self.l4_meta.shape[2]

    def tree_flatten(self):
        return (
            (
                self.id_table,
                self.id_direct,
                self.id_lo_len,
                self.port_slot,
                self.l4_meta,
                self.l4_allow_bits,
                self.l3_allow_bits,
                self.generation,
                self.l4_hash_rows,
                self.l4_hash_stash,
                self.l4_wild_rows,
                self.l4_wild_stash,
            ),
            None,
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def _register_pytree() -> None:
    try:
        import jax

        jax.tree_util.register_pytree_node(
            PolicyTables,
            lambda t: t.tree_flatten(),
            lambda aux, ch: PolicyTables.tree_unflatten(aux, ch),
        )
    except Exception:  # pragma: no cover - jax always present in CI
        pass


_register_pytree()


# -- hot/cold leaf planes ----------------------------------------------------
# The fused single-chip kernels (engine/verdict._probes with the
# hashed entry tables, engine/datapath) touch only the HOT leaves:
# everything the per-tuple verdict gathers read.  The COLD leaves are
# the dense-bitmap fallback layout — the 32 MB (proto, dport) slot
# table and the [E, 2, Kg, W] allow bitmap, by far the largest leaves
# — consumed only by the table-axis-sharded mesh evaluator and
# hand-built tables without the hash pair.  A hot-only publication
# (engine/publish.DeviceTableStore(hot_only=True)) keeps the cold
# plane host-resident: HBM holds and delta publishes ship only the
# words the verdict path can ever gather.
HOT_LEAVES = (
    "id_table",
    "id_direct",
    "id_lo_len",
    "l4_meta",
    "l3_allow_bits",
    "generation",
    "l4_hash_rows",
    "l4_hash_stash",
    "l4_wild_rows",
    "l4_wild_stash",
)
COLD_LEAVES = ("port_slot", "l4_allow_bits")


def split_hot(tables: "PolicyTables") -> "PolicyTables":
    """The hot plane of `tables`: cold leaves dropped (None).  Only
    valid for tables carrying the hashed entry pair — without it the
    kernel's fallback path needs the cold dense layout."""
    if tables.l4_hash_rows is None:
        raise ValueError(
            "hot/cold split requires the hashed L4 entry tables "
            "(dense-fallback tables gather the cold plane)"
        )
    import dataclasses

    return dataclasses.replace(
        tables, **{leaf: None for leaf in COLD_LEAVES}
    )


def is_hot_only(tables) -> bool:
    return any(getattr(tables, leaf) is None for leaf in COLD_LEAVES)


def tables_layout_version(tables) -> int:
    """Layout stamp of a PolicyTables instance: hashed-table pack
    widths + hot/cold coldness bits.  Two tables with different
    stamps have structurally different leaf sets or lane widths, so a
    TableDelta recorded against one cannot scatter into an epoch
    holding the other — DeviceTableStore falls back to a full upload
    on mismatch (the layout guard beside the reset-counter guard)."""
    if tables is None:
        return 0
    rows = getattr(tables, "l4_hash_rows", None)
    wrows = getattr(tables, "l4_wild_rows", None)
    lanes = 0 if rows is None else int(rows.shape[1])
    wlanes = 0 if wrows is None else int(wrows.shape[1])
    cold_bits = 0
    for i, leaf in enumerate(COLD_LEAVES):
        if getattr(tables, leaf, None) is None:
            cold_bits |= 1 << i
    # sub-word marker: the compact 2-word entry form at the same lane
    # count is a DIFFERENT layout (a delta recorded against one can
    # never scatter into the other)
    compact_bit = (
        1 if l4_entry_words(tables) == 2 else 0
    ) if getattr(tables, "l4_hash_stash", None) is not None else 0
    return (
        lanes | (wlanes << 11) | (cold_bits << 22)
        | (compact_bit << 24)
    )


def repack_hash_lanes(
    tables: "PolicyTables", lanes: int
) -> "PolicyTables":
    """Re-place both hashed entry tables at a different hot-plane
    pack width IN THE 3-WORD LAYOUT — the autotuner's layout knob.
    Entry fields are read back from the existing rows (either layout
    — a compact input is expanded through l4_entry_records), so no
    compiler state is needed; verdicts are identical by construction
    (probe hits are keyed, not positional).  The result's layout
    stamp differs from the source compiler's, so delta publication
    refuses it (full upload) — the repacked layout is a
    dispatch-side choice, not a new compile."""
    import dataclasses

    if tables.l4_hash_rows is None:
        raise ValueError("no hashed entry tables to repack")
    recs = l4_entry_records(tables)
    out = {}
    for key, rows_leaf, stash_leaf, min_rows in (
        ("exact", "l4_hash_rows", "l4_hash_stash", 64),
        ("wild", "l4_wild_rows", "l4_wild_stash", 16),
    ):
        r = recs[key]
        w0 = l4h_key0(r["idx"], r["d"], r["ep"])
        w1 = l4h_key1(r["dport"], r["proto"], r["ep"])
        val = (r["j"] << np.uint32(16)) | r["proxy"]
        h = _fnv1a_host_2(w0, w1)
        rows, stash, _, _ = place_l4_hash(
            w0, w1, val, h, min_rows, lanes=lanes
        )
        out[rows_leaf] = rows
        out[stash_leaf] = trim_stash(stash)
    return dataclasses.replace(tables, **out)


def place_l4_hash_compact(
    cw0: np.ndarray,
    cw1_key: np.ndarray,
    j: np.ndarray,
    h: np.ndarray,
    min_rows: int,
    lanes: int = L4C_LANES,
) -> Tuple[np.ndarray, np.ndarray]:
    """Compact sibling of place_l4_hash: 2-word planar entries (cw0
    plane then cw1 plane, the slot index ORed into cw1's value bits).
    Same sizing rule (lanes/8 target load, rows double until the
    overflow fits the stash); returns (rows, stash untrimmed)."""
    t = len(cw0)
    entries = lanes // 2
    cw1 = cw1_key | (j.astype(np.uint32) << np.uint32(19))
    n_rows = _pow2_at_least(max(t // l4h_load(lanes), 1), min_rows)
    while True:
        b = (h & np.uint32(n_rows - 1)).astype(np.int64)
        order = np.argsort(b, kind="stable")
        sb = b[order]
        first = np.searchsorted(sb, sb)
        rank = np.arange(t, dtype=np.int64) - first
        main = rank < entries
        if int((~main).sum()) <= L4H_STASH:
            break
        n_rows <<= 1
    rows = np.zeros((n_rows, lanes), dtype=np.uint32)
    rows[:, entries : 2 * entries] = L4C_EMPTY_W1
    flat = rows.reshape(-1)
    mo = order[main]
    base = sb[main] * lanes + rank[main]
    flat[base] = cw0[mo]
    flat[base + entries] = cw1[mo]
    stash = np.zeros((L4H_STASH, 2), dtype=np.uint32)
    stash[:, 1] = L4C_EMPTY_W1
    so = order[~main]
    stash[: len(so), 0] = cw0[so]
    stash[: len(so), 1] = cw1[so]
    return rows, stash


def l4_entry_records(tables: "PolicyTables") -> Dict[str, dict]:
    """Decode every realized hashed-pair entry — either layout — back
    to its field columns: {"exact"/"wild": {ep, d, idx, dport, proto,
    j, proxy}} (idx carries L4H_WILD_IDX for wildcard entries).  The
    repack helpers rebuild ANY layout from these, so the autotuner can
    sweep widths and forms without recompiling policy."""
    if tables.l4_hash_rows is None:
        raise ValueError("no hashed entry tables to decode")
    ew = l4_entry_words(tables)
    meta = np.asarray(tables.l4_meta)
    out = {}
    for key, rows_leaf, stash_leaf in (
        ("exact", "l4_hash_rows", "l4_hash_stash"),
        ("wild", "l4_wild_rows", "l4_wild_stash"),
    ):
        rows = np.asarray(getattr(tables, rows_leaf))
        stash = np.asarray(getattr(tables, stash_leaf))
        e = rows.shape[1] // ew
        if ew == 3:
            w0 = np.concatenate(
                [rows[:, :e].reshape(-1), stash[:, 0]]
            )
            w1 = np.concatenate(
                [rows[:, e : 2 * e].reshape(-1), stash[:, 1]]
            )
            val = np.concatenate(
                [rows[:, 2 * e : 3 * e].reshape(-1), stash[:, 2]]
            )
            keep = w1 != np.uint32(0xFFFFFFFF)
            w0, w1, val = w0[keep], w1[keep], val[keep]
            # key1's low byte holds ep >> 9 (8 bits — build guards
            # ep < 2^16, but decode the encoder's full field width)
            ep = ((w0 >> 23) & 0x1FF) | ((w1 & 0xFF) << 9)
            rec = {
                "ep": ep.astype(np.uint32),
                "d": ((w0 >> 22) & 1).astype(np.uint32),
                "idx": (w0 & np.uint32(0x3FFFFF)).astype(np.uint32),
                "dport": (w1 >> 16).astype(np.uint32),
                "proto": ((w1 >> 8) & 0xFF).astype(np.uint32),
                "j": (val >> 16).astype(np.uint32),
                "proxy": (val & 0xFFFF).astype(np.uint32),
            }
        else:
            cw0 = np.concatenate(
                [rows[:, :e].reshape(-1), stash[:, 0]]
            )
            cw1 = np.concatenate(
                [rows[:, e : 2 * e].reshape(-1), stash[:, 1]]
            )
            keep = (cw1 & L4C_EMPTY_W1) == 0
            cw0, cw1 = cw0[keep], cw1[keep]
            idx18 = cw0 & np.uint32(0x3FFFF)
            idx = np.where(
                idx18 == L4C_WILD_IDX18, L4H_WILD_IDX, idx18
            ).astype(np.uint32)
            ep = ((cw1 >> 10) & 0xFF).astype(np.uint32)
            d = ((cw1 >> 18) & 1).astype(np.uint32)
            j = ((cw1 >> 19) & 0xFFF).astype(np.uint32)
            rec = {
                "ep": ep,
                "d": d,
                "idx": idx,
                "dport": (
                    (cw0 >> 18) | ((cw1 & 3) << 14)
                ).astype(np.uint32),
                "proto": ((cw1 >> 2) & 0xFF).astype(np.uint32),
                "j": j,
                # proxy rides the l4_meta plane in the compact form
                "proxy": (
                    meta[
                        ep.astype(np.int64), d.astype(np.int64),
                        j.astype(np.int64),
                    ]
                    >> 1
                ).astype(np.uint32),
            }
        out[key] = rec
    return out


def repack_l4_subword(
    tables: "PolicyTables", lanes: int = L4C_LANES
) -> "PolicyTables":
    """Re-place both hashed entry tables in the SUB-WORD (2-word)
    layout — nibble/byte-packed verdict lanes for the lattice probe.
    Verdicts are identical by construction (keys compare exactly; the
    proxy port is reconstructed from the l4_meta plane, which the
    lowering keeps bit-equal to the entry's copy).  Raises ValueError
    when the world's ranges don't fit the compact fields (universe
    > 2^18-2 padded identities, > 256 endpoints, > 4096 L4 slots) —
    semantics first, the caller keeps the 3-word layout then.  The
    result's layout stamp differs, so delta publication refuses it
    (full upload), exactly like repack_hash_lanes."""
    import dataclasses

    n = int(tables.id_table.shape[0])
    e_count, _, kg = tables.l4_meta.shape
    if n > (1 << 18) - 2:
        raise ValueError(
            f"identity axis {n} exceeds the compact 18-bit idx field"
        )
    if e_count > 256:
        raise ValueError(
            f"endpoint axis {e_count} exceeds the compact 8-bit field"
        )
    if kg > (1 << 12):
        raise ValueError(
            f"L4 slot axis {kg} exceeds the compact 12-bit field"
        )
    recs = l4_entry_records(tables)
    meta = np.asarray(tables.l4_meta)
    out = {}
    for key, rows_leaf, stash_leaf, min_rows in (
        ("exact", "l4_hash_rows", "l4_hash_stash", 64),
        ("wild", "l4_wild_rows", "l4_wild_stash", 16),
    ):
        r = recs[key]
        # the compact form DROPS the per-entry proxy copy: verify the
        # l4_meta plane agrees (the lowering invariant) so the probe's
        # reconstruction is provably exact
        meta_proxy = (
            meta[
                r["ep"].astype(np.int64), r["d"].astype(np.int64),
                r["j"].astype(np.int64),
            ]
            >> 1
        )
        if not np.array_equal(meta_proxy, r["proxy"]):
            raise ValueError(
                "entry proxy diverges from the l4_meta plane — "
                "compact layout would change verdicts"
            )
        idx18 = np.where(
            r["idx"] == L4H_WILD_IDX, L4C_WILD_IDX18, r["idx"]
        ).astype(np.uint32)
        cw0 = l4c_key0(idx18, r["dport"])
        cw1k = l4c_key1(r["dport"], r["proto"], r["ep"], r["d"])
        h = _fnv1a_host_2(cw0, cw1k)
        rows, stash = place_l4_hash_compact(
            cw0, cw1k, r["j"], h, min_rows, lanes=lanes
        )
        out[rows_leaf] = rows
        out[stash_leaf] = trim_stash(stash)
    return dataclasses.replace(tables, **out)


def build_id_table(
    identity_ids: Sequence[int], identity_pad: int = 1024
) -> np.ndarray:
    """Sorted, padded identity universe (the shape-defining snapshot,
    reference getLabelsMap pkg/endpoint/policy.go:194)."""
    ids = sorted(set(int(i) for i in identity_ids))
    n = _round_up(len(ids), identity_pad)
    # Identity axis must stay a multiple of 32 for bit packing.
    n = _round_up(n, 32)
    table = np.full((n,), PAD_ID, dtype=np.uint32)
    table[: len(ids)] = np.asarray(ids, dtype=np.uint32)
    return table


def _build_direct_index(id_table: np.ndarray) -> Tuple[np.ndarray, int]:
    """One fused direct id→index table for the O(1) identity probe:
    [0, lo_len) maps cluster-scope ids, [lo_len, end) maps local CIDR
    ids offset by LOCAL_ID_BASE.  Returns (id_direct, lo_len)."""
    ids = id_table[id_table != PAD_ID].astype(np.int64)
    index = np.arange(len(ids), dtype=np.uint32)

    local_mask = ids >= LOCAL_ID_BASE
    lo_ids, lo_idx = ids[~local_mask], index[~local_mask]
    local_ids, local_idx = ids[local_mask] - LOCAL_ID_BASE, index[local_mask]

    lo_max = int(lo_ids.max()) + 1 if len(lo_ids) else 1
    ll_max = int(local_ids.max()) + 1 if len(local_ids) else 1
    if lo_max > MAX_DIRECT or ll_max > MAX_DIRECT:
        raise ValueError(
            f"identity id range too large for direct indexing "
            f"(lo={lo_max}, local={ll_max}, cap={MAX_DIRECT})"
        )
    lo_len = _pow2_at_least(lo_max, 1024)
    ll_len = _pow2_at_least(ll_max, 32)
    id_direct = np.full(lo_len + ll_len, NO_INDEX, dtype=np.uint32)
    id_direct[lo_ids] = lo_idx
    id_direct[lo_len + local_ids] = local_idx
    return id_direct, lo_len


def lower_map_state(
    states: Sequence[PolicyMapState],
    id_table: np.ndarray,
    filter_pad: int = 64,
    hash_lanes: int = L4H_LANES,
) -> PolicyTables:
    """Lower E desired map states onto a shared identity universe.

    Any state entry whose identity is absent from `id_table` would be
    unreachable in the reference too (the BPF map key could never be
    probed with that source identity derived from ipcache); we raise
    on it to surface compiler/universe skew early — the moral
    equivalent of pkg/alignchecker.
    """
    n = id_table.shape[0]
    if n >= int(L4H_WILD_IDX):
        raise ValueError(
            "identity axis too large for the hashed L4 probe "
            f"(n={n}, cap={int(L4H_WILD_IDX)})"
        )
    w = n // 32
    id_index: Dict[int, int] = {}
    for i, v in enumerate(id_table.tolist()):
        if v == int(PAD_ID):
            break
        id_index[v] = i
    id_direct, id_lo_len = _build_direct_index(id_table)

    e_count = len(states)

    # Global slot space: distinct (dport, proto) over all endpoints.
    all_keys = sorted(
        {
            (k.dest_port, k.nexthdr)
            for state in states
            for k in state
            if not k.is_l3_only()
        }
    )
    kg = _round_up(max(len(all_keys), 1), filter_pad)
    slot_of = {key: j for j, key in enumerate(all_keys)}

    port_slot = np.full((256, 65536), NO_SLOT, dtype=np.uint16)
    for (dport, proto), j in slot_of.items():
        port_slot[proto & 0xFF, dport] = j

    l4_meta = np.zeros((e_count, 2, kg), dtype=np.uint32)
    # Bits are set directly into the packed words — never materialize
    # the dense [E, 2, Kg, N] bool tensor.
    l4_allow_bits = np.zeros((e_count, 2, kg, w), dtype=np.uint32)
    l3_allow_bits = np.zeros((e_count, 2, w), dtype=np.uint32)

    def _id_idx(num_id: int) -> int:
        idx = id_index.get(num_id)
        if idx is None:
            raise ValueError(
                f"identity {num_id} in map state but not in the "
                f"identity universe (universe/table skew)"
            )
        return idx

    # Track per-(e,d,slot) proxy consistency: one L4Filter per
    # port/proto key in an L4PolicyMap (pkg/policy/l4.go:276), so one
    # proxy port; conflicting states can't be lowered without
    # diverging from the per-entry oracle.
    proxy_seen: Dict[Tuple[int, int, int], int] = {}

    # hashed entry-table columns (one row per non-L3 map entry)
    h_ep: List[int] = []
    h_d: List[int] = []
    h_idx: List[int] = []
    h_dport: List[int] = []
    h_proto: List[int] = []
    h_val: List[int] = []

    for e, state in enumerate(states):
        for key, entry in state.items():
            d = key.traffic_direction
            if key.is_l3_only():
                idx = _id_idx(key.identity)
                l3_allow_bits[e, d, idx >> 5] |= np.uint32(1 << (idx & 31))
                continue
            j = slot_of[(key.dest_port, key.nexthdr)]
            prev = proxy_seen.setdefault((e, d, j), entry.proxy_port)
            if prev != entry.proxy_port:
                raise ValueError(
                    f"conflicting proxy ports for endpoint {e} slot "
                    f"{(key.dest_port, key.nexthdr, d)}: "
                    f"{prev} vs {entry.proxy_port}"
                )
            l4_meta[e, d, j] |= np.uint32(entry.proxy_port << 1)
            if key.identity == 0:
                l4_meta[e, d, j] |= np.uint32(1)
                idx = int(L4H_WILD_IDX)
            else:
                idx = _id_idx(key.identity)
                l4_allow_bits[e, d, j, idx >> 5] |= np.uint32(
                    1 << (idx & 31)
                )
            h_ep.append(e)
            h_d.append(d)
            h_idx.append(idx)
            h_dport.append(key.dest_port)
            h_proto.append(key.nexthdr)
            h_val.append((j << 16) | entry.proxy_port)

    rows, stash, wrows, wstash = build_l4_hash_pair(
        np.asarray(h_ep, np.uint32),
        np.asarray(h_d, np.uint32),
        np.asarray(h_idx, np.uint32),
        np.asarray(h_dport, np.uint32),
        np.asarray(h_proto, np.uint32),
        np.asarray(h_val, np.uint32),
        lanes=hash_lanes,
    )
    return PolicyTables(
        id_table=id_table,
        id_direct=id_direct,
        id_lo_len=np.int32(id_lo_len),
        port_slot=port_slot,
        l4_meta=l4_meta,
        l4_allow_bits=l4_allow_bits,
        l3_allow_bits=l3_allow_bits,
        l4_hash_rows=rows,
        l4_hash_stash=stash,
        l4_wild_rows=wrows,
        l4_wild_stash=wstash,
    )


def compile_map_states(
    states: Sequence[PolicyMapState],
    identity_ids: Sequence[int],
    identity_pad: int = 1024,
    filter_pad: int = 64,
    hash_lanes: int = L4H_LANES,
) -> PolicyTables:
    """One-shot: build the shared identity table and lower E states."""
    return lower_map_state(
        states,
        build_id_table(identity_ids, identity_pad),
        filter_pad,
        hash_lanes=hash_lanes,
    )


class FleetCompiler:
    """Incremental fleet lowering — the delta-compilation seam.

    The one-shot path rebuilds everything per policy event: the 32 MB
    `port_slot`, the direct identity index, and every endpoint's bit
    rows — O(fleet) per event (SURVEY §7 hard part 4; the reference
    gates this per-endpoint with revision checks,
    pkg/endpoint/policy.go:540-552).  This compiler caches each piece
    keyed on what actually invalidates it:

      * identity universe — arrival-ordered, append-only: adding an
        identity appends an index instead of re-sorting, so existing
        bit rows stay valid.  Removing one forces a full reset (rare:
        identity GC).
      * L4 slot space — monotonic: new (dport, proto) keys append new
        slots; `port_slot` is copied-on-write only when keys appear.
      * per-endpoint rows — relowered only when the endpoint's
        `state_token` changes (the endpoint bumps it in
        sync_policy_map); stacked rows are padded up lazily when the
        identity/slot buckets grow.

    The produced PolicyTables are bit-compatible with the engine but
    NOT byte-identical to compile_map_states (slot and identity order
    differ); verdicts are identical — tests compare through the
    engine/oracle, never raw tables.
    """

    def __init__(
        self,
        identity_pad: int = 1024,
        filter_pad: int = 64,
        hash_lanes: int = L4H_LANES,
    ) -> None:
        self.identity_pad = identity_pad
        self.filter_pad = filter_pad
        # hot-plane pack width of the hashed entry tables; fixed for
        # the compiler's lifetime (the delta machinery's row/stash
        # state is lane-width-specific)
        self.hash_lanes = hash_lanes
        # publish generation: tables one generation old are intact
        # (double buffering); older ones may have been mutated in
        # place.  Survives _reset() — it counts publishes, not state.
        # The instance nonce scopes stamps to THIS compiler: stamps
        # from another FleetCompiler are not comparable and the check
        # must not apply its arithmetic to them.
        self._generation = 0
        self._instance_nonce = next(_COMPILER_NONCE)
        # compile() mutates every cache (slot table, universe, row
        # cache, stack buffers); callers run from both the daemon's
        # trigger thread and test/bench main threads, so serialize —
        # a concurrent _reset() mid-_lower_rows otherwise drops slots
        # out from under the lowering loop.
        self._compile_lock = threading.Lock()
        self._reset()

    def set_hash_lanes(self, lanes: int) -> None:
        """Online pack-width change (the autotuner's re-tune knob,
        applied WITHOUT a compiler reset): swap in a fresh
        IncrementalHashPair at the new width.  The fresh pair's
        empty row state forces build()'s full-rebuild branch on the
        next compile, so the produced tables carry a different
        layout stamp (tables_layout_version folds the lane counts)
        — the device store's layout guard then refuses the delta,
        full-uploads, and deltas resume on the publishes after.
        Everything else (identity universe, slot space, cached
        endpoint rows, the generation counter) is lane-agnostic and
        survives, so verdicts are identical by construction."""
        from cilium_tpu.compiler.delta import IncrementalHashPair

        with self._compile_lock:
            if int(lanes) == self.hash_lanes:
                return
            self.hash_lanes = int(lanes)
            self._hash_pair = IncrementalHashPair(
                lanes=self.hash_lanes
            )

    def _reset(self) -> None:
        from cilium_tpu.compiler.delta import IncrementalHashPair

        # monotone reset marker: a reset mid-compile (identity
        # removal) invalidates every delta precondition captured
        # before it
        self._reset_count = getattr(self, "_reset_count", 0) + 1
        self._id_list: List[int] = []
        self._id_index: Dict[int, int] = {}
        self._slot_of: Dict[Tuple[int, int], int] = {}
        self._slot_list: List[Tuple[int, int]] = []  # arrival order
        # delta-publication state: the incremental hashed-table pair,
        # the last compile's shape class, the per-publish change
        # records delta_for merges, and the caller-provided universe
        # version that short-circuits _sync_universe
        self._hash_pair = IncrementalHashPair(lanes=self.hash_lanes)
        self._shape_state: Optional[dict] = None
        self._pub_records = deque(maxlen=8)
        self._universe_token = None
        # (len, sorted_pairs, order) cache for _slot_pair_lut
        self._slot_lut_cache = None
        # double-buffered port_slot: each buffer tracks how many slots
        # it has applied; updates write only the new cells
        self._port_slot_bufs = [
            {
                "arr": np.full((256, 65536), NO_SLOT, dtype=np.uint16),
                "applied": 0,
            }
            for _ in range(2)
        ]
        self._port_slot_flip = 0
        # cached per-endpoint rows: ep_id → dict(token, kg, w, meta,
        # l4, l3)
        self._rows: Dict[int, dict] = {}
        # double-buffered stacked tensors (the realized/backup map
        # shuffle of pkg/datapath/ipcache/listener.go:167): each
        # buffer records the token its copy of every endpoint's rows
        # reflects, so a delta compile copies only rows that moved
        # since THIS buffer was last published.  Consumers may hold
        # the previously-published tables safely for one flip.
        self._stack_bufs: List[Optional[dict]] = [None, None]
        self._stack_flip = 0
        self._id_table: np.ndarray = None  # rebuilt lazily
        self._id_direct: np.ndarray = None
        self._id_lo_len: int = 0
        self._id_tables_dirty = True

    # -- identity universe ---------------------------------------------------

    def _sync_universe(self, identity_ids: Sequence[int]) -> None:
        want = set(int(i) for i in identity_ids)
        have = self._id_index.keys()
        if not want >= have:
            # removal: indices would shift — full reset
            self._reset()
            want = set(int(i) for i in identity_ids)
        new = want - self._id_index.keys()
        if new:
            for num_id in sorted(new):
                self._id_index[num_id] = len(self._id_list)
                self._id_list.append(num_id)
            self._id_tables_dirty = True

    def _padded_n(self) -> int:
        n = _round_up(
            max(len(self._id_list), 1), self.identity_pad
        )
        return _round_up(n, 32)

    def identity_index(self) -> Tuple[Dict[int, int], int]:
        """(identity id → dense index, padded identity count) for the
        CURRENT universe — the same index space as the produced
        tables' id_direct.  Consumers compiling parallel per-identity
        tensors (the L7 ident_rules) MUST use this, not a sorted
        rebuild, or their identity axes diverge from the engine's."""
        return dict(self._id_index), self._padded_n()

    def _ensure_id_tables(self) -> None:
        if not self._id_tables_dirty and self._id_table is not None:
            return
        n = self._padded_n()
        if n >= int(L4H_WILD_IDX):
            raise ValueError(
                "identity axis too large for the hashed L4 probe "
                f"(n={n}, cap={int(L4H_WILD_IDX)})"
            )
        table = np.full((n,), PAD_ID, dtype=np.uint32)
        table[: len(self._id_list)] = np.asarray(
            self._id_list, dtype=np.uint32
        )
        # arrival order ≠ sorted: build the direct index from the
        # arrival-ordered table (never via build_id_table, which sorts)
        ids = np.asarray(self._id_list, dtype=np.int64)
        index = np.arange(len(ids), dtype=np.uint32)
        local_mask = ids >= LOCAL_ID_BASE
        lo_ids, lo_idx = ids[~local_mask], index[~local_mask]
        local_ids = ids[local_mask] - LOCAL_ID_BASE
        local_idx = index[local_mask]
        lo_max = int(lo_ids.max()) + 1 if len(lo_ids) else 1
        ll_max = int(local_ids.max()) + 1 if len(local_ids) else 1
        if lo_max > MAX_DIRECT or ll_max > MAX_DIRECT:
            raise ValueError(
                f"identity id range too large for direct indexing "
                f"(lo={lo_max}, local={ll_max}, cap={MAX_DIRECT})"
            )
        lo_len = _pow2_at_least(lo_max, 1024)
        ll_len = _pow2_at_least(ll_max, 32)
        direct = np.full(lo_len + ll_len, NO_INDEX, dtype=np.uint32)
        direct[lo_ids] = lo_idx
        direct[lo_len + local_ids] = local_idx
        self._id_table = table
        self._id_direct = direct
        self._id_lo_len = lo_len
        self._id_tables_dirty = False

    # -- slot space ----------------------------------------------------------

    def _ensure_slots(self, state: PolicyMapState) -> bool:
        """Append slots for unseen (dport, proto) keys.  Returns True
        if the slot space grew."""
        grew = False
        if isinstance(state, MapStateArrays):
            _, dport, proto, _ = unpack_keys(state.keys_packed)
            nonl3 = (dport != 0) | (proto != 0)
            pairs = np.unique(
                (dport[nonl3].astype(np.int64) << 8) | proto[nonl3]
            )
            for p in pairs.tolist():
                key = (p >> 8, p & 0xFF)
                if key not in self._slot_of:
                    self._slot_of[key] = len(self._slot_list)
                    self._slot_list.append(key)
                    grew = True
            return grew
        for k in state:
            if k.is_l3_only():
                continue
            key = (k.dest_port, k.nexthdr)
            if key not in self._slot_of:
                self._slot_of[key] = len(self._slot_list)
                self._slot_list.append(key)
                grew = True
        return grew

    def _current_port_slot(self) -> np.ndarray:
        """Flip to the standby port_slot buffer and catch it up with
        the slots appended since it was last published (cells are
        written exactly once, so catching up is O(new slots))."""
        buf = self._port_slot_bufs[self._port_slot_flip]
        if buf["applied"] == len(self._slot_list):
            return buf["arr"]
        self._port_slot_flip ^= 1
        buf = self._port_slot_bufs[self._port_slot_flip]
        for j in range(buf["applied"], len(self._slot_list)):
            dport, proto = self._slot_list[j]
            buf["arr"][proto & 0xFF, dport] = j
        buf["applied"] = len(self._slot_list)
        return buf["arr"]

    def _padded_kg(self) -> int:
        return _round_up(
            max(len(self._slot_of), 1), self.filter_pad
        )

    def _slot_pair_lut(self) -> Tuple[np.ndarray, np.ndarray]:
        """(sorted packed (dport<<8|proto) pairs, slot index by sorted
        position) for the vectorized slot lookup — rebuilt only when
        the append-only slot list grows."""
        cached = self._slot_lut_cache
        if cached is not None and cached[0] == len(self._slot_list):
            return cached[1], cached[2]
        pairs = np.asarray(
            [(dp << 8) | pr for dp, pr in self._slot_list], np.int64
        )
        order = np.argsort(pairs, kind="stable")
        self._slot_lut_cache = (len(self._slot_list), pairs[order], order)
        return self._slot_lut_cache[1], self._slot_lut_cache[2]

    # -- per-endpoint rows ---------------------------------------------------

    def _lower_rows_arrays(self, state: MapStateArrays) -> dict:
        """Vectorized row lowering: the per-entry bit-set loop of
        _lower_rows as array scatters (np.bitwise_or.at) — no Python
        per-entry work.  Bit-identical rows to the dict path."""
        n = self._padded_n()
        w = n // 32
        kg = self._padded_kg()
        meta = np.zeros((2, kg), dtype=np.uint32)
        l4 = np.zeros((2, kg, w), dtype=np.uint32)
        l3 = np.zeros((2, w), dtype=np.uint32)
        m = len(state.keys_packed)
        empty_ent = {
            "d": np.zeros(0, np.uint32),
            "idx": np.zeros(0, np.uint32),
            "dport": np.zeros(0, np.uint32),
            "proto": np.zeros(0, np.uint32),
            "val": np.zeros(0, np.uint32),
        }
        if m == 0:
            return {
                "kg": kg, "w": w, "meta": meta, "l4": l4, "l3": l3,
                "ent": empty_ent,
            }

        ident, dport, proto, d = unpack_keys(state.keys_packed)
        l3_mask = (dport == 0) & (proto == 0)
        wild_mask = (ident == 0) & ~l3_mask

        # identity → dense index through the arrival-ordered direct
        # table (same derivation as the device _index kernel)
        ident64 = ident.astype(np.int64)
        is_local = ident64 >= LOCAL_ID_BASE
        pos = np.where(
            is_local,
            self._id_lo_len + ident64 - LOCAL_ID_BASE,
            ident64,
        )
        idx = np.full(m, NO_INDEX, dtype=np.uint32)
        in_range = (pos >= 0) & (pos < len(self._id_direct))
        idx[in_range] = self._id_direct[pos[in_range]]
        need_idx = ~wild_mask
        bad = need_idx & (idx == NO_INDEX)
        if bad.any():
            missing = int(ident[np.argmax(bad)])
            raise ValueError(
                f"identity {missing} in map state but not in the "
                f"identity universe (universe/table skew)"
            )
        word = (idx >> 5).astype(np.int64)
        bit = (np.uint32(1) << (idx & np.uint32(31))).astype(np.uint32)

        # -- L3-only rows -------------------------------------------------
        sel3 = l3_mask
        np.bitwise_or.at(
            l3.reshape(-1),
            d[sel3].astype(np.int64) * w + word[sel3],
            bit[sel3],
        )

        # -- L4 slots -----------------------------------------------------
        sel4 = ~l3_mask
        ent = empty_ent
        if sel4.any():
            sorted_pairs, order = self._slot_pair_lut()
            pair = (dport[sel4].astype(np.int64) << 8) | proto[sel4]
            j = order[np.searchsorted(sorted_pairs, pair)].astype(
                np.int64
            )
            dj = d[sel4].astype(np.int64) * kg + j
            proxy4 = state.proxy[sel4].astype(np.int64)
            # proxy-consistency check (one L4Filter per slot): any slot
            # carrying two distinct proxy values is a lowering error
            uniq_pairs = np.unique(
                np.stack([dj, proxy4], axis=1), axis=0
            )
            if len(np.unique(uniq_pairs[:, 0])) != len(uniq_pairs):
                dup = uniq_pairs[:, 0][
                    np.nonzero(np.diff(uniq_pairs[:, 0]) == 0)[0][0]
                ]
                jj = int(dup) % kg
                dd = int(dup) // kg
                dpp, prr = self._slot_list[jj]
                raise ValueError(
                    f"conflicting proxy ports for slot "
                    f"{(dpp, prr, dd)}"
                )
            np.bitwise_or.at(
                meta.reshape(-1),
                dj,
                (proxy4.astype(np.uint32) << np.uint32(1))
                | wild_mask[sel4].astype(np.uint32),
            )
            setbits = sel4 & ~wild_mask
            if setbits.any():
                dj_all = np.full(m, -1, np.int64)
                dj_all[sel4] = dj
                np.bitwise_or.at(
                    l4.reshape(-1),
                    (dj_all[setbits]) * w + word[setbits],
                    bit[setbits],
                )
            # hashed-probe entry columns (ep bits added at stack time
            # — the endpoint's stack position is not known here)
            ent_idx = idx[sel4].copy()
            ent_idx[wild_mask[sel4]] = L4H_WILD_IDX
            ent = {
                "d": d[sel4].astype(np.uint32),
                "idx": ent_idx.astype(np.uint32),
                "dport": dport[sel4].astype(np.uint32),
                "proto": proto[sel4].astype(np.uint32),
                "val": (
                    (j.astype(np.uint32) << np.uint32(16))
                    | proxy4.astype(np.uint32)
                ),
            }
        return {
            "kg": kg, "w": w, "meta": meta, "l4": l4, "l3": l3,
            "ent": ent,
        }

    def _lower_rows(self, state: PolicyMapState) -> dict:
        if isinstance(state, MapStateArrays):
            return self._lower_rows_arrays(state)
        n = self._padded_n()
        w = n // 32
        kg = self._padded_kg()
        meta = np.zeros((2, kg), dtype=np.uint32)
        l4 = np.zeros((2, kg, w), dtype=np.uint32)
        l3 = np.zeros((2, w), dtype=np.uint32)
        proxy_seen: Dict[Tuple[int, int], int] = {}
        h_d: List[int] = []
        h_idx: List[int] = []
        h_dport: List[int] = []
        h_proto: List[int] = []
        h_val: List[int] = []
        for key, entry in state.items():
            d = key.traffic_direction
            if key.is_l3_only():
                idx = self._id_index.get(key.identity)
                if idx is None:
                    raise ValueError(
                        f"identity {key.identity} in map state but not "
                        f"in the identity universe (universe/table skew)"
                    )
                l3[d, idx >> 5] |= np.uint32(1 << (idx & 31))
                continue
            j = self._slot_of[(key.dest_port, key.nexthdr)]
            prev = proxy_seen.setdefault((d, j), entry.proxy_port)
            if prev != entry.proxy_port:
                raise ValueError(
                    f"conflicting proxy ports for slot "
                    f"{(key.dest_port, key.nexthdr, d)}: "
                    f"{prev} vs {entry.proxy_port}"
                )
            meta[d, j] |= np.uint32(entry.proxy_port << 1)
            if key.identity == 0:
                meta[d, j] |= np.uint32(1)
                idx = int(L4H_WILD_IDX)
            else:
                idx = self._id_index.get(key.identity)
                if idx is None:
                    raise ValueError(
                        f"identity {key.identity} in map state but not "
                        f"in the identity universe (universe/table skew)"
                    )
                l4[d, j, idx >> 5] |= np.uint32(1 << (idx & 31))
            h_d.append(d)
            h_idx.append(idx)
            h_dport.append(key.dest_port)
            h_proto.append(key.nexthdr)
            h_val.append((j << 16) | entry.proxy_port)
        ent = {
            "d": np.asarray(h_d, np.uint32),
            "idx": np.asarray(h_idx, np.uint32),
            "dport": np.asarray(h_dport, np.uint32),
            "proto": np.asarray(h_proto, np.uint32),
            "val": np.asarray(h_val, np.uint32),
        }
        return {
            "kg": kg, "w": w, "meta": meta, "l4": l4, "l3": l3,
            "ent": ent,
        }

    @staticmethod
    def _pad_rows(rows: dict, kg: int, w: int) -> dict:
        """Grow cached rows to the current buckets (zero columns for
        new slots / identity words keep old bits valid)."""
        if rows["kg"] == kg and rows["w"] == w:
            return rows
        dk, dw = kg - rows["kg"], w - rows["w"]
        rows = dict(
            rows,
            kg=kg,
            w=w,
            meta=np.pad(rows["meta"], ((0, 0), (0, dk))),
            l4=np.pad(rows["l4"], ((0, 0), (0, dk), (0, dw))),
            l3=np.pad(rows["l3"], ((0, 0), (0, dw))),
        )
        return rows

    # -- compile -------------------------------------------------------------

    def compile(
        self,
        endpoints: Sequence[Tuple[int, PolicyMapState, int]],
        identity_ids: Sequence[int],
        universe_token=None,
    ) -> Tuple[PolicyTables, Dict[int, int]]:
        """Lower the fleet incrementally.

        `endpoints` is [(ep_id, realized_map_state, state_token)];
        rows are relowered only when the token differs from the cached
        one.  `universe_token`, when provided, is the caller's version
        stamp of `identity_ids` (the identity-allocator version): a
        compile whose token matches the previous one skips the
        O(universe) identity-set diff entirely — the caller warrants
        the id set is unchanged.  Returns (tables, ep_id →
        endpoint-axis index).
        """
        from cilium_tpu import tracing

        with self._compile_lock, tracing.tracer.span(
            "compiler.compile", site="compiler",
            attrs={"endpoints": len(endpoints)},
        ) as sp:
            tables, index = self._compile_locked(
                endpoints, identity_ids, universe_token
            )
            sp.attrs["identities"] = len(self._id_list)
            sp.attrs["slots"] = len(self._slot_list)
            return tables, index

    def _compile_locked(
        self,
        endpoints: Sequence[Tuple[int, PolicyMapState, int]],
        identity_ids: Sequence[int],
        universe_token=None,
    ) -> Tuple[PolicyTables, Dict[int, int]]:
        prev_id_len = len(self._id_list)
        prev_slots = len(self._slot_list)
        shape_prev = self._shape_state
        reset_before = self._reset_count
        if (
            universe_token is None
            or self._universe_token is None
            or universe_token != self._universe_token
        ):
            self._sync_universe(identity_ids)
            if self._reset_count != reset_before:  # _reset() ran
                prev_id_len = 0
                prev_slots = 0
                shape_prev = None
            self._universe_token = universe_token

        live = {ep_id for ep_id, _, _ in endpoints}
        for gone in set(self._rows) - live:
            del self._rows[gone]

        dirty = []
        for ep_id, state, token in endpoints:
            cached = self._rows.get(ep_id)
            if cached is None or cached["token"] != token:
                dirty.append((ep_id, state, token))
                self._ensure_slots(state)

        self._ensure_id_tables()
        n = self._padded_n()
        w = n // 32
        kg = self._padded_kg()

        for ep_id, state, token in dirty:
            rows = self._lower_rows(state)
            rows["token"] = token
            self._rows[ep_id] = rows

        order = sorted(live)
        index = {ep_id: i for i, ep_id in enumerate(order)}
        if order:
            for ep_id in order:
                self._rows[ep_id] = self._pad_rows(
                    self._rows[ep_id], kg, w
                )
            l4_meta, l4_bits, l3_bits = self._stacked(order, kg, w)
        else:
            l4_meta = np.zeros((1, 2, kg), dtype=np.uint32)
            l4_bits = np.zeros((1, 2, kg, w), dtype=np.uint32)
            l3_bits = np.zeros((1, 2, w), dtype=np.uint32)

        (hash_rows, hash_stash, wild_rows, wild_stash), hash_info = (
            self._hash_pair.build(
                order, self._rows, [ep_id for ep_id, _, _ in dirty]
            )
        )
        tables = PolicyTables(
            id_table=self._id_table,
            id_direct=self._id_direct,
            id_lo_len=np.int32(self._id_lo_len),
            port_slot=self._current_port_slot(),
            l4_meta=l4_meta,
            l4_allow_bits=l4_bits,
            l3_allow_bits=l3_bits,
            l4_hash_rows=hash_rows,
            l4_hash_stash=hash_stash,
            l4_wild_rows=wild_rows,
            l4_wild_stash=wild_stash,
        )
        self._generation += 1
        tables.generation = np.uint64(
            (self._instance_nonce << 32) | self._generation
        )
        self._record_publish(
            shape_prev, order, index, dirty, n, w, kg,
            prev_id_len, prev_slots, hash_info,
        )
        return tables, index

    # -- delta publication records -------------------------------------------

    def _record_publish(
        self,
        shape_prev: Optional[dict],
        order: List[int],
        index: Dict[int, int],
        dirty: list,
        n: int,
        w: int,
        kg: int,
        prev_id_len: int,
        prev_slots: int,
        hash_info: dict,
    ) -> None:
        """Append the per-publish change record delta_for merges: per
        leaf, either the indices that changed since the previous
        publish or None (= the leaf's shape class moved and it must
        ship whole)."""
        order_t = tuple(order)
        direct_len = (
            len(self._id_direct) if self._id_direct is not None else 0
        )
        shape_now = {
            "order": order_t, "kg": kg, "w": w, "n": n,
            "direct_len": direct_len, "lo_len": self._id_lo_len,
        }
        stack_full = (
            shape_prev is None
            or shape_prev["order"] != order_t
            or shape_prev["kg"] != kg
            or shape_prev["w"] != w
        )
        id_full = shape_prev is None or shape_prev["n"] != n
        direct_full = (
            shape_prev is None
            or shape_prev["direct_len"] != direct_len
            or shape_prev["lo_len"] != self._id_lo_len
        )
        new_ids = self._id_list[prev_id_len:]
        direct_pos = None
        if not direct_full:
            direct_pos = np.asarray(
                [
                    i if i < LOCAL_ID_BASE
                    else self._id_lo_len + i - LOCAL_ID_BASE
                    for i in new_ids
                ],
                np.int64,
            )
        rec = {
            "gen": self._generation,
            "stack": (
                None if stack_full
                else sorted({index[ep_id] for ep_id, _, _ in dirty})
            ),
            "id_table": (
                None if id_full else (prev_id_len, len(self._id_list))
            ),
            "id_direct": direct_pos,
            "slots": (prev_slots, len(self._slot_list)),
            "hash_exact": hash_info.get("exact"),
            "hash_exact_stash": hash_info.get("exact_stash", True),
            "hash_wild": hash_info.get("wild"),
            "hash_wild_stash": hash_info.get("wild_stash", True),
        }
        self._pub_records.append(rec)
        self._shape_state = shape_now

    def delta_for(
        self, base_stamp: Optional[int], tables: PolicyTables
    ):
        """TableDelta describing every change from the publish stamped
        `base_stamp` to `tables` (which must be THIS compiler's most
        recent compile), or None when no delta can be derived (unknown
        base, record gap, different compiler instance) and the caller
        must full-upload.  Scatter values are fresh copies taken from
        `tables` — safe to ship asynchronously."""
        from cilium_tpu import tracing
        from cilium_tpu.compiler.delta import LeafUpdate, TableDelta

        with self._compile_lock, tracing.tracer.span(
            "compiler.delta_for", site="compiler"
        ):
            if not base_stamp:
                return None
            if (base_stamp >> 32) != self._instance_nonce:
                return None
            cur_stamp = int(np.asarray(tables.generation))
            if cur_stamp != (
                (self._instance_nonce << 32) | self._generation
            ):
                return None
            layout = tables_layout_version(tables)
            base_gen = base_stamp & 0xFFFFFFFF
            if base_gen == self._generation:
                return TableDelta(base_stamp, cur_stamp, layout=layout)
            recs = [
                r for r in self._pub_records
                if base_gen < r["gen"] <= self._generation
            ]
            if len(recs) != self._generation - base_gen:
                return None  # record gap (reset or deque overflow)
            delta = TableDelta(base_stamp, cur_stamp, layout=layout)
            delta.replace["generation"] = np.uint64(cur_stamp)

            def scatter1(name, arr, idx_list):
                idx = np.asarray(sorted(idx_list), np.int64)
                if len(idx):
                    delta.updates[name] = LeafUpdate(
                        (idx,), arr[idx]
                    )

            # stacked per-endpoint rows
            if any(r["stack"] is None for r in recs):
                delta.replace["l4_meta"] = tables.l4_meta
                delta.replace["l4_allow_bits"] = tables.l4_allow_bits
                delta.replace["l3_allow_bits"] = tables.l3_allow_bits
            else:
                rows = set()
                for r in recs:
                    rows.update(r["stack"])
                scatter1("l4_meta", tables.l4_meta, rows)
                scatter1("l4_allow_bits", tables.l4_allow_bits, rows)
                scatter1("l3_allow_bits", tables.l3_allow_bits, rows)

            # identity universe
            if any(r["id_table"] is None for r in recs):
                delta.replace["id_table"] = tables.id_table
            else:
                lo = min(r["id_table"][0] for r in recs)
                hi = max(r["id_table"][1] for r in recs)
                if hi > lo:
                    delta.updates["id_table"] = LeafUpdate(
                        (np.arange(lo, hi, dtype=np.int64),),
                        tables.id_table[lo:hi].copy(),
                    )
            if any(r["id_direct"] is None for r in recs):
                delta.replace["id_direct"] = tables.id_direct
                delta.replace["id_lo_len"] = np.int32(
                    self._id_lo_len
                )
            else:
                pos = np.unique(
                    np.concatenate(
                        [r["id_direct"] for r in recs]
                        + [np.zeros(0, np.int64)]
                    )
                )
                if len(pos):
                    delta.updates["id_direct"] = LeafUpdate(
                        (pos,), tables.id_direct[pos]
                    )

            # (proto, dport) → slot cells: append-only, write-once
            slot_lo = min(r["slots"][0] for r in recs)
            slot_hi = max(r["slots"][1] for r in recs)
            if slot_hi > slot_lo:
                cells = self._slot_list[slot_lo:slot_hi]
                delta.updates["port_slot"] = LeafUpdate(
                    (
                        np.asarray(
                            [pr & 0xFF for _, pr in cells], np.int64
                        ),
                        np.asarray([dp for dp, _ in cells], np.int64),
                    ),
                    np.arange(slot_lo, slot_hi, dtype=np.uint16),
                )

            # hashed entry tables
            for leaf, stash_leaf, key in (
                ("l4_hash_rows", "l4_hash_stash", "hash_exact"),
                ("l4_wild_rows", "l4_wild_stash", "hash_wild"),
            ):
                arr = getattr(tables, leaf)
                if any(r[key] is None for r in recs) or (
                    arr.shape[0]
                    != getattr(
                        self._hash_pair,
                        "exact" if key == "hash_exact" else "wild",
                    ).n_rows
                ):
                    delta.replace[leaf] = arr
                    delta.replace[stash_leaf] = getattr(
                        tables, stash_leaf
                    )
                    continue
                rows = set()
                for r in recs:
                    rows.update(r[key])
                scatter1(leaf, arr, rows)
                if any(r[key + "_stash"] for r in recs):
                    delta.replace[stash_leaf] = getattr(
                        tables, stash_leaf
                    )
            return delta

    def check_tables_current(self, tables) -> None:
        """Enforce the documented one-flip staleness window on the
        STACKED tensors (l4_meta/l4_allow_bits/l3_allow_bits): tables
        produced two or more compiles ago share stack buffers that
        have been rewritten in place — evaluating flows against them
        returns wrong verdicts silently.  (id_table/id_direct are
        freshly allocated per rebuild and port_slot cells are
        write-once, so *reading the index tables* of a stale snapshot
        stays safe; the hazard is the per-endpoint rows.)

        Raises ValueError on violation; tables without a generation
        stamp (hand-built via lower_map_state, generation=0) or
        stamped by a different FleetCompiler instance are accepted —
        the stamp is instance-scoped.  It is a pytree child, so it
        survives device_put / flatten round trips."""
        raw = getattr(tables, "generation", None)
        stamp = int(np.asarray(raw)) if raw is not None else 0
        if stamp == 0 or (stamp >> 32) != self._instance_nonce:
            return
        gen = stamp & 0xFFFFFFFF
        if self._generation - gen > 1:
            raise ValueError(
                f"stale PolicyTables: generation {gen} is "
                f"{self._generation - gen} publishes old (max 1 — "
                f"double-buffered rows have been overwritten)"
            )

    def _stacked(self, order: List[int], kg: int, w: int):
        """Write rows into the standby stacked buffer, copying only
        endpoints whose token differs from what this buffer already
        holds.  A full np.stack happens only when the endpoint set or
        the padded shapes change."""
        self._stack_flip ^= 1
        buf = self._stack_bufs[self._stack_flip]
        shape_key = (tuple(order), kg, w)
        if buf is None or buf["shape_key"] != shape_key:
            e = len(order)
            buf = {
                "shape_key": shape_key,
                "meta": np.empty((e, 2, kg), dtype=np.uint32),
                "l4": np.empty((e, 2, kg, w), dtype=np.uint32),
                "l3": np.empty((e, 2, w), dtype=np.uint32),
                "tokens": {},
            }
            self._stack_bufs[self._stack_flip] = buf
        tokens = buf["tokens"]
        for i, ep_id in enumerate(order):
            rows = self._rows[ep_id]
            if tokens.get(ep_id) == rows["token"]:
                continue
            buf["meta"][i] = rows["meta"]
            buf["l4"][i] = rows["l4"]
            buf["l3"][i] = rows["l3"]
            tokens[ep_id] = rows["token"]
        # pre-warm the standby buffer: its first full clone happens
        # at full-(re)stack time, so the next publish copies only the
        # endpoints dirtied in between instead of the whole fleet
        other_i = self._stack_flip ^ 1
        other = self._stack_bufs[other_i]
        if other is None or other["shape_key"] != shape_key:
            self._stack_bufs[other_i] = {
                "shape_key": shape_key,
                "meta": buf["meta"].copy(),
                "l4": buf["l4"].copy(),
                "l3": buf["l3"].copy(),
                "tokens": dict(tokens),
            }
        return buf["meta"], buf["l4"], buf["l3"]
