"""Mesh-sharded verdict evaluation (SPMD over batch × identity axes).

Two parallel axes, mirroring §2.9 of SURVEY.md:

  * `batch` — data parallelism over flow tuples (packets shard across
    nodes in the reference; zero-communication).
  * `table` — the identity (bit-word) axis of the allow tensors is
    sharded when the rule/identity tensors exceed a single chip's HBM
    (a 512k-identity universe × 16k L4 slots would not fit).  The
    small index tables (id_direct/port_slot) replicate
    and resolve a tuple's *global* identity index; each shard then
    tests only the bit-words it owns, and probe hits combine with a
    psum over the axis — the "verdict lattice psum" described in
    SURVEY.md §5 (0/1 hits, associative, order-safe).

The step also accumulates per-entry packet counters (policy_entry
packets, bpf/lib/policy.h:66-68): L4-slot counters replicate, L3
per-identity counters stay sharded along `table`.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from cilium_tpu.compiler.tables import PolicyTables
from cilium_tpu.engine.oracle import MATCH_L3, MATCH_L4, MATCH_L4_WILD
from cilium_tpu.engine.verdict import (
    TupleBatch,
    Verdicts,
    _combine,
    _index,
)


def table_specs(batch_axis: str, table_axis: str) -> PolicyTables:
    """PartitionSpecs for a PolicyTables pytree: allow-bit word axes
    sharded along `table_axis`, index tables replicated."""
    return PolicyTables(
        id_table=P(),
        id_direct=P(),
        id_lo_len=P(),
        port_slot=P(),
        l4_meta=P(),
        l4_allow_bits=P(None, None, None, table_axis),
        l3_allow_bits=P(None, None, table_axis),
        generation=P(),
        # the hashed entry table is a single-chip layout (row buckets
        # mix all identities); the table-sharded evaluator replicates
        # it untouched and probes the dense sharded bitmap instead
        l4_hash_rows=P(),
        l4_hash_stash=P(),
        l4_wild_rows=P(),
        l4_wild_stash=P(),
    )


def replicated_table_shardings(mesh: Mesh) -> PolicyTables:
    """NamedShardings replicating every PolicyTables leaf across the
    mesh — the layout make_sharded_evaluator consumes (tables
    replicate like per-node BPF maps)."""
    r = NamedSharding(mesh, P())
    return PolicyTables(
        id_table=r, id_direct=r, id_lo_len=r, port_slot=r,
        l4_meta=r, l4_allow_bits=r, l3_allow_bits=r, generation=r,
        l4_hash_rows=r, l4_hash_stash=r, l4_wild_rows=r,
        l4_wild_stash=r,
    )


def make_replicated_store(mesh: Mesh):
    """DeviceTableStore whose epochs replicate across `mesh`: one
    delta publish applies the same in-place scatter on EVERY chip
    (tables are replicated, so each chip's copy receives identical
    `.at[idx].set(rows)` updates inside one SPMD program)."""
    from cilium_tpu.engine.publish import DeviceTableStore

    return DeviceTableStore(
        shardings=replicated_table_shardings(mesh)
    )


def batch_specs(batch_axis: str) -> TupleBatch:
    s = P(batch_axis)
    return TupleBatch(
        ep_index=s, identity=s, dport=s, proto=s, direction=s, is_fragment=s
    )


def _counts_and_telemetry(
    v,
    tables_l,
    batch_l,
    j,
    idx,
    p2_local,
    word_off,
    w_local,
    batch_axis,
    collect_telemetry,
):
    """Shared counter + per-chip telemetry epilogue of the mesh and
    partitioned evaluators.  The bit-identity contract across the
    fused kernel and both mesh evaluators depends on there being ONE
    copy of this logic: L4-slot hits come from globally-combined
    verdict columns (identical on every table shard), the L3 hit
    counter stays shard-local (`p2_local` true only on the identity
    word's owner, `word_off` that shard's first bit-word), and the
    [2, T] stage rows reduce from the same telemetry_masks set the
    single-chip instrumented kernels fuse."""
    e_count, _, kg = tables_l.l4_meta.shape
    hit_l4 = (v.match_kind == MATCH_L4) | (
        v.match_kind == MATCH_L4_WILD
    )
    l4_counts = jnp.zeros((e_count, 2, kg), jnp.uint32).at[
        batch_l.ep_index, batch_l.direction, j
    ].add(hit_l4.astype(jnp.uint32))
    l3_hit_here = p2_local & (v.match_kind == MATCH_L3)
    idx_l = jnp.clip(idx - word_off * 32, 0, w_local * 32 - 1)
    l3_counts = jnp.zeros(
        (e_count, 2, w_local * 32), jnp.uint32
    ).at[
        batch_l.ep_index, batch_l.direction, idx_l
    ].add(l3_hit_here.astype(jnp.uint32))
    l4_counts = jax.lax.psum(l4_counts, batch_axis)
    l3_counts = jax.lax.psum(l3_counts, batch_axis)
    if not collect_telemetry:
        return v, l4_counts, l3_counts

    from cilium_tpu.engine.verdict import telemetry_masks

    zeros = jnp.zeros(v.allowed.shape, jnp.int32)
    masks = telemetry_masks(
        zeros, zeros, v.match_kind, v.allowed, zeros,
        v.proxy_port, zeros, zeros,
    )
    ingress = batch_l.direction == 0
    row_in = jnp.stack(
        [jnp.sum(m & ingress, dtype=jnp.uint32) for m in masks]
    )
    col_total = jnp.stack(
        [jnp.sum(m, dtype=jnp.uint32) for m in masks]
    )
    trow = jnp.stack([row_in, col_total - row_in])
    return v, l4_counts, l3_counts, trow[None]


def make_mesh_evaluator(
    mesh: Mesh,
    batch_axis: str = "batch",
    table_axis: str = "table",
    collect_telemetry: bool = False,
):
    """Jitted full datapath step over a 2D (batch × table) mesh.

    Returns fn(tables, batch) -> (Verdicts, l4_counts, l3_counts):
      l4_counts u32 [E, 2, Kg]       replicated
      l3_counts u32 [E, 2, N]        sharded along identity (table) axis

    With `collect_telemetry` the step additionally returns a
    PER-CHIP stage histogram u32 [n_batch_shards, 2, TELEM_COLS]:
    each batch shard reduces its own [2, T] rows inside the dispatch
    (the same ~20 masked sums the single-chip instrumented kernels
    fuse, from the SAME telemetry_masks definition set) and the rows
    all-gather along the batch axis — so ONE host fold
    (telemetry.fold_telemetry_per_chip) yields both the mesh-total
    counters and the `chip`-labeled per-chip rows of the ROADMAP's
    multi-chip aggregation item.  The lattice path carries no
    LB/CT/prefilter stages; their columns fold as zeros, exactly
    what they contribute on this path."""
    t_specs = table_specs(batch_axis, table_axis)
    b_specs = batch_specs(batch_axis)
    v_specs = Verdicts(
        allowed=P(batch_axis),
        proxy_port=P(batch_axis),
        match_kind=P(batch_axis),
    )
    out_specs = (v_specs, P(), P(None, None, table_axis))
    if collect_telemetry:
        out_specs = out_specs + (P(batch_axis, None, None),)

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(t_specs, b_specs),
        out_specs=out_specs,
        check_vma=False,
    )
    def step(tables_l: PolicyTables, batch_l: TupleBatch):
        # Index resolution uses only replicated tables → global values.
        idx, word, bit, known, j, has_port = _index(tables_l, batch_l)
        # slot metadata from the replicated l4_meta (the fused
        # single-chip path reads it from the hashed entry table)
        meta = tables_l.l4_meta[batch_l.ep_index, batch_l.direction, j]
        proxy = (meta >> 1).astype(jnp.int32)
        wild = (meta & 1).astype(bool)

        # This shard owns bit-words [off, off + w_local).
        w_local = tables_l.l3_allow_bits.shape[-1]
        off = jax.lax.axis_index(table_axis) * w_local
        wl = word - off
        in_shard = (wl >= 0) & (wl < w_local)
        wl = jnp.clip(wl, 0, w_local - 1)

        exact_words = tables_l.l4_allow_bits[
            batch_l.ep_index, batch_l.direction, j, wl
        ]
        p1 = (
            known
            & has_port
            & in_shard
            & ((exact_words >> bit) & 1).astype(bool)
        )
        l3_words = tables_l.l3_allow_bits[
            batch_l.ep_index, batch_l.direction, wl
        ]
        p2 = known & in_shard & ((l3_words >> bit) & 1).astype(bool)
        p3 = wild & has_port  # identity-independent: same in all shards

        # Combine probe hits across identity shards: each identity is
        # resident in exactly one shard, so the sums are 0/1.
        p1g = jax.lax.psum(p1.astype(jnp.int32), table_axis) > 0
        p2g = jax.lax.psum(p2.astype(jnp.int32), table_axis) > 0

        v = _combine(p1g, p2g, p3, proxy, batch_l.is_fragment)

        return _counts_and_telemetry(
            v, tables_l, batch_l, j, idx, p2, off, w_local,
            batch_axis, collect_telemetry,
        )

    in_shardings = (
        jax.tree.map(lambda s: NamedSharding(mesh, s), t_specs),
        jax.tree.map(lambda s: NamedSharding(mesh, s), b_specs),
    )
    return jax.jit(step, in_shardings=in_shardings)


def make_partitioned_store(
    mesh: Mesh,
    table_axis: str = "table",
    hot_only: bool = False,
):
    """DeviceTableStore whose epochs PARTITION across `mesh` under
    the declarative rule table (compiler/partition.py): the
    identity-major leaves — hashed L4 entry rows, L3/L4 allow-bit
    words — each live on exactly one chip's HBM slice, small leaves
    replicate, and a delta publish scatters each payload into the
    OWNING chip's shard only (the scatter runs over the sharded
    resident pytree, so XLA routes every row to the chip that holds
    it — no full-table re-upload, no cross-chip copies of unchanged
    rows).  The rule-table digest is folded into every epoch's
    layout stamp."""
    from cilium_tpu.compiler import partition
    from cilium_tpu.engine.publish import DeviceTableStore

    return DeviceTableStore(
        shardings_fn=lambda tables: partition.table_shardings(
            mesh, tables, table_axis
        ),
        partition_digest=partition.partition_digest(
            partition.default_table_rules(table_axis)
        ),
        hot_only=hot_only,
    )


def make_partitioned_evaluator(
    mesh: Mesh,
    tables: PolicyTables,
    batch_axis: str = "batch",
    table_axis: str = "table",
    collect_telemetry: bool = False,
):
    """Routed-gather evaluator over identity-SHARDED tables.

    Where make_mesh_evaluator shards only the dense bitmap word axis
    and replicates the hashed entry plane, this evaluator consumes
    the declarative rule table (compiler/partition.py): the hashed
    L4 entry rows shard along the bucket-row axis and the L3 words
    along the identity word axis, so per-chip HBM holds ~1/num_shards
    of the identity-major bytes — the refactor that lifts the
    universe cap past one chip.

    Routing: inside shard_map each tuple's global bucket/word index
    is offset into the local shard; the shard that OWNS the row
    gathers it (everyone else contributes a masked zero) and the
    verdict columns return to the originating batch shard through
    one integer psum per probe — bit-identical to the replicated
    evaluator at every mesh size because each key lives in exactly
    one shard, so the sums are exact 0/1 combinations (the same
    argument as make_mesh_evaluator's psum lattice).

    `tables` supplies the SHAPES the partition layout is derived
    from (which leaves divide evenly, bucket/word counts); the
    returned fn(tables, batch) is jitted against those shapes.
    Requires the hashed entry pair (FleetCompiler always builds it).

    Returns fn(tables, batch) -> (Verdicts, l4_counts, l3_counts
    [, per-chip telemetry rows]) with the same output contract as
    make_mesh_evaluator."""
    from cilium_tpu.compiler.partition import (
        divisible_partition_specs,
    )
    from cilium_tpu.compiler.tables import L4H_WILD_IDX
    from cilium_tpu.engine.hashtable import fnv1a_device
    from cilium_tpu.engine.verdict import (
        MATCH_L3,
        _index_identity,
        _l4hash_probe,
        l4hash_probe_keys,
        l4hash_row_parts,
        l4hash_stash_parts,
        l4hash_value_decode,
    )

    if tables.l4_hash_rows is None:
        raise ValueError(
            "partitioned evaluator requires the hashed L4 entry "
            "tables (hand-built dense tables: use "
            "make_mesh_evaluator)"
        )
    ntp = int(mesh.shape[table_axis])
    t_specs = divisible_partition_specs(tables, ntp, table_axis)
    # static layout facts the kernel routes by (closure, not traced)
    rows_sharded = table_axis in tuple(
        ax for ax in t_specs.l4_hash_rows
    )
    l3_sharded = table_axis in tuple(
        ax for ax in t_specs.l3_allow_bits
    )
    n_rows_global = int(tables.l4_hash_rows.shape[0])

    b_specs = batch_specs(batch_axis)
    v_specs = Verdicts(
        allowed=P(batch_axis),
        proxy_port=P(batch_axis),
        match_kind=P(batch_axis),
    )
    l3c_spec = P(None, None, table_axis) if l3_sharded else P()
    out_specs = (v_specs, P(), l3c_spec)
    if collect_telemetry:
        out_specs = out_specs + (P(batch_axis, None, None),)

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(t_specs, b_specs),
        out_specs=out_specs,
        check_vma=False,
    )
    def step(tables_l: PolicyTables, batch_l: TupleBatch):
        # identity index from the replicated direct table (global)
        idx, known = _index_identity(tables_l, batch_l)
        proto = jnp.clip(batch_l.proto, 0, 255).astype(jnp.int32)
        dport = jnp.clip(batch_l.dport, 0, 65535).astype(jnp.int32)

        # -- routed exact probe: the bucket row lives on ONE shard ------
        from cilium_tpu.compiler.tables import l4_entry_words

        entry_words = l4_entry_words(tables_l)
        w0, w1 = l4hash_probe_keys(
            entry_words, batch_l.ep_index, batch_l.direction,
            idx.astype(jnp.uint32), dport, proto,
        )
        h = fnv1a_device(jnp.stack([w0, w1], axis=1))
        bucket = (h & jnp.uint32(n_rows_global - 1)).astype(jnp.int32)
        rows_l = tables_l.l4_hash_rows
        n_local = rows_l.shape[0]
        if rows_sharded:
            off = jax.lax.axis_index(table_axis) * n_local
            bl = bucket - off
            owns = (bl >= 0) & (bl < n_local)
            bl = jnp.clip(bl, 0, n_local - 1)
        else:
            owns = jnp.ones(bucket.shape, bool)
            bl = bucket
        # local gather: only the owning shard's hit
        found_local, val_local = l4hash_row_parts(
            rows_l[bl], w0, w1, entry_words, owns=owns
        )
        if rows_sharded:
            # return the verdict column to the originating shard:
            # the key lives in exactly one shard, so the sums are
            # exact (this psum pair is the all-to-all traffic per
            # tuple of the row-sharded layout)
            val1 = jax.lax.psum(val_local, table_axis)
            found1 = (
                jax.lax.psum(
                    found_local.astype(jnp.int32), table_axis
                )
                > 0
            )
        else:
            val1, found1 = val_local, found_local
        # overflow stash replicates (≤64 rows): same on every shard
        s_found, s_val = l4hash_stash_parts(
            tables_l.l4_hash_stash, w0, w1, entry_words
        )
        val1 = val1 + s_val
        found1 = found1 | s_found

        # -- wildcard probe: identity-free, tiny, replicated ------------
        wild_idx = jnp.full(
            idx.shape, jnp.uint32(L4H_WILD_IDX), jnp.uint32
        )
        hit3, val3 = _l4hash_probe(
            tables_l.l4_wild_rows, tables_l.l4_wild_stash,
            batch_l.ep_index, batch_l.direction, wild_idx,
            dport, proto,
        )
        probe1 = known & found1
        probe3 = hit3
        proxy, j = l4hash_value_decode(
            tables_l, batch_l.ep_index, batch_l.direction,
            probe1, val1, hit3, val3, entry_words,
        )

        # -- routed L3 probe: the identity's bit-word has one owner -----
        word = idx >> 5
        bit = (idx & 31).astype(jnp.uint32)
        w_local = tables_l.l3_allow_bits.shape[-1]
        if l3_sharded:
            offw = jax.lax.axis_index(table_axis) * w_local
            wl = word - offw
            owns_w = (wl >= 0) & (wl < w_local)
            wl = jnp.clip(wl, 0, w_local - 1)
        else:
            offw = 0
            owns_w = jnp.ones(word.shape, bool)
            wl = word
        l3_words = tables_l.l3_allow_bits[
            batch_l.ep_index, batch_l.direction, wl
        ]
        p2_local = (
            known & owns_w & ((l3_words >> bit) & 1).astype(bool)
        )
        if l3_sharded:
            probe2 = (
                jax.lax.psum(p2_local.astype(jnp.int32), table_axis)
                > 0
            )
        else:
            probe2 = p2_local

        v = _combine(probe1, probe2, probe3, proxy,
                     batch_l.is_fragment)

        return _counts_and_telemetry(
            v, tables_l, batch_l, j, idx, p2_local, offw, w_local,
            batch_axis, collect_telemetry,
        )

    in_shardings = (
        jax.tree.map(
            lambda s: NamedSharding(mesh, s), t_specs,
            is_leaf=lambda x: isinstance(x, P),
        ),
        jax.tree.map(lambda s: NamedSharding(mesh, s), b_specs),
    )
    jitted = jax.jit(step, in_shardings=in_shardings)
    # the routing mask (n_rows_global) and shard flags are closure
    # constants derived from the build-time shapes; a retrace on
    # different shapes would route buckets with a stale mask and
    # silently mis-verdict, so refuse loudly instead
    built_geom = (
        tuple(tables.l4_hash_rows.shape),
        tuple(tables.l3_allow_bits.shape),
    )

    def run(tables_in: PolicyTables, batch: TupleBatch):
        if tables_in.l4_hash_rows is None:
            raise ValueError(
                "partitioned evaluator requires the hashed L4 "
                "entry tables"
            )
        got = (
            tuple(tables_in.l4_hash_rows.shape),
            tuple(tables_in.l3_allow_bits.shape),
        )
        if got != built_geom:
            raise ValueError(
                "partitioned evaluator was built for table geometry "
                f"{built_geom} but called with {got}; rebuild with "
                "make_partitioned_evaluator"
            )
        return jitted(tables_in, batch)

    return run


def make_partitioned_cache(
    mesh: Mesh,
    n_rows_local: int = 1 << 10,
    entries: int = 8,
    batch_axis: str = "batch",
    table_axis: str = "table",
):
    """VerdictCache (engine/memo.py) laid out for the partitioned
    memo evaluator: rows [dp, tp, n_rows_local + 1, 5 * entries]
    sharded P(batch, table) — each chip owns its batch row's slice of
    the bucket-row space (co-located with the table shard that owns
    the same hashed rows), plus its private scratch row.  Batch rows
    warm independent copies (their tuple streams differ), so capacity
    scales with the mesh in both axes."""
    from cilium_tpu.engine.memo import (
        CACHE_WORDS,
        EMPTY,
        VerdictCache,
    )

    if n_rows_local & (n_rows_local - 1):
        raise ValueError(
            f"cache rows per shard must be a power of two: "
            f"{n_rows_local}"
        )
    dp = int(mesh.shape[batch_axis])
    tp = int(mesh.shape[table_axis])

    def factory():
        import numpy as np

        rows = np.full(
            (dp, tp, n_rows_local + 1, CACHE_WORDS * entries + 1),
            EMPTY, np.uint32,
        )
        # trailing hit-rank word (engine/memo.py layout): zeroed;
        # the partitioned kernel keeps the rotation eviction today
        # (rank maintenance is single-chip), so the word stays cold
        rows[..., -1] = 0
        return rows

    sharding = NamedSharding(mesh, P(batch_axis, table_axis))
    return VerdictCache(rows_factory=factory, sharding=sharding)


def make_partitioned_memo_evaluator(
    mesh: Mesh,
    tables: PolicyTables,
    cache_rows,
    rep_cap: int,
    miss_cap: int = None,
    batch_axis: str = "batch",
    table_axis: str = "table",
    collect_telemetry: bool = False,
):
    """make_partitioned_evaluator with the verdict-memoization plane
    in front (engine/memo.py): each batch shard dedups its own tuple
    stream in-jit, the representatives probe a cache whose bucket
    rows shard along the table axis exactly like l4_hash_rows (the
    owning chip gathers, one psum pair returns the hit + value
    words), and only the missed representatives run the routed
    lattice gathers.  Cache inserts land on the owning chip only.

    `cache_rows` fixes the cache geometry (a make_partitioned_cache
    rows array: [dp, tp, R_local + 1, 5e]); `rep_cap`/`miss_cap` are
    the per-batch-shard compaction capacities.  All tp chips of a
    mesh row compute identical dedup/probe decisions from identical
    replicated inputs, so the routing stays SPMD-uniform.

    Returns fn(tables, batch, cache_rows) -> (Verdicts, l4_counts,
    l3_counts, cache_rows', hit bool [B], stats u32 [STATS]
    [, per-chip telemetry rows]) — same counter/telemetry contract
    as make_partitioned_evaluator; when stats[STAT_OVERFLOW] != 0
    every output except cache_rows' (returned unchanged) is
    unspecified and the caller must re-dispatch through the uncached
    evaluator."""
    from cilium_tpu.compiler.partition import (
        divisible_partition_specs,
    )
    from cilium_tpu.compiler.tables import L4H_WILD_IDX
    from cilium_tpu.engine.hashtable import fnv1a_device
    from cilium_tpu.engine import memo as vm
    from cilium_tpu.engine.verdict import (
        _index_identity,
        _l4hash_probe,
        l4hash_probe_keys,
        l4hash_row_parts,
        l4hash_stash_parts,
        l4hash_value_decode,
    )

    if tables.l4_hash_rows is None:
        raise ValueError(
            "partitioned memo evaluator requires the hashed L4 "
            "entry tables"
        )
    if miss_cap is None:
        miss_cap = rep_cap
    ntp = int(mesh.shape[table_axis])
    ndp = int(mesh.shape[batch_axis])
    t_specs = divisible_partition_specs(tables, ntp, table_axis)
    rows_sharded = table_axis in tuple(
        ax for ax in t_specs.l4_hash_rows
    )
    l3_sharded = table_axis in tuple(
        ax for ax in t_specs.l3_allow_bits
    )
    n_rows_global = int(tables.l4_hash_rows.shape[0])
    cshape = tuple(cache_rows.shape)
    if cshape[0] != ndp or cshape[1] != ntp:
        raise ValueError(
            f"cache rows {cshape} do not match the mesh "
            f"({ndp}, {ntp})"
        )
    c_local = int(cshape[2]) - 1  # per-chip bucket rows (last=scratch)
    c_global = c_local * ntp
    entries = int(cshape[3]) // vm.CACHE_WORDS

    b_specs = batch_specs(batch_axis)
    v_specs = Verdicts(
        allowed=P(batch_axis),
        proxy_port=P(batch_axis),
        match_kind=P(batch_axis),
    )
    l3c_spec = P(None, None, table_axis) if l3_sharded else P()
    cache_spec = P(batch_axis, table_axis)
    out_specs = (
        v_specs, P(), l3c_spec, cache_spec, P(batch_axis), P(),
    )
    if collect_telemetry:
        out_specs = out_specs + (P(batch_axis, None, None),)

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(t_specs, b_specs, cache_spec),
        out_specs=out_specs,
        check_vma=False,
    )
    def step(tables_l: PolicyTables, batch_l: TupleBatch, cache_l):
        cache2 = cache_l[0, 0]  # [R_local + 1, 5e]
        my_col = jax.lax.axis_index(table_axis)
        idx, known = _index_identity(tables_l, batch_l)
        proto = jnp.clip(batch_l.proto, 0, 255).astype(jnp.int32)
        dport = jnp.clip(batch_l.dport, 0, 65535).astype(jnp.int32)

        # -- Level A: per-batch-shard dedup (identical on every
        # table chip of the row: same replicated inputs) --------------
        k0, k1, k2 = vm.memo_key_words(
            idx, known, None, batch_l.ep_index, batch_l.direction,
            dport, proto,
        )
        g = vm.dedup_groups(k0, k1, k2, rep_cap)
        rep_orig = g["rep_orig"]
        r = rep_orig[:rep_cap]
        rk0, rk1, rk2 = k0[r], k1[r], k2[r]

        # -- Level B: routed cache probe (bucket rows shard along
        # the table axis like l4_hash_rows) ---------------------------
        h = fnv1a_device(jnp.stack([rk0, rk1, rk2], axis=1))
        bucket = (h & jnp.uint32(c_global - 1)).astype(jnp.int32)
        if ntp > 1:
            pc = bucket // c_local
            owns_c = pc == my_col
            cl = jnp.clip(bucket - pc * c_local, 0, c_local - 1)
        else:
            pc = jnp.zeros(bucket.shape, jnp.int32)
            owns_c = jnp.ones(bucket.shape, bool)
            cl = bucket
        crow = cache2[cl]  # [U, 5e] local gather
        e = entries
        lane_hit = (
            (crow[:, :e] == rk0[:, None])
            & (crow[:, e : 2 * e] == rk1[:, None])
            & (crow[:, 2 * e : 3 * e] == rk2[:, None])
            & owns_c[:, None]
        )
        hit_local = jnp.any(lane_hit, axis=1)
        cv0_l = jnp.sum(
            jnp.where(lane_hit, crow[:, 3 * e : 4 * e], 0),
            axis=1, dtype=jnp.uint32,
        )
        cv1_l = jnp.sum(
            jnp.where(lane_hit, crow[:, 4 * e : 5 * e], 0),
            axis=1, dtype=jnp.uint32,
        )
        if ntp > 1:
            hit = (
                jax.lax.psum(
                    hit_local.astype(jnp.int32), table_axis
                )
                > 0
            )
            cv0 = jax.lax.psum(cv0_l, table_axis)
            cv1 = jax.lax.psum(cv1_l, table_axis)
        else:
            hit, cv0, cv1 = hit_local, cv0_l, cv1_l
        hit = hit & g["rep_valid"]
        # owner-local insert-lane choice (only the owner's is used);
        # bucket_insert_lanes guarantees distinct (bucket, lane)
        # targets per batch — the duplicate-index scatter atomicity
        # argument lives in ONE place (engine/memo.py)
        ins_lane, ins_ok = vm.bucket_insert_lanes(
            (crow[:, :e] == vm.EMPTY) & owns_c[:, None], bucket, e
        )

        # -- miss compaction + routed lattice on missed reps ----------
        miss = g["rep_valid"] & ~hit
        n_miss = jnp.sum(miss.astype(jnp.int32))
        (miss_pos,) = jnp.nonzero(
            miss, size=miss_cap, fill_value=rep_cap
        )
        m_orig = rep_orig[miss_pos]
        m_idx = idx[m_orig]
        m_known = known[m_orig]
        m_ep = batch_l.ep_index[m_orig]
        m_dir = batch_l.direction[m_orig]
        m_dport = dport[m_orig]
        m_proto = proto[m_orig]

        from cilium_tpu.compiler.tables import l4_entry_words

        entry_words = l4_entry_words(tables_l)
        w0, w1 = l4hash_probe_keys(
            entry_words, m_ep, m_dir, m_idx.astype(jnp.uint32),
            m_dport, m_proto,
        )
        hh = fnv1a_device(jnp.stack([w0, w1], axis=1))
        hb = (hh & jnp.uint32(n_rows_global - 1)).astype(jnp.int32)
        rows_l = tables_l.l4_hash_rows
        n_local = rows_l.shape[0]
        if rows_sharded:
            off = jax.lax.axis_index(table_axis) * n_local
            bl = hb - off
            owns = (bl >= 0) & (bl < n_local)
            bl = jnp.clip(bl, 0, n_local - 1)
        else:
            owns = jnp.ones(hb.shape, bool)
            bl = hb
        found_local, val_local = l4hash_row_parts(
            rows_l[bl], w0, w1, entry_words, owns=owns
        )
        if rows_sharded:
            val1 = jax.lax.psum(val_local, table_axis)
            found1 = (
                jax.lax.psum(
                    found_local.astype(jnp.int32), table_axis
                )
                > 0
            )
        else:
            val1, found1 = val_local, found_local
        s_found, s_val = l4hash_stash_parts(
            tables_l.l4_hash_stash, w0, w1, entry_words
        )
        val1 = val1 + s_val
        found1 = found1 | s_found
        wild_idx = jnp.full(
            m_idx.shape, jnp.uint32(L4H_WILD_IDX), jnp.uint32
        )
        hit3, val3 = _l4hash_probe(
            tables_l.l4_wild_rows, tables_l.l4_wild_stash,
            m_ep, m_dir, wild_idx, m_dport, m_proto,
        )
        p1m = m_known & found1
        p3m = hit3
        m_proxy, m_j = l4hash_value_decode(
            tables_l, m_ep, m_dir, p1m, val1, hit3, val3,
            entry_words,
        )
        # routed L3 probe for the missed reps
        m_word = m_idx >> 5
        m_bit = (m_idx & 31).astype(jnp.uint32)
        w_local = tables_l.l3_allow_bits.shape[-1]
        if l3_sharded:
            offw = jax.lax.axis_index(table_axis) * w_local
            wl = m_word - offw
            owns_w = (wl >= 0) & (wl < w_local)
            wl = jnp.clip(wl, 0, w_local - 1)
        else:
            offw = 0
            owns_w = jnp.ones(m_word.shape, bool)
            wl = m_word
        l3_words = tables_l.l3_allow_bits[m_ep, m_dir, wl]
        p2m_local = (
            m_known & owns_w & ((l3_words >> m_bit) & 1).astype(bool)
        )
        if l3_sharded:
            p2m = (
                jax.lax.psum(
                    p2m_local.astype(jnp.int32), table_axis
                )
                > 0
            )
        else:
            p2m = p2m_local
        mv0, mv1 = vm.pack_value_words(p1m, p2m, p3m, m_proxy, m_j)

        # -- rep values -> per-tuple scatter-back (shared helper:
        # the bit-identity index arithmetic lives in engine/memo.py)
        bsz = k0.shape[0]
        v0, v1, tuple_hit = vm.scatter_back(
            g, rep_cap, hit, cv0, cv1, miss_pos, mv0, mv1
        )

        overflow = g["overflow"] + jnp.maximum(n_miss - miss_cap, 0)
        ok = overflow == 0
        # -- owner-local insert of missed reps ------------------------
        do_ins = (jnp.arange(miss_cap) < n_miss) & ok
        mp = miss_pos
        pc_p = vm.pad_rep(pc, mp)
        cl_p = vm.pad_rep(cl, mp)
        lane_p = vm.pad_rep(ins_lane, mp)
        ok_p = vm.pad_rep(ins_ok, mp)
        own_ins = do_ins & ok_p & (pc_p == my_col)
        k0_p = vm.pad_rep(rk0, mp)
        k1_p = vm.pad_rep(rk1, mp)
        k2_p = vm.pad_rep(rk2, mp)
        ins_row = jnp.where(own_ins, cl_p, c_local)
        rows_idx = jnp.concatenate([ins_row] * vm.CACHE_WORDS)
        lanes_idx = jnp.concatenate(
            [lane_p + c * e for c in range(vm.CACHE_WORDS)]
        )
        vals = jnp.concatenate([k0_p, k1_p, k2_p, mv0, mv1])
        cache_out = cache2.at[rows_idx, lanes_idx].set(vals)
        cache_out = jnp.where(ok, cache_out, cache2)[None, None]

        # -- combine + the shared counter/telemetry epilogue ----------
        probe1, probe2, probe3, t_proxy, t_j = vm.unpack_value_words(
            v0, v1
        )
        v = _combine(
            probe1, probe2, probe3, t_proxy, batch_l.is_fragment
        )
        # p2_local for the shard-local L3 counter: each identity
        # word has ONE owner, so the global probe2 restricted to the
        # owned word range IS the local hit (no gather needed)
        t_word = idx >> 5
        if l3_sharded:
            t_offw = jax.lax.axis_index(table_axis) * w_local
            t_owns = ((t_word - t_offw) >= 0) & (
                (t_word - t_offw) < w_local
            )
        else:
            t_offw = 0
            t_owns = jnp.ones(t_word.shape, bool)
        p2_local_t = probe2 & t_owns
        stats = jnp.stack(
            [
                g["n_unique"].astype(jnp.uint32),
                jnp.sum(tuple_hit, dtype=jnp.uint32),
                jnp.sum((do_ins & ok_p).astype(jnp.uint32)),
                overflow.astype(jnp.uint32),
                jnp.uint32(bsz),
            ]
        )
        stats = jax.lax.psum(stats, batch_axis)
        epilogue = _counts_and_telemetry(
            v, tables_l, batch_l, t_j, idx, p2_local_t, t_offw,
            w_local, batch_axis, collect_telemetry,
        )
        if collect_telemetry:
            v, l4c, l3c, trow = epilogue
            return (
                v, l4c, l3c, cache_out, tuple_hit, stats, trow,
            )
        v, l4c, l3c = epilogue
        return v, l4c, l3c, cache_out, tuple_hit, stats

    in_shardings = (
        jax.tree.map(
            lambda s: NamedSharding(mesh, s), t_specs,
            is_leaf=lambda x: isinstance(x, P),
        ),
        jax.tree.map(lambda s: NamedSharding(mesh, s), b_specs),
        NamedSharding(mesh, cache_spec),
    )
    jitted = jax.jit(step, in_shardings=in_shardings)
    built_geom = (
        tuple(tables.l4_hash_rows.shape),
        tuple(tables.l3_allow_bits.shape),
        cshape,
    )

    def run(tables_in: PolicyTables, batch: TupleBatch, cache_in):
        got = (
            tuple(tables_in.l4_hash_rows.shape),
            tuple(tables_in.l3_allow_bits.shape),
            tuple(cache_in.shape),
        )
        if got != built_geom:
            raise ValueError(
                "partitioned memo evaluator was built for geometry "
                f"{built_geom} but called with {got}; rebuild with "
                "make_partitioned_memo_evaluator"
            )
        return jitted(tables_in, batch, cache_in)

    return run


def failover_lattice_probes(
    tables_l: PolicyTables,
    ep_index,
    direction,
    dport,
    proto,
    idx,
    known,
    alive_row,
    my_col,
    ntp: int,
    rows_sharded: bool,
    l3_sharded: bool,
    n_rows_global: int,
    n_row_shard: int,
    wn: int,
    table_axis: str,
):
    """The replica-aware routed 3-probe lattice — the kernel body
    shared by make_failover_evaluator (post-ipcache TupleBatch form)
    and the fused datapath evaluator (engine/datapath_mesh.py, which
    derives `idx`/`known` from the routed ipcache lookup instead of
    id_direct).  Consumes the N+1 AUGMENTED l4_hash_rows /
    l3_allow_bits planes plus the mesh row's `alive_row` health
    vector; a dead primary's bucket/word routes to the backup owner
    next shard over.

    Returns a dict: probe1/probe2/probe3/proxy/j (the _combine
    inputs + counter slot), p2_local (this chip's L3 hit — feeds the
    shard-local counter scatter), wp/apw (the L3 word's primary
    owner + its liveness; None on a replicated L3 plane) and
    `replica` (bool [B]: the tuple was served from a backup
    region)."""
    from cilium_tpu.compiler import partition
    from cilium_tpu.compiler.tables import L4H_WILD_IDX
    from cilium_tpu.engine.hashtable import fnv1a_device
    from cilium_tpu.engine.verdict import (
        _l4hash_probe,
        l4hash_probe_keys,
        l4hash_row_parts,
        l4hash_stash_parts,
        l4hash_value_decode,
    )

    # -- routed exact probe with replica fallback (layout-generic:
    # the 3-word and the sub-word compact entry forms share one
    # compare/psum body — the stash width is the marker) ------------
    from cilium_tpu.compiler.tables import l4_entry_words as _l4ew

    entry_words = _l4ew(tables_l)
    w0, w1 = l4hash_probe_keys(
        entry_words, ep_index, direction, idx.astype(jnp.uint32),
        dport, proto,
    )
    h = fnv1a_device(jnp.stack([w0, w1], axis=1))
    bucket = (h & jnp.uint32(n_rows_global - 1)).astype(jnp.int32)
    rows_l = tables_l.l4_hash_rows
    replica_exact = jnp.zeros(bucket.shape, bool)
    if rows_sharded:
        n = n_row_shard
        p = bucket // n
        ap = alive_row[p]
        owner = jnp.where(
            ap, p, (p + partition.REPLICA_BACKUP_OFFSET) % ntp
        )
        owns = owner == my_col
        # serving chip's local row: primary region [0, n) when
        # the owner IS the primary, backup region [n, 2n) when
        # the next shard over serves its neighbour's copy
        bl = (bucket - p * n) + jnp.where(ap, 0, n)
        bl = jnp.clip(bl, 0, 2 * n - 1)
        replica_exact = owns & ~ap
    else:
        owns = jnp.ones(bucket.shape, bool)
        bl = bucket
    row = rows_l[bl]
    found_local, val_local = l4hash_row_parts(
        row, w0, w1, entry_words, owns=owns
    )
    if rows_sharded:
        val1 = jax.lax.psum(val_local, table_axis)
        found1 = (
            jax.lax.psum(found_local.astype(jnp.int32), table_axis)
            > 0
        )
    else:
        val1, found1 = val_local, found_local
    s_found, s_val = l4hash_stash_parts(
        tables_l.l4_hash_stash, w0, w1, entry_words
    )
    val1 = val1 + s_val
    found1 = found1 | s_found

    wild_idx = jnp.full(
        idx.shape, jnp.uint32(L4H_WILD_IDX), jnp.uint32
    )
    hit3, val3 = _l4hash_probe(
        tables_l.l4_wild_rows, tables_l.l4_wild_stash,
        ep_index, direction, wild_idx, dport, proto,
    )
    probe1 = known & found1
    probe3 = hit3
    proxy, j = l4hash_value_decode(
        tables_l, ep_index, direction, probe1, val1, hit3, val3,
        entry_words,
    )

    # -- routed L3 probe with replica fallback ----------------------
    word = idx >> 5
    bit = (idx & 31).astype(jnp.uint32)
    replica_l3 = jnp.zeros(word.shape, bool)
    wp = apw = None
    if l3_sharded:
        wp = word // wn
        apw = alive_row[wp]
        owner_w = jnp.where(
            apw, wp, (wp + partition.REPLICA_BACKUP_OFFSET) % ntp
        )
        owns_w = owner_w == my_col
        wl = (word - wp * wn) + jnp.where(apw, 0, wn)
        wl = jnp.clip(wl, 0, 2 * wn - 1)
        replica_l3 = owns_w & ~apw
    else:
        owns_w = jnp.ones(word.shape, bool)
        wl = word
    l3_words = tables_l.l3_allow_bits[ep_index, direction, wl]
    p2_local = known & owns_w & ((l3_words >> bit) & 1).astype(bool)
    if l3_sharded:
        probe2 = (
            jax.lax.psum(p2_local.astype(jnp.int32), table_axis) > 0
        )
    else:
        probe2 = p2_local
    return {
        "probe1": probe1,
        "probe2": probe2,
        "probe3": probe3,
        "proxy": proxy,
        "j": j,
        "p2_local": p2_local,
        "wp": wp,
        "apw": apw,
        "replica": replica_exact | replica_l3,
    }


def failover_counts(
    tables_l: PolicyTables,
    ep_index,
    direction,
    match_kind,
    j,
    idx,
    p2_local,
    valid_l,
    l3_sharded: bool,
    wn: int,
    wp,
    apw,
    n_ids: int,
    batch_axis: str,
):
    """Valid-masked counter epilogue of the failover kernels: L4-slot
    hits from the globally-combined verdict columns; L3 hits
    shard-LOCAL at the augmented local identity index (primary region
    [0, g), backup region [g, 2g) — the same routing as the word
    gather), folded back to the global [E, 2, N] surface host-side
    by fold_l3_aug.  Padding positions (valid=False) are excluded
    everywhere — a re-split batch counts exactly its real tuples."""
    e_count, _, kg = tables_l.l4_meta.shape
    hit_l4 = (
        (match_kind == MATCH_L4) | (match_kind == MATCH_L4_WILD)
    ) & valid_l
    l4_counts = jnp.zeros((e_count, 2, kg), jnp.uint32).at[
        ep_index, direction, j
    ].add(hit_l4.astype(jnp.uint32))
    l4_counts = jax.lax.psum(l4_counts, batch_axis)
    l3_hit_here = p2_local & (match_kind == MATCH_L3) & valid_l
    if l3_sharded:
        # shard-LOCAL counters at the augmented local identity
        # index: each hit lands exactly once on its serving chip, so
        # the global [E, 2, N] tensor is never materialized on
        # device (it would be 32x the bit plane, replicated per
        # chip — defeating the HBM sharding this plane exists for).
        g = wn * 32
        lid = jnp.clip(idx - wp * g, 0, g - 1) + jnp.where(
            apw, 0, g
        )
        l3_counts = jnp.zeros(
            (e_count, 2, 2 * g), jnp.uint32
        ).at[
            ep_index, direction, lid
        ].add(l3_hit_here.astype(jnp.uint32))
    else:
        # replicated fallback plane: p2_local is IDENTICAL on
        # every table chip — count at the global index and take
        # one copy (a table-axis psum would inflate every hit
        # by tp)
        l3_counts = jnp.zeros(
            (e_count, 2, n_ids), jnp.uint32
        ).at[
            ep_index, direction, jnp.clip(idx, 0, n_ids - 1),
        ].add(l3_hit_here.astype(jnp.uint32))
    l3_counts = jax.lax.psum(l3_counts, batch_axis)
    return l4_counts, l3_counts


def fold_l3_aug(l3_aug, ntp: int):
    """[E, 2, ntp*2g] chip-major (primary region then backup region
    per chip) → global [E, 2, N]: slice p reassembles from chip p's
    primary region + chip (p+offset)'s backup region.  Rows whose
    owner moved were counted in the backup region, so summing both
    regions is exact whatever mix each mesh row's survivor set
    routed."""
    import numpy as np

    from cilium_tpu.compiler import partition

    a = np.asarray(l3_aug)
    g = a.shape[-1] // (2 * ntp)
    blocks = a.reshape(a.shape[0], a.shape[1], ntp, 2 * g)
    back = np.roll(
        blocks[..., g:],
        -partition.REPLICA_BACKUP_OFFSET,
        axis=2,
    )
    return np.ascontiguousarray(
        (blocks[..., :g] + back).reshape(
            a.shape[0], a.shape[1], ntp * g
        )
    )


def make_replica_store(
    mesh: Mesh,
    table_axis: str = "table",
    hot_only: bool = False,
):
    """make_partitioned_store under the N+1 replica placement rule:
    every published epoch carries the AUGMENTED layout
    (compiler.partition.replicate_table_leaves — each sharded
    replica-rule leaf's shard also holds its left neighbour's slice),
    and every delta publish scatters each changed row into BOTH its
    primary and backup positions (partition.replica_delta), so the
    two copies stay bit-identical through churn.  The replica
    placement digest is folded into the epoch layout stamp — a delta
    recorded under plain sharding can never scatter into a replica
    epoch, and vice versa."""
    from cilium_tpu.compiler import partition
    from cilium_tpu.engine.publish import DeviceTableStore

    ntp = int(mesh.shape[table_axis])
    return DeviceTableStore(
        shardings_fn=lambda aug: partition.table_shardings(
            mesh, aug, table_axis
        ),
        partition_digest=partition.replica_partition_digest(
            table_axis, ntp=ntp
        ),
        transform_fn=lambda t: partition.replicate_table_leaves(
            t, ntp, table_axis
        ),
        delta_transform_fn=lambda d, pre: partition.replica_delta(
            d, pre, ntp, table_axis
        ),
        hot_only=hot_only,
    )


def make_failover_evaluator(
    mesh: Mesh,
    tables: PolicyTables,
    batch_axis: str = "batch",
    table_axis: str = "table",
    collect_telemetry: bool = False,
):
    """Replica-aware routed-gather evaluator — the per-chip failure
    domain's kernel half.  Consumes the N+1 AUGMENTED tables
    (compiler.partition.replicate_table_leaves: each sharded leaf's
    shard also carries a copy of its left neighbour's slice) plus two
    routing inputs:

      * `alive` bool [dp, tp] (replicated) — per-(mesh row, table
        column) chip health from the ChipBreakerBank.  A tuple whose
        bucket/word's primary owner is dead routes to the BACKUP
        owner (next shard over), which gathers from its backup
        region — the gathered rows are bit-identical copies, so
        verdicts never depend on the dead chip's table slice.
      * `valid` bool [B] (batch-sharded) — real-tuple mask from the
        shard router's batch re-split: positions padding a dead
        row's shard are excluded from counters and telemetry, so the
        full observable surface equals the healthy mesh's.

    Returns fn(tables_aug, batch, alive, valid) ->
    (Verdicts, l4_counts [E,2,Kg] replicated, l3_counts [E,2,N]
    replicated (N = the GLOBAL identity pad — unlike the partitioned
    evaluator's shard-local slices, so comparators need no reassembly
    under a changing survivor set), replica_hits u32 scalar (valid
    tuples served from a backup region — the replica_gather_total
    feed) [, per-chip telemetry rows [dp, 2, TELEM_COLS]]).

    Verdict columns for INVALID positions are unspecified when their
    row hosts a dead chip (the router discards them); everything the
    valid mask covers is bit-identical to the healthy mesh and the
    host oracle — the acceptance contract of the per-chip failover
    plane."""
    from cilium_tpu.compiler import partition
    from cilium_tpu.engine.verdict import (
        _index_identity,
        telemetry_masks,
    )

    if tables.l4_hash_rows is None:
        raise ValueError(
            "failover evaluator requires the hashed L4 entry tables"
        )
    ntp = int(mesh.shape[table_axis])
    rep_axes = partition.replica_axes(tables, ntp, table_axis)
    rows_sharded = "l4_hash_rows" in rep_axes
    l3_sharded = "l3_allow_bits" in rep_axes
    # geometry of the UN-augmented layout (hash masks / owner maps
    # are functions of the original shapes; the augmentation only
    # doubles the resident axis)
    n_rows_global = int(tables.l4_hash_rows.shape[0])
    n_row_shard = n_rows_global // ntp if rows_sharded else 0
    w_global = int(tables.l3_allow_bits.shape[-1])
    wn = w_global // ntp if l3_sharded else 0
    n_ids = w_global * 32

    t_specs = partition.divisible_partition_specs(
        tables, ntp, table_axis
    )
    b_specs = batch_specs(batch_axis)
    v_specs = Verdicts(
        allowed=P(batch_axis),
        proxy_port=P(batch_axis),
        match_kind=P(batch_axis),
    )
    # a sharded L3 plane keeps its counters shard-local too — the
    # stitched last axis is chip-major [ntp, 2*wn*32] regions the
    # host wrapper folds back into the global counter
    l3_spec = P(None, None, table_axis) if l3_sharded else P()
    out_specs = (v_specs, P(), l3_spec, P())
    if collect_telemetry:
        out_specs = out_specs + (P(batch_axis, None, None),)

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(t_specs, b_specs, P(), P(batch_axis)),
        out_specs=out_specs,
        check_vma=False,
    )
    def step(tables_l: PolicyTables, batch_l: TupleBatch,
             alive_l, valid_l):
        idx, known = _index_identity(tables_l, batch_l)
        proto = jnp.clip(batch_l.proto, 0, 255).astype(jnp.int32)
        dport = jnp.clip(batch_l.dport, 0, 65535).astype(jnp.int32)
        # this chip's coordinates + its mesh row's health vector
        # (tuples on batch row r only ever touch row r's chips — the
        # table-axis psum reduces within the row subgroup)
        alive_row = alive_l[jax.lax.axis_index(batch_axis)]
        my_col = jax.lax.axis_index(table_axis)

        lat = failover_lattice_probes(
            tables_l, batch_l.ep_index, batch_l.direction, dport,
            proto, idx, known, alive_row, my_col, ntp,
            rows_sharded, l3_sharded, n_rows_global, n_row_shard,
            wn, table_axis,
        )
        v = _combine(
            lat["probe1"], lat["probe2"], lat["probe3"],
            lat["proxy"], batch_l.is_fragment,
        )
        l4_counts, l3_counts = failover_counts(
            tables_l, batch_l.ep_index, batch_l.direction,
            v.match_kind, lat["j"], idx, lat["p2_local"], valid_l,
            l3_sharded, wn, lat["wp"], lat["apw"], n_ids,
            batch_axis,
        )
        served_backup = (lat["replica"] & valid_l).astype(jnp.uint32)
        replica_hits = jax.lax.psum(
            jax.lax.psum(jnp.sum(served_backup), batch_axis),
            table_axis,
        )
        out = (v, l4_counts, l3_counts, replica_hits)
        if not collect_telemetry:
            return out
        zeros = jnp.zeros(v.allowed.shape, jnp.int32)
        masks = telemetry_masks(
            zeros, zeros, v.match_kind, v.allowed, zeros,
            v.proxy_port, zeros, zeros,
        )
        ingress = (batch_l.direction == 0) & valid_l
        row_in = jnp.stack(
            [
                jnp.sum(m & ingress, dtype=jnp.uint32)
                for m in masks
            ]
        )
        col_total = jnp.stack(
            [
                jnp.sum(m & valid_l, dtype=jnp.uint32)
                for m in masks
            ]
        )
        trow = jnp.stack([row_in, col_total - row_in])
        return out + (trow[None],)

    in_shardings = (
        jax.tree.map(
            lambda s: NamedSharding(mesh, s), t_specs,
            is_leaf=lambda x: isinstance(x, P),
        ),
        jax.tree.map(lambda s: NamedSharding(mesh, s), b_specs),
        NamedSharding(mesh, P()),
        NamedSharding(mesh, P(batch_axis)),
    )
    jitted = jax.jit(step, in_shardings=in_shardings)
    built_geom = (
        tuple(tables.l4_hash_rows.shape),
        tuple(tables.l3_allow_bits.shape),
    )
    aug_rows = (
        n_rows_global * 2 if rows_sharded else n_rows_global
    )
    aug_words = w_global * 2 if l3_sharded else w_global

    def run(tables_aug: PolicyTables, batch: TupleBatch, alive,
            valid):
        if tables_aug.l4_hash_rows is None:
            raise ValueError(
                "failover evaluator requires the hashed L4 entry "
                "tables"
            )
        got = (
            int(tables_aug.l4_hash_rows.shape[0]),
            int(tables_aug.l3_allow_bits.shape[-1]),
        )
        if got != (aug_rows, aug_words):
            raise ValueError(
                "failover evaluator was built for augmented table "
                f"geometry {(aug_rows, aug_words)} (from un-augmented "
                f"{built_geom}) but called with {got}; rebuild with "
                "make_failover_evaluator"
            )
        out = jitted(tables_aug, batch, alive, valid)
        if l3_sharded:
            out = (out[0], out[1], fold_l3_aug(out[2], ntp)) + tuple(
                out[3:]
            )
        return out

    run.replica_axes = rep_axes
    return run


def make_failover_memo_evaluator(
    mesh: Mesh,
    tables: PolicyTables,
    cache_rows,
    rep_cap: int,
    miss_cap: int = None,
    batch_axis: str = "batch",
    table_axis: str = "table",
    collect_telemetry: bool = False,
):
    """make_failover_evaluator with the verdict-memoization plane in
    front — the serving-plane memo carried onto the PRODUCTION
    router path (ChipFailoverRouter.dispatch).  Each batch shard
    dedups its tuple stream in-jit; representatives probe a cache
    whose bucket rows shard along the table axis (the owning chip
    gathers, one psum pair returns hit + value words) with the
    ALIVE mask folded into ownership — a dead chip's cache slice
    contributes nothing (those keys just miss) and its inserts
    route to the scratch row, so cache routing can never depend on
    a dead chip; only the MISSED representatives run the
    replica-aware routed lattice (failover_lattice_probes).

    Returns run(tables_aug, batch, alive, valid, cache_rows) ->
    (Verdicts, l4_counts, l3_counts GLOBAL, replica_hits, cache',
    hit bool [B], stats u32 [STATS] [, per-chip telemetry rows]) —
    the failover evaluator's counter/telemetry contract plus the
    memo plane's.  On stats[STAT_OVERFLOW] != 0 every output except
    cache' (returned unchanged) is unspecified: the caller
    re-dispatches through the uncached failover evaluator.
    replica_hits counts backup-region gathers on the missed-rep
    lattice path (cache hits gather no table rows at all)."""
    from cilium_tpu.compiler import partition
    from cilium_tpu.engine import memo as vm
    from cilium_tpu.engine.hashtable import fnv1a_device
    from cilium_tpu.engine.verdict import (
        _index_identity,
        telemetry_masks,
    )

    if tables.l4_hash_rows is None:
        raise ValueError(
            "failover memo evaluator requires the hashed L4 entry "
            "tables"
        )
    if miss_cap is None:
        miss_cap = rep_cap
    ntp = int(mesh.shape[table_axis])
    ndp = int(mesh.shape[batch_axis])
    rep_axes = partition.replica_axes(tables, ntp, table_axis)
    rows_sharded = "l4_hash_rows" in rep_axes
    l3_sharded = "l3_allow_bits" in rep_axes
    n_rows_global = int(tables.l4_hash_rows.shape[0])
    n_row_shard = n_rows_global // ntp if rows_sharded else 0
    w_global = int(tables.l3_allow_bits.shape[-1])
    wn = w_global // ntp if l3_sharded else 0
    n_ids = w_global * 32
    t_specs = partition.divisible_partition_specs(
        tables, ntp, table_axis
    )
    cshape = tuple(cache_rows.shape)
    if cshape[0] != ndp or cshape[1] != ntp:
        raise ValueError(
            f"cache rows {cshape} do not match the mesh "
            f"({ndp}, {ntp})"
        )
    c_local = int(cshape[2]) - 1
    c_global = c_local * ntp
    entries = int(cshape[3]) // vm.CACHE_WORDS

    b_specs = batch_specs(batch_axis)
    v_specs = Verdicts(
        allowed=P(batch_axis),
        proxy_port=P(batch_axis),
        match_kind=P(batch_axis),
    )
    l3_spec = P(None, None, table_axis) if l3_sharded else P()
    cache_spec = P(batch_axis, table_axis)
    out_specs = (
        v_specs, P(), l3_spec, P(), cache_spec, P(batch_axis), P(),
    )
    if collect_telemetry:
        out_specs = out_specs + (P(batch_axis, None, None),)

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(t_specs, b_specs, P(), P(batch_axis), cache_spec),
        out_specs=out_specs,
        check_vma=False,
    )
    def step(tables_l, batch_l, alive_l, valid_l, cache_l):
        cache2 = cache_l[0, 0]  # [R_local + 1, 5e]
        alive_row = alive_l[jax.lax.axis_index(batch_axis)]
        my_col = jax.lax.axis_index(table_axis)
        idx, known = _index_identity(tables_l, batch_l)
        proto = jnp.clip(batch_l.proto, 0, 255).astype(jnp.int32)
        dport = jnp.clip(batch_l.dport, 0, 65535).astype(jnp.int32)

        # -- Level A: per-batch-shard dedup ---------------------------
        k0, k1, k2 = vm.memo_key_words(
            idx, known, None, batch_l.ep_index, batch_l.direction,
            dport, proto,
        )
        g = vm.dedup_groups(k0, k1, k2, rep_cap)
        rep_orig = g["rep_orig"]
        r = rep_orig[:rep_cap]
        rk0, rk1, rk2 = k0[r], k1[r], k2[r]

        # -- Level B: alive-masked routed cache probe -----------------
        h = fnv1a_device(jnp.stack([rk0, rk1, rk2], axis=1))
        bucket = (h & jnp.uint32(c_global - 1)).astype(jnp.int32)
        if ntp > 1:
            pc = bucket // c_local
            owns_c = (pc == my_col) & alive_row[pc]
            cl = jnp.clip(bucket - pc * c_local, 0, c_local - 1)
        else:
            pc = jnp.zeros(bucket.shape, jnp.int32)
            owns_c = jnp.ones(bucket.shape, bool) & alive_row[0]
            cl = bucket
        crow = cache2[cl]
        e = entries
        lane_hit = (
            (crow[:, :e] == rk0[:, None])
            & (crow[:, e : 2 * e] == rk1[:, None])
            & (crow[:, 2 * e : 3 * e] == rk2[:, None])
            & owns_c[:, None]
        )
        hit_local = jnp.any(lane_hit, axis=1)
        cv0_l = jnp.sum(
            jnp.where(lane_hit, crow[:, 3 * e : 4 * e], 0),
            axis=1, dtype=jnp.uint32,
        )
        cv1_l = jnp.sum(
            jnp.where(lane_hit, crow[:, 4 * e : 5 * e], 0),
            axis=1, dtype=jnp.uint32,
        )
        if ntp > 1:
            hit = (
                jax.lax.psum(
                    hit_local.astype(jnp.int32), table_axis
                )
                > 0
            )
            cv0 = jax.lax.psum(cv0_l, table_axis)
            cv1 = jax.lax.psum(cv1_l, table_axis)
        else:
            hit, cv0, cv1 = hit_local, cv0_l, cv1_l
        hit = hit & g["rep_valid"]
        ins_lane, ins_ok = vm.bucket_insert_lanes(
            (crow[:, :e] == vm.EMPTY) & owns_c[:, None], bucket, e
        )

        # -- miss compaction + replica-aware routed lattice -----------
        miss = g["rep_valid"] & ~hit
        n_miss = jnp.sum(miss.astype(jnp.int32))
        (miss_pos,) = jnp.nonzero(
            miss, size=miss_cap, fill_value=rep_cap
        )
        m_orig = rep_orig[miss_pos]
        lat = failover_lattice_probes(
            tables_l, batch_l.ep_index[m_orig],
            batch_l.direction[m_orig], dport[m_orig], proto[m_orig],
            idx[m_orig], known[m_orig], alive_row, my_col, ntp,
            rows_sharded, l3_sharded, n_rows_global, n_row_shard,
            wn, table_axis,
        )
        mv0, mv1 = vm.pack_value_words(
            lat["probe1"], lat["probe2"], lat["probe3"],
            lat["proxy"], lat["j"],
        )

        v0, v1, tuple_hit = vm.scatter_back(
            g, rep_cap, hit, cv0, cv1, miss_pos, mv0, mv1
        )
        overflow = g["overflow"] + jnp.maximum(n_miss - miss_cap, 0)
        ok = overflow == 0

        # -- owner-local insert of missed reps ------------------------
        do_ins = (jnp.arange(miss_cap) < n_miss) & ok
        mp = miss_pos
        pc_p = vm.pad_rep(pc, mp)
        cl_p = vm.pad_rep(cl, mp)
        lane_p = vm.pad_rep(ins_lane, mp)
        ok_p = vm.pad_rep(ins_ok, mp)
        own_alive = alive_row[jnp.clip(pc_p, 0, ntp - 1)]
        own_ins = (
            do_ins & ok_p & (pc_p == my_col) & own_alive
        )
        ins_row = jnp.where(own_ins, cl_p, c_local)
        rows_idx = jnp.concatenate([ins_row] * vm.CACHE_WORDS)
        lanes_idx = jnp.concatenate(
            [lane_p + c * e for c in range(vm.CACHE_WORDS)]
        )
        vals = jnp.concatenate(
            [
                vm.pad_rep(rk0, mp), vm.pad_rep(rk1, mp),
                vm.pad_rep(rk2, mp), mv0, mv1,
            ]
        )
        cache_out = cache2.at[rows_idx, lanes_idx].set(vals)
        cache_out = jnp.where(ok, cache_out, cache2)[None, None]

        # -- combine + the failover counter epilogue ------------------
        probe1, probe2, probe3, t_proxy, t_j = (
            vm.unpack_value_words(v0, v1)
        )
        v = _combine(
            probe1, probe2, probe3, t_proxy, batch_l.is_fragment
        )
        # full-batch L3 ownership under the alive routing: each
        # identity word has exactly one SERVING owner (backup when
        # the primary is dead), so restricting the global probe2 to
        # the owned words reproduces the shard-local hit without a
        # gather
        word = idx >> 5
        if l3_sharded:
            wp = word // wn
            apw = alive_row[wp]
            owner_w = jnp.where(
                apw, wp,
                (wp + partition.REPLICA_BACKUP_OFFSET) % ntp,
            )
            owns_w = owner_w == my_col
        else:
            wp = apw = None
            owns_w = jnp.ones(word.shape, bool)
        p2_local = probe2 & owns_w
        l4_counts, l3_counts = failover_counts(
            tables_l, batch_l.ep_index, batch_l.direction,
            v.match_kind, t_j, idx, p2_local, valid_l,
            l3_sharded, wn, wp, apw, n_ids, batch_axis,
        )
        miss_live = jnp.arange(miss_cap) < n_miss
        replica_hits = jax.lax.psum(
            jax.lax.psum(
                jnp.sum(
                    (lat["replica"] & miss_live).astype(jnp.uint32)
                ),
                batch_axis,
            ),
            table_axis,
        )
        stats = jnp.stack(
            [
                g["n_unique"].astype(jnp.uint32),
                jnp.sum(
                    (tuple_hit & valid_l).astype(jnp.uint32)
                ),
                jnp.sum((do_ins & ok_p).astype(jnp.uint32)),
                overflow.astype(jnp.uint32),
                jnp.sum(valid_l.astype(jnp.uint32)),
            ]
        )
        stats = jax.lax.psum(stats, batch_axis)
        out = (
            v, l4_counts, l3_counts, replica_hits, cache_out,
            tuple_hit, stats,
        )
        if not collect_telemetry:
            return out
        zeros = jnp.zeros(v.allowed.shape, jnp.int32)
        masks = telemetry_masks(
            zeros, zeros, v.match_kind, v.allowed, zeros,
            v.proxy_port, zeros, zeros,
        )
        ingress = (batch_l.direction == 0) & valid_l
        row_in = jnp.stack(
            [jnp.sum(m & ingress, dtype=jnp.uint32) for m in masks]
        )
        col_total = jnp.stack(
            [jnp.sum(m & valid_l, dtype=jnp.uint32) for m in masks]
        )
        trow = jnp.stack([row_in, col_total - row_in])
        return out + (trow[None],)

    in_shardings = (
        jax.tree.map(
            lambda s: NamedSharding(mesh, s), t_specs,
            is_leaf=lambda x: isinstance(x, P),
        ),
        jax.tree.map(lambda s: NamedSharding(mesh, s), b_specs),
        NamedSharding(mesh, P()),
        NamedSharding(mesh, P(batch_axis)),
        NamedSharding(mesh, cache_spec),
    )
    jitted = jax.jit(step, in_shardings=in_shardings)
    aug_rows = n_rows_global * 2 if rows_sharded else n_rows_global
    aug_words = w_global * 2 if l3_sharded else w_global

    def run(tables_aug, batch, alive, valid, cache_in):
        got = (
            int(tables_aug.l4_hash_rows.shape[0]),
            int(tables_aug.l3_allow_bits.shape[-1]),
        )
        if got != (aug_rows, aug_words) or tuple(
            cache_in.shape
        ) != cshape:
            raise ValueError(
                "failover memo evaluator geometry mismatch; rebuild "
                "with make_failover_memo_evaluator"
            )
        out = jitted(tables_aug, batch, alive, valid, cache_in)
        if l3_sharded:
            out = (out[0], out[1], fold_l3_aug(out[2], ntp)) + tuple(
                out[3:]
            )
        return out

    return run


def make_async_mesh_dispatcher(
    step, mesh, batch_axis: str = "batch", depth: int = 1
):
    """Double-buffered dispatch over a mesh evaluator
    (engine.publish.AsyncBatchDispatcher applied to SPMD batches):
    the host packs + shards batch N+1 across the mesh while the
    chips compute batch N.  `step` is a one-argument closure
    batch → result with the tables already bound (e.g.
    `partial(make_sharded_evaluator(mesh), dev_tables)`);
    `submit((ep_index, identity, dport, proto, direction[,
    is_fragment]), meta)` stages a TupleBatch with the batch axis
    sharded; results drain one batch behind in submission order.

    This is the mesh serving loop's missing overlap: the sharded
    device_put (scatter of the batch across chips) is exactly the
    host-side work the single-chip path hides behind compute."""
    import numpy as np

    from cilium_tpu.engine.publish import AsyncBatchDispatcher

    sharded = NamedSharding(mesh, P(batch_axis))

    def pack(ep_index, identity, dport, proto, direction,
             is_fragment=None):
        b = len(ep_index)
        if is_fragment is None:
            is_fragment = np.zeros(b, dtype=bool)
        put = lambda a, dt: jax.device_put(
            np.asarray(a).astype(dt, copy=False), sharded
        )
        return (
            TupleBatch(
                ep_index=put(ep_index, np.int32),
                identity=put(identity, np.uint32),
                dport=put(dport, np.int32),
                proto=put(proto, np.int32),
                direction=put(direction, np.int32),
                is_fragment=put(is_fragment, bool),
            ),
        )

    def dispatch(batch):
        return step(batch)

    return AsyncBatchDispatcher(pack, dispatch, depth=depth)


def traced_dispatch(step, mesh, site: str = "engine.sharded"):
    """Wrap a mesh evaluator with span-plane dispatch attribution:
    each call opens a `mesh.dispatch` span with the chip and row
    counts (blocking on the result so the span covers the device
    execution, not just the enqueue).  The wrapped step also counts
    jit cache hits/misses per call (site label `site`)."""
    from cilium_tpu import tracing

    n_chips = int(mesh.devices.size)
    tracked = tracing.track_jit(step, site)

    def dispatch(tables, batch, *rest):
        rows = int(batch.ep_index.shape[0])
        with tracing.tracer.span(
            "mesh.dispatch", site=site,
            attrs={"chips": n_chips, "rows": rows},
        ):
            out = tracked(tables, batch, *rest)
            jax.block_until_ready(out)
        return out

    dispatch.__wrapped__ = step
    return dispatch
