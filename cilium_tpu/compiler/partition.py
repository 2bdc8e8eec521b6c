"""Declarative per-leaf partition rules for the device table pytrees.

The replicated layout (engine/sharded.replicated_table_shardings) caps
the identity universe at ONE chip's HBM: every chip holds every leaf,
so a 50k-rule/65k-identity world costs ~442 MB on each chip and a
mesh buys zero capacity.  This module is the t5x-style answer
(PAPERS.md [1], arXiv:2203.17189): a REGEX RULE TABLE matched over
the named pytree — `match_partition_rules` + `named_tree_map`, the
SNIPPETS.md [2]/[3] pattern — instead of hand-placed shardings, with
`replicated` as the explicit fallback so small leaves (stashes, the
identity index tables, DFA transition tables) stay replicated while
the identity-major leaves shard:

  * `l4_hash_rows`     — the hashed L4 entry plane, sharded along the
                         bucket-row axis (each chip owns a contiguous
                         row slice; the probe routes each tuple's
                         bucket to its owning shard);
  * `l3_allow_bits`    — the L3-only lattice rows, sharded along the
                         identity WORD axis (the layout the 2D mesh
                         evaluator already combines with a psum);
  * `l4_allow_bits`    — the dense allow bitmap (the cold fallback
                         plane), same word axis;
  * ipcache `buckets`  — the /32 prefix-row plane, bucket-row axis.

Everything else — `id_table`/`id_direct` (a few MB even at 512k ids),
`port_slot`, stashes, `l4_wild_rows` (per-(ep,port) — identity-free
and tiny), scalars — matches the fallback rule and replicates.

The rule table is DATA: `partition_digest` hashes it into a stamp the
device store folds into its epoch layout, so a delta recorded against
one partitioning can never scatter into an epoch laid out under
another.
"""

from __future__ import annotations

import re
import zlib
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# The mesh axis the identity-major leaves shard along (the existing
# 2D (batch × table) mesh of engine/sharded.py).
TABLE_AXIS = "table"


def tree_path_to_string(path, sep: str = "/") -> str:
    """jax key-path → 'a/b/c' (SNIPPETS.md [3] tree_path_to_string)."""
    keys = []
    for key in path:
        if isinstance(key, jax.tree_util.SequenceKey):
            keys.append(str(key.idx))
        elif isinstance(key, jax.tree_util.DictKey):
            keys.append(str(key.key))
        elif isinstance(key, jax.tree_util.GetAttrKey):
            keys.append(str(key.name))
        elif isinstance(key, jax.tree_util.FlattenedIndexKey):
            keys.append(str(key.key))
        else:
            keys.append(str(key))
    return sep.join(keys)


def named_tree_map(f, tree, *rest, is_leaf=None, sep: str = "/"):
    """tree_map where `f` receives (path-string, leaf, *rest-leaves) —
    the extended tree_map of SNIPPETS.md [2]/[3].  For dict/list
    pytrees the names are real key paths; the registered table
    dataclasses flatten positionally, so the helpers below pair their
    children with the explicit *_LEAF_NAMES tables instead."""
    return jax.tree_util.tree_map_with_path(
        lambda path, x, *r: f(tree_path_to_string(path, sep=sep), x, *r),
        tree,
        *rest,
        is_leaf=is_leaf,
    )


# Child order of the registered table pytrees (== tree_flatten order;
# the pytrees flatten positionally, so the names live here, beside
# the rules that consume them).
POLICY_LEAF_NAMES = (
    "id_table", "id_direct", "id_lo_len", "port_slot", "l4_meta",
    "l4_allow_bits", "l3_allow_bits", "generation", "l4_hash_rows",
    "l4_hash_stash", "l4_wild_rows", "l4_wild_stash",
)
IPCACHE_LEAF_NAMES = (
    "buckets", "stash", "range_base", "range_mask", "range_plen",
    "range_value", "range_l3_in", "range_l3_out", "range_rows",
)
# remaining fused-datapath leaf families (tree_flatten child order)
CT_LEAF_NAMES = ("buckets", "stash")
LB_INLINE_LEAF_NAMES = ("rows", "stash")
LB_CLASSIC_LEAF_NAMES = ("buckets", "stash", "backend_rows")


# -- the rule tables ---------------------------------------------------------
# (regex, PartitionSpec) pairs, first match wins; the final catch-all
# IS the replicated fallback — explicit, so a new leaf added to
# PolicyTables replicates by default instead of failing to place.


def default_table_rules(table_axis: str = TABLE_AXIS) -> List[tuple]:
    """The PolicyTables rule table (identity-major leaves sharded)."""
    return [
        # dense allow bitmap [E, 2, Kg, W]: identity WORD axis
        (r"^l4_allow_bits$", P(None, None, None, table_axis)),
        # L3-only rows [E, 2, W]: identity WORD axis
        (r"^l3_allow_bits$", P(None, None, table_axis)),
        # hashed L4 entry plane [R, lanes]: bucket-row axis (the row
        # count is pow2 and identities spread uniformly by hash, so
        # equal row slices carry near-equal entry loads)
        (r"^l4_hash_rows$", P(table_axis)),
        # wild rows are per-(ep, dir, port, proto) — identity-free and
        # a few KB; stashes are ≤64 rows: replicated (the fallback
        # would catch them too, but the intent is worth spelling out)
        (r"^l4_(wild_rows|hash_stash|wild_stash)$", P()),
        # replicated fallback: id tables, port_slot, generation, ...
        (r".*", P()),
    ]


def default_ipcache_rules(table_axis: str = TABLE_AXIS) -> List[tuple]:
    """IPCacheDevice rule table: the /32 bucket-row plane AND the
    hashed range-class rows shard along their bucket-row axis; the
    (base, mask, plen, value) broadcast-fallback arrays and the
    stash replicate (they are small and every shard compares them)."""
    return [
        (r"^(buckets|range_rows)$", P(table_axis)),
        (r".*", P()),
    ]


def default_ct_rules(table_axis: str = TABLE_AXIS) -> List[tuple]:
    """CTSnapshot rule table: the [Cb, 128] bucket-row plane shards
    along the bucket-row axis (rows spread uniformly by the
    direction-normalized tuple hash); the overflow stash replicates
    (broadcast-compared by every probe)."""
    return [
        (r"^buckets$", P(table_axis)),
        (r".*", P()),
    ]


def default_lb_rules(table_axis: str = TABLE_AXIS) -> List[tuple]:
    """LB rule table: the INLINE layout's service rows (service key +
    backends in one 128-lane row) shard along the bucket-row axis.
    The classic two-gather layout replicates wholesale — its backend
    rows are indexed by the service entry's stored row index, not a
    hash, so a split would need a second routing hop for the rare
    >40-backend fallback; the stash replicates like every stash."""
    return [
        (r"^rows$", P(table_axis)),
        (r".*", P()),
    ]


def match_partition_rules(
    rules: Sequence[tuple], names: Sequence[str], leaves: Sequence
) -> list:
    """PartitionSpec per leaf: each `names[i]` is matched against
    `rules` in order; scalars/0-d/None leaves never partition.
    Unmatched leaves raise — the catch-all fallback rule makes that
    unreachable for the default tables."""
    compiled = [(re.compile(pat), spec) for pat, spec in rules]
    out = []
    for name, leaf in zip(names, leaves):
        if leaf is None:
            out.append(P())
            continue
        shape = getattr(leaf, "shape", ())
        if len(shape) == 0 or int(np.prod(shape)) == 1:
            out.append(P())  # never partition scalars
            continue
        for rx, spec in compiled:
            if rx.search(name) is not None:
                out.append(spec)
                break
        else:
            raise ValueError(
                f"partition rule not found for leaf: {name}"
            )
    return out


def policy_partition_specs(tables, table_axis: str = TABLE_AXIS):
    """PartitionSpecs for a PolicyTables pytree under the default
    rule table, as a PolicyTables of specs (shape-aware: scalars
    replicate regardless of rules)."""
    children, _ = tables.tree_flatten()
    specs = match_partition_rules(
        default_table_rules(table_axis), POLICY_LEAF_NAMES, children
    )
    return type(tables).tree_unflatten(None, tuple(specs))


def ipcache_partition_specs(dev, table_axis: str = TABLE_AXIS):
    """PartitionSpecs for an IPCacheDevice (or replicated specs for
    the DIR-24-8 fallback form)."""
    from cilium_tpu.ipcache.lpm import IPCacheDevice

    if not isinstance(dev, IPCacheDevice):
        children, aux = dev.tree_flatten()
        return type(dev).tree_unflatten(
            aux, tuple(P() for _ in children)
        )
    children, aux = dev.tree_flatten()
    specs = match_partition_rules(
        default_ipcache_rules(table_axis), IPCACHE_LEAF_NAMES, children
    )
    return type(dev).tree_unflatten(aux, tuple(specs))


def partition_digest(rules: Sequence[tuple]) -> int:
    """Stable 32-bit digest of a rule table — folded into the device
    store's epoch layout stamp so cross-partitioning deltas are
    refused (engine/publish.DeviceTableStore)."""
    text = ";".join(
        f"{pat}->{tuple(spec)}" for pat, spec in rules
    ).encode()
    return zlib.crc32(text) & 0xFFFFFFFF


def _divisible(spec: P, shape, ntp: int, table_axis: str) -> bool:
    for axis, name in enumerate(spec):
        if name == table_axis and (
            axis >= len(shape) or shape[axis] % ntp != 0
        ):
            return False
    return True


def divisible_partition_specs(
    tables, ntp: int, table_axis: str = TABLE_AXIS
):
    """policy_partition_specs with the shard-axis divisibility check
    applied: leaves whose sharded axis does not split evenly over
    `ntp` shards fall back to replicated (the shard_map evaluator and
    the store must agree on this, so it lives in the rule layer)."""
    specs = policy_partition_specs(tables, table_axis)
    spec_children, _ = specs.tree_flatten()
    leaf_children, _ = tables.tree_flatten()
    out = []
    for spec, leaf in zip(spec_children, leaf_children):
        if leaf is None or not _divisible(
            spec, getattr(leaf, "shape", ()), ntp, table_axis
        ):
            spec = P()
        out.append(spec)
    return type(tables).tree_unflatten(None, tuple(out))


def table_shardings(mesh: Mesh, tables, table_axis: str = TABLE_AXIS):
    """NamedShardings pytree for device_put / DeviceTableStore: the
    default rule table resolved against `mesh`.  Leaves whose sharded
    axis does not divide by the mesh's table-axis size fall back to
    replicated (correctness first; shard_bytes_model reports it)."""
    specs = divisible_partition_specs(
        tables, int(mesh.shape[table_axis]), table_axis
    )
    spec_children, _ = specs.tree_flatten()
    out = tuple(NamedSharding(mesh, s) for s in spec_children)
    return type(tables).tree_unflatten(None, out)


# -- N+1 shard replicas (per-chip failover placement) ------------------------
#
# The t5x lesson — placement rules are DATA — applied to failure
# domains: each identity-sharded leaf's rows also live on a BACKUP
# owner, the next shard over (slice i's replica sits on shard
# (i+1) % ntp), so a chip whose breaker opens takes down one COPY of
# its rows, not the rows themselves.  The failover evaluator
# (engine/sharded.make_failover_evaluator) routes a tuple's gather to
# the backup region when the primary owner is dead; the host lattice
# fold remains the terminal fallback only when primary AND backup are
# both gone.
#
# Replication applies to the leaves the ROUTED evaluator gathers —
# the hashed L4 entry rows and the L3 bit-words.  The dense
# l4_allow_bits fallback plane stays single-sharded: nothing on the
# hashed hot path reads it, and doubling the largest leaf would spend
# the replica HBM budget on rows no routed gather can reach.

REPLICA_LEAVES = ("l4_hash_rows", "l3_allow_bits")
# slice i's backup owner is shard (i + REPLICA_BACKUP_OFFSET) % ntp
REPLICA_BACKUP_OFFSET = 1


def replica_axes(tables, ntp: int, table_axis: str = TABLE_AXIS):
    """{leaf name: sharded-axis position} for the leaves the replica
    layout augments: REPLICA_LEAVES that the divisibility-checked
    rule layer actually shards at `ntp` (an indivisible leaf falls
    back to replicated and needs no backup copy)."""
    specs = divisible_partition_specs(tables, ntp, table_axis)
    out = {}
    for name in REPLICA_LEAVES:
        spec = getattr(specs, name)
        for axis, ax_name in enumerate(spec):
            if ax_name == table_axis:
                out[name] = axis
                break
    return out


def replicate_shard_axis(arr, ntp: int, axis: int):
    """Augment one sharded leaf with its backup copies: the sharded
    axis [S] becomes [2S], laid out per shard q as
    [primary slice q ; copy of slice (q - 1) % ntp] — so a
    NamedSharding along the same axis gives every chip its own rows
    plus its left neighbour's, and the in-kernel backup gather is
    `n + (i mod n)` on shard (owner + 1) % ntp."""
    arr = np.asarray(arr)
    n = arr.shape[axis] // ntp
    slices = [
        np.take(arr, np.arange(q * n, (q + 1) * n), axis=axis)
        for q in range(ntp)
    ]
    parts = []
    for q in range(ntp):
        parts.append(slices[q])
        parts.append(slices[(q - REPLICA_BACKUP_OFFSET) % ntp])
    return np.concatenate(parts, axis=axis)


def replicate_table_leaves(tables, ntp: int,
                           table_axis: str = TABLE_AXIS):
    """PolicyTables with every replica-rule leaf augmented (the
    device layout the replica store publishes); non-replica leaves
    pass through untouched."""
    import dataclasses

    axes = replica_axes(tables, ntp, table_axis)
    return dataclasses.replace(
        tables,
        **{
            name: replicate_shard_axis(
                getattr(tables, name), ntp, axis
            )
            for name, axis in axes.items()
        },
    )


def replica_positions(idx, n: int, ntp: int):
    """Map original global sharded-axis indices to their two
    positions in the augmented layout: (primary, backup)."""
    idx = np.asarray(idx)
    shard = idx // n
    within = idx % n
    primary = shard * (2 * n) + within
    backup = (
        ((shard + REPLICA_BACKUP_OFFSET) % ntp) * (2 * n)
        + n
        + within
    )
    return primary, backup


def replica_delta(delta, tables, ntp: int,
                  table_axis: str = TABLE_AXIS):
    """Rewrite a TableDelta recorded against the un-augmented layout
    into augmented coordinates, so one delta publish keeps primary
    and backup copies bit-identical.  Two shapes of update exist:

      * the scatter INDEXES the sharded axis (l4_hash_rows: idx[0]
        is the bucket row) — every row lands twice, at its primary
        and backup augmented positions, values repeated;
      * the scatter indexes LEADING axes only and its values SPAN
        the sharded axis (l3_allow_bits: idx is the endpoint, values
        are whole [2, W] slabs) — the values augment along the
        corresponding value axis, exactly as the resident leaf did.

    Whole-leaf replacements of replica leaves ship in augmented
    form; leaves outside the replica set pass through untouched."""
    from cilium_tpu.compiler.delta import LeafUpdate, TableDelta

    axes = replica_axes(tables, ntp, table_axis)
    updates = {}
    for name, up in delta.updates.items():
        axis = axes.get(name)
        if axis is None:
            updates[name] = up
            continue
        n = getattr(tables, name).shape[axis] // ntp
        if axis < len(up.idx):
            primary, backup = replica_positions(
                up.idx[axis], n, ntp
            )
            idx = tuple(
                np.concatenate([primary, backup])
                if i == axis
                else np.concatenate([comp, comp])
                for i, comp in enumerate(up.idx)
            )
            values = np.concatenate([up.values, up.values], axis=0)
        else:
            # leaf axis `axis` sits inside the values: idx consumes
            # the first len(idx) leaf axes, the values' axis 0 is
            # the scatter row, so leaf axis a maps to values axis
            # a - len(idx) + 1
            idx = up.idx
            values = replicate_shard_axis(
                up.values, ntp, axis - len(up.idx) + 1
            )
        updates[name] = LeafUpdate(idx=idx, values=values)
    replace = {
        name: (
            replicate_shard_axis(arr, ntp, axes[name])
            if name in axes
            else arr
        )
        for name, arr in delta.replace.items()
    }
    return TableDelta(
        base_stamp=delta.base_stamp,
        new_stamp=delta.new_stamp,
        updates=updates,
        replace=replace,
        layout=delta.layout,
    )


def replica_partition_digest(
    table_axis: str = TABLE_AXIS, ntp: Optional[int] = None
) -> int:
    """Digest of the replica placement (rule table + replica set +
    backup offset): a replica-layout epoch can never accept a delta
    recorded under plain sharding, and vice versa.  With `ntp` the
    SHARD COUNT folds in too: the augmented leaves have the same
    total shape [2S] at every ntp (a reshard is a pure permutation of
    the augmented layout), so without the count in the digest a
    source-layout delta or repair could scatter bit-compatibly — but
    row-incorrectly — into a target-layout epoch.  The reshard
    engine's refusal seam depends on the two layouts stamping
    differently."""
    text = ";".join(
        f"{pat}->{tuple(spec)}"
        for pat, spec in default_table_rules(table_axis)
    )
    text += (
        f";replicas={','.join(REPLICA_LEAVES)}"
        f";backup_offset={REPLICA_BACKUP_OFFSET}"
    )
    if ntp is not None:
        text += f";ntp={int(ntp)}"
    return zlib.crc32(text.encode()) & 0xFFFFFFFF


def replica_bytes_model(tables, num_shards: int,
                        table_axis: str = TABLE_AXIS):
    """shard_bytes_model under the N+1 replica layout: replica leaves
    cost 2/num_shards per chip (their own slice + the neighbour's
    backup copy), everything else as the plain sharded model.
    Returns (rows, per_chip_total, replica_overhead_per_chip) where
    the overhead is exactly the backup copies' bytes, bounded by
    sharded_bytes / num_shards."""
    axes = replica_axes(tables, num_shards, table_axis)
    rows, per_chip, _replicated = shard_bytes_model(
        tables, num_shards, table_axis
    )
    overhead = 0
    for r in rows:
        if r["leaf"] in axes and r["sharded"]:
            r["replicated_n_plus_1"] = True
            overhead += r["bytes_per_chip"]
            r["bytes_per_chip"] *= 2
        else:
            r["replicated_n_plus_1"] = False
    return rows, per_chip + overhead, overhead


# -- bytes / headroom models -------------------------------------------------


def shard_bytes_model(tables, num_shards: int,
                      table_axis: str = TABLE_AXIS):
    """Per-leaf per-chip bytes under the default rule table.  Returns
    (rows, per_chip_total, replicated_total): rows are dicts with
    leaf/sharded/bytes; replicated_total is the per-chip overhead the
    acceptance bound allows on top of sharded_bytes / num_shards.
    Applies the same divisibility fallback as table_shardings, so the
    model classifies each leaf exactly as the store will place it."""
    specs_tree = divisible_partition_specs(
        tables, num_shards, table_axis
    )
    children, _ = tables.tree_flatten()
    specs, _ = specs_tree.tree_flatten()
    rows = []
    per_chip = 0
    replicated = 0
    for name, leaf, spec in zip(POLICY_LEAF_NAMES, children, specs):
        if leaf is None:
            continue
        nbytes = int(getattr(leaf, "nbytes", None) or np.asarray(leaf).nbytes)
        sharded = any(ax == table_axis for ax in spec)
        chip = (
            (nbytes + num_shards - 1) // num_shards
            if sharded
            else nbytes
        )
        if not sharded:
            replicated += nbytes
        per_chip += chip
        rows.append(
            {"leaf": name, "sharded": sharded,
             "bytes_total": nbytes, "bytes_per_chip": chip}
        )
    return rows, per_chip, replicated


def universe_max_identities(
    tables,
    num_shards: int,
    hbm_bytes: int = 16 << 30,
    table_axis: str = TABLE_AXIS,
) -> int:
    """Headroom model: the identity-universe size one mesh can hold.

    Identity-major leaf bytes scale linearly with the padded identity
    count N and divide across `num_shards`; replicated leaves are a
    per-chip constant.  Solving
        replicated + identity_bytes_per_id * N / num_shards ≤ hbm
    for N gives the universe's max identities — the
    capacity the sharding refactor actually buys (the replicated
    layout is the num_shards=1 row).

    Classification is by RULE INTENT, not current-shape divisibility:
    at the universe being solved for, the identity axis is padded to
    a shard multiple, so a leaf the rules shard contributes to the
    per-id slope even if today's word count happens not to divide by
    `num_shards` (shard_bytes_model, which accounts the CURRENT
    shapes, applies the divisibility fallback instead)."""
    children, _ = tables.tree_flatten()
    specs = match_partition_rules(
        default_table_rules(table_axis), POLICY_LEAF_NAMES, children
    )
    n = int(tables.id_table.shape[0])
    id_bytes = 0
    replicated = 0
    for leaf, spec in zip(children, specs):
        if leaf is None:
            continue
        nbytes = int(getattr(leaf, "nbytes", None) or np.asarray(leaf).nbytes)
        if any(ax == table_axis for ax in spec):
            id_bytes += nbytes
        else:
            replicated += nbytes
    per_id = id_bytes / max(n, 1)
    budget = hbm_bytes - replicated
    if per_id <= 0 or budget <= 0:
        return 0
    return int(budget * num_shards / per_id)


# -- fused-datapath leaf families (ipcache / CT / LB planes) -----------------
#
# The same declarative layer extended to the REMAINING DatapathTables
# families: every hashed bucket-row plane the fused pipeline gathers
# (CT buckets, ipcache /32 buckets + range-class rows, LB service
# rows) shards along the same table axis as l4_hash_rows, and the hot
# ones join the N+1 replica placement so a dead chip's CT/ipcache/LB
# rows serve from their backup owner exactly like the policy rows.
# Everything else — stashes, broadcast-fallback range arrays, the
# classic LB backend-row table, prefilter, tunnel — replicates.

# the datapath leaves the N+1 failover layout augments, as
# (family, leaf) pairs; entries whose family lacks the leaf (lb.rows
# on the classic layout, lb.buckets on the inline one) are skipped
DATAPATH_REPLICA_LEAVES = (
    ("ct", "buckets"),
    ("ipcache", "buckets"),
    ("ipcache", "range_rows"),
    ("lb", "rows"),
)


def _family_spec_children(children, names, rules, ntp, table_axis):
    """Per-child PartitionSpecs for one table family with the
    shard-axis divisibility fallback applied; None children keep a
    None spec (empty subtrees must stay empty subtrees so the spec
    tree's structure matches the value tree's)."""
    specs = match_partition_rules(rules, names, children)
    out = []
    for leaf, spec in zip(children, specs):
        if leaf is None:
            out.append(None)
            continue
        if not _divisible(spec, np.shape(leaf), ntp, table_axis):
            spec = P()
        out.append(spec)
    return tuple(out)


def _replicated_specs(tree):
    """All-replicated spec tree matching `tree`'s structure."""
    return jax.tree.map(lambda _: P(), tree)


def ct_family_specs(ct, ntp: int, table_axis: str = TABLE_AXIS):
    """CTSnapshot of PartitionSpecs under default_ct_rules."""
    children, aux = ct.tree_flatten()
    return type(ct).tree_unflatten(
        aux,
        _family_spec_children(
            children, CT_LEAF_NAMES, default_ct_rules(table_axis),
            ntp, table_axis,
        ),
    )


def lb_family_specs(lb, ntp: int, table_axis: str = TABLE_AXIS):
    """LBInline/LBTables of PartitionSpecs under default_lb_rules."""
    from cilium_tpu.lb.device import LBInline

    children, aux = lb.tree_flatten()
    names = (
        LB_INLINE_LEAF_NAMES
        if isinstance(lb, LBInline)
        else LB_CLASSIC_LEAF_NAMES
    )
    return type(lb).tree_unflatten(
        aux,
        _family_spec_children(
            children, names, default_lb_rules(table_axis), ntp,
            table_axis,
        ),
    )


def ipcache_family_specs(dev, ntp: int, table_axis: str = TABLE_AXIS):
    """IPCacheDevice of PartitionSpecs under default_ipcache_rules
    (divisibility-checked); the DIR-24-8 fallback form replicates."""
    from cilium_tpu.ipcache.lpm import IPCacheDevice

    children, aux = dev.tree_flatten()
    if not isinstance(dev, IPCacheDevice):
        return type(dev).tree_unflatten(
            aux, tuple(None if c is None else P() for c in children)
        )
    return type(dev).tree_unflatten(
        aux,
        _family_spec_children(
            children, IPCACHE_LEAF_NAMES,
            default_ipcache_rules(table_axis), ntp, table_axis,
        ),
    )


def datapath_partition_specs(
    dtables, ntp: int, table_axis: str = TABLE_AXIS
):
    """PartitionSpecs for a full DatapathTables pytree: every family
    resolved under its own rule table, prefilter/tunnel replicated,
    the policy sub-tree under the existing policy rules."""
    from cilium_tpu.engine.datapath import DatapathTables

    return DatapathTables(
        prefilter=_replicated_specs(dtables.prefilter),
        ipcache=ipcache_family_specs(
            dtables.ipcache, ntp, table_axis
        ),
        ct=ct_family_specs(dtables.ct, ntp, table_axis),
        lb=lb_family_specs(dtables.lb, ntp, table_axis),
        policy=divisible_partition_specs(
            dtables.policy, ntp, table_axis
        ),
        tunnel=(
            None
            if dtables.tunnel is None
            else _replicated_specs(dtables.tunnel)
        ),
    )


def datapath_table_shardings(
    mesh: Mesh, dtables, table_axis: str = TABLE_AXIS
):
    """NamedShardings for a DatapathTables pytree under the family
    rule tables (the datapath store's placement resolver)."""
    specs = datapath_partition_specs(
        dtables, int(mesh.shape[table_axis]), table_axis
    )
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def datapath_replica_axes(
    dtables, ntp: int, table_axis: str = TABLE_AXIS
):
    """{(family, leaf): sharded-axis position} for the datapath
    leaves the N+1 layout augments: DATAPATH_REPLICA_LEAVES that the
    divisibility-checked rule layer actually shards at `ntp`."""
    specs = datapath_partition_specs(dtables, ntp, table_axis)
    out = {}
    for fam, leaf in DATAPATH_REPLICA_LEAVES:
        fobj = getattr(dtables, fam)
        if fobj is None or not hasattr(fobj, leaf):
            continue
        if getattr(fobj, leaf, None) is None:
            continue
        spec = getattr(getattr(specs, fam), leaf, None)
        if spec is None:
            continue
        for axis, ax in enumerate(spec):
            if ax == table_axis:
                out[(fam, leaf)] = axis
                break
    return out


def datapath_all_replica_axes(
    dtables, ntp: int, table_axis: str = TABLE_AXIS
):
    """{(family, leaf): sharded-axis} over the WHOLE datapath tree —
    the datapath families (datapath_replica_axes) merged with the
    policy replica leaves keyed as ("policy", name).  THE augmented-
    leaf enumeration the delta publish, the chip repair and the
    residency assertions all share, so they can never disagree about
    which leaves carry N+1 copies."""
    out = dict(datapath_replica_axes(dtables, ntp, table_axis))
    out.update(
        {
            ("policy", name): axis
            for name, axis in replica_axes(
                dtables.policy, ntp, table_axis
            ).items()
        }
    )
    return out


def replicate_datapath_leaves(
    dtables, ntp: int, table_axis: str = TABLE_AXIS
):
    """DatapathTables with every datapath replica-rule leaf augmented
    along its sharded axis (replicate_shard_axis: each shard's slice
    plus its left neighbour's backup copy) and the policy sub-tree
    augmented by replicate_table_leaves — the device layout the fused
    failover evaluator consumes."""
    import dataclasses

    axes = datapath_replica_axes(dtables, ntp, table_axis)
    fam_updates = {}
    for (fam, leaf), axis in axes.items():
        fam_updates.setdefault(fam, {})[leaf] = replicate_shard_axis(
            getattr(getattr(dtables, fam), leaf), ntp, axis
        )
    new_fams = {
        fam: dataclasses.replace(getattr(dtables, fam), **ups)
        for fam, ups in fam_updates.items()
    }
    return dataclasses.replace(
        dtables,
        policy=replicate_table_leaves(
            dtables.policy, ntp, table_axis
        ),
        **new_fams,
    )


def datapath_partition_digest(
    table_axis: str = TABLE_AXIS, ntp: Optional[int] = None
) -> int:
    """Digest of the WHOLE fused-datapath placement — every family's
    rule table plus both replica sets and the backup offset — folded
    into the datapath store's epoch layout, so a delta recorded under
    one partitioning can never scatter into an epoch laid out under
    another (the cross-layout refusal the policy store already has,
    extended to the CT/ipcache/LB planes)."""
    parts = []
    for fam, rules in (
        ("policy", default_table_rules(table_axis)),
        ("ipcache", default_ipcache_rules(table_axis)),
        ("ct", default_ct_rules(table_axis)),
        ("lb", default_lb_rules(table_axis)),
    ):
        parts.append(
            fam + ":" + ";".join(
                f"{pat}->{tuple(spec)}" for pat, spec in rules
            )
        )
    parts.append("replicas=" + ",".join(REPLICA_LEAVES))
    parts.append(
        "dp_replicas="
        + ",".join(f"{f}.{l}" for f, l in DATAPATH_REPLICA_LEAVES)
    )
    parts.append(f"backup_offset={REPLICA_BACKUP_OFFSET}")
    if ntp is not None:
        # the reshard refusal seam: same reason as
        # replica_partition_digest(ntp=...) — augmented shapes are
        # ntp-invariant, so only the digest separates the layouts
        parts.append(f"ntp={int(ntp)}")
    return zlib.crc32("|".join(parts).encode()) & 0xFFFFFFFF


# ---------------------------------------------------------------------------
# Elastic resharding: the owned-row delta between two shard counts
# ---------------------------------------------------------------------------
#
# The augmented replica layout is ntp-INVARIANT in total shape: a
# sharded axis [S] becomes [2S] under ANY shard count (each shard
# holds its primary slice plus one backup copy), so migrating a leaf
# from ntp_src to ntp_dst is a pure index permutation of the
# augmented axis.  The owned-row delta below says which target
# augmented positions a migration must actually MOVE.
#
# Byte-accounting model (the simulation boundary the reshard engine
# documents): a target augmented row j — holding un-augmented row u,
# owned by target LOGICAL column c — is RETAINED (a device-local
# copy, zero H2D bytes) iff column c also existed in the source
# layout (c < ntp_src) and the source chip at the same logical
# column already held u in its primary or backup region.  Every
# other row is MOVED: streamed host→device in bounded-byte steps and
# counted into reshard_bytes_h2d.  Growth 2→4 therefore moves
# exactly the new columns' contents — the rows whose owner changed —
# never O(world).


def reshard_row_map(
    n_rows: int, ntp_src: int, ntp_dst: int
) -> Tuple[np.ndarray, np.ndarray]:
    """For one sharded leaf axis of un-augmented length `n_rows`:
    (src_unaug, moved) over the TARGET augmented axis [2 * n_rows].

      * src_unaug[j] — the un-augmented row index target augmented
        position j holds under ntp_dst (primary region [0, n) of
        each column block holds the column's own slice, backup
        region [n, 2n) its left neighbour's);
      * moved[j]     — True when position j must be streamed under
        the column-identity retention model above.

    Both shard counts must divide `n_rows` (the divisibility-checked
    rule layer guarantees it for every sharded leaf)."""
    S = int(n_rows)
    if S % ntp_src or S % ntp_dst:
        raise ValueError(
            f"shard counts {ntp_src}->{ntp_dst} must divide the "
            f"sharded axis ({S} rows)"
        )
    n_t = S // ntp_dst
    n_s = S // ntp_src
    j = np.arange(2 * S)
    col = j // (2 * n_t)
    within = j - col * 2 * n_t
    primary = within < n_t
    src_unaug = np.where(
        primary,
        col * n_t + within,
        ((col - REPLICA_BACKUP_OFFSET) % ntp_dst) * n_t
        + (within - n_t),
    )
    src_shard = src_unaug // n_s
    resident = (col < ntp_src) & (
        (src_shard == col)
        | (src_shard == (col - REPLICA_BACKUP_OFFSET) % ntp_src)
    )
    return src_unaug, ~resident


def reshard_moved_rows(
    tables, ntp_src: int, ntp_dst: int,
    table_axis: str = TABLE_AXIS,
) -> Dict[str, Tuple[int, np.ndarray]]:
    """{leaf: (axis, moved target-augmented indices)} for the policy
    replica leaves — the owned-row delta a ReshardPlan streams.  The
    replica leaf SET must agree between the two shard counts (a leaf
    sharded at one count but replicated at the other is a geometry
    change, not a permutation): the plan refuses and full-uploads
    into the target instead."""
    src_axes = replica_axes(tables, ntp_src, table_axis)
    dst_axes = replica_axes(tables, ntp_dst, table_axis)
    if set(src_axes) != set(dst_axes):
        raise ValueError(
            "replica leaf sets differ between shard counts "
            f"{ntp_src} ({sorted(src_axes)}) and {ntp_dst} "
            f"({sorted(dst_axes)}): not a permutation reshard"
        )
    out: Dict[str, Tuple[int, np.ndarray]] = {}
    for name, axis in dst_axes.items():
        n = int(
            np.asarray(getattr(tables, name)).shape[axis]
        )
        _, moved = reshard_row_map(n, ntp_src, ntp_dst)
        out[name] = (axis, np.flatnonzero(moved))
    return out


def datapath_reshard_moved_rows(
    dtables, ntp_src: int, ntp_dst: int,
    table_axis: str = TABLE_AXIS,
) -> Dict[Tuple[str, str], Tuple[int, np.ndarray]]:
    """reshard_moved_rows over the WHOLE datapath tree: {(family,
    leaf): (axis, moved target-augmented indices)} for every
    N+1-augmented leaf — policy + CT + ipcache + LB, the same
    enumeration the delta publish and chip repair share
    (datapath_all_replica_axes)."""
    src_axes = datapath_all_replica_axes(
        dtables, ntp_src, table_axis
    )
    dst_axes = datapath_all_replica_axes(
        dtables, ntp_dst, table_axis
    )
    if set(src_axes) != set(dst_axes):
        raise ValueError(
            "datapath replica leaf sets differ between shard "
            f"counts {ntp_src} and {ntp_dst}: not a permutation "
            "reshard"
        )
    out: Dict[Tuple[str, str], Tuple[int, np.ndarray]] = {}
    for (fam, leaf), axis in dst_axes.items():
        n = int(
            np.asarray(
                getattr(getattr(dtables, fam), leaf)
            ).shape[axis]
        )
        _, moved = reshard_row_map(n, ntp_src, ntp_dst)
        out[(fam, leaf)] = (axis, np.flatnonzero(moved))
    return out


def _family_byte_rows(
    fam, obj, names, rules, ntp, table_axis, rep_axes
):
    children, _ = obj.tree_flatten()
    specs = _family_spec_children(
        children, names, rules, ntp, table_axis
    )
    rows = []
    for name, leaf, spec in zip(names, children, specs):
        if leaf is None:
            continue
        # leaf.nbytes avoids a D2H copy when the model runs over a
        # device-resident tree (bench does)
        nbytes = int(
            getattr(leaf, "nbytes", None) or np.asarray(leaf).nbytes
        )
        sharded = spec is not None and any(
            ax == table_axis for ax in spec
        )
        chip = (nbytes + ntp - 1) // ntp if sharded else nbytes
        rep = (fam, name) in rep_axes
        if rep:
            chip *= 2
        rows.append(
            {
                "leaf": f"{fam}.{name}",
                "sharded": sharded,
                "replicated_n_plus_1": rep,
                "bytes_total": nbytes,
                "bytes_per_chip": chip,
            }
        )
    return rows


def datapath_bytes_model(
    dtables, num_shards: int, table_axis: str = TABLE_AXIS
):
    """Per-leaf per-chip bytes of the WHOLE fused datapath under the
    family rule tables + the N+1 replica placement (policy leaves via
    replica_bytes_model, CT/ipcache/LB via their family rules).
    Returns (rows, per_chip_total, replicated_total, overhead):
    `replicated_total` is the per-chip constant the acceptance bound
    allows on top of replicated-bytes / num_shards; `overhead` is
    exactly the backup copies' bytes — bounded by replicated/N."""
    from cilium_tpu.lb.device import LBInline

    rep_axes = datapath_replica_axes(dtables, num_shards, table_axis)
    pol_rows, pol_per_chip, pol_overhead = replica_bytes_model(
        dtables.policy, num_shards, table_axis
    )
    rows = [
        {**r, "leaf": f"policy.{r['leaf']}"} for r in pol_rows
    ]
    per_chip = pol_per_chip
    overhead = pol_overhead
    replicated = sum(
        r["bytes_per_chip"] for r in rows if not r["sharded"]
    )
    fam_args = [
        ("ct", dtables.ct, CT_LEAF_NAMES,
         default_ct_rules(table_axis)),
        (
            "lb", dtables.lb,
            LB_INLINE_LEAF_NAMES
            if isinstance(dtables.lb, LBInline)
            else LB_CLASSIC_LEAF_NAMES,
            default_lb_rules(table_axis),
        ),
    ]
    from cilium_tpu.ipcache.lpm import IPCacheDevice

    if isinstance(dtables.ipcache, IPCacheDevice):
        fam_args.append(
            ("ipcache", dtables.ipcache, IPCACHE_LEAF_NAMES,
             default_ipcache_rules(table_axis))
        )
    for fam, obj, names, rules in fam_args:
        frows = _family_byte_rows(
            fam, obj, names, rules, num_shards, table_axis, rep_axes
        )
        rows.extend(frows)
        for r in frows:
            per_chip += r["bytes_per_chip"]
            if not r["sharded"]:
                replicated += r["bytes_per_chip"]
            elif r["replicated_n_plus_1"]:
                overhead += r["bytes_per_chip"] // 2
    # prefilter / tunnel / a DIR-24-8 ipcache: replicated constants
    extra = [dtables.prefilter, dtables.tunnel]
    if not isinstance(dtables.ipcache, IPCacheDevice):
        extra.append(dtables.ipcache)
    for tree in extra:
        if tree is None:
            continue
        nbytes = sum(
            int(getattr(l, "nbytes", None) or np.asarray(l).nbytes)
            for l in jax.tree.leaves(tree)
        )
        if nbytes:
            per_chip += nbytes
            replicated += nbytes
    return rows, per_chip, replicated, overhead


def datapath_universe_max_identities(
    dtables,
    num_shards: int,
    hbm_bytes: int = 16 << 30,
    table_axis: str = TABLE_AXIS,
) -> int:
    """universe_max_identities extended to the WHOLE datapath
    footprint.  Identity-scaling bytes = the policy identity-major
    leaves (by rule intent, as universe_max_identities classifies)
    PLUS the ipcache /32 bucket plane (every identity is reachable
    at ≥ 1 /32 entry, so the bucket table grows linearly with the
    universe) — N+1 replica leaves count twice.  The CT/LB planes
    scale with flows and services, not identities: their sharded
    leaves divide by num_shards (×2 where replicated N+1) and join
    the per-chip constant alongside the replicated leaves."""
    children, _ = dtables.policy.tree_flatten()
    specs = match_partition_rules(
        default_table_rules(table_axis), POLICY_LEAF_NAMES, children
    )
    n = int(dtables.policy.id_table.shape[0])
    id_bytes = 0.0
    constant = 0.0
    for name, leaf, spec in zip(POLICY_LEAF_NAMES, children, specs):
        if leaf is None:
            continue
        nbytes = int(
            getattr(leaf, "nbytes", None) or np.asarray(leaf).nbytes
        )
        if any(ax == table_axis for ax in spec):
            id_bytes += nbytes * (2 if name in REPLICA_LEAVES else 1)
        else:
            constant += nbytes
    rows, per_chip, _replicated, _overhead = datapath_bytes_model(
        dtables, num_shards, table_axis
    )
    for r in rows:
        if r["leaf"].startswith("policy."):
            continue  # accounted above (slope or constant)
        if r["leaf"] == "ipcache.buckets" and r["sharded"]:
            id_bytes += r["bytes_total"] * (
                2 if r["replicated_n_plus_1"] else 1
            )
        else:
            constant += r["bytes_per_chip"]
    # prefilter / tunnel constants datapath_bytes_model folded into
    # per_chip but not into rows: recover them from the totals
    row_chip = sum(r["bytes_per_chip"] for r in rows)
    constant += max(per_chip - row_chip, 0)
    per_id = id_bytes / max(n, 1)
    budget = hbm_bytes - constant
    if per_id <= 0 or budget <= 0:
        return 0
    return int(budget * num_shards / per_id)


def datapath_alltoall_bytes_per_tuple(
    num_shards: int, range_classes: int = 2
) -> float:
    """Collective bytes the fused routed-gather pipeline moves per
    tuple along the table axis: the lattice's exact+L3 psum pair
    (12 B, alltoall_bytes_per_tuple) plus the CT service probe
    (found + value, 8 B), the CT flow probe (fwd/rev found + values,
    16 B), the LB service resolution (found/slave/daddr/dport/rev_nat,
    20 B), the ipcache exact probe (found + value, 8 B) and one
    (found + value) pair per hashed range-length class.  A 1-shard
    mesh moves nothing."""
    if num_shards <= 1:
        return 0.0
    return 12.0 + 8.0 + 16.0 + 20.0 + 8.0 + 8.0 * range_classes


def alltoall_bytes_per_tuple(num_shards: int) -> float:
    """Collective bytes the routed-gather evaluator moves per tuple
    along the identity axis: each routed probe returns its verdict
    column to the originating shard through one integer psum —
    exact-probe found+value (8 B) plus the L3 word-probe bit (4 B).
    A 1-shard mesh moves nothing (the psum folds away)."""
    if num_shards <= 1:
        return 0.0
    return 12.0
