"""One datapath, one mesh: the FULL fused pipeline over partitioned
N+1 tables.

engine/datapath.py fuses prefilter + LB/DNAT + CT + ipcache + lattice
into one jit, but every leaf it gathers is REPLICATED per chip — a
mesh buys throughput, never capacity, and the CT/ipcache/LB planes
cap the universe at one chip's HBM exactly as the policy leaves did
before PR 7.  This module is the closing move: the same routed-gather
construction the partitioned/failover lattice evaluators use
(engine/sharded.py), applied to EVERY hashed bucket-row plane of the
pipeline under the declarative family rules of compiler/partition.py:

  * CT bucket rows, ipcache /32 bucket rows + hashed range-class
    rows, and the inline LB service rows shard along the same table
    axis as `l4_hash_rows` and join the N+1 replica placement
    (DATAPATH_REPLICA_LEAVES) — each shard holds its slice plus its
    left neighbour's backup copy;
  * inside shard_map, each tuple's bucket routes to its owning shard
    (the backup owner when the primary's chip is dead, exactly the
    alive-masked routing of make_failover_evaluator); the owner
    computes the probe's SMALL outputs locally — found bits, masked
    value sums, LB backend selection — and one integer psum per probe
    returns them to the batch shard (`ct_probe_row_parts` /
    `lb_slot_outputs` / `ipcache_bucket_parts` / `range_row_parts`
    are the owner-maskable halves the single-chip kernels now share);
  * stashes, the broadcast-fallback range arrays, prefilter and
    tunnel tables replicate and contribute OUTSIDE the psums (a
    replicated term summed across the table axis would inflate by
    tp);
  * the policy lattice is the shared `failover_lattice_probes` body —
    identical routing, counters and replica semantics to
    make_failover_evaluator, with idx/known derived from the routed
    ipcache lookup instead of id_direct.

The result is bit-identical to the single-device fused program (which
is itself gated against the composed host oracle in
tests/test_datapath.py) at every table-axis size and under any
survivor set that keeps one owner per slice alive — and per-chip HBM
for the CT/ipcache/LB planes drops toward replicated/N.

`DatapathStore` is the publication half: the augmented pytree lives
sharded on device, and a re-publish diffs each sharded plane's rows
against the previously published host snapshot and scatters ONLY the
changed rows (in augmented coordinates, so primary and backup copies
stay bit-identical through churn) — CT writeback churn, DNS-driven
ipcache upserts and backend flips all ride the delta path, bytes
proportional to the change.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from dataclasses import dataclass
from functools import partial
from typing import Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from cilium_tpu import tracing
from cilium_tpu.compiler import partition
from cilium_tpu.engine.datapath import (
    DatapathTables,
    DatapathVerdicts,
    FlowBatch,
    flow_batch_from_packed4,
)
from cilium_tpu.engine.publish import next_pow2
from cilium_tpu.engine.sharded import (
    failover_counts,
    failover_lattice_probes,
    fold_l3_aug,
)
from cilium_tpu.maps.policymap import INGRESS

__all__ = [
    "DatapathStore",
    "make_failover_datapath_evaluator",
    "make_failover_datapath_pair_evaluator",
]


def _routed_rows(rows_l, bucket, ntp, my_col, alive_row, sharded,
                 n_global):
    """One routed bucket-row gather with N+1 replica fallback — the
    shared routing step of every hashed plane: the owning shard
    (backup owner when the primary's chip is dead) gathers its local
    row, everyone else gathers a clipped dummy and masks.  Returns
    (row [B, lanes], owns bool [B], served_from_backup bool [B])."""
    if not sharded:
        ones = jnp.ones(bucket.shape, bool)
        return rows_l[bucket], ones, jnp.zeros(bucket.shape, bool)
    n = n_global // ntp
    p = bucket // n
    ap = alive_row[p]
    owner = jnp.where(
        ap, p, (p + partition.REPLICA_BACKUP_OFFSET) % ntp
    )
    owns = owner == my_col
    bl = (bucket - p * n) + jnp.where(ap, 0, n)
    bl = jnp.clip(bl, 0, 2 * n - 1)
    return rows_l[bl], owns, owns & ~ap


def _geometry(dtables: DatapathTables) -> tuple:
    """Static geometry signature the evaluator closures route by —
    any change (hash-plane regrow, stash trim crossing a pow2 class,
    range-class schedule change, layout form flip) must rebuild the
    evaluator AND full-upload the store."""
    from cilium_tpu.ipcache.lpm import IPCacheDevice
    from cilium_tpu.lb.device import LBInline

    ipc = dtables.ipcache
    lb = dtables.lb
    return (
        tuple(np.asarray(dtables.ct.buckets).shape),
        type(ipc).__name__,
        tuple(np.asarray(ipc.buckets).shape)
        if isinstance(ipc, IPCacheDevice) else (),
        None
        if not isinstance(ipc, IPCacheDevice)
        or ipc.range_rows is None
        else tuple(np.asarray(ipc.range_rows).shape),
        tuple(ipc.range_class_plens)
        if isinstance(ipc, IPCacheDevice) else (),
        bool(getattr(ipc, "l3_planes", False)),
        int(getattr(ipc, "world_plus1", 0)),
        type(lb).__name__,
        tuple(np.asarray(lb.rows).shape)
        if isinstance(lb, LBInline)
        else tuple(np.asarray(lb.buckets).shape),
        type(dtables.prefilter).__name__,
        tuple(np.asarray(dtables.policy.l4_hash_rows).shape),
        tuple(np.asarray(dtables.policy.l3_allow_bits).shape),
        # sub-word layout markers: a width flip at an unchanged
        # shape is still a different program AND a different
        # resident encoding — must rebuild + full upload
        int(getattr(dtables.ct, "entry_words", 5)),
        (
            int(getattr(ipc, "bucket_entries", 0)),
            int(getattr(ipc, "value_width", 32)),
            int(getattr(ipc, "l3_width", 32)),
            tuple(getattr(ipc, "range_widths", ()) or ()),
        )
        if isinstance(ipc, IPCacheDevice) else (),
        int(np.asarray(dtables.policy.l4_hash_stash).shape[-1]),
        int(np.asarray(dtables.policy.l4_wild_stash).shape[-1]),
    )


def _check_fused_world(dtables: DatapathTables) -> None:
    from cilium_tpu.ipcache.lpm import IPCacheDevice

    if dtables.policy.l4_hash_rows is None:
        raise ValueError(
            "fused mesh datapath requires the hashed L4 entry tables"
        )
    ipc = dtables.ipcache
    if not isinstance(ipc, IPCacheDevice) or not ipc.values_are_idx:
        raise ValueError(
            "fused mesh datapath requires an idx-form IPCacheDevice "
            "(specialize_ipcache_to_idx); the DIR-24-8 fallback is "
            "host-compiled for range-heavy worlds only"
        )


def _fused_geom(dtables: DatapathTables, ntp: int, table_axis: str):
    """Closure constants of the fused kernel: per-plane global row
    counts + sharded flags (from the divisibility-checked family
    rules) and the lattice geometry of the failover evaluator."""
    from cilium_tpu.lb.device import LBInline

    rep_axes = partition.datapath_replica_axes(
        dtables, ntp, table_axis
    )
    pol = dtables.policy
    rows_sharded = "l4_hash_rows" in partition.replica_axes(
        pol, ntp, table_axis
    )
    l3_sharded = "l3_allow_bits" in partition.replica_axes(
        pol, ntp, table_axis
    )
    ipc = dtables.ipcache
    return {
        "ntp": ntp,
        "ct_sharded": ("ct", "buckets") in rep_axes,
        "ct_ew": int(getattr(dtables.ct, "entry_words", 5)),
        "range_widths": tuple(
            getattr(ipc, "range_widths", ()) or ()
        ),
        "n_ct": int(np.asarray(dtables.ct.buckets).shape[0]),
        "lb_inline": isinstance(dtables.lb, LBInline),
        "lb_sharded": ("lb", "rows") in rep_axes,
        "n_lb": int(
            np.asarray(dtables.lb.rows).shape[0]
            if isinstance(dtables.lb, LBInline)
            else 0
        ),
        "ipc_sharded": ("ipcache", "buckets") in rep_axes,
        "n_ipc": int(np.asarray(ipc.buckets).shape[0]),
        "range_sharded": ("ipcache", "range_rows") in rep_axes,
        "n_range": (
            0
            if ipc.range_rows is None
            else int(np.asarray(ipc.range_rows).shape[0])
        ),
        "range_planes": 5 if ipc.l3_planes else 3,
        "world_plus1": int(ipc.world_plus1),
        "rows_sharded": rows_sharded,
        "l3_sharded": l3_sharded,
        "n_rows_global": int(pol.l4_hash_rows.shape[0]),
        "n_row_shard": (
            int(pol.l4_hash_rows.shape[0]) // ntp
            if rows_sharded else 0
        ),
        "w_global": int(pol.l3_allow_bits.shape[-1]),
        "wn": (
            int(pol.l3_allow_bits.shape[-1]) // ntp
            if l3_sharded else 0
        ),
        "n_ids": int(pol.l3_allow_bits.shape[-1]) * 32,
    }


def _fused_core(
    dt_l: DatapathTables,
    flows_l: FlowBatch,
    alive_row,
    my_col,
    valid_l,
    g: dict,
    table_axis: str,
    batch_axis: str,
    static_direction=None,
    collect_telemetry: bool = False,
):
    """The routed fused pipeline body (one direction program when
    `static_direction` is set — the per-direction specialization of
    engine/datapath.py carried onto the mesh).  Stage order and
    combine semantics mirror _datapath_core exactly; every hashed
    gather is owner-routed with replica fallback and returned
    through one small integer psum."""
    from cilium_tpu.ct.device import (
        _normalize_device,
        ct_probe_combine,
        ct_probe_keys,
        ct_probe_row_parts,
        ct_probe_stash_parts,
    )
    from cilium_tpu.ct.table import (
        CT_ESTABLISHED,
        CT_NEW,
        CT_RELATED,
        CT_REPLY,
        CT_SERVICE,
    )
    from cilium_tpu.engine.hashtable import fnv1a_device
    from cilium_tpu.engine.verdict import _combine, telemetry_masks
    from cilium_tpu.ipcache.lpm import (
        UNKNOWN_IDX,
        ipcache_bucket_parts,
        ipcache_stash_parts,
        range_class_key,
        range_row_parts,
        range_take_fold,
    )
    from cilium_tpu.lb.device import (
        flow_hash,
        lb_inline_slot,
        lb_inline_stash_slot,
        lb_service_key,
        lb_slot_outputs,
    )
    from cilium_tpu.prefilter import prefilter_drop

    ntp = g["ntp"]

    def psum_i(x):
        return jax.lax.psum(x.astype(jnp.int32), table_axis) > 0

    def psum_u(x):
        return jax.lax.psum(x, table_axis)

    if static_direction is None:
        ingress = flows_l.direction == INGRESS
    else:
        ingress = jnp.full(
            flows_l.direction.shape, static_direction == INGRESS
        )
    saddr = flows_l.saddr.astype(jnp.uint32)
    daddr = flows_l.daddr.astype(jnp.uint32)
    backup = jnp.zeros(saddr.shape, bool)

    # -- 1. XDP prefilter (replicated broadcast) ------------------------
    pre_drop = prefilter_drop(dt_l.prefilter, flows_l.saddr)

    # -- 2+3. routed CT row gather serves both probes -------------------
    lo_a, hi_a, lo_p, hi_p, _sw = _normalize_device(
        flows_l.daddr, flows_l.saddr, flows_l.dport, flows_l.sport
    )
    proto_u = flows_l.proto.astype(jnp.uint32) & 0xFF
    hct = fnv1a_device(
        jnp.stack([lo_a, hi_a, (lo_p << 16) | hi_p, proto_u], axis=1)
    )
    ct_bucket = (hct & jnp.uint32(g["n_ct"] - 1)).astype(jnp.int32)
    ct_rows, owns_ct, rep_ct = _routed_rows(
        dt_l.ct.buckets, ct_bucket, ntp, my_col, alive_row,
        g["ct_sharded"], g["n_ct"],
    )
    backup = backup | rep_ct

    def ct_probe(p_daddr, p_dport, direction_v):
        """One routed CT probe against the fetched rows: owner-local
        row parts psum'd, replicated stash parts added after."""
        ka, kb, kw, w3f, w3r, rel = ct_probe_keys(
            p_daddr, flows_l.saddr, p_dport, flows_l.sport,
            flows_l.proto, direction_v,
        )
        rf, rr, rfv, rrv = ct_probe_row_parts(
            ct_rows, ka, kb, kw, w3f, w3r, owns=owns_ct,
            entry_words=g["ct_ew"],
        )
        if g["ct_sharded"]:
            rf, rr = psum_i(rf), psum_i(rr)
            rfv, rrv = psum_u(rfv), psum_u(rrv)
        sf, sr, sfv, srv = ct_probe_stash_parts(
            dt_l.ct, ka, kb, kw, w3f, w3r
        )
        return ct_probe_combine(
            rf | sf, rr | sr, rfv + sfv, rrv + srv, rel
        )

    if static_direction == INGRESS:
        zero = jnp.zeros(flows_l.dport.shape, jnp.int32)
        eff_daddr = daddr
        eff_dport = flows_l.dport
        rev_nat = zero
        lb_slave = zero
    else:
        svc_dir = jnp.full_like(flows_l.direction, CT_SERVICE)
        _, _, svc_slave = ct_probe(
            flows_l.daddr, flows_l.dport, svc_dir
        )
        # routed LB service resolution (inline rows): the owner
        # computes the backend selection from its slot and the
        # five small output columns psum back
        vip, w1lb = lb_service_key(
            flows_l.daddr, flows_l.dport, flows_l.proto
        )
        fh = flow_hash(
            flows_l.saddr, flows_l.daddr, flows_l.sport,
            flows_l.dport, flows_l.proto,
        )
        if g["lb_inline"]:
            hlb = fnv1a_device(jnp.stack([vip, w1lb], axis=1))
            lb_bucket = (
                hlb & jnp.uint32(g["n_lb"] - 1)
            ).astype(jnp.int32)
            lb_rows, owns_lb, rep_lb = _routed_rows(
                dt_l.lb.rows, lb_bucket, ntp, my_col, alive_row,
                g["lb_sharded"], g["n_lb"],
            )
            backup = backup | rep_lb
            slot_r, row_found = lb_inline_slot(
                lb_rows, vip, w1lb, owns=owns_lb
            )
            f_r, sl_r, da_r, dp_r, rn_r = lb_slot_outputs(
                slot_r, row_found, fh, ct_slave=svc_slave
            )
            if g["lb_sharded"]:
                f_r = psum_i(f_r)
                sl_r = jax.lax.psum(sl_r, table_axis)
                da_r = psum_u(da_r)
                dp_r = jax.lax.psum(dp_r, table_axis)
                rn_r = jax.lax.psum(rn_r, table_axis)
            slot_s, s_found = lb_inline_stash_slot(
                dt_l.lb, vip, w1lb
            )
            f_s, sl_s, da_s, dp_s, rn_s = lb_slot_outputs(
                slot_s, s_found, fh, ct_slave=svc_slave
            )
            svc_found = f_r | f_s
            slave = sl_r + sl_s
            lb_daddr = da_r + da_s
            lb_dport = dp_r + dp_s
            lb_rev = rn_r + rn_s
        else:
            # classic layout: replicated wholesale (identical on
            # every shard), so the single-chip select is exact
            from cilium_tpu.lb.device import lb_select_batch

            svc_found, slave, lb_daddr, lb_dport, lb_rev = (
                lb_select_batch(
                    dt_l.lb, flows_l.saddr, flows_l.daddr,
                    flows_l.sport, flows_l.dport, flows_l.proto,
                    ct_slave=svc_slave,
                )
            )
        do_lb = (~ingress) & svc_found
        eff_daddr = jnp.where(do_lb, lb_daddr, daddr)
        eff_dport = jnp.where(do_lb, lb_dport, flows_l.dport)
        rev_nat = jnp.where(do_lb, lb_rev, 0)
        lb_slave = jnp.where(do_lb, slave, 0)

    ct_res, _ct_rev, _ = ct_probe(
        eff_daddr, eff_dport, flows_l.direction
    )

    # -- 4. routed ipcache (idx-form) -----------------------------------
    ipc = dt_l.ipcache
    sec_ip = jnp.where(ingress, saddr, eff_daddr)
    hip = fnv1a_device(sec_ip[:, None])
    ip_bucket = (hip & jnp.uint32(g["n_ipc"] - 1)).astype(jnp.int32)
    ip_rows, owns_ip, rep_ip = _routed_rows(
        ipc.buckets, ip_bucket, ntp, my_col, alive_row,
        g["ipc_sharded"], g["n_ipc"],
    )
    backup = backup | rep_ip
    bf, bv, _bl3 = ipcache_bucket_parts(
        ipc, ip_rows, sec_ip, ingress=ingress, owns=owns_ip
    )
    if g["ipc_sharded"]:
        bf, bv = psum_i(bf), psum_u(bv)
    sf2, sv2, _sl3 = ipcache_stash_parts(
        ipc, sec_ip, ingress=ingress
    )
    exact_found = bf | sf2
    exact_val = bv + sv2
    if ipc.range_rows is not None:
        classes = []
        for sp in ipc.range_class_plens:  # static, longest first
            w0c, hc = range_class_key(sec_ip, sp)
            r_bucket = (
                hc & jnp.uint32(g["n_range"] - 1)
            ).astype(jnp.int32)
            r_row, owns_r, rep_r = _routed_rows(
                ipc.range_rows, r_bucket, ntp, my_col, alive_row,
                g["range_sharded"], g["n_range"],
            )
            backup = backup | rep_r
            hitc, rv, _li, _lo = range_row_parts(
                r_row, w0c, sp, g["range_planes"], owns=owns_r,
                widths=g["range_widths"],
            )
            if g["range_sharded"]:
                hitc, rv = psum_i(hitc), psum_u(rv)
            zero_u = jnp.zeros(sec_ip.shape, jnp.uint32)
            classes.append((hitc, rv, zero_u, zero_u))
        range_found, range_val, _, _ = range_take_fold(
            classes, sec_ip.shape
        )
    else:
        # broadcast fallback over the replicated range arrays —
        # same selection as ipcache_lookup_fused's fallback branch
        match = (
            sec_ip[:, None] & jnp.asarray(ipc.range_mask)[None, :]
        ) == jnp.asarray(ipc.range_base)[None, :]
        plen = jnp.asarray(ipc.range_plen)
        best = jnp.max(jnp.where(match, plen[None, :], 0), axis=1)
        range_sel = match & (plen[None, :] == best[:, None])
        range_found = best > 0
        range_val = jnp.sum(
            jnp.where(
                range_sel, jnp.asarray(ipc.range_value)[None, :], 0
            ),
            axis=1, dtype=jnp.uint32,
        )
    looked = jnp.where(
        exact_found, exact_val,
        jnp.where(range_found, range_val, 0),
    )
    n_pad = dt_l.policy.id_table.shape[0]
    miss = looked == 0
    ipc_miss = miss
    vp = jnp.where(miss, jnp.uint32(g["world_plus1"]), looked)
    known = (vp != 0) & (vp != jnp.uint32(UNKNOWN_IDX))
    idx = jnp.where(known, vp - 1, jnp.uint32(n_pad - 1)).astype(
        jnp.int32
    )
    sec_id = dt_l.policy.id_table[idx]

    # -- 5. the routed replica-aware policy lattice ---------------------
    lat_dport = jnp.clip(eff_dport, 0, 65535).astype(jnp.int32)
    lat_proto = jnp.clip(flows_l.proto, 0, 255).astype(jnp.int32)
    lat = failover_lattice_probes(
        dt_l.policy, flows_l.ep_index, flows_l.direction, lat_dport,
        lat_proto, idx, known, alive_row, my_col, ntp,
        g["rows_sharded"], g["l3_sharded"], g["n_rows_global"],
        g["n_row_shard"], g["wn"], table_axis,
    )
    v = _combine(
        lat["probe1"], lat["probe2"], lat["probe3"], lat["proxy"],
        flows_l.is_fragment,
    )
    backup = backup | lat["replica"]
    l4_counts, l3_counts = failover_counts(
        dt_l.policy, flows_l.ep_index, flows_l.direction,
        v.match_kind, lat["j"], idx, lat["p2_local"], valid_l,
        g["l3_sharded"], g["wn"], lat["wp"], lat["apw"], g["n_ids"],
        batch_axis,
    )

    # -- 6. combine (bpf_lxc.c:962-985) ---------------------------------
    pol_allow = v.allowed.astype(bool)
    pass_ct = (ct_res == CT_REPLY) | (ct_res == CT_RELATED)
    allowed = (~pre_drop) & (pass_ct | pol_allow)
    ct_delete = (
        (ct_res == CT_ESTABLISHED) & ~pol_allow & ~pass_ct & ~pre_drop
    )
    ct_create = (ct_res == CT_NEW) & allowed
    proxy = jnp.where(
        pol_allow
        & ((ct_res == CT_NEW) | (ct_res == CT_ESTABLISHED))
        & allowed,
        v.proxy_port,
        0,
    )

    # -- 7. overlay forwarding (replicated tunnel tables) ---------------
    if dt_l.tunnel is not None and static_direction != INGRESS:
        from cilium_tpu.tunnel import tunnel_select

        tunnel_ep = jnp.where(
            allowed & ~ingress,
            tunnel_select(dt_l.tunnel, eff_daddr),
            jnp.uint32(0),
        )
    else:
        tunnel_ep = jnp.zeros(eff_daddr.shape, jnp.uint32)

    out = DatapathVerdicts(
        allowed=allowed.astype(jnp.uint8),
        proxy_port=proxy,
        match_kind=v.match_kind,
        ct_result=ct_res,
        pre_dropped=pre_drop,
        sec_id=sec_id,
        final_daddr=eff_daddr,
        final_dport=eff_dport,
        rev_nat=rev_nat,
        lb_slave=lb_slave,
        ct_create=ct_create,
        ct_delete=ct_delete,
        tunnel_endpoint=tunnel_ep,
        l4_slot=lat["j"],
        ipcache_miss=ipc_miss,
    )
    replica_hits = jax.lax.psum(
        jax.lax.psum(
            jnp.sum((backup & valid_l).astype(jnp.uint32)),
            batch_axis,
        ),
        table_axis,
    )
    trow = None
    if collect_telemetry:
        masks = telemetry_masks(
            pre_drop, ct_res, v.match_kind, allowed, ct_delete,
            proxy, lb_slave, ipc_miss,
        )
        ing_v = ingress & valid_l
        row_in = jnp.stack(
            [jnp.sum(m & ing_v, dtype=jnp.uint32) for m in masks]
        )
        col_total = jnp.stack(
            [jnp.sum(m & valid_l, dtype=jnp.uint32) for m in masks]
        )
        trow = jnp.stack([row_in, col_total - row_in])
    return out, l4_counts, l3_counts, replica_hits, trow


def _verdict_out_specs(batch_axis: str):
    s = P(batch_axis)
    return DatapathVerdicts(
        allowed=s, proxy_port=s, match_kind=s, ct_result=s,
        pre_dropped=s, sec_id=s, final_daddr=s, final_dport=s,
        rev_nat=s, lb_slave=s, ct_create=s, ct_delete=s,
        tunnel_endpoint=s, l4_slot=s, ipcache_miss=s,
    )


def _flow_specs(batch_axis: str) -> FlowBatch:
    s = P(batch_axis)
    return FlowBatch(
        ep_index=s, saddr=s, daddr=s, sport=s, dport=s, proto=s,
        direction=s, is_fragment=s,
    )


def make_failover_datapath_evaluator(
    mesh: Mesh,
    dtables: DatapathTables,
    batch_axis: str = "batch",
    table_axis: str = "table",
    collect_telemetry: bool = False,
):
    """The fused failover datapath program: the FULL pipeline over
    the N+1 AUGMENTED DatapathTables (replicate_datapath_leaves) with
    the same two routing inputs as make_failover_evaluator —
    `alive` bool [dp, tp] chip health and `valid` bool [B] real-tuple
    mask from the router's batch re-split.

    Returns run(dtables_aug, flows, alive, valid) ->
    (DatapathVerdicts [batch-sharded columns], l4_counts [E, 2, Kg],
    l3_counts [E, 2, N] GLOBAL (fold_l3_aug applied host-side),
    replica_hits u32 scalar [, per-chip telemetry [dp, 2, T]]) —
    bit-identical on the valid mask to the single-device fused
    program (engine/datapath.datapath_step_with_counters) and the
    composed host
    oracle, whatever the survivor set, as long as one owner of every
    slice is alive."""
    _check_fused_world(dtables)
    ntp = int(mesh.shape[table_axis])
    g = _fused_geom(dtables, ntp, table_axis)
    t_specs = partition.datapath_partition_specs(
        dtables, ntp, table_axis
    )
    f_specs = _flow_specs(batch_axis)
    l3_spec = (
        P(None, None, table_axis) if g["l3_sharded"] else P()
    )
    out_specs = (_verdict_out_specs(batch_axis), P(), l3_spec, P())
    if collect_telemetry:
        out_specs = out_specs + (P(batch_axis, None, None),)

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(t_specs, f_specs, P(), P(batch_axis)),
        out_specs=out_specs,
        check_vma=False,
    )
    def step(dt_l, flows_l, alive_l, valid_l):
        alive_row = alive_l[jax.lax.axis_index(batch_axis)]
        my_col = jax.lax.axis_index(table_axis)
        out, l4c, l3c, hits, trow = _fused_core(
            dt_l, flows_l, alive_row, my_col, valid_l, g,
            table_axis, batch_axis,
            collect_telemetry=collect_telemetry,
        )
        base = (out, l4c, l3c, hits)
        return base + ((trow[None],) if collect_telemetry else ())

    sh = lambda s: NamedSharding(mesh, s)  # noqa: E731
    in_shardings = (
        jax.tree.map(sh, t_specs, is_leaf=lambda x: isinstance(x, P)),
        jax.tree.map(sh, f_specs, is_leaf=lambda x: isinstance(x, P)),
        sh(P()),
        sh(P(batch_axis)),
    )
    jitted = jax.jit(step, in_shardings=in_shardings)
    built = _geometry(dtables)

    def run(dtables_aug, flows: FlowBatch, alive, valid):
        got_rows = int(
            np.asarray(dtables_aug.policy.l4_hash_rows).shape[0]
        )
        want_rows = g["n_rows_global"] * (
            2 if g["rows_sharded"] else 1
        )
        if got_rows != want_rows:
            raise ValueError(
                "fused datapath evaluator was built for another "
                f"table geometry (hash rows {want_rows} != "
                f"{got_rows}); rebuild with "
                "make_failover_datapath_evaluator"
            )
        out = jitted(dtables_aug, flows, alive, valid)
        if g["l3_sharded"]:
            out = (out[0], out[1], fold_l3_aug(out[2], ntp)) + tuple(
                out[3:]
            )
        return out

    run.geometry = built
    run.geom = g
    return run


def make_failover_datapath_pair_evaluator(
    mesh: Mesh,
    dtables: DatapathTables,
    batch_axis: str = "batch",
    table_axis: str = "table",
    collect_telemetry: bool = True,
):
    """The packed4 PAIR shape of the fused failover datapath: both
    direction-specialized half-batch programs in ONE dispatch over a
    [2, 4, B] staged array (row 0 = ingress half, row 1 = egress
    half — the engine/datapath.py headline staging format carried
    onto the mesh), with the counters and telemetry riding the same
    dispatch.  The ingress program compiles with no LB/service-CT
    stages at all, exactly like the ingress half of
    engine/datapath.py's pair program.

    Returns run(dtables_aug, pair, alive, valid [2, B]) ->
    (out_ingress, out_egress, l4_counts, l3_counts (global),
    replica_hits [, telemetry rows [dp, 2, T] folded over both
    halves])."""
    from cilium_tpu.maps.policymap import EGRESS

    _check_fused_world(dtables)
    ntp = int(mesh.shape[table_axis])
    g = _fused_geom(dtables, ntp, table_axis)
    t_specs = partition.datapath_partition_specs(
        dtables, ntp, table_axis
    )
    l3_spec = (
        P(None, None, table_axis) if g["l3_sharded"] else P()
    )
    v_specs = _verdict_out_specs(batch_axis)
    out_specs = (v_specs, v_specs, P(), l3_spec, P())
    if collect_telemetry:
        out_specs = out_specs + (P(batch_axis, None, None),)
    pair_spec = P(None, None, batch_axis)
    valid_spec = P(None, batch_axis)

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(t_specs, pair_spec, P(), valid_spec),
        out_specs=out_specs,
        check_vma=False,
    )
    def step(dt_l, pair_l, alive_l, valid_l):
        alive_row = alive_l[jax.lax.axis_index(batch_axis)]
        my_col = jax.lax.axis_index(table_axis)
        out_i, l4_i, l3_i, hits_i, trow_i = _fused_core(
            dt_l, flow_batch_from_packed4(pair_l[0]), alive_row,
            my_col, valid_l[0], g, table_axis, batch_axis,
            static_direction=INGRESS,
            collect_telemetry=collect_telemetry,
        )
        out_e, l4_e, l3_e, hits_e, trow_e = _fused_core(
            dt_l, flow_batch_from_packed4(pair_l[1]), alive_row,
            my_col, valid_l[1], g, table_axis, batch_axis,
            static_direction=EGRESS,
            collect_telemetry=collect_telemetry,
        )
        base = (
            out_i, out_e, l4_i + l4_e, l3_i + l3_e, hits_i + hits_e,
        )
        if collect_telemetry:
            base = base + ((trow_i + trow_e)[None],)
        return base

    sh = lambda s: NamedSharding(mesh, s)  # noqa: E731
    in_shardings = (
        jax.tree.map(sh, t_specs, is_leaf=lambda x: isinstance(x, P)),
        sh(pair_spec),
        sh(P()),
        sh(valid_spec),
    )
    jitted = jax.jit(step, in_shardings=in_shardings)

    def run(dtables_aug, pair, alive, valid):
        out = jitted(dtables_aug, pair, alive, valid)
        if g["l3_sharded"]:
            out = out[:3] + (fold_l3_aug(out[3], ntp),) + tuple(
                out[4:]
            )
        return out

    run.geom = g
    return run


# ---------------------------------------------------------------------------
# Publication: the datapath epoch with generic row-diff delta scatter
# ---------------------------------------------------------------------------


@dataclass
class DatapathPublishStats:
    epoch: int
    mode: str  # "full" | "delta"
    bytes_h2d: int
    seconds: float
    scattered_rows: int = 0
    replaced_leaves: int = 0


class DatapathStore:
    """TWO device-resident fused-datapath epochs over the
    partitioned N+1 layout, ping-ponging exactly like the policy
    plane's DeviceTableStore: a publish lands in the SPARE slot (the
    donated row scatter patches only buffers no in-flight dispatch
    can hold) while batches dispatched against the CURRENT epoch
    finish on it untouched — a publish concurrent with a fused
    serving-plane dispatch is safe by construction.

    Publication is ROW-DIFF delta: each sharded plane's new
    augmented host rows are diffed against the SPARE slot's retained
    snapshot and only the CHANGED rows scatter (XLA routes each row
    to its owning chip, in augmented coordinates so primary and
    backup copies stay bit-identical).  Replicated leaves re-place
    wholesale only when they changed.  A geometry change (hash-plane
    regrow, layout form, idx-form world change) forces a full upload
    — and the caller must rebuild the fused evaluator, which closes
    over the same geometry (the partition digest guards
    cross-partitioning publishes the same way the policy store's
    layout stamp does).

    Scaling note: the diff itself is a host-side compare of every
    augmented leaf — H2D bytes are proportional to the CHANGE, but
    publish CPU is O(world).  Scoping the diff through per-subsystem
    change records (the compiler-delta pattern) is the follow-on for
    multi-million-identity worlds; at today's scales the vectorized
    compare is microseconds per MB."""

    def __init__(self, mesh: Mesh, table_axis: str = "table") -> None:
        self.mesh = mesh
        self.table_axis = table_axis
        self.ntp = int(mesh.shape[table_axis])
        self.partition_digest = partition.datapath_partition_digest(
            table_axis, ntp=self.ntp
        )
        self._lock = threading.Lock()
        # each slot: {"dev": device pytree, "host": augmented host
        # pytree (the diff base + repair value source), "geom":
        # geometry signature, "digest": partition digest,
        # "epoch": publish counter at install}
        self._slots = [None, None]
        self._cur = 0
        self.epoch = 0
        self._scatter_cache: Dict[tuple, object] = {}
        self._shardings = None
        # per-epoch change records (publish(changes=...)): epoch ->
        # {family: {leaf: row-idx array | True}} or None (= no
        # record, that publish was full-diffed).  A scoped publish
        # unions the records since the SPARE slot's epoch — the
        # ping-pong means the spare is two publishes old.
        self._change_log: Dict[int, object] = {}
        # open relayout window (engine/reshard.py): the spare slot
        # holds the migration target epoch under the NEW ntp/digest;
        # publish() patches the LIVE slot (non-donated) until the
        # cutover rebinds mesh/ntp/digest to the target
        self._relayout: Optional[Dict] = None

    # -- internals -----------------------------------------------------------

    def _scatter_fn(self, key: tuple, axis: int,
                    donate: bool = True):
        key = key + (bool(donate),)
        fn = self._scatter_cache.get(key)
        if fn is None:
            def apply(leaf, idx, rows):
                index = (slice(None),) * axis + (idx,)
                return leaf.at[index].set(rows)

            fn = tracing.track_jit(
                jax.jit(
                    apply,
                    donate_argnums=(0,) if donate else (),
                ),
                "datapath.scatter" if donate
                else "datapath.scatter_live",
            )
            self._scatter_cache[key] = fn
        return fn

    @staticmethod
    def _tree_nbytes(tree) -> int:
        return sum(
            int(np.asarray(l).nbytes) for l in jax.tree.leaves(tree)
        )

    def _full_place(self, aug: DatapathTables):
        self._shardings = partition.datapath_table_shardings(
            self.mesh, aug, self.table_axis
        )
        dev = jax.tree.map(
            lambda leaf, s: jax.device_put(np.asarray(leaf), s),
            aug, self._shardings,
        )
        jax.block_until_ready(dev)
        return dev, self._tree_nbytes(aug)

    # -- API -----------------------------------------------------------------

    def publish(
        self, dtables: DatapathTables, changes=None
    ) -> Tuple[DatapathTables, DatapathPublishStats]:
        """Install `dtables` (host, UN-augmented) as the serving
        datapath epoch — into the SPARE slot (in-flight batches
        finish on the current epoch untouched), then flip.
        Steady-state churn (CT writeback, ipcache upserts, LB
        backend flips, policy deltas) rides the row-diff scatter
        against the spare's retained snapshot; geometry changes
        full-upload.

        `changes` is an optional per-subsystem CHANGE RECORD —
        {family: {leaf: sharded-row index array | True}} — the
        compiler-delta pattern applied to the fused plane: with a
        record the publish diffs ONLY the named rows (publish CPU is
        O(change), not O(world); no re-augmentation of unchanged
        leaves), shipping exactly the rows that really moved.  The
        caller WARRANTS every unlisted leaf unchanged since the
        previous publish (the churn gate proves resident equality).
        The record is logged per epoch so the ping-pong unions the
        right set against the two-publishes-old spare; any
        intervening record-less publish falls back to the full
        row-diff, as does a geometry/digest change."""
        _check_fused_world(dtables)
        with self._lock, tracing.tracer.span(
            "datapath.publish", site="engine.datapath_mesh"
        ) as sp:
            t0 = time.perf_counter()
            geom = _geometry(dtables)
            self.epoch += 1
            self._change_log[self.epoch] = changes
            for e in list(self._change_log):
                if e <= self.epoch - 8:
                    del self._change_log[e]
            if (
                self._relayout is not None
                and not self._relayout.get("broken")
            ):
                # the spare slot is the staged reshard target: churn
                # patches the LIVE slot instead (non-donated — fused
                # dispatches may hold the live pytree), and anything
                # the delta path cannot absorb breaks the window so
                # the migration plan restarts as a full upload into
                # the target layout
                dev, stats = self._publish_relayout_locked(
                    dtables, geom, sp
                )
                stats.seconds = time.perf_counter() - t0
                return dev, stats
            spare_i = self._cur ^ 1
            spare = self._slots[spare_i]
            union = (
                None if spare is None
                else self._union_changes(spare.get("epoch", 0))
            )
            if (
                spare is None
                or geom != spare["geom"]
                or spare["digest"] != self.partition_digest
            ):
                aug = partition.replicate_datapath_leaves(
                    dtables, self.ntp, self.table_axis
                )
                dev, nbytes = self._full_place(aug)
                stats = DatapathPublishStats(
                    epoch=self.epoch, mode="full",
                    bytes_h2d=nbytes, seconds=0.0,
                )
                slot = {
                    "dev": dev, "host": aug, "geom": geom,
                    "digest": self.partition_digest,
                    "epoch": self.epoch,
                    "mesh": self.mesh, "ntp": self.ntp,
                }
            elif union is not None:
                dev, stats = self._publish_scoped(
                    dtables, spare, union
                )
                slot = dict(
                    spare, dev=dev, geom=geom, epoch=self.epoch
                )
            else:
                aug = partition.replicate_datapath_leaves(
                    dtables, self.ntp, self.table_axis
                )
                dev, stats = self._publish_delta(aug, spare)
                slot = {
                    "dev": dev, "host": aug, "geom": geom,
                    "digest": self.partition_digest,
                    "epoch": self.epoch,
                    "mesh": self.mesh, "ntp": self.ntp,
                }
            self._slots[spare_i] = slot
            self._cur = spare_i
            stats.seconds = time.perf_counter() - t0
            sp.attrs.update(
                mode=stats.mode, epoch=stats.epoch,
                bytes_h2d=stats.bytes_h2d,
                scattered_rows=stats.scattered_rows,
            )
            return dev, stats

    def _publish_relayout_locked(self, dtables, geom, sp):
        """Publish while a relayout window is open (caller holds the
        lock): the live slot absorbs the churn through the row-diff
        scatter WITHOUT donation (in-flight fused dispatches keep
        their buffers — the zero-drain seam); a geometry or digest
        change full-uploads into the live slot and marks the window
        broken (the plan's deterministic restart trigger)."""
        live_i = self._cur
        live = self._slots[live_i]
        if (
            live is None
            or geom != live["geom"]
            or live["digest"] != self.partition_digest
        ):
            aug = partition.replicate_datapath_leaves(
                dtables, self.ntp, self.table_axis
            )
            dev, nbytes = self._full_place(aug)
            stats = DatapathPublishStats(
                epoch=self.epoch, mode="full",
                bytes_h2d=nbytes, seconds=0.0,
            )
            slot = {
                "dev": dev, "host": aug, "geom": geom,
                "digest": self.partition_digest,
                "epoch": self.epoch,
                "mesh": self.mesh, "ntp": self.ntp,
            }
            self._relayout["broken"] = True
            sp.attrs["relayout_broken"] = True
        else:
            aug = partition.replicate_datapath_leaves(
                dtables, self.ntp, self.table_axis
            )
            dev, stats = self._publish_delta(
                aug, live, donate=False
            )
            slot = {
                "dev": dev, "host": aug, "geom": geom,
                "digest": self.partition_digest,
                "epoch": self.epoch,
                "mesh": self.mesh, "ntp": self.ntp,
            }
        self._slots[live_i] = slot
        sp.attrs.update(
            mode=stats.mode, epoch=stats.epoch,
            bytes_h2d=stats.bytes_h2d,
            scattered_rows=stats.scattered_rows, relayout=True,
        )
        return dev, stats

    def _union_changes(self, spare_epoch: int):
        """Union of the change records for every publish since the
        spare slot's epoch, or None when any of them is missing
        (record-less publish → the caller made no warranty and the
        full row-diff must run)."""
        union: Dict[str, Dict[str, object]] = {}
        for e in range(spare_epoch + 1, self.epoch + 1):
            rec = self._change_log.get(e)
            if rec is None:
                return None
            for fam, leafmap in rec.items():
                dst = union.setdefault(fam, {})
                for leaf, idx in leafmap.items():
                    prev = dst.get(leaf)
                    if idx is True or prev is True:
                        dst[leaf] = True
                    elif prev is None:
                        dst[leaf] = np.asarray(idx, np.int64)
                    else:
                        dst[leaf] = np.concatenate(
                            [prev, np.asarray(idx, np.int64)]
                        )
        return union

    def _publish_scoped(
        self, dtables: DatapathTables, spare: dict, changes
    ):
        """The O(change) publish: compare/scatter ONLY the rows the
        change records name, against (and into) the spare slot's
        retained augmented snapshot — no re-augmentation, no
        whole-world compare.  Sharded rows land at both their
        primary and backup augmented positions; `True` records
        re-place the whole leaf."""
        dev = spare["dev"]
        aug_host = spare["host"]
        rep_axes = partition.datapath_all_replica_axes(
            aug_host, self.ntp, self.table_axis
        )
        n_rows = 0
        bytes_h2d = 0
        replaced = 0
        fam_new: Dict[str, Dict[str, object]] = {}
        for fam, leafmap in changes.items():
            new_f = getattr(dtables, fam)
            host_f = getattr(aug_host, fam)
            dev_f = getattr(dev, fam)
            for leaf, rec in leafmap.items():
                new_arr = np.asarray(getattr(new_f, leaf))
                host_leaf = np.asarray(getattr(host_f, leaf))
                axis = rep_axes.get((fam, leaf))
                dev_leaf = getattr(dev_f, leaf)
                if axis is None or rec is True:
                    if axis is not None:
                        new_arr = partition.replicate_shard_axis(
                            new_arr, self.ntp, axis
                        )
                    if host_leaf.shape == new_arr.shape and (
                        np.array_equal(host_leaf, new_arr)
                    ):
                        continue
                    sharding = getattr(
                        getattr(self._shardings, fam), leaf, None
                    ) or NamedSharding(self.mesh, P())
                    fam_new.setdefault(fam, {})[leaf] = (
                        jax.device_put(new_arr, sharding)
                    )
                    setattr(host_f, leaf, new_arr)
                    bytes_h2d += int(new_arr.nbytes)
                    replaced += 1
                    continue
                idx = np.unique(np.asarray(rec, np.int64))
                nb = new_arr.shape[axis] // self.ntp
                primary, backup = partition.replica_positions(
                    idx, nb, self.ntp
                )
                rows = np.take(new_arr, idx, axis=axis)
                prev_rows = np.take(host_leaf, primary, axis=axis)
                moved = np.moveaxis(rows, axis, 0).reshape(
                    len(idx), -1
                ) != np.moveaxis(prev_rows, axis, 0).reshape(
                    len(idx), -1
                )
                chg = np.flatnonzero(np.any(moved, axis=1))
                if chg.size == 0:
                    continue
                rows = np.take(rows, chg, axis=axis)
                aug_idx = np.concatenate(
                    [primary[chg], backup[chg]]
                )
                aug_rows = np.concatenate([rows, rows], axis=axis)
                size = next_pow2(aug_idx.size)
                if size != aug_idx.size:
                    pad = size - aug_idx.size
                    aug_idx = np.concatenate(
                        [aug_idx, np.repeat(aug_idx[-1:], pad)]
                    )
                    aug_rows = np.concatenate(
                        [
                            aug_rows,
                            np.repeat(
                                np.take(
                                    aug_rows, [-1], axis=axis
                                ),
                                pad, axis=axis,
                            ),
                        ],
                        axis=axis,
                    )
                # keep the retained snapshot exact (the next diff
                # base + the chip-repair value source)
                host_index = (slice(None),) * axis + (aug_idx,)
                host_leaf[host_index] = aug_rows
                idx_dev = jax.device_put(
                    aug_idx, NamedSharding(self.mesh, P())
                )
                rows_dev = jax.device_put(
                    aug_rows, NamedSharding(self.mesh, P())
                )
                new_leaf = self._scatter_fn(
                    (fam, leaf, int(size), int(axis)), int(axis)
                )(dev_leaf, idx_dev, rows_dev)
                fam_new.setdefault(fam, {})[leaf] = new_leaf
                n_rows += int(chg.size)
                bytes_h2d += int(aug_rows.nbytes + aug_idx.nbytes)
        if fam_new:
            fam_objs = {
                fam: dataclasses.replace(getattr(dev, fam), **ups)
                for fam, ups in fam_new.items()
            }
            dev = dataclasses.replace(dev, **fam_objs)
            jax.block_until_ready(dev)
        return dev, DatapathPublishStats(
            epoch=self.epoch, mode="delta-scoped",
            bytes_h2d=bytes_h2d, seconds=0.0,
            scattered_rows=n_rows, replaced_leaves=replaced,
        )

    def _publish_delta(
        self, aug: DatapathTables, spare: dict, donate: bool = True
    ):
        prev = spare["host"]
        n_rows = 0
        bytes_h2d = 0
        replaced = 0
        dev = spare["dev"]
        fam_new: Dict[str, Dict[str, object]] = {}

        def leaf_path_iter():
            """((family, leaf, new_arr, prev_arr, dev_leaf) ...) for
            every array leaf, family-qualified — generic over the
            registered family dataclasses."""
            for fam in (
                "prefilter", "ipcache", "ct", "lb", "policy",
                "tunnel",
            ):
                new_f = getattr(aug, fam)
                prev_f = getattr(prev, fam)
                dev_f = getattr(dev, fam)
                if new_f is None:
                    continue
                new_ch, _ = new_f.tree_flatten()
                prev_ch, _ = prev_f.tree_flatten()
                dev_ch, _ = dev_f.tree_flatten()
                names = _family_leaf_names(new_f)
                for name, a, b, d in zip(
                    names, new_ch, prev_ch, dev_ch
                ):
                    yield fam, name, a, b, d

        rep_axes = partition.datapath_all_replica_axes(
            aug, self.ntp, self.table_axis
        )
        for fam, name, new_arr, prev_arr, dev_leaf in leaf_path_iter():
            if new_arr is None:
                continue
            new_np = np.asarray(new_arr)
            prev_np = np.asarray(prev_arr)
            axis = rep_axes.get((fam, name))
            if axis is not None and new_np.shape == prev_np.shape:
                # row diff along the sharded axis: only changed
                # index slices ship, in augmented coordinates (a
                # changed row lands at both its primary and backup
                # positions — replica copies stay bit-identical)
                moved_new = np.moveaxis(new_np, axis, 0)
                moved_prev = np.moveaxis(prev_np, axis, 0)
                changed = np.flatnonzero(
                    np.any(
                        moved_new.reshape(moved_new.shape[0], -1)
                        != moved_prev.reshape(
                            moved_prev.shape[0], -1
                        ),
                        axis=1,
                    )
                )
                if changed.size == 0:
                    continue
                size = next_pow2(changed.size)
                if size != changed.size:
                    changed = np.concatenate(
                        [
                            changed,
                            np.repeat(
                                changed[-1:], size - changed.size
                            ),
                        ]
                    )
                rows = np.take(new_np, changed, axis=axis)
                idx_dev = jax.device_put(
                    changed, NamedSharding(self.mesh, P())
                )
                rows_dev = jax.device_put(
                    rows, NamedSharding(self.mesh, P())
                )
                new_leaf = self._scatter_fn(
                    (fam, name, int(size), int(axis)), int(axis),
                    donate=donate,
                )(dev_leaf, idx_dev, rows_dev)
                fam_new.setdefault(fam, {})[name] = new_leaf
                n_rows += int(changed.size)
                bytes_h2d += int(rows.nbytes + changed.nbytes)
            else:
                if new_np.shape == prev_np.shape and np.array_equal(
                    new_np, prev_np
                ):
                    continue
                sharding = getattr(
                    getattr(self._shardings, fam), name, None
                )
                if sharding is None:
                    sharding = NamedSharding(self.mesh, P())
                fam_new.setdefault(fam, {})[name] = jax.device_put(
                    new_np, sharding
                )
                bytes_h2d += int(new_np.nbytes)
                replaced += 1
        if fam_new:
            fam_objs = {
                fam: dataclasses.replace(getattr(dev, fam), **ups)
                for fam, ups in fam_new.items()
            }
            dev = dataclasses.replace(dev, **fam_objs)
            jax.block_until_ready(dev)
        return dev, DatapathPublishStats(
            epoch=self.epoch, mode="delta", bytes_h2d=bytes_h2d,
            seconds=0.0, scattered_rows=n_rows,
            replaced_leaves=replaced,
        )

    def _repair_slot(self, slot: dict, col: int) -> int:
        aug = slot["host"]
        # a slot created under a DIFFERENT layout than the store's
        # current one (the pre-cutover source epoch, or the staged
        # reshard target) repairs in ITS OWN coordinates — column
        # arithmetic and payload placement follow the slot's mesh
        ntp = int(slot.get("ntp", self.ntp))
        mesh = slot.get("mesh", self.mesh)
        if col >= ntp:
            # the column does not exist under this slot's layout
            # (e.g. a grown mesh's new chip vs the source epoch)
            return 0
        rep_axes = partition.datapath_all_replica_axes(
            aug, ntp, self.table_axis
        )
        dev = slot["dev"]
        fam_new: Dict[str, Dict[str, object]] = {}
        bytes_h2d = 0
        for (fam, name), axis in rep_axes.items():
            host_leaf = np.asarray(
                getattr(getattr(aug, fam), name)
            )
            per = host_leaf.shape[axis] // ntp
            idx = np.arange(
                col * per, (col + 1) * per, dtype=np.int64
            )
            rows = np.take(host_leaf, idx, axis=axis)
            dev_leaf = getattr(getattr(dev, fam), name)
            idx_dev = jax.device_put(
                idx, NamedSharding(mesh, P())
            )
            rows_dev = jax.device_put(
                rows, NamedSharding(mesh, P())
            )
            new_leaf = self._scatter_fn(
                (fam, name, int(next_pow2(idx.size)), int(axis)),
                int(axis),
            )(dev_leaf, idx_dev, rows_dev)
            fam_new.setdefault(fam, {})[name] = new_leaf
            bytes_h2d += int(rows.nbytes + idx.nbytes)
        if fam_new:
            fam_objs = {
                fam: dataclasses.replace(getattr(dev, fam), **ups)
                for fam, ups in fam_new.items()
            }
            slot["dev"] = dataclasses.replace(dev, **fam_objs)
            jax.block_until_ready(slot["dev"])
        return bytes_h2d

    def repair_chip(self, col: int) -> int:
        """Re-scatter one table column's owned augmented regions of
        every sharded plane from each slot's retained host snapshot
        — the datapath half of the re-admission rebalance, applied
        to BOTH epochs (the chip missed publishes into both slots
        while out; repairing only the live one would leave the
        standby semantically stale on its slices, the spare_stale
        hazard the policy store's ledger handles).  Donates the
        repaired slots' buffers — the router calls this at a stream
        boundary, before the probe dispatch, same contract as
        DeviceTableStore.repair_rows.  On the virtual CPU mesh the
        SPMD publish scatter already landed everywhere, so this is
        semantically idempotent; what it models (and what the chaos
        storm bounds) is the repair TRAFFIC a physically absent chip
        would need: bytes proportional to its slices, never a full
        upload.  Returns bytes shipped."""
        with self._lock:
            bytes_h2d = 0
            for slot in self._slots:
                if slot is not None:
                    bytes_h2d += self._repair_slot(slot, col)
            return bytes_h2d

    # -- live elastic resharding (engine/reshard.py drives these) ------------

    def begin_relayout(self, dtables: DatapathTables, target_mesh):
        """Open a relayout window toward `target_mesh`: stage the
        fused datapath epoch re-augmented for the target table-axis
        size into the SPARE slot while the live epoch keeps serving.
        The staged device epoch is seeded with every MOVED augmented
        row (compiler.partition.datapath_reshard_moved_rows — rows
        not device-resident under the source column assignment)
        ZEROED; the migration scatters (`relayout_scatter`) stream
        them in, so the cutover's bit-identity proves the streamed
        bytes.  Returns the moved-row sets ({(family, leaf): (axis,
        index array)}) — the plan's work queue."""
        _check_fused_world(dtables)
        with self._lock, tracing.tracer.span(
            "datapath.begin_relayout", site="engine.datapath_mesh"
        ) as sp:
            if self._relayout is not None:
                raise RuntimeError(
                    "datapath relayout window already open"
                )
            live = self._slots[self._cur]
            if live is None:
                raise RuntimeError(
                    "no live datapath epoch to reshard from"
                )
            ntp_dst = int(target_mesh.shape[self.table_axis])
            aug = partition.replicate_datapath_leaves(
                dtables, ntp_dst, self.table_axis
            )
            moved = partition.datapath_reshard_moved_rows(
                dtables, self.ntp, ntp_dst, self.table_axis
            )
            digest = partition.datapath_partition_digest(
                self.table_axis, ntp=ntp_dst
            )
            shardings = partition.datapath_table_shardings(
                target_mesh, aug, self.table_axis
            )
            fam_zero: Dict[str, Dict[str, object]] = {}
            for (fam, name), (axis, idx) in moved.items():
                idx = np.asarray(idx, np.int64)
                if idx.size == 0:
                    continue
                arr = np.array(
                    np.asarray(getattr(getattr(aug, fam), name))
                )
                arr[(slice(None),) * int(axis) + (idx,)] = 0
                fam_zero.setdefault(fam, {})[name] = arr
            seed = aug
            if fam_zero:
                fam_objs = {
                    fam: dataclasses.replace(
                        getattr(aug, fam), **ups
                    )
                    for fam, ups in fam_zero.items()
                }
                seed = dataclasses.replace(aug, **fam_objs)
            dev = jax.tree.map(
                lambda leaf, s: jax.device_put(
                    np.asarray(leaf), s
                ),
                seed, shardings,
            )
            jax.block_until_ready(dev)
            self.epoch += 1
            spare_i = self._cur ^ 1
            self._slots[spare_i] = {
                "dev": dev, "host": aug,
                "geom": _geometry(dtables), "digest": digest,
                "epoch": self.epoch,
                "mesh": target_mesh, "ntp": ntp_dst,
            }
            self._relayout = {
                "epoch": self.epoch, "mesh": target_mesh,
                "ntp": ntp_dst, "digest": digest,
                "shardings": shardings, "broken": False,
            }
            sp.attrs.update(
                epoch=self.epoch, ntp_src=self.ntp,
                ntp_dst=ntp_dst,
            )
            return moved

    def relayout_state(self) -> Optional[Dict]:
        with self._lock:
            rel = self._relayout
            if rel is None:
                return None
            return {
                "epoch": rel["epoch"], "ntp": rel["ntp"],
                "broken": bool(rel.get("broken")),
            }

    def relayout_scatter(self, row_sets) -> int:
        """One bounded migration step: scatter `row_sets`
        ({(family, leaf): (axis, index array)}) of the STAGED target
        epoch from its retained augmented host — the datapath analog
        of DeviceTableStore.repair_rows(spare=True).  The staged
        buffers are donated (nothing serves from them until
        cutover).  Returns bytes shipped."""
        with self._lock:
            rel = self._relayout
            if rel is None or rel.get("broken"):
                raise RuntimeError(
                    "no open datapath relayout window; scatter "
                    "refused"
                )
            spare_i = self._cur ^ 1
            slot = self._slots[spare_i]
            if slot is None or slot["epoch"] != rel["epoch"]:
                raise RuntimeError(
                    "staged datapath relayout epoch is gone"
                )
            aug = slot["host"]
            dev = slot["dev"]
            mesh = rel["mesh"]
            fam_new: Dict[str, Dict[str, object]] = {}
            bytes_h2d = 0
            for fam, name in sorted(row_sets):
                axis, idx = row_sets[(fam, name)]
                idx = np.asarray(idx, np.int64)
                if idx.size == 0:
                    continue
                size = next_pow2(idx.size)
                if size != idx.size:
                    idx = np.concatenate(
                        [idx, np.repeat(idx[-1:], size - idx.size)]
                    )
                host_leaf = np.asarray(
                    getattr(getattr(aug, fam), name)
                )
                rows = np.take(host_leaf, idx, axis=axis)
                dev_leaf = getattr(getattr(dev, fam), name)
                idx_dev = jax.device_put(
                    idx, NamedSharding(mesh, P())
                )
                rows_dev = jax.device_put(
                    rows, NamedSharding(mesh, P())
                )
                new_leaf = self._scatter_fn(
                    (fam, name, int(size), int(axis)), int(axis)
                )(dev_leaf, idx_dev, rows_dev)
                fam_new.setdefault(fam, {})[name] = new_leaf
                bytes_h2d += int(rows.nbytes + idx.nbytes)
            if fam_new:
                fam_objs = {
                    fam: dataclasses.replace(
                        getattr(dev, fam), **ups
                    )
                    for fam, ups in fam_new.items()
                }
                slot["dev"] = dataclasses.replace(dev, **fam_objs)
                jax.block_until_ready(slot["dev"])
            return bytes_h2d

    def relayout_update(self, dtables: DatapathTables):
        """Churn dual-apply: fold a new fused world into the STAGED
        target epoch's retained host, returning the sharded row sets
        whose contents changed ({(family, leaf): (axis, augmented
        index array)}) so the plan can re-queue them (re-streaming
        an already-migrated row is always safe).  Changed REPLICATED
        leaves re-place on the staged device immediately.  A
        geometry change marks the window broken and returns None —
        the plan restarts as a full upload into the target."""
        _check_fused_world(dtables)
        with self._lock:
            rel = self._relayout
            if rel is None or rel.get("broken"):
                raise RuntimeError(
                    "no open datapath relayout window to update"
                )
            spare_i = self._cur ^ 1
            slot = self._slots[spare_i]
            if slot is None or slot["epoch"] != rel["epoch"]:
                raise RuntimeError(
                    "staged datapath relayout epoch is gone"
                )
            if _geometry(dtables) != slot["geom"]:
                rel["broken"] = True
                return None
            ntp = rel["ntp"]
            aug = partition.replicate_datapath_leaves(
                dtables, ntp, self.table_axis
            )
            prev = slot["host"]
            dev = slot["dev"]
            rep_axes = partition.datapath_all_replica_axes(
                aug, ntp, self.table_axis
            )
            changed_sets: Dict[tuple, tuple] = {}
            fam_new: Dict[str, Dict[str, object]] = {}
            for fam in (
                "prefilter", "ipcache", "ct", "lb", "policy",
                "tunnel",
            ):
                new_f = getattr(aug, fam)
                prev_f = getattr(prev, fam)
                if new_f is None:
                    continue
                new_ch, _ = new_f.tree_flatten()
                prev_ch, _ = prev_f.tree_flatten()
                names = _family_leaf_names(new_f)
                for name, a, b in zip(names, new_ch, prev_ch):
                    if a is None:
                        continue
                    new_np = np.asarray(a)
                    prev_np = np.asarray(b)
                    axis = rep_axes.get((fam, name))
                    if new_np.shape != prev_np.shape:
                        # shape drift outside the geometry
                        # signature — refuse into the restart path
                        rel["broken"] = True
                        return None
                    if axis is not None:
                        mn = np.moveaxis(new_np, axis, 0)
                        mp = np.moveaxis(prev_np, axis, 0)
                        chg = np.flatnonzero(
                            np.any(
                                mn.reshape(mn.shape[0], -1)
                                != mp.reshape(mp.shape[0], -1),
                                axis=1,
                            )
                        )
                        if chg.size:
                            changed_sets[(fam, name)] = (
                                int(axis), chg
                            )
                    elif not np.array_equal(new_np, prev_np):
                        sharding = getattr(
                            getattr(rel["shardings"], fam),
                            name, None,
                        ) or NamedSharding(rel["mesh"], P())
                        fam_new.setdefault(fam, {})[name] = (
                            jax.device_put(new_np, sharding)
                        )
            if fam_new:
                fam_objs = {
                    fam: dataclasses.replace(
                        getattr(dev, fam), **ups
                    )
                    for fam, ups in fam_new.items()
                }
                slot["dev"] = dataclasses.replace(dev, **fam_objs)
                jax.block_until_ready(slot["dev"])
            slot["host"] = aug
            return changed_sets

    def cutover_relayout(self) -> int:
        """Flip the staged target epoch live and rebind the store to
        the target mesh/ntp/digest.  The previous live epoch's
        buffers are untouched (zero drain); it remains as the
        source-layout spare, which the next publish full-uploads
        over (digest mismatch).  Refused while broken."""
        with self._lock, tracing.tracer.span(
            "datapath.cutover_relayout", site="engine.datapath_mesh"
        ) as sp:
            rel = self._relayout
            if rel is None:
                raise RuntimeError(
                    "no open datapath relayout window"
                )
            if rel.get("broken"):
                raise RuntimeError(
                    "datapath relayout window broken; cutover "
                    "refused — restart the migration"
                )
            spare_i = self._cur ^ 1
            slot = self._slots[spare_i]
            if slot is None or slot["epoch"] != rel["epoch"]:
                raise RuntimeError(
                    "staged datapath relayout epoch is gone; "
                    "cutover refused"
                )
            self._cur = spare_i
            self._relayout = None
            self.mesh = rel["mesh"]
            self.ntp = rel["ntp"]
            self.partition_digest = rel["digest"]
            self._shardings = rel["shardings"]
            # change records were keyed against source-layout
            # epochs; a scoped publish must not union across the
            # layout seam
            self._change_log.clear()
            sp.attrs.update(epoch=slot["epoch"], ntp=self.ntp)
            return slot["epoch"]

    def rollback_relayout(self) -> bool:
        """Abandon the staged target epoch (the live source layout
        was never touched — rollback is a pointer drop)."""
        with self._lock:
            rel = self._relayout
            if rel is None:
                return False
            spare_i = self._cur ^ 1
            slot = self._slots[spare_i]
            if slot is not None and slot["epoch"] == rel["epoch"]:
                self._slots[spare_i] = None
            self._relayout = None
            return True

    def current(self) -> Optional[DatapathTables]:
        with self._lock:
            slot = self._slots[self._cur]
            return None if slot is None else slot["dev"]

    def host_augmented(self) -> Optional[DatapathTables]:
        with self._lock:
            slot = self._slots[self._cur]
            return None if slot is None else slot["host"]

    def full_bytes(self) -> int:
        with self._lock:
            slot = self._slots[self._cur]
            return (
                0 if slot is None
                else self._tree_nbytes(slot["host"])
            )

    def chip_bytes(self) -> Dict[int, int]:
        """Measured per-chip resident bytes of the CURRENT datapath
        epoch (addressable shards) — the CT/ipcache/LB extension of
        DeviceTableStore.chip_bytes."""
        from cilium_tpu.engine.publish import _chip_resident_bytes

        with self._lock:
            slot = self._slots[self._cur]
            if slot is None:
                return {}
            return _chip_resident_bytes(slot["dev"])


def _family_leaf_names(obj) -> tuple:
    """tree_flatten child names of a registered family pytree —
    paired from the compiler.partition name tables (the pytrees
    flatten positionally)."""
    from cilium_tpu.ct.device import CTSnapshot
    from cilium_tpu.ipcache.lpm import IPCacheDevice, LPMTables
    from cilium_tpu.lb.device import LBInline, LBTables
    from cilium_tpu.prefilter import PrefilterRanges

    if isinstance(obj, CTSnapshot):
        return partition.CT_LEAF_NAMES
    if isinstance(obj, IPCacheDevice):
        return partition.IPCACHE_LEAF_NAMES
    if isinstance(obj, LBInline):
        return partition.LB_INLINE_LEAF_NAMES
    if isinstance(obj, LBTables):
        return partition.LB_CLASSIC_LEAF_NAMES
    if isinstance(obj, PrefilterRanges):
        return ("base", "mask")
    if isinstance(obj, LPMTables):
        return ("l1", "l2")
    from cilium_tpu.compiler.tables import PolicyTables

    if isinstance(obj, PolicyTables):
        return partition.POLICY_LEAF_NAMES
    children, _ = obj.tree_flatten()
    return tuple(f"leaf{i}" for i in range(len(children)))
