"""Fused per-packet datapath: one jitted step over the whole pipeline.

The reference's hot path is a single BPF program per packet —
prefilter (bpf/bpf_xdp.c), LB service DNAT + conntrack + identity
derivation + policy verdict (`handle_ipv4_from_lxc`
bpf/bpf_lxc.c:440 egress, `ipv4_policy` bpf_lxc.c:899 ingress) — not
a chain of separately-invoked kernels.  This module is the TPU
equivalent: every stage is already a fixed number of gathers, so the
whole pipeline fuses into ONE jit (XLA overlaps the gathers; no
host↔device round trips between stages).

Stage order (mirrors the C):

  1. XDP prefilter on the remote (source) address — bpf_xdp.c,
     CIDR4_*_MAP deny sets.
  2. Egress only: LB service probe on the original (daddr, dport,
     proto), backend stickiness via the CT service-scope entry, DNAT
     rewrite — lb4_lookup_service/lb4_local (bpf_lxc.c:486-492).
  3. Conntrack lookup on the (possibly DNATed) tuple, reverse probe
     first — ct_lookup4 (bpf_lxc.c:933, :509).
  4. Identity derivation: ingress takes the ipcache LPM of saddr (what
     bpf_netdev.c derives before the policy tail-call), egress the
     ipcache of the post-DNAT daddr, falling back to WORLD_ID
     (bpf_lxc.c:520-531).
  5. Policy lattice — policy_can_access_ingress / policy_can_egress4
     3-probe verdict (lib/policy.h:46), *always* evaluated.
  6. Combine — REPLY/RELATED bypass a deny verdict; an ESTABLISHED
     flow that is now denied is dropped and its CT entry flagged for
     deletion; NEW+allowed flows are flagged for CT creation; a
     proxy_port verdict redirects only NEW/ESTABLISHED flows
     (bpf_lxc.c:962-985).

CT state mutation (create/delete) happens host-side after the batch
(`apply_ct_writeback`) — the same split as the agent reading/GC'ing
the kernel-owned CT map asynchronously.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from cilium_tpu import tracing
from cilium_tpu.compiler.tables import PolicyTables
from cilium_tpu.ct.device import (
    CTSnapshot,
    ct_fetch_rows,
    ct_lookup_batch,
    ct_probe_rows,
)
from cilium_tpu.ct.table import (
    CT_EGRESS,
    CT_ESTABLISHED,
    CT_INGRESS,
    CT_NEW,
    CT_RELATED,
    CT_REPLY,
    CT_SERVICE,
    CTMap,
    CTTuple,
    TUPLE_F_IN,
    TUPLE_F_OUT,
    TUPLE_F_SERVICE,
)
from cilium_tpu.engine.verdict import (
    TupleBatch,
    _accumulate_counters,
    _combine,
    _probes,
    _verdict_kernel_with_counters,
    make_counter_buffers,
    split_counters,
)
from cilium_tpu.identity import RESERVED_WORLD
from cilium_tpu.ipcache.lpm import LPMTables, _lookup_kernel
from cilium_tpu.lb.device import LBTables, lb_select_batch
from cilium_tpu.maps.policymap import EGRESS, INGRESS
from cilium_tpu.metrics import registry as metrics


def _register(cls):
    try:
        jax.tree_util.register_pytree_node(
            cls,
            lambda t: t.tree_flatten(),
            lambda aux, ch: cls.tree_unflatten(aux, ch),
        )
    except Exception:  # pragma: no cover
        pass
    return cls


@_register
@dataclass
class DatapathTables:
    """Everything the fused step consumes, as one pytree — the set of
    pinned maps a bpf_lxc program sees (lib/maps.h).  `tunnel` is the
    node-discovery-fed prefix→node-IP map (pkg/maps/tunnel); None
    compiles the no-overlay program (native routing mode)."""

    prefilter: object  # PrefilterRanges (broadcast) or LPMTables
    ipcache: LPMTables
    ct: CTSnapshot
    lb: LBTables
    policy: PolicyTables
    tunnel: object = None  # TunnelTables or None

    def tree_flatten(self):
        return (
            (
                self.prefilter, self.ipcache, self.ct, self.lb,
                self.policy, self.tunnel,
            ),
            None,
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@_register
@dataclass
class FlowBatch:
    """Raw 5-tuple flows (pre identity resolution) — what arrives on
    the wire, as opposed to TupleBatch which is post-ipcache."""

    ep_index: jax.Array  # i32 [B]
    saddr: jax.Array  # u32 [B]
    daddr: jax.Array  # u32 [B]
    sport: jax.Array  # i32 [B]
    dport: jax.Array  # i32 [B]
    proto: jax.Array  # i32 [B]
    direction: jax.Array  # i32 [B] 0=ingress 1=egress
    is_fragment: jax.Array  # bool [B]

    def tree_flatten(self):
        return (
            (
                self.ep_index,
                self.saddr,
                self.daddr,
                self.sport,
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
        ep_index, saddr, daddr, sport, dport, proto, direction,
        is_fragment=None,
    ) -> "FlowBatch":
        """Pack all eight columns into ONE [8, B] u32 host array and
        upload it as a single transfer instead of eight per-array
        device_puts, each with its own fixed cost (not measured on
        the local chip).  A tiny jitted splitter restores the typed
        columns on device."""
        b = len(ep_index)
        if is_fragment is None:
            is_fragment = np.zeros(b, dtype=bool)
        cols = dict(
            ep_index=ep_index, saddr=saddr, daddr=daddr, sport=sport,
            dport=dport, proto=proto, direction=direction,
            is_fragment=is_fragment,
        )
        packed = np.empty((len(FLOW_COLUMNS), b), dtype=np.uint32)
        for j, name in enumerate(FLOW_COLUMNS):
            packed[j] = np.asarray(cols[name]).astype(
                np.uint32, copy=False
            )
        return _unpack_flow_batch(jnp.asarray(packed))


# THE column-order contract for packed flow transfers: row j of a
# [8, B] u32 pack is FLOW_COLUMNS[j].  from_numpy's pack,
# flow_batch_from_packed, and replay.pack_flow_pool all derive from
# this one tuple — reorder here and nowhere else.
FLOW_COLUMNS = (
    "ep_index", "saddr", "daddr", "sport", "dport", "proto",
    "direction", "is_fragment",
)


# -- packed4: the narrow-dtype staging format ---------------------------------
# The eight u32 flow columns carry at most 16 meaningful bits each
# outside the two addresses, so the H2D staging pack halves to FOUR
# u32 rows (16 B/tuple instead of 32):
#   row 0  saddr
#   row 1  daddr
#   row 2  sport << 16 | dport
#   row 3  ep_index << 16 | proto << 8 | direction << 1 | is_fragment
# The unpack runs INSIDE the jitted program (host-visible semantics
# unchanged — bit-identity gated in bench and tests); ranges are the
# same invariants the tables already rely on (ports < 2^16, proto <
# 2^8, ep_index < 2^16 per the hashed-key endpoint cap).
def pack_flow_records4(
    ep_index, saddr, daddr, sport, dport, proto, direction,
    is_fragment=None,
) -> np.ndarray:
    """Host half of the packed4 staging format: [4, B] u32."""
    b = len(ep_index)
    if is_fragment is None:
        is_fragment = np.zeros(b, dtype=bool)
    ep = np.asarray(ep_index).astype(np.uint32)
    if b and int(ep.max()) >= 1 << 16:
        raise ValueError("ep_index exceeds the packed4 16-bit field")
    packed = np.empty((4, b), dtype=np.uint32)
    packed[0] = np.asarray(saddr).astype(np.uint32, copy=False)
    packed[1] = np.asarray(daddr).astype(np.uint32, copy=False)
    packed[2] = (
        (np.asarray(sport).astype(np.uint32) & 0xFFFF) << 16
    ) | (np.asarray(dport).astype(np.uint32) & 0xFFFF)
    packed[3] = (
        (ep << 16)
        | ((np.asarray(proto).astype(np.uint32) & 0xFF) << 8)
        | ((np.asarray(direction).astype(np.uint32) & 1) << 1)
        | np.asarray(is_fragment).astype(np.uint32)
    )
    return packed


def flow_batch_from_packed4(packed) -> "FlowBatch":
    """Device half of packed4 (traced: call from inside a jit)."""
    with jax.named_scope("unpack"):
        w3 = packed[3]
        return FlowBatch(
            ep_index=(w3 >> jnp.uint32(16)).astype(jnp.int32),
            saddr=packed[0],
            daddr=packed[1],
            sport=(packed[2] >> jnp.uint32(16)).astype(jnp.int32),
            dport=(packed[2] & jnp.uint32(0xFFFF)).astype(jnp.int32),
            proto=((w3 >> jnp.uint32(8)) & jnp.uint32(0xFF)).astype(
                jnp.int32
            ),
            direction=((w3 >> jnp.uint32(1)) & jnp.uint32(1)).astype(
                jnp.int32
            ),
            is_fragment=(w3 & jnp.uint32(1)).astype(bool),
        )


def flow_batch_from_packed(packed) -> "FlowBatch":
    """[8, B] u32 rows (FLOW_COLUMNS order) → typed FlowBatch columns.
    Traced helper: call from inside a jit (device-side half of the
    single-transfer pack; also the pool-mode gather's splitter)."""
    cols = dict(zip(FLOW_COLUMNS, packed))
    return FlowBatch(
        ep_index=cols["ep_index"].astype(jnp.int32),
        saddr=cols["saddr"],
        daddr=cols["daddr"],
        sport=cols["sport"].astype(jnp.int32),
        dport=cols["dport"].astype(jnp.int32),
        proto=cols["proto"].astype(jnp.int32),
        direction=cols["direction"].astype(jnp.int32),
        is_fragment=cols["is_fragment"].astype(bool),
    )


# jitted splitter (jax.jit is lazy — no trace until first call): the
# device-side half of FlowBatch.from_numpy's single-transfer pack
_unpack_flow_batch = jax.jit(flow_batch_from_packed)


@_register
@dataclass
class DatapathVerdicts:
    """Per-flow outcome of the fused step plus the CT writeback
    intents the host applies after the batch."""

    allowed: jax.Array  # u8 [B]
    proxy_port: jax.Array  # i32 [B]
    match_kind: jax.Array  # u8 [B] MATCH_* of the lattice
    ct_result: jax.Array  # u8 [B] CT_NEW/ESTABLISHED/REPLY/RELATED
    pre_dropped: jax.Array  # bool [B] killed by the XDP prefilter
    sec_id: jax.Array  # u32 [B] derived peer identity
    final_daddr: jax.Array  # u32 [B] post-DNAT dst address
    final_dport: jax.Array  # i32 [B] post-DNAT dst port
    rev_nat: jax.Array  # i32 [B] rev-NAT index for CT create
    lb_slave: jax.Array  # i32 [B] chosen backend (0 = not a service)
    ct_create: jax.Array  # bool [B] NEW + allowed → host CT create
    ct_delete: jax.Array  # bool [B] ESTABLISHED + denied → host delete
    # u32 [B] remote node IP to encapsulate to (0 = direct/local) —
    # bpf_overlay's encap decision; all-zero without a tunnel map
    tunnel_endpoint: jax.Array = None
    # i32 [B] global L4 slot of the matched entry (0 on L3/no match) —
    # keys the fleet L7 scope tables (l7/fleet.py) for redirected flows
    l4_slot: jax.Array = None
    # bool [B] identity derivation fell back to WORLD (ipcache miss) —
    # the telemetry plane's ipcache_world stage column
    ipcache_miss: jax.Array = None

    def tree_flatten(self):
        return (
            (
                self.allowed,
                self.proxy_port,
                self.match_kind,
                self.ct_result,
                self.pre_dropped,
                self.sec_id,
                self.final_daddr,
                self.final_dport,
                self.rev_nat,
                self.lb_slave,
                self.ct_create,
                self.ct_delete,
                self.tunnel_endpoint,
                self.l4_slot,
                self.ipcache_miss,
            ),
            None,
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def _datapath_core(
    tables: DatapathTables,
    flows: FlowBatch,
    with_counters: bool,
    acc=None,
    emit_sec_id: bool = True,
    static_direction=None,
    defer_counters: bool = False,
    collect_telemetry: bool = False,
    lattice_fn=None,
):
    """The fused per-packet pipeline.  With an idx-form ipcache
    (specialize_ipcache_to_idx) the identity lookup yields the dense
    lattice index directly and the id_direct gather disappears; with
    `emit_sec_id=False` (the streaming accum path) the sec output is
    that raw index — consumers translate through id_table host-side —
    saving the id_table gather too.

    `static_direction` compiles a direction-specialized program, the
    analog of bpf_lxc's separate from-container/to-container sections:
    the INGRESS program has no LB/service-CT stages at all (3 fewer
    gathers), exactly as ingress packets never traverse lb4_local.

    Each stage runs under a `jax.named_scope` (prefilter, ct, lb,
    ipcache, lattice, verdict, accounting; the packed4 unpack under
    unpack): metadata only, so a profiler trace names a device op by
    its stage and the compiled program is otherwise unchanged."""
    if static_direction is None:
        ingress = flows.direction == INGRESS
    else:
        ingress = jnp.full(
            flows.direction.shape, static_direction == INGRESS
        )

    # -- 1. XDP prefilter (deny-by-CIDR before everything): small
    # deny lists are a broadcast compare — zero gathers ------------------
    from cilium_tpu.prefilter import prefilter_drop

    with jax.named_scope("prefilter"):
        pre_drop = prefilter_drop(tables.prefilter, flows.saddr)

    # -- 2+3. ONE CT row gather serves both probes: the bucket row is
    # fetched by the ORIGINAL tuple's normalized hash; the
    # service-scope stickiness probe (lb4_local's ct lookup,
    # bpf_lxc.c:486) compares the original key, and after LB the flow
    # probe (ct_lookup4, bpf_lxc.c:509) compares the post-DNAT key
    # against the SAME row — DNATed entries are dual-homed there by
    # CTBucketIndex, so the second row gather the reference pays in
    # nanoseconds (and we'd pay ~7 ns/flow for) disappears.
    with jax.named_scope("ct"):
        ct_rows = ct_fetch_rows(
            tables.ct, flows.daddr, flows.saddr, flows.dport,
            flows.sport, flows.proto,
        )
    if static_direction == INGRESS:
        with jax.named_scope("lb"):
            zero = jnp.zeros(flows.dport.shape, jnp.int32)
            eff_daddr = flows.daddr.astype(jnp.uint32)
            eff_dport = flows.dport
            rev_nat = zero
            lb_slave = zero
    else:
        with jax.named_scope("ct"):
            svc_dir = jnp.full_like(flows.direction, CT_SERVICE)
            _, _, svc_slave = ct_probe_rows(
                tables.ct,
                ct_rows,
                flows.daddr,
                flows.saddr,
                flows.dport,
                flows.sport,
                flows.proto,
                svc_dir,
            )
        with jax.named_scope("lb"):
            svc_found, slave, lb_daddr, lb_dport, lb_rev = lb_select_batch(
                tables.lb,
                flows.saddr,
                flows.daddr,
                flows.sport,
                flows.dport,
                flows.proto,
                ct_slave=svc_slave,
            )
            do_lb = (~ingress) & svc_found
            eff_daddr = jnp.where(
                do_lb, lb_daddr, flows.daddr.astype(jnp.uint32)
            )
            eff_dport = jnp.where(do_lb, lb_dport, flows.dport)
            rev_nat = jnp.where(do_lb, lb_rev, 0)
            lb_slave = jnp.where(do_lb, slave, 0)

    with jax.named_scope("ct"):
        ct_res, ct_rev, _ = ct_probe_rows(
            tables.ct,
            ct_rows,
            eff_daddr,
            flows.saddr,
            eff_dport,
            flows.sport,
            flows.proto,
            flows.direction,
        )

    # -- 4. identity derivation (ipcache LPM; WORLD fallback) ---------------
    from cilium_tpu.ipcache.lpm import (
        UNKNOWN_IDX,
        IPCacheDevice,
        ipcache_lookup_fused,
    )

    with jax.named_scope("ipcache"):
        sec_ip = jnp.where(
            ingress, flows.saddr.astype(jnp.uint32), eff_daddr
        )
        idx_known = None
        if (
            isinstance(tables.ipcache, IPCacheDevice)
            and tables.ipcache.values_are_idx
        ):
            looked, l3_word = ipcache_lookup_fused(
                tables.ipcache, sec_ip, ingress=ingress
            )
            n = tables.policy.id_table.shape[0]
            miss = looked == 0
            ipc_miss = miss
            # UNKNOWN_IDX = ipcache entry whose identity is outside the
            # policy universe: present (no WORLD fallback) but not-known
            vp = jnp.where(
                miss, jnp.uint32(tables.ipcache.world_plus1), looked
            )
            known = (vp != 0) & (vp != jnp.uint32(UNKNOWN_IDX))
            idx = jnp.where(known, vp - 1, jnp.uint32(n - 1)).astype(
                jnp.int32
            )
            if l3_word is not None:
                # miss → WORLD's l3 bits, selected by direction
                l3_word = jnp.where(
                    miss,
                    jnp.where(
                        ingress,
                        jnp.uint32(tables.ipcache.world_l3_in),
                        jnp.uint32(tables.ipcache.world_l3_out),
                    ),
                    l3_word,
                )
                l3_bit = (
                    (l3_word >> flows.ep_index.astype(jnp.uint32)) & 1
                ).astype(bool)
                idx_known = (idx, known, l3_bit)
            else:
                idx_known = (idx, known)
            if emit_sec_id:
                sec_id = tables.policy.id_table[idx]
            else:
                sec_id = idx.astype(jnp.uint32)  # sec_idx form
            lattice_identity = jnp.zeros_like(looked)  # unused
        else:
            looked = _lookup_kernel(tables.ipcache, sec_ip)
            ipc_miss = looked == 0
            sec_id = jnp.where(
                looked == 0, jnp.uint32(RESERVED_WORLD), looked
            ).astype(jnp.uint32)
            lattice_identity = sec_id

    # -- 5. policy lattice (always evaluated, bpf_lxc.c:959) ----------------
    resolved = TupleBatch(
        ep_index=flows.ep_index,
        identity=lattice_identity,
        dport=eff_dport,
        proto=flows.proto,
        direction=flows.direction,
        is_fragment=flows.is_fragment,
    )
    # `lattice_fn` swaps the probe chain for a memoized equivalent
    # (engine/memo.py: intra-batch dedup + device verdict cache) —
    # same (probe1, probe2, probe3, proxy, j, idx) contract, so the
    # combine / counter / telemetry stages below are shared code and
    # the bit-identity surface is the probe outputs alone
    with jax.named_scope("lattice"):
        if lattice_fn is None:
            probe1, probe2, probe3, proxy, j, idx = _probes(
                tables.policy, resolved, idx_known=idx_known
            )
        else:
            probe1, probe2, probe3, proxy, j, idx = lattice_fn(
                tables.policy, resolved, idx_known
            )
        v = _combine(probe1, probe2, probe3, proxy, resolved.is_fragment)
    deferred = None
    if with_counters:
        if defer_counters:
            # hand the scatter ingredients back to the caller: the
            # paired-dispatch program concatenates both directions'
            # columns and pays ONE scatter per pair instead of two
            # (scatter cost is near size-independent on this chip)
            deferred = (resolved, j, idx)
        else:
            with jax.named_scope("accounting"):
                if acc is None:
                    acc = make_counter_buffers(tables.policy)
                acc = _accumulate_counters(
                    v, resolved, j, idx, acc,
                    tables.policy.l4_meta.shape[2],
                )

    # -- 6. combine (bpf_lxc.c:962-985) -------------------------------------
    with jax.named_scope("verdict"):
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

        # -- 7. overlay forwarding decision (encap_and_redirect,
        # bpf/lib/encap.h:26 via bpf_lxc's ipv4 tail): an ALLOWED egress
        # flow whose destination falls in a remote node's pod CIDR gets
        # the tunnel endpoint (the identity to carry rides in sec_id,
        # exactly as the reference stuffs seclabel into the tunnel key);
        # 0 = direct route / local delivery ---------------------------------
        if tables.tunnel is not None and static_direction != INGRESS:
            from cilium_tpu.tunnel import tunnel_select

            tunnel_ep = jnp.where(
                allowed & ~ingress,
                tunnel_select(tables.tunnel, eff_daddr),
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
        l4_slot=j,
        ipcache_miss=ipc_miss,
    )
    trow = None
    if collect_telemetry:
        # [2, TELEM_COLS] u32 stage histogram of THIS batch: the same
        # shared mask definitions the host fold applies to per-tuple
        # outputs, reduced per direction inside the fused program —
        # ~20 masked sums ride the dispatch (no extra launch, no
        # per-tuple D2H)
        from cilium_tpu.engine.verdict import telemetry_masks

        with jax.named_scope("accounting"):
            masks = telemetry_masks(
                pre_drop, ct_res, v.match_kind, allowed, ct_delete,
                proxy, lb_slave, ipc_miss,
            )
            # one reduction pair per column: the egress row is the
            # column total minus the ingress row (direction partitions
            # the batch), so 2T sums become T+T-with-const-folding —
            # and in the direction-specialized programs `ingress` is a
            # constant, so XLA folds one of the two rows to zeros
            row_in = jnp.stack(
                [jnp.sum(m & ingress, dtype=jnp.uint32) for m in masks]
            )
            col_total = jnp.stack(
                [jnp.sum(m, dtype=jnp.uint32) for m in masks]
            )
            trow = jnp.stack([row_in, col_total - row_in])
    if with_counters:
        if defer_counters:
            tail = (v, *deferred)
            return (out, tail, trow) if collect_telemetry else (out, tail)
        return (out, acc, trow) if collect_telemetry else (out, acc)
    return (out, trow) if collect_telemetry else out


def _datapath_kernel(
    tables: DatapathTables, flows: FlowBatch
) -> DatapathVerdicts:
    return _datapath_core(tables, flows, with_counters=False)


def _datapath_kernel_with_counters(
    tables: DatapathTables, flows: FlowBatch
):
    """Fused step + per-entry packet counters (policy.h:66-68), same
    counter semantics as the lattice-only counters kernel: a counter
    bump per lattice hit, indexed in the published tables' slot and
    identity axes.  Returns (out, l4_counts, l3_counts)."""
    out, acc = _datapath_core(tables, flows, with_counters=True)
    l4_counts, l3_counts = split_counters(acc, tables.policy)
    return out, l4_counts, l3_counts


def _datapath_kernel_accum(
    tables: DatapathTables, flows: FlowBatch, acc
):
    """Streaming fused step: counters scatter into the CARRIED flat
    buffer the caller threads (and jit donates) across batches — no
    per-batch [E, 2, N] materialization and ONE scatter.  This is the
    headline-path kernel; the agent folds the buffer back into
    realized map states once per replay (the async kernel-map read of
    pkg/maps/policymap).  With an idx-form ipcache the sec output is
    the dense identity INDEX (translate via tables.policy.id_table
    host-side, as the monitor fold does)."""
    return _datapath_core(
        tables, flows, with_counters=True, acc=acc, emit_sec_id=False
    )


datapath_step = jax.jit(_datapath_kernel)
datapath_step_with_counters = jax.jit(_datapath_kernel_with_counters)
datapath_step_accum = jax.jit(_datapath_kernel_accum, donate_argnums=(2,))


def _accum_dir_kernel(direction):
    def kernel(tables, flows, acc):
        return _datapath_core(
            tables,
            flows,
            with_counters=True,
            acc=acc,
            emit_sec_id=False,
            static_direction=direction,
        )

    return kernel


# direction-specialized streaming programs (bpf_lxc's separate
# ingress/egress sections): callers that split their flow stream per
# direction — as the kernel datapath inherently does — dispatch these
datapath_step_accum_ingress = jax.jit(
    _accum_dir_kernel(INGRESS), donate_argnums=(2,)
)
datapath_step_accum_egress = jax.jit(
    _accum_dir_kernel(EGRESS), donate_argnums=(2,)
)


def _datapath_kernel_accum_pair(tables, flows_in, flows_eg, acc):
    """BOTH direction-specialized programs in ONE dispatch, with the
    two batches' counter hits concatenated into a SINGLE scatter.
    Per pair of half-batches this saves one dispatch floor and one
    scatter relative to alternating the per-direction programs —
    a measurable slice of the headline loop on v5e — while computing
    bit-identical verdicts and counters (scatter-adds commute)."""
    from cilium_tpu.engine.verdict import _counter_cols

    out_i, (v_i, res_i, j_i, idx_i) = _datapath_core(
        tables, flows_in, with_counters=True, emit_sec_id=False,
        static_direction=INGRESS, defer_counters=True,
    )
    out_e, (v_e, res_e, j_e, idx_e) = _datapath_core(
        tables, flows_eg, with_counters=True, emit_sec_id=False,
        static_direction=EGRESS, defer_counters=True,
    )
    with jax.named_scope("accounting"):
        kg = tables.policy.l4_meta.shape[2]
        ep_i, d_i, c_i, w_i = _counter_cols(v_i, res_i, j_i, idx_i, kg)
        ep_e, d_e, c_e, w_e = _counter_cols(v_e, res_e, j_e, idx_e, kg)
        acc = acc.at[
            jnp.concatenate([ep_i, ep_e]),
            jnp.concatenate([d_i, d_e]),
            jnp.concatenate([c_i, c_e]),
        ].add(jnp.concatenate([w_i, w_e]))
    return out_i, out_e, acc


# the headline streaming shape: one dispatch evaluates an ingress
# half-batch AND an egress half-batch with one merged counter scatter
datapath_step_accum_pair = jax.jit(
    _datapath_kernel_accum_pair, donate_argnums=(3,)
)


def _datapath_kernel_telem(tables: DatapathTables, flows: FlowBatch):
    """One-shot instrumented step: full verdicts + this batch's
    [2, TELEM_COLS] stage histogram (tests, trace tooling, smoke)."""
    return _datapath_core(
        tables, flows, with_counters=False, collect_telemetry=True
    )


def _datapath_kernel_accum_telem(
    tables: DatapathTables, flows: FlowBatch, acc, telem
):
    """Streaming fused step + telemetry: the counter scatter AND the
    stage-histogram reduction both ride the one dispatch; `telem` is
    a carried donated [2, TELEM_COLS] u32 buffer
    (verdict.make_telemetry_buffers)."""
    out, acc, trow = _datapath_core(
        tables, flows, with_counters=True, acc=acc,
        emit_sec_id=False, collect_telemetry=True,
    )
    return out, acc, telem + trow


def _datapath_kernel_accum_pair_telem(
    tables, flows_in, flows_eg, acc, telem
):
    """The instrumented headline shape: the paired-dispatch program
    (one dispatch, one merged counter scatter per direction pair)
    plus per-direction stage accounting folded into the carried
    telemetry buffer — bit-identical verdicts and counters to
    datapath_step_accum_pair, with the [2, TELEM_COLS] reductions
    fused into the same program."""
    from cilium_tpu.engine.verdict import _counter_cols

    out_i, (v_i, res_i, j_i, idx_i), trow_i = _datapath_core(
        tables, flows_in, with_counters=True, emit_sec_id=False,
        static_direction=INGRESS, defer_counters=True,
        collect_telemetry=True,
    )
    out_e, (v_e, res_e, j_e, idx_e), trow_e = _datapath_core(
        tables, flows_eg, with_counters=True, emit_sec_id=False,
        static_direction=EGRESS, defer_counters=True,
        collect_telemetry=True,
    )
    with jax.named_scope("accounting"):
        kg = tables.policy.l4_meta.shape[2]
        ep_i, d_i, c_i, w_i = _counter_cols(v_i, res_i, j_i, idx_i, kg)
        ep_e, d_e, c_e, w_e = _counter_cols(v_e, res_e, j_e, idx_e, kg)
        acc = acc.at[
            jnp.concatenate([ep_i, ep_e]),
            jnp.concatenate([d_i, d_e]),
            jnp.concatenate([c_i, c_e]),
        ].add(jnp.concatenate([w_i, w_e]))
        telem = telem + trow_i + trow_e
    return out_i, out_e, acc, telem


datapath_step_telem = jax.jit(_datapath_kernel_telem)
datapath_step_accum_telem = jax.jit(
    _datapath_kernel_accum_telem, donate_argnums=(2, 3)
)
datapath_step_accum_pair_telem = jax.jit(
    _datapath_kernel_accum_pair_telem, donate_argnums=(3, 4)
)


def _datapath_kernel_accum_pair_telem_packed4(
    tables, packed_in, packed_eg, acc, telem
):
    """The async-dispatch headline shape: both half-batches arrive in
    the packed4 staging format ([4, B] u32, 16 B/tuple H2D) and
    unpack INSIDE the fused program — bit-identical verdicts,
    counters and telemetry to datapath_step_accum_pair_telem over the
    same flows (the unpack is exact; bench gates it)."""
    return _datapath_kernel_accum_pair_telem(
        tables,
        flow_batch_from_packed4(packed_in),
        flow_batch_from_packed4(packed_eg),
        acc,
        telem,
    )


datapath_step_accum_pair_telem_packed4 = jax.jit(
    _datapath_kernel_accum_pair_telem_packed4, donate_argnums=(3, 4)
)


def _datapath_kernel_accum_pair_telem_packed4_stacked(
    tables, pair, acc, telem
):
    """Both packed4 half-batches in ONE staged array ([2, 4, B] u32):
    the async staging pipeline pays a single device_put per batch
    pair, and the direction split happens inside the jit."""
    return _datapath_kernel_accum_pair_telem_packed4(
        tables, pair[0], pair[1], acc, telem
    )


datapath_step_accum_pair_telem_packed4_stacked = jax.jit(
    _datapath_kernel_accum_pair_telem_packed4_stacked,
    donate_argnums=(2, 3),
)


# ---------------------------------------------------------------------------
# Sub-word hot planes: the whole-datapath transform + layout stamp
# ---------------------------------------------------------------------------


def subword_datapath_tables(
    dtables: DatapathTables,
    l4_lanes: "int | None" = None,
    ct_lanes: "int | None" = None,
    strict: bool = False,
) -> Tuple[DatapathTables, dict]:
    """Apply every sub-word hot-lane transform the world's semantics
    allow — ONE entry point, ONE layout stamp: the compact 2-word
    hashed L4 pair (compiler.tables.repack_l4_subword), the 4-word
    CT bucket rows (ct.device.compact_ct_snapshot) and the packed
    idx/l3/prefix-class ipcache planes (ipcache.lpm.subword_ipcache).

    Each plane transforms independently; one whose ranges don't fit
    its compact fields keeps its wide layout (or raises when
    `strict`).  Returns (tables, report) — report maps plane ->
    "packed"/"kept: <why>" so bench/gatherprof can emit the
    per-width model honestly.  Verdicts are bit-identical by
    construction (each transform's contract), and every changed
    plane moves the layout stamp (datapath_layout_version) so
    delta publication refuses across the seam."""
    import dataclasses

    from cilium_tpu.compiler.tables import (
        L4C_LANES,
        repack_l4_subword,
    )
    from cilium_tpu.ct.device import (
        CT_COMPACT_LANES,
        compact_ct_snapshot,
    )
    from cilium_tpu.ipcache.lpm import IPCacheDevice, subword_ipcache

    report = {}
    out = dtables
    try:
        pol = repack_l4_subword(
            dtables.policy, lanes=l4_lanes or L4C_LANES
        )
        out = dataclasses.replace(out, policy=pol)
        report["l4_hash"] = "packed"
    except ValueError as exc:
        if strict:
            raise
        report["l4_hash"] = f"kept: {exc}"
    try:
        ct = compact_ct_snapshot(
            dtables.ct, lanes=ct_lanes or CT_COMPACT_LANES
        )
        out = dataclasses.replace(out, ct=ct)
        report["ct"] = "packed"
    except ValueError as exc:
        if strict:
            raise
        report["ct"] = f"kept: {exc}"
    ipc = dtables.ipcache
    if isinstance(ipc, IPCacheDevice) and ipc.values_are_idx:
        try:
            out = dataclasses.replace(
                out, ipcache=subword_ipcache(ipc)
            )
            report["ipcache"] = "packed"
        except ValueError as exc:
            if strict:
                raise
            report["ipcache"] = f"kept: {exc}"
    else:
        report["ipcache"] = "kept: not an idx-form IPCacheDevice"
    return out, report


def datapath_layout_version(dtables: DatapathTables) -> tuple:
    """The whole-datapath layout stamp: policy layout version (lane
    widths + coldness + compact bit) plus every sub-word marker of
    the CT/ipcache planes.  Joins the partition digest in
    DatapathStore's geometry check — a delta recorded under one
    layout can never scatter into an epoch holding another."""
    from cilium_tpu.compiler.tables import tables_layout_version
    from cilium_tpu.ipcache.lpm import IPCacheDevice

    ipc = dtables.ipcache
    return (
        tables_layout_version(dtables.policy),
        int(getattr(dtables.ct, "entry_words", 5)),
        int(np.asarray(dtables.ct.buckets).shape[1]),
        (
            int(getattr(ipc, "bucket_entries", 0)),
            int(getattr(ipc, "value_width", 32)),
            int(getattr(ipc, "l3_width", 32)),
            tuple(getattr(ipc, "range_widths", ()) or ()),
        )
        if isinstance(ipc, IPCacheDevice) else (),
    )


# ---------------------------------------------------------------------------
# Persistent fused-pair program: zero per-pair dispatch
# ---------------------------------------------------------------------------
# The headline loop's remaining host cost is the PER-PAIR dispatch
# floor: one launch + one drain round trip per pair batch, which the
# async overlap hides only partially (the host still touches the
# executable K times).  The persistent program evaluates K staged
# pairs in ONE launch: a lax.scan walks the [K, 2, 4, B] super-batch,
# the counter/telemetry carry is donated device-resident state woven
# through the scan, and the stacked verdict outputs stay on device
# until the caller drains — carry state commits once per drain, not
# once per pair.

_PERSISTENT_CACHE = {}


def persistent_pair_program(k_pairs: int):
    """Jitted persistent fused-pair program.

    fn(tables, pairs [K, 2, 4, B] u32, acc, telem) ->
        (out_i stacked [K, ...], out_e stacked [K, ...], acc', telem')

    acc/telem are donated; verdict columns for pair i sit at leading
    index i of every output leaf — bit-identical per pair to
    datapath_step_accum_pair_telem_packed4_stacked over the same
    pairs (scan order matches submission order, counter scatter adds
    commute)."""
    key = int(k_pairs)
    fn = _PERSISTENT_CACHE.get(key)
    if fn is not None:
        return fn

    def program(tables, pairs, acc, telem):
        def step(carry, pair):
            acc, telem = carry
            with jax.named_scope("unpack"):
                packed_in, packed_eg = pair[0], pair[1]
            out_i, out_e, acc, telem = (
                _datapath_kernel_accum_pair_telem_packed4(
                    tables, packed_in, packed_eg, acc, telem
                )
            )
            return (acc, telem), (out_i, out_e)

        (acc, telem), (outs_i, outs_e) = jax.lax.scan(
            step, (acc, telem), pairs
        )
        return outs_i, outs_e, acc, telem

    fn = jax.jit(program, donate_argnums=(2, 3))
    _PERSISTENT_CACHE[key] = fn
    return fn


class PersistentPairDispatcher:
    """Host driver of the persistent program: stages up to `k_pairs`
    packed4 pair batches, ships them as ONE [K, 2, 4, B] device_put
    and ONE launch, and keeps the counter/telemetry carry
    device-resident across launches (donated) — zero per-pair
    dispatch, zero per-pair host sync.  `submit(pair)` returns a
    list of drained (out_i, out_e) results (empty until a super-batch
    completes); `flush()` runs any staged remainder through the
    per-pair program (same jit class as the reference pair — padding
    the scan would corrupt the carried counters) and returns the
    final (results, acc, telem).

    The jit-tracking proof rides `site`: wrap-tracked launches land
    in cilium_jit_cache_*{site} so a test (or the bench) can assert
    K pairs cost exactly one executable call.

    Each launch runs under a `datapath.launch` span (attrs pairs,
    tuples, bytes) with four children in order: `datapath.stack`
    (the host np.stack), `datapath.upload` (device_put),
    `datapath.enqueue` (the program call) and `datapath.outputs` (the
    per-pair slices).  Nothing waits on the device: each span times
    the host call, so an asynchronous upload shows as a short upload
    span."""

    def __init__(
        self, tables, k_pairs: int, acc, telem,
        site: str = "datapath.persistent",
    ) -> None:
        self.site = site
        self.tables = tables
        self.k = max(int(k_pairs), 1)
        self.acc = acc
        self.telem = telem
        self._staged = []
        self._program = tracing.track_jit(
            persistent_pair_program(self.k), site
        )
        self._pair_fallback = tracing.track_jit(
            datapath_step_accum_pair_telem_packed4_stacked,
            site + ".remainder",
        )
        self.launches = 0

    def submit(self, pair_host: np.ndarray):
        """Stage one [2, 4, B] host pair; when the K-th arrives the
        super-batch launches (one dispatch for all K).  Returns the
        drained per-pair (out_i, out_e) tuples, [] while staging."""
        tracer = tracing.tracer
        self._staged.append(pair_host)
        if len(self._staged) < self.k:
            return []
        staged, self._staged = self._staged, []
        attrs = {
            "pairs": len(staged),
            "tuples": sum(p.shape[0] * p.shape[-1] for p in staged),
            "bytes": sum(p.nbytes for p in staged),
        }
        with tracer.span("datapath.launch", site=self.site, attrs=attrs):
            with tracer.span("datapath.stack", site=self.site):
                host = np.stack(staged)
            with tracer.span("datapath.upload", site=self.site):
                stacked = jax.device_put(host)
            with tracer.span("datapath.enqueue", site=self.site):
                outs_i, outs_e, self.acc, self.telem = self._program(
                    self.tables, stacked, self.acc, self.telem
                )
            self.launches += 1
            # persistent-program launch accounting for the perf plane:
            # pairs/launches = realized staging depth at scrape time
            metrics.datapath_persistent_launches.inc()
            metrics.datapath_persistent_pairs.inc(value=len(staged))
            with tracer.span("datapath.outputs", site=self.site):
                outs = [
                    (
                        jax.tree.map(lambda a: a[i], outs_i),
                        jax.tree.map(lambda a: a[i], outs_e),
                    )
                    for i in range(self.k)
                ]
        return outs

    def flush(self):
        """Drain the staged remainder through the per-pair program
        (one launch per leftover pair — still no per-direction
        dispatch) and return (results, acc, telem).  This is the
        ONE carry commit point: callers host-read acc/telem here."""
        results = []
        for pair in self._staged:
            out_i, out_e, self.acc, self.telem = (
                self._pair_fallback(
                    self.tables, jax.device_put(pair),
                    self.acc, self.telem,
                )
            )
            results.append((out_i, out_e))
        self._staged = []
        return results, self.acc, self.telem


def _unique_rows(cols: list, sel: np.ndarray) -> np.ndarray:
    """Stack selected rows of the given columns and dedupe — the
    columns are packed into one u64-pair view so np.unique sorts a
    contiguous array instead of doing per-row tuple compares."""
    rows = np.stack(
        [np.asarray(c)[sel].astype(np.uint64) for c in cols], axis=1
    )
    if rows.shape[0] == 0:
        return rows
    return np.unique(rows, axis=0)


def apply_ct_writeback_host(
    ct: CTMap,
    create,
    delete,
    daddr,
    dport,
    saddr,
    sport,
    proto,
    direction,
    rev_nat,
    slave,
    now: int = 0,
    orig_daddr=None,
    orig_dport=None,
) -> tuple:
    """Host-side CT mutation after a batch (all inputs host arrays):
    create entries for NEW+allowed flows (ct_create4, bpf_lxc.c:978)
    and delete ESTABLISHED-but-now-denied entries (ct_delete4,
    bpf_lxc.c:968).  For load-balanced flows (rev_nat > 0 and the
    pre-DNAT columns provided) the SERVICE-scope entry is created
    alongside, carrying the selected backend for stickiness — exactly
    lb4_local's ct_create4 on the service tuple (bpf/lib/lb.h).
    Returns (created_keys, deleted_keys) — the key lists feed the
    incremental device-snapshot delta (ct.device.CTBucketIndex.apply).

    Vectorized: flagged rows are deduplicated with one np.unique over
    packed tuple columns, so host dict work is O(unique flows), not
    O(batch) — a 1M-tuple batch over a 64k-flow universe touches the
    dict at most 64k times regardless of batch size."""
    created_keys = []
    deleted_keys = []
    if orig_daddr is None:
        orig_daddr = daddr
        orig_dport = dport
    create_cols = [
        daddr, saddr, dport, sport, proto, direction, rev_nat, slave,
        orig_daddr, orig_dport,
    ]
    for row in _unique_rows(create_cols, create):
        (c_daddr, c_saddr, c_dport, c_sport, c_proto, c_dir,
         c_rev, c_slave, c_odaddr, c_odport) = (int(v) for v in row)
        flags = TUPLE_F_OUT if c_dir == CT_INGRESS else TUPLE_F_IN
        key = CTTuple(c_daddr, c_saddr, c_dport, c_sport, c_proto, flags)
        dnat = c_rev > 0 and (
            c_odaddr != c_daddr or c_odport != c_dport
        )
        if key not in ct.entries:
            if ct.create_best_effort(
                CTTuple(c_daddr, c_saddr, c_dport, c_sport, c_proto),
                c_dir, now=now, rev_nat_index=c_rev, slave=c_slave,
                orig_daddr=c_odaddr if dnat else 0,
                orig_dport=c_odport if dnat else 0,
            ):
                created_keys.append(key)
        if dnat:
            # the service-scope stickiness entry (lb4_local)
            svc_key = CTTuple(
                c_odaddr, c_saddr, c_odport, c_sport, c_proto,
                TUPLE_F_SERVICE,
            )
            if svc_key not in ct.entries:
                if ct.create_best_effort(
                    CTTuple(
                        c_odaddr, c_saddr, c_odport, c_sport, c_proto
                    ),
                    CT_SERVICE, now=now, rev_nat_index=c_rev,
                    slave=c_slave,
                ):
                    created_keys.append(svc_key)
    delete_cols = [daddr, saddr, dport, sport, proto, direction]
    for row in _unique_rows(delete_cols, delete):
        c_daddr, c_saddr, c_dport, c_sport, c_proto, c_dir = (
            int(v) for v in row
        )
        flags = TUPLE_F_OUT if c_dir == CT_INGRESS else TUPLE_F_IN
        key = CTTuple(c_daddr, c_saddr, c_dport, c_sport, c_proto, flags)
        if ct.entries.pop(key, None) is not None:
            deleted_keys.append(key)
    return created_keys, deleted_keys


def apply_ct_writeback(
    ct: CTMap, out: DatapathVerdicts, flows: FlowBatch, now: int = 0
) -> tuple:
    """Device-output convenience wrapper over apply_ct_writeback_host;
    returns (created, deleted) counts."""
    created_keys, deleted_keys = apply_ct_writeback_host(
        ct,
        np.asarray(out.ct_create),
        np.asarray(out.ct_delete),
        np.asarray(out.final_daddr),
        np.asarray(out.final_dport),
        np.asarray(flows.saddr),
        np.asarray(flows.sport),
        np.asarray(flows.proto),
        np.asarray(flows.direction),
        np.asarray(out.rev_nat),
        np.asarray(out.lb_slave),
        now=now,
        orig_daddr=np.asarray(flows.daddr),
        orig_dport=np.asarray(flows.dport),
    )
    return len(created_keys), len(deleted_keys)
