"""Composed host-side datapath oracle: the differential-testing twin
of the fused device step (engine/datapath.py).

Every stage is the plain-Python reference implementation over the host
data structures (HostLPM / ServiceManager / CTMap / policy map
states), evaluated per tuple in the same order the fused program
fuses: prefilter → LB/DNAT with service-scope stickiness → conntrack
→ ipcache identity derivation → policy lattice → combine
(bpf_lxc.c:440/899).  Device outputs must be BIT-IDENTICAL to this on
any input — chip_smoke.py, the multichip dryrun and the test suite
all cross-check through it.
"""

from __future__ import annotations

import ipaddress
from typing import Dict

import numpy as np


class HostLPM:
    """Fast host-side LPM oracle: /32s in a dict, other prefixes
    scanned longest-first (their count stays small in the config-5
    worlds, unlike the /32 population)."""

    def __init__(self, mapping: Dict[str, int]):
        self.exact = {}
        self.ranges = []
        for cidr, num_id in mapping.items():
            net = ipaddress.ip_network(cidr, strict=False)
            if net.version != 4:
                continue
            if net.prefixlen == 32:
                self.exact[int(net.network_address)] = num_id
            else:
                self.ranges.append(
                    (
                        net.prefixlen,
                        int(net.network_address),
                        int(net.netmask),
                        num_id,
                    )
                )
        self.ranges.sort(key=lambda r: -r[0])

    def lookup(self, ip: int) -> int:
        hit = self.exact.get(ip)
        if hit is not None:
            return hit
        for _, base, mask, num_id in self.ranges:
            if (ip & mask) == base:
                return num_id
        return 0


def lb_select_host(ct, svc, saddr, daddr, sport, dport, proto):
    """Host-side backend selection for one flow against a looked-up
    service: the CT service-scope stickiness probe first (lb4_local's
    ct lookup over both key layouts), fnv1a hash fallback.  The ONE
    reference implementation — composed_oracle and
    policy.trace.trace_tuple both call it, so the explain tool can
    never diverge from the oracle's backend choice.  Returns
    (slave 1-based, sticky bool)."""
    from cilium_tpu.ct.table import (
        CT_ESTABLISHED,
        CT_REPLY,
        CT_SERVICE,
        CTTuple,
        TUPLE_F_SERVICE,
    )
    from cilium_tpu.engine.hashtable import _fnv1a_host

    slave = 0
    sticky = False
    st_res = ct.lookup(
        CTTuple(daddr, saddr, dport, sport, proto), CT_SERVICE
    )
    if st_res in (CT_ESTABLISHED, CT_REPLY):
        for key in (
            CTTuple(saddr, daddr, sport, dport, proto,
                    TUPLE_F_SERVICE | 1),
            CTTuple(daddr, saddr, dport, sport, proto,
                    TUPLE_F_SERVICE),
            CTTuple(saddr, daddr, sport, dport, proto,
                    TUPLE_F_SERVICE),
            CTTuple(daddr, saddr, dport, sport, proto,
                    TUPLE_F_SERVICE | 1),
        ):
            e = ct.entries.get(key)
            if e is not None:
                slave = e.slave
                sticky = True
                break
    if not (0 < slave <= len(svc.backends)):
        words = np.array(
            [[saddr, daddr, (sport << 16) | dport, proto]],
            dtype=np.uint32,
        )
        slave = (
            int(_fnv1a_host(words)[0]) % len(svc.backends)
        ) + 1
        sticky = False
    return slave, sticky


def lattice_fold_host(
    states,
    ep_index,
    identity,
    dport,
    proto,
    direction,
    is_fragment=None,
    pad_to: int = 0,
):
    """Host-path fold of the bare verdict lattice — the degraded-mode
    twin of engine.verdict.evaluate_batch: the ONE lattice reference
    (engine.oracle.policy_can_access, counterless form) applied per
    tuple over the per-endpoint realized map states, so verdicts are
    bit-identical to the device kernel on any input.  The daemon
    fails over to this when the dispatch circuit breaker opens.

    `count_hits=False` on the oracle call: the device path this
    substitutes for (evaluate_batch) carries no entry counters, and
    degraded service must not leave different observable state than
    healthy service.  Missing endpoints (None state) evaluate
    against an empty map: default-deny, like an axis the compiler
    padded.

    Returns a Verdicts-shaped namespace (allowed u8, proxy_port i32,
    match_kind u8), zero-padded to `pad_to` when given — the batch
    shape the drain/event-fold slices with [:valid]."""
    from types import SimpleNamespace

    from cilium_tpu.engine.oracle import policy_can_access

    b = len(ep_index)
    n = max(b, pad_to)
    allowed = np.zeros(n, np.uint8)
    proxy = np.zeros(n, np.int32)
    kind = np.zeros(n, np.uint8)
    if is_fragment is None:
        is_fragment = np.zeros(b, bool)
    empty: Dict = {}
    for i in range(b):
        state = states[int(ep_index[i])]
        v = policy_can_access(
            empty if state is None else state,
            int(identity[i]),
            int(dport[i]),
            int(proto[i]),
            int(direction[i]),
            bool(is_fragment[i]),
            count_hits=False,
        )
        allowed[i] = 1 if v.allowed else 0
        proxy[i] = v.proxy_port
        kind[i] = v.match_kind
    return SimpleNamespace(
        allowed=allowed, proxy_port=proxy, match_kind=kind
    )


def composed_oracle(ctx, states, flows_dict, idx_list,
                    return_stages: bool = False):
    """Per-tuple host evaluation of the FULL fused pipeline.  `ctx`
    carries {"prefilter": HostLPM, "ipcache": HostLPM, "ct": CTMap,
    "mgr": ServiceManager}; `states` is the per-endpoint realized map
    state list in endpoint-axis order.  Returns (allowed, proxy,
    sec_id) arrays for the sampled indices; with `return_stages` a
    fourth dict {pre_drop, ct_res, match_kind, lb_hit, ipcache_miss}
    of per-stage intermediate decisions rides along — the telemetry
    plane's per-stage bit-identity gate compares the device's stage
    columns against these."""
    from cilium_tpu.ct.table import (
        CT_EGRESS,
        CT_ESTABLISHED,
        CT_INGRESS,
        CT_NEW,
        CT_RELATED,
        CT_REPLY,
        CTTuple,
    )
    from cilium_tpu.engine.oracle import policy_can_access
    from cilium_tpu.identity import RESERVED_WORLD
    from cilium_tpu.lb.service import L3n4Addr
    from cilium_tpu.maps.policymap import INGRESS

    pre, ipc, ct, mgr = (
        ctx["prefilter"], ctx["ipcache"], ctx["ct"], ctx["mgr"],
    )
    out_allow = np.zeros(len(idx_list), np.uint8)
    out_proxy = np.zeros(len(idx_list), np.int32)
    out_sec = np.zeros(len(idx_list), np.uint32)
    st_pre = np.zeros(len(idx_list), bool)
    st_ct = np.zeros(len(idx_list), np.uint8)
    st_kind = np.zeros(len(idx_list), np.uint8)
    st_lb = np.zeros(len(idx_list), bool)
    st_miss = np.zeros(len(idx_list), bool)
    f = flows_dict
    for row, i in enumerate(idx_list):
        ep = int(f["ep_index"][i])
        saddr, daddr = int(f["saddr"][i]), int(f["daddr"][i])
        sport, dport = int(f["sport"][i]), int(f["dport"][i])
        proto = int(f["proto"][i])
        direction = int(f["direction"][i])
        frag = bool(f["is_fragment"][i])

        pre_drop = pre.lookup(saddr) != 0

        eff_daddr, eff_dport = daddr, dport
        if direction != INGRESS:
            svc = mgr.lookup(
                L3n4Addr(str(ipaddress.ip_address(daddr)), dport, proto)
            )
            if svc is not None and svc.backends:
                slave, _ = lb_select_host(
                    ct, svc, saddr, daddr, sport, dport, proto
                )
                b = svc.backends[slave - 1]
                eff_daddr = b.addr.ip_u32()
                eff_dport = b.addr.port
                st_lb[row] = True

        ct_res = ct.lookup(
            CTTuple(eff_daddr, saddr, eff_dport, sport, proto),
            CT_INGRESS if direction == INGRESS else CT_EGRESS,
        )

        sec_ip = saddr if direction == INGRESS else eff_daddr
        sec_id = ipc.lookup(sec_ip)
        if sec_id == 0:
            sec_id = RESERVED_WORLD
            st_miss[row] = True

        v = policy_can_access(
            states[ep], sec_id, eff_dport, proto, direction, frag
        )
        pass_ct = ct_res in (CT_REPLY, CT_RELATED)
        allowed = (not pre_drop) and (pass_ct or v.allowed)
        proxy = (
            v.proxy_port
            if v.allowed and ct_res in (CT_NEW, CT_ESTABLISHED) and allowed
            else 0
        )
        out_allow[row] = 1 if allowed else 0
        out_proxy[row] = proxy
        out_sec[row] = sec_id
        st_pre[row] = pre_drop
        st_ct[row] = ct_res
        st_kind[row] = v.match_kind
    if return_stages:
        return out_allow, out_proxy, out_sec, {
            "pre_drop": st_pre,
            "ct_res": st_ct,
            "match_kind": st_kind,
            "lb_hit": st_lb,
            "ipcache_miss": st_miss,
        }
    return out_allow, out_proxy, out_sec
