"""Fused datapath step vs composed host oracles.

The fused kernel (engine/datapath.py) must agree flow-by-flow with
running the pipeline's host-side reference components in sequence:
prefilter host LPM → LB host selection → CTMap.lookup → ipcache host
LPM → policy oracle lattice → the bpf_lxc.c combine rules.  This is
the TPU analog of the reference's in-kernel unit tests
(test/bpf/unit-test.c) for the full program rather than per-helper.
"""

import ipaddress

import numpy as np
import pytest

from cilium_tpu.ct.device import compile_ct
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
)
from cilium_tpu.compiler.tables import compile_map_states
from cilium_tpu.engine.datapath import (
    DatapathTables,
    FlowBatch,
    apply_ct_writeback,
    datapath_step,
)
from cilium_tpu.engine.hashtable import _fnv1a_host
from cilium_tpu.engine.oracle import evaluate_batch_oracle
from cilium_tpu.identity import RESERVED_WORLD
from cilium_tpu.ipcache.lpm import build_ipcache, build_lpm, lookup_host
from cilium_tpu.prefilter import build_prefilter
from cilium_tpu.lb.device import compile_lb
from cilium_tpu.lb.service import L3n4Addr, ServiceManager
from cilium_tpu.maps.policymap import EGRESS, INGRESS

from tests.test_verdict_engine import random_map_state

IDENTITY_IDS = [1, 2, 3, 4, 5, 256, 257, 300, 1000]


def ip_u32(s: str) -> int:
    return int(ipaddress.ip_address(s))


def ip_str(v: int) -> str:
    return str(ipaddress.ip_address(int(v)))


def _host_flow_hash(saddr, daddr, sport, dport, proto):
    words = np.array(
        [[saddr, daddr, (sport << 16) | dport, proto]], dtype=np.uint32
    )
    return int(_fnv1a_host(words)[0])


def _host_oracle(
    prefilter_map, ipcache_map, ct, mgr, states, flow
):
    """One flow through the composed host reference components."""
    ep, saddr, daddr, sport, dport, proto, direction, frag = flow
    pre_drop = lookup_host(prefilter_map, ip_str(saddr)) != 0

    # LB (egress only)
    eff_daddr, eff_dport, rev_nat = daddr, dport, 0
    if direction == EGRESS:
        svc = mgr.lookup(L3n4Addr(ip_str(daddr), dport, proto))
        if svc is not None and svc.backends:
            # stickiness: service-scope CT entry first
            st_res = ct.lookup(
                CTTuple(daddr, saddr, dport, sport, proto),
                CT_SERVICE,
            )
            slave = 0
            if st_res in (CT_ESTABLISHED, CT_REPLY):
                # recover entry's slave by probing both key layouts
                from cilium_tpu.ct.table import (
                    TUPLE_F_SERVICE,
                )
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
                        break
            if not (0 < slave <= len(svc.backends)):
                h = _host_flow_hash(saddr, daddr, sport, dport, proto)
                slave = (h % len(svc.backends)) + 1
            b = svc.backends[slave - 1]
            eff_daddr = b.addr.ip_u32()
            eff_dport = b.addr.port
            rev_nat = svc.id

    # conntrack on the effective tuple
    ct_res = ct.lookup(
        CTTuple(eff_daddr, saddr, eff_dport, sport, proto),
        CT_INGRESS if direction == INGRESS else CT_EGRESS,
    )

    # identity derivation
    sec_ip = saddr if direction == INGRESS else eff_daddr
    sec_id = lookup_host(ipcache_map, ip_str(sec_ip))
    if sec_id == 0:
        sec_id = RESERVED_WORLD

    # policy lattice
    import copy

    allow, proxy, kind = evaluate_batch_oracle(
        copy.deepcopy(states),
        ep_index=np.array([ep]),
        identity=np.array([sec_id], np.uint32),
        dport=np.array([eff_dport]),
        proto=np.array([proto]),
        direction=np.array([direction]),
        is_fragment=np.array([frag]),
    )
    pol_allow = bool(allow[0])

    pass_ct = ct_res in (CT_REPLY, CT_RELATED)
    allowed = (not pre_drop) and (pass_ct or pol_allow)
    proxy_out = (
        int(proxy[0])
        if pol_allow and ct_res in (CT_NEW, CT_ESTABLISHED) and allowed
        else 0
    )
    ct_create = ct_res == CT_NEW and allowed
    ct_delete = (
        ct_res == CT_ESTABLISHED
        and not pol_allow
        and not pass_ct
        and not pre_drop
    )
    return allowed, proxy_out, ct_res, ct_create, ct_delete, sec_id


def _build_world(seed):
    rng = np.random.default_rng(seed)

    prefilter_map = {"203.0.113.0/24": 1}
    ipcache_map = {
        "10.0.0.0/8": 256,
        "10.1.0.0/16": 257,
        "10.1.2.0/24": 300,
        "10.1.2.3/32": 1000,
        "192.168.0.0/16": 5,
    }
    n_eps = 3
    states = [
        random_map_state(rng, IDENTITY_IDS, n_l4=10, n_l3=10)
        for _ in range(n_eps)
    ]
    policy = compile_map_states(states, IDENTITY_IDS, 32, 16)

    mgr = ServiceManager()
    mgr.upsert(
        L3n4Addr("172.16.0.1", 80, 6),
        [L3n4Addr("10.1.2.3", 8080, 6), L3n4Addr("10.1.2.4", 8080, 6)],
    )
    mgr.upsert(
        L3n4Addr("172.16.0.2", 443, 6), [L3n4Addr("10.1.9.9", 9443, 6)]
    )

    ct = CTMap()
    # some established flows (forward created at egress+ingress scope)
    for saddr, daddr, sport, dport, proto, d in [
        (ip_u32("10.0.0.1"), ip_u32("10.1.2.3"), 4001, 80, 6, CT_INGRESS),
        (ip_u32("10.0.0.2"), ip_u32("10.1.2.3"), 4002, 443, 6, CT_EGRESS),
        (ip_u32("192.168.1.1"), ip_u32("10.1.2.4"), 4003, 8080, 17,
         CT_INGRESS),
    ]:
        ct.create(CTTuple(daddr, saddr, dport, sport, proto), d)
    # a sticky service-scope entry for the 2-backend vip
    ct.create(
        CTTuple(ip_u32("172.16.0.1"), ip_u32("10.0.0.9"), 80, 4009, 6),
        CT_SERVICE,
        slave=2,
    )

    tables = DatapathTables(
        prefilter=build_prefilter(prefilter_map),
        ipcache=build_ipcache(ipcache_map),
        ct=compile_ct(ct),
        lb=compile_lb(mgr),
        policy=policy,
    )
    return (
        rng, prefilter_map, ipcache_map, ct, mgr, states, tables, n_eps
    )


def _random_flows(rng, n, n_eps):
    pool = [
        "10.0.0.1", "10.0.0.2", "10.0.0.9", "10.1.2.3", "10.1.2.4",
        "192.168.1.1", "203.0.113.7", "8.8.8.8",
    ]
    saddr = np.array([ip_u32(rng.choice(pool)) for _ in range(n)],
                     np.uint32)
    daddr = np.array(
        [
            ip_u32(
                rng.choice(pool + ["172.16.0.1", "172.16.0.2"])
            )
            for _ in range(n)
        ],
        np.uint32,
    )
    return dict(
        ep_index=rng.integers(0, n_eps, size=n),
        saddr=saddr,
        daddr=daddr,
        sport=rng.choice([4001, 4002, 4003, 4009, 5000], size=n),
        dport=rng.choice([53, 80, 443, 8080, 9090, 9443], size=n),
        proto=rng.choice([6, 17], size=n),
        direction=rng.integers(0, 2, size=n),
        is_fragment=rng.random(size=n) < 0.05,
    )


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_fused_datapath_matches_composed_oracle(seed):
    (rng, prefilter_map, ipcache_map, ct, mgr, states, tables,
     n_eps) = _build_world(seed)
    n = 256
    f = _random_flows(rng, n, n_eps)
    flows = FlowBatch.from_numpy(**f)
    out = datapath_step(tables, flows)

    got_allowed = np.asarray(out.allowed)
    got_proxy = np.asarray(out.proxy_port)
    got_ct = np.asarray(out.ct_result)
    got_create = np.asarray(out.ct_create)
    got_delete = np.asarray(out.ct_delete)
    got_sec = np.asarray(out.sec_id)

    for i in range(n):
        flow = (
            int(f["ep_index"][i]), int(f["saddr"][i]), int(f["daddr"][i]),
            int(f["sport"][i]), int(f["dport"][i]), int(f["proto"][i]),
            int(f["direction"][i]), bool(f["is_fragment"][i]),
        )
        allowed, proxy, ct_res, create, delete, sec_id = _host_oracle(
            prefilter_map, ipcache_map, ct, mgr, states, flow
        )
        ctx = f"flow {i}: {flow}"
        assert bool(got_allowed[i]) == allowed, ctx
        assert int(got_proxy[i]) == proxy, ctx
        assert int(got_ct[i]) == ct_res, ctx
        assert bool(got_create[i]) == create, ctx
        assert bool(got_delete[i]) == delete, ctx
        assert int(got_sec[i]) == sec_id, ctx


def test_ct_writeback_roundtrip():
    (rng, prefilter_map, ipcache_map, ct, mgr, states, tables,
     n_eps) = _build_world(3)
    f = _random_flows(rng, 128, n_eps)
    flows = FlowBatch.from_numpy(**f)
    out = datapath_step(tables, flows)

    before = len(ct.entries)
    created, deleted = apply_ct_writeback(ct, out, flows)
    assert created >= 0 and deleted >= 0
    assert len(ct.entries) == before + created - deleted

    # a second pass over the SAME flows against the refreshed snapshot
    # must see no NEW+allowed flows that aren't duplicates: every
    # previously-created flow is now ESTABLISHED.
    tables2 = DatapathTables(
        prefilter=tables.prefilter,
        ipcache=tables.ipcache,
        ct=compile_ct(ct),
        lb=tables.lb,
        policy=tables.policy,
    )
    out2 = datapath_step(tables2, flows)
    was_created = np.asarray(out.ct_create)
    now_res = np.asarray(out2.ct_result)
    # flows flagged ct_create in pass 1 are no longer NEW in pass 2
    assert not np.any(now_res[was_created] == CT_NEW)


def test_prefilter_blocks_before_everything():
    (rng, prefilter_map, ipcache_map, ct, mgr, states, tables,
     n_eps) = _build_world(4)
    # source in the prefiltered CIDR, ESTABLISHED entry present
    saddr = ip_u32("203.0.113.7")
    daddr = ip_u32("10.1.2.3")
    ct.create(CTTuple(daddr, saddr, 80, 4000, 6), CT_INGRESS)
    tables = DatapathTables(
        prefilter=tables.prefilter,
        ipcache=tables.ipcache,
        ct=compile_ct(ct),
        lb=tables.lb,
        policy=tables.policy,
    )
    flows = FlowBatch.from_numpy(
        ep_index=[0], saddr=[saddr], daddr=[daddr], sport=[4000],
        dport=[80], proto=[6], direction=[INGRESS],
    )
    out = datapath_step(tables, flows)
    assert not bool(np.asarray(out.allowed)[0])
    assert bool(np.asarray(out.pre_dropped)[0])
    assert not bool(np.asarray(out.ct_create)[0])


@pytest.mark.parametrize("seed", [0, 1])
def test_idx_form_ipcache_matches_generic(seed):
    """specialize_ipcache_to_idx must leave every datapath output
    bit-identical (all _build_world ipcache identities are in the
    universe, so sec_id round-trips through id_table)."""
    from cilium_tpu.ipcache.lpm import specialize_ipcache_to_idx

    (rng, pf, ipc, ct, mgr, states, tables, n_eps) = _build_world(seed)
    spec = DatapathTables(
        prefilter=tables.prefilter,
        ipcache=specialize_ipcache_to_idx(tables.ipcache, tables.policy),
        ct=tables.ct,
        lb=tables.lb,
        policy=tables.policy,
    )
    f = _random_flows(rng, 512, n_eps)
    flows = FlowBatch.from_numpy(**f)
    a = datapath_step(tables, flows)
    b = datapath_step(spec, flows)
    for field in (
        "allowed", "proxy_port", "match_kind", "ct_result",
        "pre_dropped", "sec_id", "final_daddr", "final_dport",
        "rev_nat", "lb_slave", "ct_create", "ct_delete",
    ):
        np.testing.assert_array_equal(
            np.asarray(getattr(a, field)),
            np.asarray(getattr(b, field)),
            err_msg=field,
        )


_VERDICT_FIELDS = (
    "allowed", "proxy_port", "match_kind", "ct_result",
    "pre_dropped", "sec_id", "final_daddr", "final_dport",
    "rev_nat", "lb_slave", "ct_create", "ct_delete", "l4_slot",
)


def _pair_program(tables, flows_in, flows_eg):
    """One pair through the fused-pair program, staged as the
    dispatcher stages it: both halves packed4 into one [2, 4, B]."""
    import jax

    from cilium_tpu.engine.datapath import (
        datapath_step_accum_pair_telem_packed4_stacked,
        pack_flow_records4,
    )
    from cilium_tpu.engine.verdict import (
        make_counter_buffers,
        make_telemetry_buffers,
    )

    pair = np.stack([pack_flow_records4(**f) for f in (flows_in, flows_eg)])
    return datapath_step_accum_pair_telem_packed4_stacked(
        tables, pair,
        jax.device_put(make_counter_buffers(tables.policy)),
        jax.device_put(make_telemetry_buffers()),
    )


def _accum_in_sequence(tables, *flow_dicts):
    """The generic streaming step over each batch in turn, one carried
    counter buffer."""
    import jax

    from cilium_tpu.engine.datapath import datapath_step_accum
    from cilium_tpu.engine.verdict import make_counter_buffers

    acc = jax.device_put(make_counter_buffers(tables.policy))
    outs = []
    for f in flow_dicts:
        out, acc = datapath_step_accum(tables, FlowBatch.from_numpy(**f), acc)
        outs.append(out)
    return outs, acc


def _assert_verdicts_equal(a, b):
    for field in _VERDICT_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, field)),
            np.asarray(getattr(b, field)),
            err_msg=field,
        )


@pytest.mark.parametrize("direction", [0, 1])
def test_direction_specialized_kernels_match_generic(direction):
    """The pair program's direction-specialized halves (bpf_lxc's
    separate ingress/egress sections) must agree with the generic
    kernel on single-direction batches, counters included: the batch
    of `direction` rides its own half, a batch of the other direction
    the other."""
    from cilium_tpu.ipcache.lpm import specialize_ipcache_to_idx

    (rng, pf, ipc, ct, mgr, states, tables, n_eps) = _build_world(4)
    tables = DatapathTables(
        prefilter=tables.prefilter,
        ipcache=specialize_ipcache_to_idx(tables.ipcache, tables.policy),
        ct=tables.ct,
        lb=tables.lb,
        policy=tables.policy,
    )
    f = _random_flows(rng, 512, n_eps)
    f["direction"] = np.full(512, direction)
    other = _random_flows(rng, 512, n_eps)
    other["direction"] = np.full(512, 1 - direction)
    halves = (f, other) if direction == 0 else (other, f)

    (a, a_other), acc_a = _accum_in_sequence(tables, f, other)
    out_i, out_e, acc_b, _ = _pair_program(tables, *halves)
    b, b_other = (out_i, out_e) if direction == 0 else (out_e, out_i)
    _assert_verdicts_equal(a, b)
    _assert_verdicts_equal(a_other, b_other)
    np.testing.assert_array_equal(np.asarray(acc_a), np.asarray(acc_b))


def test_paired_dispatch_matches_sequential():
    """The one-dispatch ingress+egress pair program must produce
    bit-identical verdicts AND counters to running the generic
    streaming step over the two half-batches sequentially."""
    (rng, _, _, ct, _, states, tables, n_eps) = _build_world(23)
    pool = _random_flows(rng, 256, n_eps)
    idx_in = np.nonzero(pool["direction"] == 0)[0]
    idx_eg = np.nonzero(pool["direction"] == 1)[0]
    half = 96

    def batch_of(rows):
        picks = rows[rng.integers(0, len(rows), size=half)]
        return {k: pool[k][picks] for k in (
            "ep_index", "saddr", "daddr", "sport", "dport",
            "proto", "direction", "is_fragment",
        )}

    fin, feg = batch_of(idx_in), batch_of(idx_eg)

    (oi1, oe1), acc1 = _accum_in_sequence(tables, fin, feg)
    oi2, oe2, acc2, _ = _pair_program(tables, fin, feg)

    for a, b in ((oi1, oi2), (oe1, oe2)):
        _assert_verdicts_equal(a, b)
    np.testing.assert_array_equal(np.asarray(acc1), np.asarray(acc2))
