"""Per-stage cost breakdown of the fused datapath programs.

Builds the bench's config-5 world at reduced control-plane scale (the
datapath shapes that matter — CT/LB/ipcache/lattice table layouts —
are identical; only rule compile time shrinks), then times variant
programs with stages progressively enabled.  Differences between
successive variants = incremental stage cost.

Timing method (see memory: block_until_ready is unreliable on this
transport): run K pipelined reps with 4 outstanding, then ONE tiny
D2H np.asarray on the last output; subtract a floor variant.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def timed(fn, tables, flows, acc_factory, reps=8, outstanding=4):
    import jax

    acc = acc_factory()
    outs = []
    out, acc = fn(tables, flows, acc)  # warmup/compile
    jax.block_until_ready((out, acc))
    _ = np.asarray(out.allowed[:4])
    acc = acc_factory()
    t0 = time.perf_counter()
    for _ in range(reps):
        out, acc = fn(tables, flows, acc)
        outs.append(out)
        if len(outs) > outstanding:
            outs.pop(0)
    _ = np.asarray(outs[-1].allowed[:4])
    _ = np.asarray(acc[:1]) if hasattr(acc, "shape") else None
    return (time.perf_counter() - t0) / reps


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=1 << 21)
    ap.add_argument("--rules", type=int, default=4000)
    ap.add_argument("--identities", type=int, default=65536)
    ap.add_argument("--endpoints", type=int, default=32)
    ap.add_argument("--pool", type=int, default=50000)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    import bench as B
    from cilium_tpu.engine import datapath as dp
    from cilium_tpu.engine.verdict import make_counter_buffers

    rng = np.random.default_rng(7)

    class A:
        rules = args.rules
        endpoints = args.endpoints
        identities = args.identities
        pool = args.pool
        batch = args.batch
        oracle_sample = 64

    t0 = time.perf_counter()
    d, tables, index, pool, oracle_ctx, timings, ct, mgr = (
        B.build_config5(A, rng)
    )
    print(f"build: {time.perf_counter() - t0:.1f}s", flush=True)
    tables = jax.device_put(tables)

    # seed CT so the CT table is populated like the bench steady state
    from cilium_tpu.replay import replay_pool

    picks = rng.integers(0, args.pool, size=args.batch)
    replay_pool(tables, pool, picks, batch_size=args.batch, ct_map=ct)
    from cilium_tpu.ct.device import compile_ct

    tables = dp.DatapathTables(
        prefilter=tables.prefilter,
        ipcache=tables.ipcache,
        ct=jax.device_put(compile_ct(ct)),
        lb=tables.lb,
        policy=tables.policy,
    )
    tables = jax.device_put(tables)

    # per-direction flow batches, like the bench's timed loop
    half = args.batch
    from cilium_tpu.replay import read_flow_batches

    batches = {}
    for name, dirv in (("ingress", 0), ("egress", 1)):
        subset = np.nonzero(pool["direction"] == dirv)[0]
        picks = subset[rng.integers(0, len(subset), size=half)]
        buf = B.encode_pool_sample(pool, picks)
        batches[name] = jax.device_put(
            next(read_flow_batches(buf, half))[0]
        )

    def acc_factory():
        return jax.device_put(make_counter_buffers(tables.policy))

    # ---- stage-variant kernels -------------------------------------------
    from cilium_tpu.ct.device import ct_lookup_batch
    from cilium_tpu.ct.table import CT_SERVICE
    from cilium_tpu.engine.verdict import (
        TupleBatch,
        _accumulate_counters,
        _combine,
        _probes,
    )
    from cilium_tpu.ipcache.lpm import ipcache_lookup_fused
    from cilium_tpu.lb.device import lb_select_batch
    from cilium_tpu.maps.policymap import INGRESS
    from cilium_tpu.prefilter import prefilter_drop

    def variant(stages, static_direction):
        """stages: set of {pre, svc, lb, ct, lpm, lattice, counters}"""

        def kernel(tables, flows, acc):
            ingress = jnp.full(
                flows.direction.shape, static_direction == INGRESS
            )
            allowed = jnp.ones(flows.saddr.shape, bool)
            if "pre" in stages:
                allowed &= ~prefilter_drop(
                    tables.prefilter, flows.saddr
                )
            eff_daddr = flows.daddr.astype(jnp.uint32)
            eff_dport = flows.dport
            if "svc" in stages:
                svc_dir = jnp.full_like(flows.direction, CT_SERVICE)
                _, _, svc_slave = ct_lookup_batch(
                    tables.ct, flows.daddr, flows.saddr, flows.dport,
                    flows.sport, flows.proto, svc_dir,
                )
            else:
                svc_slave = None
            if "lb" in stages:
                svc_found, slave, lb_daddr, lb_dport, lb_rev = (
                    lb_select_batch(
                        tables.lb, flows.saddr, flows.daddr,
                        flows.sport, flows.dport, flows.proto,
                        ct_slave=svc_slave,
                    )
                )
                eff_daddr = jnp.where(svc_found, lb_daddr, eff_daddr)
                eff_dport = jnp.where(svc_found, lb_dport, eff_dport)
            if "ct" in stages:
                ct_res, _, _ = ct_lookup_batch(
                    tables.ct, eff_daddr, flows.saddr, eff_dport,
                    flows.sport, flows.proto, flows.direction,
                )
                allowed &= ct_res > 0
            if "lpm" in stages:
                sec_ip = jnp.where(
                    ingress, flows.saddr.astype(jnp.uint32), eff_daddr
                )
                looked, l3_word = ipcache_lookup_fused(
                    tables.ipcache, sec_ip, ingress=ingress
                )
                n = tables.policy.id_table.shape[0]
                miss = looked == 0
                vp = jnp.where(
                    miss,
                    jnp.uint32(tables.ipcache.world_plus1),
                    looked,
                )
                from cilium_tpu.ipcache.lpm import UNKNOWN_IDX

                known = (vp != 0) & (vp != jnp.uint32(UNKNOWN_IDX))
                idx = jnp.where(known, vp - 1, jnp.uint32(n - 1)).astype(
                    jnp.int32
                )
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
                idx_known = (
                    flows.saddr.astype(jnp.int32)
                    % tables.policy.id_table.shape[0],
                    jnp.ones(flows.saddr.shape, bool),
                    jnp.ones(flows.saddr.shape, bool),
                )
            if "lattice" in stages:
                resolved = TupleBatch(
                    ep_index=flows.ep_index,
                    identity=jnp.zeros_like(flows.saddr),
                    dport=eff_dport,
                    proto=flows.proto,
                    direction=flows.direction,
                    is_fragment=flows.is_fragment,
                )
                probe1, probe2, probe3, proxy, j, idx = _probes(
                    tables.policy, resolved, idx_known=idx_known
                )
                v = _combine(
                    probe1, probe2, probe3, proxy, resolved.is_fragment
                )
                allowed &= v.allowed.astype(bool)
                if "counters" in stages:
                    acc = _accumulate_counters(
                        v, resolved, j, idx, acc,
                        tables.policy.l4_meta.shape[2],
                    )
            out = dp.DatapathVerdicts(
                allowed=allowed.astype(jnp.uint8),
                proxy_port=jnp.zeros_like(flows.dport),
                match_kind=jnp.zeros(flows.saddr.shape, jnp.uint8),
                ct_result=jnp.zeros(flows.saddr.shape, jnp.uint8),
                pre_dropped=jnp.zeros(flows.saddr.shape, bool),
                sec_id=idx_known[0].astype(jnp.uint32),
                final_daddr=eff_daddr,
                final_dport=eff_dport,
                rev_nat=jnp.zeros_like(flows.dport),
                lb_slave=jnp.zeros_like(flows.dport),
                ct_create=jnp.zeros(flows.saddr.shape, bool),
                ct_delete=jnp.zeros(flows.saddr.shape, bool),
                tunnel_endpoint=jnp.zeros(flows.saddr.shape, jnp.uint32),
            )
            return out, acc

        return jax.jit(kernel, donate_argnums=(2,))

    ladders = {
        "ingress": [
            ("floor", set()),
            ("+pre", {"pre"}),
            ("+ct", {"pre", "ct"}),
            ("+lpm", {"pre", "ct", "lpm"}),
            ("+lattice", {"pre", "ct", "lpm", "lattice"}),
            ("+counters", {"pre", "ct", "lpm", "lattice", "counters"}),
        ],
        "egress": [
            ("floor", set()),
            ("+pre", {"pre"}),
            ("+svc", {"pre", "svc"}),
            ("+lb", {"pre", "svc", "lb"}),
            ("+ct", {"pre", "svc", "lb", "ct"}),
            ("+lpm", {"pre", "svc", "lb", "ct", "lpm"}),
            ("+lattice", {"pre", "svc", "lb", "ct", "lpm", "lattice"}),
            (
                "+counters",
                {"pre", "svc", "lb", "ct", "lpm", "lattice", "counters"},
            ),
        ],
    }
    for direction, ladder in ladders.items():
        dirv = INGRESS if direction == "ingress" else 1
        flows = batches[direction]
        prev = 0.0
        print(f"--- {direction} @ {args.batch} ---", flush=True)
        for name, stages in ladder:
            fn = variant(frozenset(stages), dirv)
            dt = timed(fn, tables, flows, acc_factory)
            print(
                f"{name:12s} {dt * 1000:8.1f} ms  "
                f"(+{(dt - prev) * 1000:6.1f} ms)",
                flush=True,
            )
            prev = dt

    # reference: the real production programs
    for direction, fn in (
        ("ingress", dp.datapath_step_accum_ingress),
        ("egress", dp.datapath_step_accum_egress),
    ):
        dt = timed(fn, tables, batches[direction], acc_factory)
        print(f"real {direction:8s} {dt * 1000:8.1f} ms", flush=True)


if __name__ == "__main__":
    main()
