#!/usr/bin/env python3
"""Bring-up smoke on the chip: the config-5 deployment through the
served policy path and the fused datapath, checked against the host
references.

    python chip_smoke.py             # one chip (what the driver runs)
    python chip_smoke.py --chips 4   # the per-chip failure domain only

One process holds the chip(s) for the whole run.  Every phase raises
on a mismatch or on any fallback counter that moved; nothing is caught.
The last line of stdout is the one JSON result object, printed only
when every phase passed on a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from types import SimpleNamespace

import numpy as np

# the 14 per-tuple columns of engine.datapath.DatapathVerdicts that the
# fused programs fill (tunnel_endpoint stays zero without a tunnel map)
VERDICT_COLUMNS = (
    "allowed", "proxy_port", "match_kind", "sec_id", "ct_result",
    "pre_dropped", "final_daddr", "final_dport", "rev_nat", "lb_slave",
    "ct_create", "ct_delete", "l4_slot", "ipcache_miss",
)
SERVE_BATCHES = 8
SEED = 7  # the config-5 world's seed


class SmokeFailure(RuntimeError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def require_tpu(n_chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SmokeFailure(
            f"no TPU: JAX found {devs[0].platform!r} devices; this "
            f"smoke never runs on another platform"
        )
    check(
        len(devs) >= n_chips,
        f"{n_chips} chip(s) requested, {len(devs)} visible",
    )
    return devs


# ---------------------------------------------------------------------------
# world
# ---------------------------------------------------------------------------


def build_world(args) -> SimpleNamespace:
    """The config-5 world through the real control plane
    (bench.build_config5), at the bench's own defaults."""
    import bench

    t0 = time.perf_counter()
    d, tables, index, pool, octx, timings, ct, mgr = bench.build_config5(
        args, np.random.default_rng(SEED)
    )
    timings["total_build_s"] = time.perf_counter() - t0
    states = [None] * len(index)
    for ep in d.endpoint_manager.endpoints():
        states[index[ep.id]] = ep.realized_map_state
    n_entries = sum(len(s) for s in states if s is not None)
    say(
        f"world: rules={args.rules} endpoints={args.endpoints} "
        f"identities={args.identities} pool={args.pool} "
        f"map_entries={n_entries} id_table="
        f"{tables.policy.id_table.shape[0]} phases="
        + json.dumps({k: round(v, 3) for k, v in timings.items()})
    )
    return SimpleNamespace(
        daemon=d, tables=tables, index=index, pool=pool, octx=octx,
        states=states,
    )


def pool_records(world, picks, with_identity: bool) -> dict:
    """Flow records (the decoded wire format the serving plane takes)
    for pool rows `picks`.  The served lattice path reads the peer
    identity from the record, so `with_identity` resolves it through
    the host ipcache (WORLD on a miss); the fused path derives it on
    device and takes 0."""
    from cilium_tpu.identity import RESERVED_WORLD

    pool = world.pool
    axis_to_ep = np.zeros(max(world.index.values()) + 1, np.uint32)
    for ep_id, axis in world.index.items():
        axis_to_ep[axis] = ep_id
    rec = {
        "ep_id": axis_to_ep[pool["ep_index"][picks]],
        "saddr": pool["saddr"][picks],
        "daddr": pool["daddr"][picks],
        "sport": pool["sport"][picks],
        "dport": pool["dport"][picks],
        "proto": pool["proto"][picks],
        "direction": pool["direction"][picks],
        "is_fragment": pool["is_fragment"][picks],
    }
    identity = np.zeros(len(picks), np.uint32)
    if with_identity:
        peer = np.where(rec["direction"] == 0, rec["saddr"], rec["daddr"])
        ipc = world.octx["ipcache"]
        identity[:] = [
            ipc.lookup(int(ip)) or RESERVED_WORLD for ip in peer
        ]
    rec["identity"] = identity
    return rec


def submit_two_tenants(plane, rec, n_submissions: int):
    """Split `rec` into submissions that alternate between two
    tenants, submit them all, and wait for every reply."""
    n = len(rec["ep_id"])
    step = n // n_submissions
    results = []
    for i in range(n_submissions):
        end = n if i == n_submissions - 1 else (i + 1) * step
        sl = slice(i * step, end)
        results.append(
            plane.submit(
                rec={k: v[sl] for k, v in rec.items()},
                tenant=("tenant-a", "tenant-b")[i % 2],
            )
        )
    for r in results:
        r.wait(timeout=600)
    for r in results:
        check(
            not r.shed and not r.shed_mask.any()
            and r.dropped_unknown == 0 and r.prefiltered == 0,
            f"served submission lost flows: shed={r.shed} "
            f"dropped_unknown={r.dropped_unknown} "
            f"prefiltered={r.prefiltered}",
        )
        check(r.degraded_batches == 0, "a served batch degraded")
    return results


def concat(results, field: str) -> np.ndarray:
    return np.concatenate([getattr(r, field) for r in results])


# ---------------------------------------------------------------------------
# phase: served policy path (single chip)
# ---------------------------------------------------------------------------


def served_phase(world) -> None:
    """SERVE_BATCHES coalesced batches through the daemon's serving
    plane (the path behind POST /datapath/flows?stream=1), every
    verdict compared with the host lattice fold."""
    from cilium_tpu.engine.hostpath import lattice_fold_host
    from cilium_tpu.replay import _ep_index_of

    d = world.daemon
    plane = d.serving_plane()
    batch = plane.batch_size
    picks = np.random.default_rng(SEED + 1).integers(
        0, len(world.pool["saddr"]), size=SERVE_BATCHES * batch
    )
    rec = pool_records(world, picks, with_identity=True)
    t0 = time.perf_counter()
    results = submit_two_tenants(plane, rec, 2 * SERVE_BATCHES)
    wall = time.perf_counter() - t0
    batch_walls = d.perf.phases["wall"].values(time.monotonic())
    batches = plane.batches
    plane.stop()
    d.serving = None

    _, _, index, host_states = d.endpoint_manager.published_with_states()
    want = lattice_fold_host(
        host_states, _ep_index_of(rec, dict(index)), rec["identity"],
        rec["dport"], rec["proto"], rec["direction"],
        is_fragment=rec["is_fragment"].astype(bool),
    )
    for field in ("allowed", "match_kind", "proxy_port"):
        got = concat(results, field)
        ref = np.asarray(getattr(want, field)).astype(got.dtype)
        check(
            np.array_equal(got, ref),
            f"served {field} differs from the host lattice fold on "
            f"{int((got != ref).sum())} of {len(ref)} flows",
        )
    say(
        f"served: batch_class={batch} flows={len(picks)} "
        f"batches={batches} "
        f"allowed={int(concat(results, 'allowed').sum())} "
        f"equal_to_host_fold=true wall_s={wall:.4f} "
        f"batch_wall_s={[round(w, 6) for w in batch_walls]}"
    )


# ---------------------------------------------------------------------------
# phase: fused datapath (single chip)
# ---------------------------------------------------------------------------


def headline_tables(world):
    """The fused-pair program's tables: the hot policy plane at the
    compiled pack width, sub-word layouts (as benchmark/world.py's
    headline_tables)."""
    from cilium_tpu.compiler.tables import split_hot
    from cilium_tpu.engine.datapath import (
        DatapathTables,
        subword_datapath_tables,
    )

    t = world.tables
    hot = DatapathTables(
        prefilter=t.prefilter, ipcache=t.ipcache, ct=t.ct, lb=t.lb,
        policy=split_hot(t.policy),
    )
    return subword_datapath_tables(hot)


def program_bytes(jitted, *args) -> dict:
    ma = jitted.lower(*args).compile().memory_analysis()
    return {
        "args": int(ma.argument_size_in_bytes),
        "out": int(ma.output_size_in_bytes),
        "temp": int(ma.temp_size_in_bytes),
        "alias": int(ma.alias_size_in_bytes),
    }


def fitted_batch(tables_dev, policy, batch: int, k: int, dev):
    """Largest power-of-two batch <= `batch` whose per-pair and
    persistent K-pair programs fit beside what the device already
    holds, by the compiler's own memory analysis.  The phase holds
    the persistent outputs while the per-pair program runs."""
    import jax
    from jax.sharding import SingleDeviceSharding

    from cilium_tpu.engine.datapath import (
        datapath_step_accum_pair_telem_packed4_stacked as pair_prog,
        persistent_pair_program,
    )
    from cilium_tpu.engine.verdict import (
        make_counter_buffers,
        make_telemetry_buffers,
    )

    stats = dev.memory_stats()
    free = int(stats["bytes_limit"]) - int(stats["bytes_in_use"])
    sh = SingleDeviceSharding(dev)
    acc = jax.ShapeDtypeStruct(
        make_counter_buffers(policy).shape, np.uint32, sharding=sh
    )
    telem = jax.ShapeDtypeStruct(
        make_telemetry_buffers().shape, np.uint32, sharding=sh
    )
    tables_bytes = sum(
        int(x.nbytes) for x in jax.tree_util.tree_leaves(tables_dev)
    )
    b = batch
    while True:
        half = b // 2
        pair = jax.ShapeDtypeStruct(
            (2, 4, half), np.uint32, sharding=sh
        )
        pairs = jax.ShapeDtypeStruct(
            (k, 2, 4, half), np.uint32, sharding=sh
        )
        t0 = time.perf_counter()
        pb = program_bytes(pair_prog, tables_dev, pair, acc, telem)
        kb = program_bytes(
            persistent_pair_program(k), tables_dev, pairs, acc, telem
        )
        # the tables are already resident (counted in bytes_in_use);
        # the per-pair program runs while the K-pair outputs are held
        need = max(
            kb["args"] - tables_bytes + kb["out"] + kb["temp"],
            kb["out"] + pb["args"] - tables_bytes + pb["out"]
            + pb["temp"],
        )
        say(
            f"fused sizing: batch={b} pair_program={pb} "
            f"persistent_k{k}={kb} need_bytes={need} "
            f"free_bytes={free} compile_s={time.perf_counter() - t0:.2f}"
        )
        if need <= 0.85 * free or half <= 1 << 12:
            return b
        b //= 2


def fused_phase(world, args, dev) -> None:
    """Batch pairs through the fused programs at the config-5
    geometry: one persistent K-pair launch (PersistentPairDispatcher)
    and the per-pair program over the same pairs.  Pair 0's columns
    are checked against the composed host oracle on a sample; its
    counters and telemetry against the host fold of its per-tuple
    columns; the persistent launch against the per-pair program on
    every column, counter and telemetry cell."""
    import jax
    import jax.numpy as jnp

    import bench
    from cilium_tpu import tracing
    from cilium_tpu.engine.datapath import (
        PersistentPairDispatcher,
        datapath_step_accum_pair_telem_packed4_stacked,
    )
    from cilium_tpu.engine.verdict import (
        make_counter_buffers,
        make_telemetry_buffers,
    )

    pool = world.pool
    policy = world.tables.policy
    host_tables, report = headline_tables(world)
    tables = jax.device_put(host_tables, dev)
    k = max(int(args.persist_pairs), 1)
    batch = fitted_batch(tables, policy, int(args.batch), k, dev)
    half = batch // 2
    say(
        f"fused: headline_batch={args.batch} run_batch={batch} "
        f"persist_pairs={k} subword={report}"
    )

    # the fused-pair loader: per-direction pool subsets, packed4 pairs
    host_pairs, picks = bench.pack_pool_pairs(
        pool, np.random.default_rng(SEED + 2), half, k
    )

    def fresh_carry():
        return (
            jax.device_put(make_counter_buffers(policy), dev),
            jax.device_put(make_telemetry_buffers(), dev),
        )

    # one persistent K-pair launch
    pd = PersistentPairDispatcher(
        tables, k, *fresh_carry(), site="datapath.persistent"
    )
    t0 = time.perf_counter()
    persisted = []
    for p in host_pairs:
        persisted.extend(pd.submit(p))
    rem, acc_k, telem_k = pd.flush()
    jax.block_until_ready((persisted, acc_k, telem_k))
    persist_s = time.perf_counter() - t0
    check(not rem and len(persisted) == k and pd.launches == 1,
          "the persistent dispatcher did not run one K-pair launch")

    @jax.jit
    def columns_equal(a, b):
        return jnp.stack(
            [jnp.array_equal(getattr(a, c), getattr(b, c))
             for c in VERDICT_COLUMNS]
        )

    pair_prog = tracing.track_jit(
        datapath_step_accum_pair_telem_packed4_stacked, "datapath.pair"
    )
    acc, telem = fresh_carry()
    pair_walls = []
    for i, pair in enumerate(host_pairs):
        t0 = time.perf_counter()
        out_i, out_e, acc, telem = pair_prog(
            tables, jax.device_put(pair, dev), acc, telem
        )
        jax.block_until_ready((out_i, out_e, acc, telem))
        pair_walls.append(time.perf_counter() - t0)
        for got, ref in zip(persisted[i], (out_i, out_e)):
            eq = np.asarray(columns_equal(got, ref))
            check(
                eq.all(),
                f"persistent launch differs from the per-pair program "
                f"in pair {i}: "
                f"{[c for c, ok in zip(VERDICT_COLUMNS, eq) if not ok]}",
            )
        if i == 0:
            check_pair_against_host(
                world, policy, picks[0], (out_i, out_e),
                np.asarray(acc), np.asarray(telem), args.oracle_sample,
            )
        del out_i, out_e
    check(
        np.array_equal(np.asarray(acc_k), np.asarray(acc)),
        "persistent counters differ from the per-pair program's",
    )
    check(
        np.array_equal(np.asarray(telem_k), np.asarray(telem)),
        "persistent telemetry differs from the per-pair program's",
    )
    say(
        f"fused: pairs={k} tuples={k * batch} persistent_launch_s="
        f"{persist_s:.4f} per_pair_s={[round(w, 6) for w in pair_walls]} "
        f"persistent_equal_to_per_pair=true counters_total="
        f"{int(np.asarray(acc).sum())}"
    )


def check_pair_against_host(
    world, policy, picks, outs, acc, telem, n_sample: int
) -> None:
    """One pair's device outputs against the host: a sample of every
    tuple-level decision against the composed oracle, and the pair's
    counters and telemetry against the host fold of its columns."""
    from cilium_tpu.engine.hostpath import composed_oracle
    from cilium_tpu.engine.oracle import MATCH_L3, MATCH_L4, MATCH_L4_WILD
    from cilium_tpu.telemetry import telemetry_from_outputs

    pool = world.pool
    id_table = np.asarray(policy.id_table)
    kg = policy.l4_meta.shape[2]
    host_acc = np.zeros(acc.shape, np.uint64)
    host_telem = np.zeros(telem.shape, np.uint64)
    rng = np.random.default_rng(SEED + 3)
    for direction, (p, out) in enumerate(zip(picks, outs)):
        cols = {c: np.asarray(getattr(out, c)) for c in VERDICT_COLUMNS}
        rows = rng.choice(len(p), size=n_sample // 2, replace=False)
        allow, proxy, sec, stages = composed_oracle(
            world.octx, world.states, pool, list(p[rows]),
            return_stages=True,
        )
        # the headline programs emit the peer's identity INDEX
        sec_ident = id_table[
            np.minimum(cols["sec_id"][rows], len(id_table) - 1)
        ]
        for name, got, want in (
            ("allowed", cols["allowed"][rows], allow),
            ("proxy_port", cols["proxy_port"][rows], proxy),
            ("sec_id", sec_ident, sec),
            ("pre_dropped", cols["pre_dropped"][rows],
             stages["pre_drop"]),
            ("ct_result", cols["ct_result"][rows], stages["ct_res"]),
            ("match_kind", cols["match_kind"][rows],
             stages["match_kind"]),
            ("ipcache_miss", cols["ipcache_miss"][rows],
             stages["ipcache_miss"]),
            ("lb_slave>0", cols["lb_slave"][rows] > 0, stages["lb_hit"]),
        ):
            got = np.asarray(got).astype(np.int64)
            want = np.asarray(want).astype(np.int64)
            check(
                np.array_equal(got, want),
                f"fused {name} differs from the composed oracle on "
                f"{int((got != want).sum())} of {len(rows)} sampled "
                f"{('ingress', 'egress')[direction]} tuples",
            )
        host_telem += telemetry_from_outputs(
            out, np.full(len(p), direction, np.int64)
        )
        kind = cols["match_kind"]
        hit_l4 = (kind == MATCH_L4) | (kind == MATCH_L4_WILD)
        hit = hit_l4 | (kind == MATCH_L3)
        col = np.where(
            hit_l4, cols["l4_slot"], kg + cols["sec_id"].astype(np.int64)
        )
        ep = pool["ep_index"][p][hit].astype(np.int64)
        np.add.at(host_acc, (ep, direction, col[hit]), 1)
    check(
        np.array_equal(host_telem, telem.astype(np.uint64)),
        "device telemetry differs from the host fold of the pair's "
        "columns",
    )
    check(
        np.array_equal(host_acc, acc.astype(np.uint64)),
        "device counters differ from the host fold of the pair's columns",
    )
    say(
        f"fused: pair 0 vs composed oracle on {n_sample} sampled tuples: "
        f"equal; counters ({int(host_acc.sum())} hits) and telemetry "
        f"equal to the host fold of {sum(len(p) for p in picks)} tuples"
    )


# ---------------------------------------------------------------------------
# phase: per-chip failure domain (four chips)
# ---------------------------------------------------------------------------


def mesh_phase(world, devs) -> None:
    """The fused pipeline over identity-sharded N+1 tables on a (2, 2)
    ("batch", "table") mesh: ServingPlane(fused=True) batches through
    ChipFailoverRouter.dispatch_flows, healthy and with chip 1 failing
    every dispatch.  Every column is compared with the single-chip
    fused program and, on a sample, with the composed host oracle."""
    import jax

    from cilium_tpu import faultinject
    from cilium_tpu.engine.datapath import FlowBatch, datapath_step
    from cilium_tpu.engine.failover import ChipFailoverRouter
    from cilium_tpu.engine.hostpath import composed_oracle
    from cilium_tpu.replay import _ep_index_of
    from cilium_tpu.resilience import ChipBreakerBank
    from cilium_tpu.serve import ServingPlane

    d = world.daemon
    mesh = jax.sharding.Mesh(
        np.array(devs).reshape(2, 2), ("batch", "table")
    )
    check(
        {dv.id for dv in mesh.devices.flat} == {dv.id for dv in devs}
        and len(devs) == 4,
        f"the mesh must span all four chips: {mesh.devices}",
    )
    router = ChipFailoverRouter(
        mesh, world.tables.policy,
        bank=ChipBreakerBank(recovery_timeout=0.05, failure_threshold=1),
    )
    router.attach_datapath(world.tables)
    # bench.build_config5 keeps the services and the prefilter outside
    # the daemon, so the router serves the world's tables as attached
    # instead of republishing the daemon's own datapath world
    d.attach_mesh_router(router, auto_publish=False)
    chip_bytes = {
        int(c): int(b) for c, b in router.dp_store.chip_bytes().items()
    }
    check(
        sorted(chip_bytes) == sorted(dv.id for dv in devs)
        and min(chip_bytes.values()) > 0,
        f"datapath tables did not land on every chip: {chip_bytes}",
    )
    say(f"mesh: grid={router.ordinals.tolist()} chip_bytes={chip_bytes}")

    plane = ServingPlane(d, fused=True)
    d.serving = plane
    plane.start()
    batch = plane.batch_size
    picks = np.random.default_rng(SEED + 4).integers(
        0, len(world.pool["saddr"]), size=SERVE_BATCHES * batch
    )
    rec = pool_records(world, picks, with_identity=False)
    cols = {
        "ep_index": _ep_index_of(rec, dict(world.index)),
        "saddr": rec["saddr"], "daddr": rec["daddr"],
        "sport": rec["sport"].astype(np.int32),
        "dport": rec["dport"].astype(np.int32),
        "proto": rec["proto"].astype(np.int32),
        "direction": rec["direction"].astype(np.int32),
        "is_fragment": rec["is_fragment"].astype(bool),
    }

    # the reference: the single-chip fused program on chip 0
    single = jax.device_put(world.tables, devs[0])
    ref = datapath_step(single, FlowBatch.from_numpy(**cols))
    ref = {c: np.asarray(getattr(ref, c)) for c in VERDICT_COLUMNS}
    rows = np.random.default_rng(SEED + 5).choice(
        len(picks), size=2048, replace=False
    )
    allow, proxy, sec = composed_oracle(
        world.octx, world.states, world.pool, list(picks[rows])
    )
    for name, want in (
        ("allowed", allow), ("proxy_port", proxy), ("sec_id", sec)
    ):
        check(
            np.array_equal(ref[name][rows].astype(np.int64),
                           want.astype(np.int64)),
            f"single-chip fused {name} differs from the composed oracle",
        )

    def run_leg(label: str) -> None:
        t0 = time.perf_counter()
        results = submit_two_tenants(plane, rec, 2 * SERVE_BATCHES)
        wall = time.perf_counter() - t0
        for field in ("allowed", "match_kind", "proxy_port"):
            got = concat(results, field)
            check(
                np.array_equal(got, ref[field].astype(got.dtype)),
                f"{label}: served {field} differs from the single-chip "
                f"fused program",
            )
        one = router.dispatch_flows(**cols)
        check(not one.degraded, f"{label}: router folded on the host")
        for c in VERDICT_COLUMNS:
            got = np.asarray(getattr(one.verdicts, c))
            bad = np.nonzero(got != ref[c])[0]
            check(
                len(bad) == 0,
                f"{label}: router {c} differs from the single-chip fused "
                f"program on {len(bad)} of {len(got)} flows (first: "
                f"router {got[bad[:4]].tolist()} single-chip "
                f"{ref[c][bad[:4]].tolist()})",
            )
        say(
            f"mesh {label}: flows={len(picks)} batch_class={batch} "
            f"served_wall_s={wall:.4f} all {len(VERDICT_COLUMNS)} columns "
            f"equal to the single-chip fused program; "
            f"replica_hits={router.stats.replica_hits} "
            f"rerouted_batches={router.stats.rerouted_batches}"
        )

    run_leg("healthy")
    hits_before = router.stats.replica_hits
    faultinject.arm("engine.dispatch", "raise:chip=1")
    try:
        run_leg("chip-1-out")
    finally:
        faultinject.disarm("engine.dispatch")
    check(
        router.stats.replica_hits > hits_before,
        "the chip-out leg never served a gather from a replica",
    )
    check(router.stats.degraded_batches == 0, "the router degraded a batch")
    plane.stop()
    d.serving = None


# ---------------------------------------------------------------------------
# fallbacks and report
# ---------------------------------------------------------------------------


def check_no_fallback(d) -> None:
    """A run that passed through a resilience fallback has not shown
    the chip working."""
    from cilium_tpu import faultinject
    from cilium_tpu.metrics import registry as metrics

    counters = {
        "daemon.degraded_batches": d.degraded_batches,
        "degraded_batches_total": metrics.degraded_batches_total.get(),
        "publish_fallback_total": metrics.publish_fallback_total.get(),
        "dispatch_retries_total": metrics.dispatch_retries_total.get(),
        # non-zero once a device table publication failed and the
        # daemon dispatched host arrays instead
        "device_publish_retry_at": d._device_publish_retry_at,
    }
    say(f"fallback counters: {json.dumps(counters)}")
    check(
        not any(counters.values()),
        f"a fallback path served part of the run: {counters}",
    )
    check(not faultinject.any_armed(), "a fault site is still armed")


def report(devs, cache_dir: str, cache_warm: bool) -> None:
    from cilium_tpu.metrics import registry as metrics

    compile_s = {
        labels[0]: round(v, 3)
        for labels, v in sorted(
            metrics.jit_compile_seconds.snapshot().items()
        )
    }
    peak = {
        dv.id: dv.memory_stats()["peak_bytes_in_use"] for dv in devs
    }
    say(f"compile_seconds_by_site={json.dumps(compile_s)}")
    say(
        f"device_kind={devs[0].device_kind} peak_bytes_in_use={peak} "
        f"compile_cache={cache_dir} cache_warm_at_start={cache_warm}"
    )


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="1: world + served path + fused datapath; 4: world + the "
        "per-chip failure domain on a 2x2 mesh, nothing else",
    )
    opts = ap.parse_args()

    devs = require_tpu(opts.chips)[: opts.chips]
    dev = devs[0]

    import bench
    from cilium_tpu import faultinject
    from cilium_tpu.compile_cache import enable_compile_cache

    check(not faultinject.any_armed(), "fault sites armed at start")
    cache_dir = enable_compile_cache()
    cache_warm = os.path.isdir(cache_dir) and bool(os.listdir(cache_dir))
    say(
        f"platform={dev.platform} device_kind={dev.device_kind} "
        f"devices={len(devs)} chips_used={opts.chips}"
    )

    args = bench.build_parser().parse_args([])
    t0 = time.perf_counter()
    world = build_world(args)
    phases = {"build_s": time.perf_counter() - t0}
    if opts.chips == 1:
        t0 = time.perf_counter()
        served_phase(world)
        phases["served_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        fused_phase(world, args, dev)
        phases["fused_s"] = time.perf_counter() - t0
    else:
        t0 = time.perf_counter()
        mesh_phase(world, devs)
        phases["mesh_s"] = time.perf_counter() - t0
    check_no_fallback(world.daemon)
    rounded = {k: round(v, 3) for k, v in phases.items()}
    say(f"phase_seconds={json.dumps(rounded)}")
    report(devs, cache_dir, cache_warm)
    print(
        json.dumps(
            {
                "ok": True,
                "device": {
                    "platform": dev.platform,
                    "kind": dev.device_kind,
                    "count": opts.chips,
                },
            }
        ),
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
