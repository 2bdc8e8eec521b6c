"""Verdict-memoization profile: the Zipf hit-rate curve + dedup
accounting of the two-level memo plane (engine/memo.py) over
bench.py's config-5 world at reduced control-plane scale.

For each skew s the tool replays Zipf(s)-sampled pool flows through
the memoized fused pair program (the fused-pair shape with the
memo plane in front) and reports the steady-state cache hit rate,
the intra-batch dedup factor, and the EFFECTIVE hot bytes gathered
per tuple — autotune.hot_bytes_per_tuple's bytes-moved model divided
by the measured dedup factor — next to the raw number.  Asserts:

  * dedup_factor >= 2 at s=1.1 (the trace-skew shape the dedup level
    exists for must actually collapse the lattice work);
  * ZERO hits on the first batch after a publish boundary (one rule
    added -> delta-scoped regenerate -> fresh epoch stamp): the
    epoch-stamped invalidation can never serve a stale verdict;
  * every memoized batch is bit-identical to the uncached program on
    the allowed column (the full-surface gate lives in
    tests/test_verdict_memo.py; this smoke keeps one cheap check).

Hit-rate ABSOLUTES here describe the sampled distribution, not
production traffic — the simulation boundary README documents.

Usage:
    python tools/cacheprof.py [--rules 500] [--batch 65536]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def build(args, rng):
    import dataclasses

    import jax

    import bench as B
    from cilium_tpu.compiler.tables import split_hot
    from cilium_tpu.engine.datapath import DatapathTables

    d, tables, index, pool, oracle_ctx, timings, ct, mgr = (
        B.build_config5(args, rng)
    )
    tables_hot = jax.device_put(
        dataclasses.replace(tables, policy=split_hot(tables.policy))
    )
    tables = jax.device_put(tables)
    return d, tables, tables_hot, pool


def pair_of(pool, picks_in, picks_eg):
    from cilium_tpu.engine.datapath import pack_flow_records4

    half = len(picks_in)
    pair = np.empty((2, 4, half), np.uint32)
    for row, picks in enumerate((picks_in, picks_eg)):
        pair[row] = pack_flow_records4(
            ep_index=pool["ep_index"][picks],
            saddr=pool["saddr"][picks],
            daddr=pool["daddr"][picks],
            sport=pool["sport"][picks],
            dport=pool["dport"][picks],
            proto=pool["proto"][picks],
            direction=pool["direction"][picks],
            is_fragment=pool["is_fragment"][picks],
        )
    return pair


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rules", type=int, default=500)
    ap.add_argument("--endpoints", type=int, default=8)
    ap.add_argument("--identities", type=int, default=4096)
    ap.add_argument("--pool", type=int, default=5000)
    ap.add_argument("--batch", type=int, default=1 << 16)
    ap.add_argument(
        "--skews", default="0.9,1.1,1.3",
        help="comma-separated Zipf s values for the hit-rate curve",
    )
    ap.add_argument(
        "--warm-batches", type=int, default=3,
        help="batches dispatched before the measured window",
    )
    ap.add_argument(
        "--measure-batches", type=int, default=3,
        help="batches in the steady-state measured window",
    )
    ap.add_argument(
        "--dedup-floor", type=float, default=2.0,
        help="minimum dedup_factor asserted at s=1.1",
    )
    args = ap.parse_args(argv)
    args.oracle_sample = 64

    import jax

    import bench as B
    from cilium_tpu.compiler.tables import tables_layout_version
    from cilium_tpu.engine import autotune as at
    from cilium_tpu.engine import memo as vm
    from cilium_tpu.engine.datapath import (
        datapath_step_accum_pair_telem_packed4_stacked,
    )
    from cilium_tpu.engine.verdict import (
        make_counter_buffers,
        make_telemetry_buffers,
    )

    rng = np.random.default_rng(17)
    d, tables, tables_hot, pool = build(args, rng)
    half = args.batch // 2
    idx_in = np.nonzero(pool["direction"] == 0)[0]
    idx_eg = np.nonzero(pool["direction"] == 1)[0]
    kern = vm.memo_pair_packed4_kernel(rep_cap=half)
    hot_bpt = at.hot_bytes_per_tuple(tables_hot, packed_io=True)

    def stamp(t):
        return (
            int(np.asarray(t.policy.generation)) & 0xFFFFFFFF,
            tables_layout_version(t.policy),
        )

    def dispatch(cache, pair, t_hot=None):
        """One memoized batch + the allowed-column identity check
        against the uncached program.  Returns the host stats row."""
        t_hot = tables_hot if t_hot is None else t_hot
        acc = jax.device_put(make_counter_buffers(tables.policy))
        tel = jax.device_put(make_telemetry_buffers())
        acc_u = jax.device_put(make_counter_buffers(tables.policy))
        tel_u = jax.device_put(make_telemetry_buffers())
        pair_dev = jax.device_put(pair)
        g_i, g_e, acc, tel, rows, h_i, h_e, st = kern(
            t_hot, pair_dev, cache.rows, acc, tel
        )
        r_i, r_e, acc_u, tel_u = (
            datapath_step_accum_pair_telem_packed4_stacked(
                t_hot, pair_dev, acc_u, tel_u
            )
        )
        for got, ref in ((g_i, r_i), (g_e, r_e)):
            assert np.array_equal(
                np.asarray(got.allowed), np.asarray(ref.allowed)
            ), "memoized program diverged from the uncached reference"
        row = cache.account(st)
        assert row["overflow"] == 0, row
        cache.rows = rows
        return row

    def zpair(prng, s):
        return pair_of(
            pool,
            idx_in[B.zipf_picks(prng, len(idx_in), half, s)],
            idx_eg[B.zipf_picks(prng, len(idx_eg), half, s)],
        )

    curve = []
    skews = [float(s) for s in args.skews.split(",")]
    for s in skews:
        prng = np.random.default_rng(int(s * 1000))
        cache = vm.VerdictCache(n_rows=1 << 12)
        cache.ensure(stamp(tables_hot))
        for _ in range(args.warm_batches):
            dispatch(cache, zpair(prng, s))
        hits = tuples = unique = 0
        for _ in range(args.measure_batches):
            row = dispatch(cache, zpair(prng, s))
            hits += row["hits"]
            tuples += row["tuples"]
            unique += row["unique"]
        hit_rate = hits / max(tuples, 1)
        dedup = tuples / max(unique, 1)
        rec = {
            "zipf_s": s,
            "hit_rate": round(hit_rate, 4),
            "dedup_factor": round(dedup, 2),
            "hot_bytes_per_tuple": round(hot_bpt, 1),
            "effective_hot_bytes_per_tuple": round(
                at.effective_hot_bytes_per_tuple(tables_hot, dedup), 1
            ),
        }
        curve.append(rec)
        print(json.dumps(rec), flush=True)
        if abs(s - 1.1) < 1e-9:
            assert dedup >= args.dedup_floor, (
                f"dedup_factor {dedup:.2f} under the "
                f"{args.dedup_floor} floor at s=1.1"
            )

    # --- publish boundary: zero hits across the epoch flush ---------------
    import dataclasses

    from cilium_tpu.compiler.tables import (
        repack_hash_lanes,
        split_hot,
    )

    s = skews[min(1, len(skews) - 1)]
    prng = np.random.default_rng(99)
    cache = vm.VerdictCache(n_rows=1 << 12)
    cache.ensure(stamp(tables_hot))
    warm_pair = zpair(prng, s)
    dispatch(cache, warm_pair)
    row = dispatch(cache, warm_pair)
    assert row["hits"] > 0, "cache did not warm before the publish"

    B.add_one_rule(d, 4391, label_prefix="cacheprof")
    d.regenerate_all("cacheprof publish boundary")
    em = d.endpoint_manager
    em.published_device()
    _, host_pol, _, _ = em.published_with_states()
    lanes = int(np.asarray(tables_hot.policy.l4_hash_rows).shape[1])
    tables_pub = jax.device_put(
        dataclasses.replace(
            tables,
            policy=split_hot(repack_hash_lanes(host_pol, lanes)),
        )
    )
    assert stamp(tables_pub) != stamp(tables_hot), (
        "publish did not change the epoch stamp"
    )
    assert cache.ensure(stamp(tables_pub)), "stamp change did not flush"
    row = dispatch(cache, warm_pair, t_hot=tables_pub)
    assert row["hits"] == 0, (
        f"{row['hits']} hits served across the publish boundary"
    )

    print(
        json.dumps(
            {
                "smoke": "ok",
                "curve": curve,
                "publish_boundary_hits": row["hits"],
                "batch": args.batch,
            }
        ),
        flush=True,
    )
    print("cacheprof OK", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
