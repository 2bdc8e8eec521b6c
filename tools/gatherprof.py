"""Per-leaf gather-byte profile of the fused datapath: hot vs cold.

Builds the bench's config-5 world at reduced control-plane scale and
dumps, per pipeline stage and table leaf, the bytes GATHERED per
tuple by the fused per-direction programs — before (legacy 128-lane
rows, no split) and after (packed hot-plane rows, hot/cold split) —
then asserts the hot plane stays under a byte budget.

The model is cilium_tpu.engine.autotune.hot_gather_profile: the same
accounting bench.py emits as `hot_bytes_per_tuple`, so a regression
here is a regression in the headline's roofline.

Usage:
    python tools/gatherprof.py [--budget-bytes 800] [--rules 500]
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


def profile_tables(tables, packed_io=True):
    from cilium_tpu.engine.autotune import (
        cold_bytes_per_tuple,
        hot_bytes_per_tuple,
        hot_gather_profile,
    )

    return (
        hot_gather_profile(tables, packed_io=packed_io),
        hot_bytes_per_tuple(tables, packed_io=packed_io),
        cold_bytes_per_tuple(tables),
    )


def dump(title, rows, hot, cold):
    print(f"--- {title} ---")
    for r in rows:
        print(
            f"  {r['stage']:8s} {r['leaf']:18s} {r['plane']:4s} "
            f"{r['bytes_per_tuple']:7.1f} B/tuple  {r['note']}"
        )
    print(f"  hot total  {hot:7.1f} B/tuple")
    print(f"  cold total {cold:7.1f} B/tuple")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rules", type=int, default=500)
    ap.add_argument("--endpoints", type=int, default=8)
    ap.add_argument("--identities", type=int, default=4096)
    ap.add_argument("--pool", type=int, default=5000)
    ap.add_argument("--batch", type=int, default=1 << 16)
    ap.add_argument(
        "--budget-bytes", type=float, default=1100.0,
        help="hot-plane bytes-gathered-per-tuple budget (hard "
        "assert) for the SUB-WORD model at default widths: compact "
        "4-word CT rows (256 B), sub-word ipcache value/l3 planes, "
        "packed prefix-class rows, and the 2-word 32-lane hashed L4 "
        "pair (128+128 B, + one 4 B l4_meta proxy gather) land "
        "~1.0 KB/tuple — down from ~2.0 KB packed-unsub-word and "
        "~2.5 KB legacy-unsplit",
    )
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()
    args.oracle_sample = 64

    import dataclasses

    import bench as B
    from cilium_tpu.compiler.tables import (
        repack_hash_lanes,
        split_hot,
    )

    rng = np.random.default_rng(7)
    d, tables, index, pool, oracle_ctx, timings, ct, mgr = (
        B.build_config5(args, rng)
    )

    # BEFORE: legacy 128-lane rows, no hot/cold split
    legacy = dataclasses.replace(
        tables, policy=repack_hash_lanes(tables.policy, 128)
    )
    rows_b, hot_b, cold_b = profile_tables(legacy, packed_io=False)
    # MIDDLE: compiled pack width + hot/cold split + packed4 staging
    packed = dataclasses.replace(
        tables, policy=split_hot(tables.policy)
    )
    rows_a, hot_a, cold_a = profile_tables(packed, packed_io=True)
    # AFTER: the sub-word hot planes (compact L4 / CT / ipcache)
    from cilium_tpu.engine.datapath import subword_datapath_tables

    sub, sub_report = subword_datapath_tables(packed)
    rows_s, hot_s, cold_s = profile_tables(sub, packed_io=True)

    if args.json:
        print(
            json.dumps(
                {
                    "before": {"rows": rows_b, "hot": hot_b,
                               "cold": cold_b},
                    "after": {"rows": rows_a, "hot": hot_a,
                              "cold": cold_a},
                    "subword": {"rows": rows_s, "hot": hot_s,
                                "cold": cold_s,
                                "report": sub_report},
                }
            )
        )
    else:
        dump("before: 128-lane rows, unsplit", rows_b, hot_b, cold_b)
        dump("packed: hot plane + split", rows_a, hot_a, cold_a)
        dump(
            f"sub-word: {sub_report}", rows_s, hot_s, cold_s
        )
        print(
            f"hot-plane reduction: {hot_b + cold_b:.0f} -> "
            f"{hot_a:.0f} -> {hot_s:.0f} B/tuple "
            f"({(hot_b + cold_b) / max(hot_s, 1e-9):.2f}x total)"
        )

    assert hot_s <= args.budget_bytes, (
        f"sub-word hot plane gathers {hot_s:.0f} B/tuple, over the "
        f"{args.budget_bytes:.0f} B budget"
    )
    assert hot_a < hot_b + cold_b, (
        "the split+pack must strictly reduce gathered bytes"
    )
    assert hot_s <= 0.6 * hot_a, (
        f"the sub-word planes must cut the packed model >= 40% "
        f"({hot_a:.0f} -> {hot_s:.0f})"
    )
    assert all(v == "packed" for v in sub_report.values()), (
        f"a default-widths plane refused to pack: {sub_report}"
    )

    # sharded-plane model: per-tuple HOT bytes are unchanged by the
    # fused mesh sharding (each row gather still happens exactly
    # once, on the owning chip); what routing ADDS is the small
    # per-probe psum traffic — priced per shard count so the
    # roofline comparison (gathered bytes vs collective bytes) is
    # explicit for the CT/ipcache/LB planes too
    from cilium_tpu.compiler import partition as pt

    n_classes = len(
        getattr(tables.ipcache, "range_class_plens", ()) or ()
    )
    # shadow second-gather model (the verdict-diff canary plane):
    # a sampled batch re-runs ONLY the lattice gathers against the
    # shadow epoch — the staged batch, the H2D upload, CT/ipcache/LB
    # gathers and every fold are shared with the live dispatch.  At
    # the default 0.1 sample rate the amortized extra bytes must
    # stay under 5% of the hot total (the bench's
    # shadow_eval_overhead_pct gate, priced deterministically here).
    lattice_hot = sum(
        r["bytes_per_tuple"]
        for r in rows_s
        if r["stage"] == "lattice" and r["plane"] == "hot"
    )
    shadow_rate = 0.1
    shadow_bytes = shadow_rate * lattice_hot
    shadow_pct = 100.0 * shadow_bytes / max(hot_s, 1e-9)
    print(
        f"shadow second-gather model: {lattice_hot:.0f} B/tuple "
        f"lattice gathers x rate {shadow_rate} = "
        f"{shadow_bytes:.1f} B/tuple amortized "
        f"({shadow_pct:.1f}% of the {hot_s:.0f} B hot total)"
    )
    assert shadow_pct < 5.0, (
        f"shadow eval at rate {shadow_rate} would add "
        f"{shadow_pct:.1f}% gathered bytes — over the 5% canary "
        f"budget"
    )

    print("sharded fused-datapath collective model:")
    for ns in (1, 4, 8):
        aa = pt.datapath_alltoall_bytes_per_tuple(
            ns, range_classes=n_classes
        )
        print(
            f"  {ns} shards: {aa:5.0f} B/tuple psum traffic "
            f"({100.0 * aa / max(hot_s, 1e-9):.1f}% of the "
            f"{hot_s:.0f} B sub-word hot gathers)"
        )
        assert aa < hot_s / 10, (
            "routed-psum traffic must stay an order of magnitude "
            "below the hot gathers"
        )
    print("gatherprof OK")


if __name__ == "__main__":
    main()
