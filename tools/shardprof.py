"""Per-chip table bytes + shard imbalance under the partition rules.

The identity-sharded layout (compiler/partition.py) only buys
capacity if the per-chip slices stay BALANCED: equal byte slices by
construction, and near-equal hashed-entry loads because identities
spread uniformly by hash.  This tool extends tools/gatherprof.py's
bytes-moved model to the sharded dimension — it dumps, per shard
count:

  * the per-leaf bytes model (sharded leaves divide, replicated ones
    repeat) and the per-chip total vs the replicated layout;
  * the `universe_max_identities` headroom line bench emits;
  * MEASURED per-chip resident bytes from a real partitioned store
    publish on the virtual CPU mesh (both epoch slots);
  * the hashed-row occupied-entry load per shard slice,

and asserts max/min shard skew ≤ --skew-bound (default 1.5×) for
both the measured bytes and the entry loads.

Usage:
    python tools/shardprof.py [--shards 2 4 8] [--identities 8192]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402


def build_world(n_identities: int, n_endpoints: int, n_rules: int):
    """Synthetic fleet at identity-major scale: enough L4 entries
    that the hashed rows dominate, enough identities that the bit
    planes stretch over many words."""
    from cilium_tpu.compiler.tables import compile_map_states
    from cilium_tpu.maps.policymap import (
        EGRESS,
        INGRESS,
        PolicyKey,
        PolicyMapStateEntry,
    )

    rng = np.random.default_rng(11)
    ids = [1, 2] + [256 + i for i in range(n_identities - 2)]
    states = []
    for _ in range(n_endpoints):
        state = {}
        for _ in range(n_rules):
            ident = int(rng.choice(ids))
            if rng.random() < 0.25:
                state[PolicyKey(ident, 0, 0, INGRESS)] = (
                    PolicyMapStateEntry()
                )
            else:
                state[
                    PolicyKey(
                        ident,
                        int(rng.integers(1, 30000)),
                        int(rng.choice([6, 17])),
                        int(rng.integers(0, 2)) and EGRESS or INGRESS,
                    )
                ] = PolicyMapStateEntry()
        states.append(state)
    return compile_map_states(
        states, ids, identity_pad=1024, filter_pad=64
    )


def entry_load_per_shard(rows: np.ndarray, ntp: int):
    """Occupied hashed entries per table-axis shard slice (the key1
    plane marks empty lanes with 0xFFFFFFFF)."""
    e = rows.shape[1] // 3
    occupied = rows[:, e : 2 * e] != np.uint32(0xFFFFFFFF)
    n = rows.shape[0] // ntp
    return [
        int(occupied[i * n : (i + 1) * n].sum()) for i in range(ntp)
    ]


def occupied_load_per_shard(occupied_rows: np.ndarray, ntp: int):
    """Occupied-entry count per shard slice from a per-row occupancy
    mask — the entry-load balance gate for the CT/ipcache/LB planes
    (each family marks empty lanes its own way; callers hand the
    boolean mask)."""
    n = occupied_rows.shape[0] // ntp
    return [
        int(occupied_rows[i * n : (i + 1) * n].sum())
        for i in range(ntp)
    ]


def build_datapath_world(policy, n_identities: int, seed: int = 5):
    """Wrap the policy tables into a FULL DatapathTables at matched
    scale: one /32 ipcache entry per identity (plus a few range
    CIDRs), a half-loaded CT, and a handful of inline LB services —
    the world datapath_bytes_model and the DatapathStore measure."""
    from cilium_tpu.ct.device import compile_ct
    from cilium_tpu.ct.table import CTMap, CTTuple
    from cilium_tpu.engine.datapath import DatapathTables
    from cilium_tpu.ipcache.lpm import (
        build_ipcache,
        specialize_ipcache_to_idx,
    )
    from cilium_tpu.lb.device import compile_lb
    from cilium_tpu.lb.service import L3n4Addr, ServiceManager
    from cilium_tpu.prefilter import build_prefilter

    rng = np.random.default_rng(seed)
    ids = [1, 2] + [256 + i for i in range(n_identities - 2)]
    ipc_map = {}
    for i, num in enumerate(ids):
        ipc_map[
            f"10.{(i >> 16) & 63}.{(i >> 8) & 255}.{i & 255}/32"
        ] = num
    ipc_map["172.16.0.0/12"] = ids[2]
    ipc_map["192.168.0.0/16"] = ids[3]
    ipc = specialize_ipcache_to_idx(build_ipcache(ipc_map), policy)
    ct = CTMap(max_entries=4 * n_identities)
    n_flows = 2 * n_identities
    sa = rng.integers(1, 1 << 31, size=n_flows)
    da = rng.integers(1, 1 << 31, size=n_flows)
    for i in range(n_flows):
        ct.create_best_effort(
            CTTuple(
                int(da[i]), int(sa[i]),
                int(rng.integers(1, 60000)),
                int(rng.integers(1024, 60000)),
                int(rng.choice([6, 17])),
            ),
            int(rng.integers(0, 2)),
            now=0,
        )
    mgr = ServiceManager()
    for s in range(16):
        mgr.upsert(
            L3n4Addr(f"192.168.200.{s + 1}", 80 + s, 6),
            [
                L3n4Addr(f"10.200.{s}.{b + 1}", 8080, 6)
                for b in range(1 + s % 4)
            ],
        )
    return DatapathTables(
        prefilter=build_prefilter(["9.9.9.0/24"]),
        ipcache=ipc,
        ct=compile_ct(ct),
        lb=compile_lb(mgr),
        policy=policy,
    )


def datapath_entry_loads(dtables, ntp: int):
    """{plane: per-shard occupied-entry loads} for each NEWLY
    sharded hashed family (skew gate evidence)."""
    from cilium_tpu.ct.device import (
        ENTRIES_PER_BUCKET as CT_E,
        _EMPTY_W3,
    )
    from cilium_tpu.ipcache.lpm import _EMPTY_IP
    from cilium_tpu.lb.device import _EMPTY_W1, INLINE_SLOT

    out = {}
    ct_rows = np.asarray(dtables.ct.buckets)
    out["ct.buckets"] = occupied_load_per_shard(
        ct_rows[:, 3 * CT_E : 4 * CT_E] != _EMPTY_W3, ntp
    )
    ipc = dtables.ipcache
    per = 32 if ipc.l3_planes else 64
    ip_rows = np.asarray(ipc.buckets)
    out["ipcache.buckets"] = occupied_load_per_shard(
        ip_rows[:, :per] != _EMPTY_IP, ntp
    )
    lb_rows = getattr(dtables.lb, "rows", None)
    if lb_rows is not None:
        lb_rows = np.asarray(lb_rows)
        occ = np.stack(
            [
                lb_rows[:, 1] != _EMPTY_W1,
                lb_rows[:, INLINE_SLOT + 1] != _EMPTY_W1,
            ],
            axis=1,
        )
        out["lb.rows"] = occupied_load_per_shard(occ, ntp)
    return out


def skew(values) -> float:
    lo = min(values)
    return float(max(values)) / float(lo) if lo else float("inf")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shards", type=int, nargs="+", default=[2, 4, 8])
    ap.add_argument("--identities", type=int, default=8192)
    ap.add_argument("--endpoints", type=int, default=8)
    ap.add_argument("--rules", type=int, default=2000)
    ap.add_argument("--skew-bound", type=float, default=1.5)
    ap.add_argument("--hbm-gb", type=float, default=16.0)
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()

    import jax

    from cilium_tpu.compiler import partition
    from cilium_tpu.compiler.delta import tables_nbytes
    from cilium_tpu.engine.sharded import make_partitioned_store

    tables = build_world(
        args.identities, args.endpoints, args.rules
    )
    full = tables_nbytes(tables)
    hbm = int(args.hbm_gb * (1 << 30))
    report = {"replicated_bytes_per_chip": full, "shards": []}
    devs = jax.devices()

    # the WHOLE fused datapath at matched scale (CT/ipcache/LB
    # planes joined the rule layer): model + measured store publish
    dtables = build_datapath_world(tables, args.identities)
    dp_full = sum(
        int(np.asarray(leaf).nbytes)
        for leaf in jax.tree.leaves(dtables)
    )
    report["datapath_replicated_bytes_per_chip"] = dp_full
    report["datapath"] = []

    for ntp in args.shards:
        rows, per_chip_model, replicated = (
            partition.shard_bytes_model(tables, ntp)
        )
        entry = {
            "num_shards": ntp,
            "bytes_per_chip_model": per_chip_model,
            "replicated_leaf_overhead": replicated,
            "universe_max_identities": (
                partition.universe_max_identities(
                    tables, ntp, hbm_bytes=hbm
                )
            ),
            "alltoall_bytes_per_tuple": (
                partition.alltoall_bytes_per_tuple(ntp)
            ),
            "leaves": rows,
        }
        # hashed-entry load balance across the row slices — only when
        # the row count splits evenly; otherwise the rule layer
        # replicates the leaf and there is no split to gate
        hash_rows = np.asarray(tables.l4_hash_rows)
        if hash_rows.shape[0] % ntp == 0:
            loads = entry_load_per_shard(hash_rows, ntp)
            entry["entry_load_per_shard"] = loads
            entry["entry_load_skew"] = round(skew(loads), 3)
        else:
            entry["entry_load_per_shard"] = None
            entry["entry_load_skew"] = None
        # the N+1 replica layout (per-chip failover placement): each
        # replica-rule leaf's chip slice doubles (its own rows + the
        # left neighbour's backup copy) — the HBM price of losing a
        # chip without losing its table rows
        rep_rows, rep_per_chip, rep_overhead = (
            partition.replica_bytes_model(tables, ntp)
        )
        entry["replica_bytes_per_chip_model"] = rep_per_chip
        entry["replica_overhead_per_chip"] = rep_overhead
        # measured per-chip bytes from a real partitioned publish
        if len(devs) % ntp == 0:
            mesh = jax.sharding.Mesh(
                np.array(devs).reshape(len(devs) // ntp, ntp),
                ("batch", "table"),
            )
            store = make_partitioned_store(mesh)
            store.publish(tables)
            per_chip = store.chip_bytes()
            entry["bytes_per_chip_measured"] = dict(
                sorted((str(k), v) for k, v in per_chip.items())
            )
            entry["bytes_skew"] = round(
                skew(list(per_chip.values())), 3
            )
            # ... and from a real N+1 replica publish
            from cilium_tpu.engine.sharded import make_replica_store

            rstore = make_replica_store(mesh)
            rstore.publish(tables)
            entry["replica_bytes_per_chip_measured"] = max(
                rstore.chip_bytes().values()
            )
        report["shards"].append(entry)

        # -- the fused-datapath planes at this shard count -------------
        dp_rows, dp_per_chip, dp_repl, dp_ovh = (
            partition.datapath_bytes_model(dtables, ntp)
        )
        dp_entry = {
            "num_shards": ntp,
            "bytes_per_chip_model": dp_per_chip,
            "replicated_leaf_overhead": dp_repl,
            "replica_overhead_per_chip": dp_ovh,
            "universe_max_identities": (
                partition.datapath_universe_max_identities(
                    dtables, ntp, hbm_bytes=hbm
                )
            ),
            "alltoall_bytes_per_tuple": (
                partition.datapath_alltoall_bytes_per_tuple(
                    ntp,
                    range_classes=len(
                        dtables.ipcache.range_class_plens
                    ),
                )
            ),
            "leaves": [
                r for r in dp_rows
                if not r["leaf"].startswith("policy.")
            ],
            "entry_loads": {},
        }
        for plane, loads in datapath_entry_loads(
            dtables, ntp
        ).items():
            dp_entry["entry_loads"][plane] = {
                "per_shard": loads,
                "skew": round(skew(loads), 3),
                "total": sum(loads),
            }
        if len(devs) % ntp == 0:
            from cilium_tpu.engine.datapath_mesh import (
                DatapathStore,
            )

            mesh = jax.sharding.Mesh(
                np.array(devs).reshape(len(devs) // ntp, ntp),
                ("batch", "table"),
            )
            dstore = DatapathStore(mesh)
            dstore.publish(dtables)
            per_chip = dstore.chip_bytes()
            dp_entry["bytes_per_chip_measured"] = dict(
                sorted((str(k), v) for k, v in per_chip.items())
            )
            dp_entry["bytes_skew"] = round(
                skew(list(per_chip.values())), 3
            )
        report["datapath"].append(dp_entry)

    if args.json:
        print(json.dumps(report))
    else:
        print(
            f"replicated layout: {full / 1e6:.1f} MB on EVERY chip"
        )
        for entry in report["shards"]:
            ntp = entry["num_shards"]
            print(f"--- {ntp} shards ---")
            for r in entry["leaves"]:
                tag = "shard" if r["sharded"] else "repl "
                print(
                    f"  {r['leaf']:15s} {tag} "
                    f"{r['bytes_total'] / 1e6:9.2f} MB total "
                    f"{r['bytes_per_chip'] / 1e6:9.2f} MB/chip"
                )
            print(
                f"  per-chip {entry['bytes_per_chip_model'] / 1e6:.1f}"
                f" MB (repl overhead "
                f"{entry['replicated_leaf_overhead'] / 1e6:.1f} MB), "
                f"universe_max_identities "
                f"{entry['universe_max_identities']:,} @ "
                f"{args.hbm_gb:.0f} GB HBM, alltoall "
                f"{entry['alltoall_bytes_per_tuple']:.0f} B/tuple"
            )
            if entry["entry_load_per_shard"] is not None:
                print(
                    f"  entry load/shard "
                    f"{entry['entry_load_per_shard']}"
                    f" (skew {entry['entry_load_skew']}x)"
                )
            else:
                print(
                    "  entry load/shard: rows indivisible — "
                    "l4_hash_rows replicates at this shard count"
                )
            if "bytes_skew" in entry:
                vals = list(
                    entry["bytes_per_chip_measured"].values()
                )
                print(
                    f"  measured bytes/chip {vals[0] / 1e6:.1f} MB "
                    f"(skew {entry['bytes_skew']}x, both epochs)"
                )
            print(
                f"  N+1 replica layout "
                f"{entry['replica_bytes_per_chip_model'] / 1e6:.1f}"
                f" MB/chip (replica overhead "
                f"{entry['replica_overhead_per_chip'] / 1e6:.1f}"
                f" MB/chip)"
            )

    for entry in report["shards"]:
        if entry["entry_load_skew"] is not None:
            assert entry["entry_load_skew"] <= args.skew_bound, (
                f"{entry['num_shards']}-shard hashed-entry load skew "
                f"{entry['entry_load_skew']}x over the "
                f"{args.skew_bound}x bound"
            )
        if "bytes_skew" in entry:
            assert entry["bytes_skew"] <= args.skew_bound, (
                f"{entry['num_shards']}-shard byte skew over bound"
            )
        # the acceptance bound: per-chip ≤ replicated/num_shards +
        # replicated-leaf overhead — asserted for the model AND the
        # measured resident bytes (one published epoch)
        bound = (
            full // entry["num_shards"]
            + entry["replicated_leaf_overhead"]
        )
        assert entry["bytes_per_chip_model"] <= bound
        if "bytes_per_chip_measured" in entry:
            measured = max(
                entry["bytes_per_chip_measured"].values()
            )
            assert measured <= bound, (
                f"{entry['num_shards']}-shard measured per-chip "
                f"{measured} over the acceptance bound {bound}"
            )
        # N+1 replica acceptance bound: the replica overhead per
        # chip (the backup copies) stays within replicated-bytes/N,
        # so the whole replica layout fits in
        # 2 * replicated-bytes/N + the replicated-leaf overhead
        ntp = entry["num_shards"]
        assert entry["replica_overhead_per_chip"] <= full // ntp, (
            f"{ntp}-shard replica overhead "
            f"{entry['replica_overhead_per_chip']} over "
            f"replicated-bytes/N = {full // ntp}"
        )
        replica_bound = 2 * (full // ntp) + (
            entry["replicated_leaf_overhead"]
        )
        assert (
            entry["replica_bytes_per_chip_model"] <= replica_bound
        )
        if "replica_bytes_per_chip_measured" in entry:
            assert (
                entry["replica_bytes_per_chip_measured"]
                <= replica_bound
            ), (
                f"{ntp}-shard measured replica per-chip "
                f"{entry['replica_bytes_per_chip_measured']} over "
                f"the N+1 bound {replica_bound}"
            )

    # -- fused-datapath acceptance: per-chip bytes ≤ replicated/N +
    # replicated-leaf overhead (2x on the N+1 replica leaves is
    # covered by the replica bound), entry-load skew ≤ bound for
    # every newly sharded hashed family with a meaningful population
    if not args.json:
        print(
            f"datapath replicated: {dp_full / 1e6:.1f} MB on "
            f"EVERY chip"
        )
    for dp_entry in report["datapath"]:
        ntp = dp_entry["num_shards"]
        if not args.json:
            print(f"--- datapath {ntp} shards ---")
            for r in dp_entry["leaves"]:
                tag = "shard" if r["sharded"] else "repl "
                nplus = "+N+1" if r["replicated_n_plus_1"] else ""
                print(
                    f"  {r['leaf']:20s} {tag}{nplus:5s}"
                    f"{r['bytes_total'] / 1e6:9.2f} MB total "
                    f"{r['bytes_per_chip'] / 1e6:9.2f} MB/chip"
                )
            print(
                f"  per-chip "
                f"{dp_entry['bytes_per_chip_model'] / 1e6:.1f} MB, "
                f"universe_max_identities "
                f"{dp_entry['universe_max_identities']:,}, "
                f"alltoall "
                f"{dp_entry['alltoall_bytes_per_tuple']:.0f} B/tuple"
            )
            for plane, row in dp_entry["entry_loads"].items():
                print(
                    f"  {plane:20s} load/shard "
                    f"{row['per_shard']} (skew {row['skew']}x)"
                )
        dp_bound = (
            dp_full // ntp
            + dp_entry["replicated_leaf_overhead"]
            + dp_entry["replica_overhead_per_chip"]
        )
        assert dp_entry["bytes_per_chip_model"] <= dp_bound, (
            f"datapath {ntp}-shard model per-chip "
            f"{dp_entry['bytes_per_chip_model']} over {dp_bound}"
        )
        assert (
            dp_entry["replica_overhead_per_chip"] <= dp_full // ntp
        )
        if "bytes_per_chip_measured" in dp_entry:
            measured = max(
                dp_entry["bytes_per_chip_measured"].values()
            )
            assert measured <= dp_bound, (
                f"datapath {ntp}-shard measured per-chip "
                f"{measured} over {dp_bound}"
            )
        for plane, row in dp_entry["entry_loads"].items():
            # skew gates need a meaningful population: a plane with
            # a handful of entries (the 16-service LB world) is
            # reported but not gated
            if row["total"] >= 64 * ntp:
                assert row["skew"] <= args.skew_bound, (
                    f"datapath {plane} {ntp}-shard entry-load skew "
                    f"{row['skew']}x over {args.skew_bound}x"
                )
    print("shardprof OK")


if __name__ == "__main__":
    main()
