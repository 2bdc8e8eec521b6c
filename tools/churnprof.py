"""Per-phase profile of the replay_pool churn loop."""

import os
import sys
import time

import numpy as np

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def main():
    import jax

    import bench as B
    from cilium_tpu import replay as R
    from cilium_tpu.engine.datapath import DatapathTables

    rng = np.random.default_rng(7)

    class A:
        rules = 4000
        endpoints = 32
        identities = 65536
        pool = 50000
        batch = 1 << 21
        oracle_sample = 64

    d, tables, index, pool, oracle_ctx, timings, ct, mgr = (
        B.build_config5(A, rng)
    )
    tables = jax.device_put(tables)
    picks = rng.integers(0, A.pool, size=2 * A.batch)
    t0 = time.perf_counter()
    R.replay_pool(tables, pool, picks, batch_size=A.batch, ct_map=ct)
    print(f"seed: {time.perf_counter() - t0:.2f}s", flush=True)

    # instrumented churn pass
    churn_pool = R._churn_fns()[2]
    churn = R._ChurnDriver(ct)
    pool_dev = pool["__device_pack__"]
    picks = rng.integers(0, A.pool, size=4 * A.batch).astype(np.uint32)
    phases = {"step+hdr": 0.0, "drain": 0.0}
    rounds = 0
    t_all = time.perf_counter()
    stats = R.ReplayStats()
    for start in range(0, len(picks), A.batch):
        chunk = picks[start : start + A.batch]
        picks_dev = jax.device_put(chunk)
        first = True
        while True:
            t = DatapathTables(
                prefilter=tables.prefilter, ipcache=tables.ipcache,
                ct=churn.dev_snap, lb=tables.lb, policy=tables.policy,
                tunnel=tables.tunnel,
            )
            t1 = time.perf_counter()
            header_d, intents_d = churn_pool(
                t, pool_dev, picks_dev, len(chunk)
            )
            header = np.asarray(header_d)  # forces the step D2H
            t2 = time.perf_counter()
            remaining = churn.drain(
                header_d, intents_d, stats, len(chunk), first
            )
            t3 = time.perf_counter()
            print(f"  round {rounds}: step+hdr {t2-t1:.3f}s "
                  f"drain {t3-t2:.3f}s k={int(header[0])} "
                  f"remaining={remaining}", flush=True)
            phases["step+hdr"] += t2 - t1
            phases["drain"] += t3 - t2
            rounds += 1
            first = False
            if remaining == 0:
                break
    total = time.perf_counter() - t_all
    print(f"churn: {len(picks)} tuples in {total:.2f}s "
          f"({len(picks)/total/1e6:.2f}M/s), rounds={rounds}", flush=True)
    for k, v in phases.items():
        print(f"  {k}: {v:.2f}s", flush=True)

    # --- delta vs full table publication -----------------------------------
    # one-rule churn through the real control plane: host recompile
    # latency, then the device publish both ways — full upload of
    # every leaf vs the delta-scoped epoch scatter
    from cilium_tpu.compiler.delta import tables_nbytes

    em = d.endpoint_manager

    def one_rule(port):
        B.add_one_rule(d, port, label_prefix="churnprof")
        t0 = time.perf_counter()
        d.regenerate_all("churnprof delta")
        host_ms = (time.perf_counter() - t0) * 1000
        t0 = time.perf_counter()
        em.published_device()
        dev_ms = (time.perf_counter() - t0) * 1000
        return host_ms, dev_ms

    # full-upload comparator: a fresh epoch pays the whole world
    host_tables = em.published()[1]
    t0 = time.perf_counter()
    jax.block_until_ready(jax.device_put(host_tables))
    full_ms = (time.perf_counter() - t0) * 1000
    print(
        f"full upload: {full_ms:.1f} ms "
        f"({tables_nbytes(host_tables) / 1e6:.1f} MB)",
        flush=True,
    )
    em.published_device()  # prime epoch A
    for i, port in enumerate((4401, 4402, 4403, 4404, 4405)):
        host_ms, dev_ms = one_rule(port)
        st = em.last_publish_stats
        print(
            f"delta publish {i}: host recompile {host_ms:.1f} ms, "
            f"device {st.mode} {dev_ms:.1f} ms, "
            f"{st.bytes_h2d / 1e6:.2f} MB shipped",
            flush=True,
        )


if __name__ == "__main__":
    main()
