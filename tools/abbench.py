"""Targeted A/B experiments for the fused-datapath hot ops.

Each experiment times two jitted variants of one op on bench-shaped
inputs (2M flows, config5-scale tables) with the pipelined chain
method.  Run on the real TPU.
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def timed(fn, *args, reps=16, outstanding=4):
    import jax

    out = fn(*args)
    jax.block_until_ready(out)
    leaf = jax.tree_util.tree_leaves(out)[0]
    _ = np.asarray(leaf[:4])
    t0 = time.perf_counter()
    outs = []
    for _ in range(reps):
        outs.append(fn(*args))
        if len(outs) > outstanding:
            outs.pop(0)
    leaf = jax.tree_util.tree_leaves(outs[-1])[0]
    _ = np.asarray(leaf[:4])
    return (time.perf_counter() - t0) / reps


def main():
    import jax
    import jax.numpy as jnp

    B = 1 << 21
    rng = np.random.default_rng(3)

    E, S, N = 32, 512, 65536 + 512  # endpoints, l4 slots, identities
    W16 = (N + 15) // 16

    # -- exp 1: lattice gathers, nd vs flattened 1D -------------------------
    port_slot = rng.integers(0, S, size=(256, 65536)).astype(np.uint16)
    l4c = rng.integers(0, 1 << 32, size=(E, 2, S, W16), dtype=np.uint64).astype(
        np.uint32
    )
    ep = rng.integers(0, E, size=B).astype(np.int32)
    dirn = rng.integers(0, 2, size=B).astype(np.int32)
    proto = rng.choice([6, 17], size=B).astype(np.int32)
    dport = rng.integers(0, 65536, size=B).astype(np.int32)
    idx = rng.integers(0, N, size=B).astype(np.int32)

    def lattice_nd(port_slot, l4c, ep, dirn, proto, dport, idx):
        slot16 = port_slot[proto, dport]
        j = slot16.astype(jnp.int32)
        word16 = idx >> 4
        bit16 = (idx & 15).astype(jnp.uint32)
        cm = l4c[ep, dirn, j, word16]
        exact = ((cm >> (jnp.uint32(16) + bit16)) & 1).astype(bool)
        meta = cm & jnp.uint32(0xFFFF)
        return exact, meta

    def lattice_flat(port_slot, l4c, ep, dirn, proto, dport, idx):
        ps = port_slot.reshape(-1)
        slot16 = ps[proto * 65536 + dport]
        j = slot16.astype(jnp.int32)
        word16 = idx >> 4
        bit16 = (idx & 15).astype(jnp.uint32)
        flat = l4c.reshape(-1)
        lin = ((ep * 2 + dirn) * S + j) * W16 + word16
        cm = flat[lin]
        exact = ((cm >> (jnp.uint32(16) + bit16)) & 1).astype(bool)
        meta = cm & jnp.uint32(0xFFFF)
        return exact, meta

    args = [
        jax.device_put(x)
        for x in (port_slot, l4c, ep, dirn, proto, dport, idx)
    ]
    t_nd = timed(jax.jit(lattice_nd), *args)
    t_flat = timed(jax.jit(lattice_flat), *args)
    print(f"lattice nd: {t_nd*1e3:7.1f} ms   flat: {t_flat*1e3:7.1f} ms",
          flush=True)

    # -- exp 2: % vs multiply-shift reduction -------------------------------
    fh = rng.integers(0, 1 << 32, size=B, dtype=np.uint64).astype(np.uint32)
    count = rng.integers(1, 64, size=B).astype(np.int32)

    def with_mod(fh, count):
        return (fh % jnp.maximum(count, 1).astype(jnp.uint32)).astype(
            jnp.int32
        ) + 1

    def with_lemire(fh, count):
        prod = fh.astype(jnp.uint64) * count.astype(jnp.uint64)
        return (prod >> jnp.uint64(32)).astype(jnp.int32) + 1

    a = [jax.device_put(fh), jax.device_put(count)]
    t_mod = timed(jax.jit(with_mod), *a)
    t_lem = timed(jax.jit(with_lemire), *a)
    print(f"mod:       {t_mod*1e3:7.1f} ms   lemire: {t_lem*1e3:6.1f} ms",
          flush=True)

    # -- exp 3: one row gather vs two on the same bucket table --------------
    CB = 1 << 14
    buckets = rng.integers(0, 1 << 32, size=(CB, 128), dtype=np.uint64).astype(
        np.uint32
    )
    b1 = rng.integers(0, CB, size=B).astype(np.int32)
    b2 = rng.integers(0, CB, size=B).astype(np.int32)

    def two_gathers(buckets, b1, b2):
        r1 = buckets[b1]
        r2 = buckets[b2]
        return r1.sum(axis=1) + r2.sum(axis=1)

    def one_gather(buckets, b1, b2):
        r1 = buckets[b1]
        return r1.sum(axis=1) * 2

    a = [jax.device_put(buckets), jax.device_put(b1), jax.device_put(b2)]
    t2 = timed(jax.jit(two_gathers), *a)
    t1 = timed(jax.jit(one_gather), *a)
    print(f"2 row gathers: {t2*1e3:6.1f} ms   1: {t1*1e3:6.1f} ms", flush=True)

    # -- exp 4: row width: 128-lane vs 64-lane rows -------------------------
    buckets64 = np.ascontiguousarray(buckets[:, :64])

    def narrow(buckets64, b1):
        return buckets64[b1].sum(axis=1)

    a = [jax.device_put(buckets64), jax.device_put(b1)]
    t64 = timed(jax.jit(narrow), *a)
    print(f"64-lane row gather: {t64*1e3:6.1f} ms", flush=True)

    # -- exp 5: counter scatter vs none -------------------------------------
    acc = np.zeros(E * 2 * S * 4, np.uint32)
    lin = rng.integers(0, len(acc), size=B).astype(np.int32)

    def scatter(acc, lin):
        return acc.at[lin].add(1)

    a = [jax.device_put(acc), jax.device_put(lin)]
    t_sc = timed(jax.jit(scatter, donate_argnums=(0,)), *a)
    print(f"scatter-add: {t_sc*1e3:6.1f} ms", flush=True)

    # -- exp 6: fnv1a hash of 4 words ---------------------------------------
    from cilium_tpu.engine.hashtable import fnv1a_device

    w = rng.integers(0, 1 << 32, size=(B, 4), dtype=np.uint64).astype(
        np.uint32
    )
    a = [jax.device_put(w)]
    t_h = timed(jax.jit(fnv1a_device), *a)
    print(f"fnv1a[4w]: {t_h*1e3:6.1f} ms", flush=True)


if __name__ == "__main__":
    main()
