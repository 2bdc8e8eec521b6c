"""Gather/scatter cost vs table size, plus the hashed-L4-probe
prototype (quarter-select row layout) vs the dense l4_combined gather."""

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
    rng = np.random.default_rng(5)

    print("-- element u32 gather, 2M indices, vs table bytes --", flush=True)
    for mb in (0.5, 2, 8, 32, 128, 512):
        n = int(mb * (1 << 20) / 4)
        tab = rng.integers(0, 1 << 31, size=n).astype(np.uint32)
        idx = rng.integers(0, n, size=B).astype(np.int32)
        f = jax.jit(lambda t, i: t[i])
        dt = timed(f, jax.device_put(tab), jax.device_put(idx))
        print(f"{mb:6.1f} MB: {dt*1e3:6.1f} ms  ({dt/B*1e9:4.1f} ns/el)",
              flush=True)

    print("-- scatter-add u32, 2M indices, vs table bytes --", flush=True)
    for mb in (2, 16, 64):
        n = int(mb * (1 << 20) / 4)
        idx = rng.integers(0, n, size=B).astype(np.int32)

        def f(i):
            acc = jnp.zeros(n, jnp.uint32)
            return acc.at[i].add(1)[:8]

        dt = timed(jax.jit(f), jax.device_put(idx))
        print(f"{mb:6.1f} MB: {dt*1e3:6.1f} ms", flush=True)

    print("-- hashed L4 probe prototype vs dense gather --", flush=True)
    # dense: [E,2,S,W16] u32 ~ 537 MB (bench scale)
    E, S, N = 32, 512, 66048
    W16 = N // 16
    l4c = rng.integers(0, 1 << 31, size=(E, 2, S, W16)).astype(np.uint32)
    ep = rng.integers(0, E, size=B).astype(np.int32)
    dirn = rng.integers(0, 2, size=B).astype(np.int32)
    j = rng.integers(0, S, size=B).astype(np.int32)
    idx = rng.integers(0, N, size=B).astype(np.int32)

    def dense(l4c, ep, dirn, j, idx):
        cm = l4c[ep, dirn, j, idx >> 4]
        exact = ((cm >> (jnp.uint32(16) + (idx & 15).astype(jnp.uint32))) & 1)
        return exact.astype(jnp.uint8)

    dt = timed(jax.jit(dense), *(jax.device_put(x)
                                 for x in (l4c, ep, dirn, j, idx)))
    print(f"dense 537MB probe: {dt*1e3:6.1f} ms", flush=True)

    # hashed: 4.2M entries in 2-word lanes; 16 entries per 32-lane
    # bucket; 4 buckets per 128-lane row
    n_entries = 1 << 22
    n_buckets = 1 << 19  # load ~ 8/16
    rows = np.zeros((n_buckets // 4, 128), np.uint32)
    rows[:, :] = rng.integers(0, 1 << 31, size=rows.shape)
    from cilium_tpu.engine.hashtable import fnv1a_device

    def hashed(rows, ep, dirn, j, idx):
        key = (
            (ep.astype(jnp.uint32) << 27)
            ^ (dirn.astype(jnp.uint32) << 26)
            ^ (j.astype(jnp.uint32) << 17)
            ^ idx.astype(jnp.uint32)
        )
        h = fnv1a_device(key[:, None])
        b = (h & jnp.uint32(n_buckets - 1)).astype(jnp.int32)
        r = rows[b >> 2]  # [B,128]
        q = (b & 3).astype(jnp.int32)
        quarters = r.reshape(-1, 4, 32)
        sel = jnp.sum(
            quarters
            * (jnp.arange(4, dtype=jnp.int32)[None, :, None]
               == q[:, None, None]),
            axis=1,
            dtype=jnp.uint32,
        )  # [B,32]
        keys = sel[:, :16]
        vals = sel[:, 16:]
        hit = keys == key[:, None]
        meta = jnp.sum(jnp.where(hit, vals, 0), axis=1, dtype=jnp.uint32)
        return (jnp.any(hit, axis=1).astype(jnp.uint8), meta)

    dt = timed(jax.jit(hashed), *(jax.device_put(x)
                                  for x in (rows, ep, dirn, j, idx)))
    print(f"hashed 64MB probe: {dt*1e3:6.1f} ms", flush=True)

    # variant: plain 32-lane rows (XLA pads minor dim; does the pad
    # cost show up in gather time?)
    rows32 = np.zeros((n_buckets, 32), np.uint32)
    rows32[:, :] = rng.integers(0, 1 << 31, size=rows32.shape)

    def hashed32(rows32, ep, dirn, j, idx):
        key = (
            (ep.astype(jnp.uint32) << 27)
            ^ (dirn.astype(jnp.uint32) << 26)
            ^ (j.astype(jnp.uint32) << 17)
            ^ idx.astype(jnp.uint32)
        )
        h = fnv1a_device(key[:, None])
        b = (h & jnp.uint32(n_buckets - 1)).astype(jnp.int32)
        sel = rows32[b]  # [B,32]
        keys = sel[:, :16]
        vals = sel[:, 16:]
        hit = keys == key[:, None]
        meta = jnp.sum(jnp.where(hit, vals, 0), axis=1, dtype=jnp.uint32)
        return (jnp.any(hit, axis=1).astype(jnp.uint8), meta)

    dt = timed(jax.jit(hashed32), *(jax.device_put(x)
                                    for x in (rows32, ep, dirn, j, idx)))
    print(f"hashed [CB,32] probe: {dt*1e3:6.1f} ms", flush=True)

    # small port_slot after proto remap: [4*65536] u16 = 512KB
    ps = rng.integers(0, S, size=4 * 65536).astype(np.uint16)
    pr = rng.integers(0, 4, size=B).astype(np.int32)
    dport = rng.integers(0, 65536, size=B).astype(np.int32)

    def small_ps(ps, pr, dport):
        return ps[pr * 65536 + dport]

    dt = timed(jax.jit(small_ps), *(jax.device_put(x)
                                    for x in (ps, pr, dport)))
    print(f"small port_slot: {dt*1e3:6.1f} ms", flush=True)

    # big port_slot (current): [256,65536] u16 = 32MB
    psbig = rng.integers(0, S, size=(256, 65536)).astype(np.uint16)
    proto = rng.choice([6, 17], size=B).astype(np.int32)

    def big_ps(psbig, proto, dport):
        return psbig[proto, dport]

    dt = timed(jax.jit(big_ps), *(jax.device_put(x)
                                  for x in (psbig, proto, dport)))
    print(f"big port_slot: {dt*1e3:6.1f} ms", flush=True)


if __name__ == "__main__":
    main()
