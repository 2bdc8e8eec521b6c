"""The `l7replay` loop: the `replay` loop's closed loop with the L7
stage (engine.datapath.PersistentPairDispatcher(l7=...)).

Each tuple carries one of its pool flow's requests: a launch stages
its pairs and, beside each, a u32 [2, B] plane of request ids (pool
row * requests per flow + request) into the device request table
(l7.fleet.pack_requests, built once at set-up from the world's
requests).  Every launch runs the fused L3/L4 program and then the L7
program over its outputs; counters, telemetry and the L7 counts are
drained to the host before the next launch.  Host calls run under
jax.profiler.TraceAnnotation spans named `l7replay.<call>`.

Traffic keys: the `replay` loop's (`pairs_per_launch`,
`tuples_per_direction`, `ring`, `picks`, `ct_seed`, `trace_seconds`)
and `requests_per_flow`, how many of each flow's requests the request
ids draw from (at most the world's).
"""

from __future__ import annotations

import gc
import inspect
import json
import time
from functools import partial
from types import SimpleNamespace

import numpy as np

from benchmark import world as W

L7_COLS = ("l7_allowed", "allowed")


def build(world, traffic: dict, seed: int, say):
    from cilium_tpu.engine.datapath import PersistentPairDispatcher

    if "l7" not in inspect.signature(PersistentPairDispatcher).parameters:
        raise SystemExit(
            "the l7replay loop needs PersistentPairDispatcher(l7=...): "
            "this program has no L7 stage on its persistent launch path"
        )
    if traffic["ct_seed"] and not getattr(world, "ct_seeded", False):
        say(f"conntrack seeded: {W.seed_conntrack(world)} entries in "
            f"{world.timings['ct_replay_s']:.3f} s replay + "
            f"{world.timings['ct_compile_s']:.3f} s snapshot")
        world.ct_seeded = True
    return L7Replay(world, traffic, seed, say)


def request_table(world, fleet):
    """The program's request table of the world's requests, row
    pool row * n + j for request j of a flow."""
    from cilium_tpu.l7.fleet import pack_requests
    from cilium_tpu.l7.kafka import KafkaRequest

    flat = [r for reqs in world.requests for r in reqs]
    return pack_requests(
        fleet,
        [(m.encode(), p.encode(), h.encode()) for m, p, h, _, _ in flat],
        [dict(hdrs) or None for _, _, _, hdrs, _ in flat],
        [KafkaRequest(*k) if k else KafkaRequest(kind=0, version=0)
         for *_, k in flat],
    )


def fleet_report(fleet) -> dict:
    """Union DFA state counts per field, whether each strided form
    was built, and the fleet's rule counts."""
    out = {}
    if fleet.http is not None:
        t = fleet.http.tables
        for f in ("method", "path", "host"):
            out[f"{f}_states"] = int(getattr(t, f + "_dfa").n_states)
            out[f"{f}_strided"] = getattr(t, f + "_sdfa") is not None
        out["http_rules"] = int(t.n_rules)
        out["header_constraints"] = int(t.hdr_rules.shape[0])
    if fleet.kafka is not None:
        out["kafka_rules"] = int(fleet.kafka.n_rules)
    return out


def _fold(n_rows, lo, hi, seen, cols, seg):
    import jax
    import jax.numpy as jnp

    words = jnp.asarray(cols).astype(jnp.uint32)  # [C, B]
    seg = seg.astype(jnp.int32)
    mn = jax.vmap(lambda w: jax.ops.segment_min(w, seg, n_rows))(words)
    mx = jax.vmap(lambda w: jax.ops.segment_max(w, seg, n_rows))(words)
    cnt = jax.ops.segment_sum(jnp.ones(seg.shape, jnp.uint32), seg, n_rows)
    return jnp.minimum(lo, mn), jnp.maximum(hi, mx), seen + cnt


class L7Replay:
    window_span = "l7replay.window"

    def __init__(self, world, traffic: dict, seed: int, say) -> None:
        import jax

        from cilium_tpu.engine.datapath import PersistentPairDispatcher
        from cilium_tpu.l7.fleet import L7Stage, compile_fleet_l7

        self.world = world
        self.say = say
        self.k = int(traffic["pairs_per_launch"])
        self.half = int(traffic["tuples_per_direction"])
        self.ct_seeded = bool(traffic["ct_seed"])
        self.n_req = len(world.requests[0])
        draw = int(traffic["requests_per_flow"])
        if not 0 < draw <= self.n_req:
            raise SystemExit(f"requests_per_flow {draw}: the world holds "
                             f"{self.n_req} per flow")
        t0 = time.perf_counter()
        self.fleet = compile_fleet_l7(world.daemon)
        world.timings["fleet_l7_compile_s"] = time.perf_counter() - t0
        say("fleet L7: " + json.dumps(dict(
            fleet_report(self.fleet),
            compile_s=round(world.timings["fleet_l7_compile_s"], 3),
        )))
        t0 = time.perf_counter()
        self.stage = L7Stage(self.fleet, request_table(world, self.fleet))
        world.timings["request_table_s"] = time.perf_counter() - t0
        # [(pairs [K x [2, 4, half]], picks, request ids [K x [2, half]])]
        self.ring = []
        prng = np.random.default_rng(np.random.SeedSequence([seed, 2]))
        kind = traffic["picks"]
        zipf_s = None if kind["kind"] == "uniform" else float(kind["s"])
        for _ in range(int(traffic["ring"])):
            pairs, picks = W.pack_pool_pairs(
                world.pool, prng, self.half, self.k, zipf_s
            )
            reqs = [
                np.stack([
                    rows * self.n_req + prng.integers(0, draw, len(rows))
                    for rows in pick
                ]).astype(np.uint32)
                for pick in picks
            ]
            self.ring.append((pairs, picks, reqs))
        self.tables = jax.device_put(W.headline_tables(world.tables)[0])
        self.pd = PersistentPairDispatcher(
            self.tables, self.k, *self._fresh_carry(),
            site="datapath.persistent", l7=self.stage,
        )
        self.launch_bytes = None  # L7 work per launch (check; l7_work)

    @property
    def tuples_per_launch(self) -> int:
        return self.k * 2 * self.half

    def _fresh_carry(self):
        import jax

        from cilium_tpu.engine.verdict import (
            make_counter_buffers,
            make_telemetry_buffers,
        )

        return (
            jax.device_put(make_counter_buffers(self.world.tables.policy)),
            jax.device_put(make_telemetry_buffers()),
        )

    def _launch(self, entry: int):
        import jax

        pairs, _, reqs = self.ring[entry]
        outs = []
        with jax.profiler.TraceAnnotation("l7replay.dispatch"):
            for pair, req in zip(pairs, reqs):
                outs.extend(self.pd.submit(pair, req))
        with jax.profiler.TraceAnnotation("l7replay.drain"):
            acc = np.asarray(self.pd.acc)
            telem = np.asarray(self.pd.telem)
            l7_counts = np.asarray(self.pd.l7_counts)
        return outs, acc, telem, l7_counts

    def warm(self) -> None:
        """Every ring entry launched once (compiles both programs and
        the per-pair slices), then a fresh carry, and a flush that
        folds the warm-up's L7 counts and restarts them from zero."""
        for entry in range(len(self.ring)):
            self._launch(entry)
        self.pd.acc, self.pd.telem = self._fresh_carry()
        self.pd.flush()
        np.asarray(self.pd.telem)
        gc.collect()

    def run(self, seconds: float) -> SimpleNamespace:
        """Launches until `seconds` have passed; the window ends when
        the last launch's counters, telemetry and L7 counts are on the
        host.  The flush that folds the L7 counts into the metrics
        comes after it."""
        import jax

        entries, telem_deltas = [], []
        last_outs = {}
        prev = np.zeros(np.asarray(self.pd.telem).shape, np.int64)
        acc = l7_counts = None
        with jax.profiler.TraceAnnotation(self.window_span):
            t0 = time.perf_counter()
            t_end = t0 + seconds
            i = 0
            while True:
                entry = i % len(self.ring)
                last_outs.pop(entry, None)
                outs, acc, telem, l7_counts = self._launch(entry)
                t_done = time.perf_counter()
                telem = telem.astype(np.int64)
                telem_deltas.append(telem - prev)
                prev = telem
                entries.append(entry)
                last_outs[entry] = outs
                i += 1
                if t_done >= t_end:
                    break
        self.pd.flush()
        return SimpleNamespace(
            window_s=t_done - t0, entries=entries,
            telem_deltas=telem_deltas, acc=acc, l7_counts=l7_counts,
            last_outs=last_outs, tuples=len(entries) * self.tuples_per_launch,
        )

    # -- correctness ---------------------------------------------------

    def _observed(self, stats):
        """Every tuple of the last launch of each ring entry, reduced
        on the device per (pool row, request): the fused program's
        columns (compare.RowExtremes) and the L7 stage's; once."""
        if getattr(stats, "obs", None) is None:
            import jax
            import jax.numpy as jnp

            from benchmark import compare as C

            n = len(self.world.pool["saddr"]) * self.n_req
            ex = C.RowExtremes(n)
            fold = jax.jit(partial(_fold, n))
            l7 = [(jnp.full((2, n), 0xFFFFFFFF, jnp.uint32),
                   jnp.zeros((2, n), jnp.uint32),
                   jnp.zeros((n,), jnp.uint32)) for _ in range(2)]
            for entry, outs in stats.last_outs.items():
                for (out_i, out_e, l7v), req in zip(outs,
                                                    self.ring[entry][2]):
                    for d, out in ((0, out_i), (1, out_e)):
                        seg = jax.device_put(req[d].astype(np.int32))
                        ex.fold(d, out, seg)
                        cols = jnp.stack([l7v.l7_allowed[d], l7v.allowed[d]])
                        l7[d] = fold(*l7[d], cols, seg)
            stats.last_outs.clear()
            stats.obs = ex.host()
            stats.obs7 = [
                ({c: np.asarray(lo[i]).astype(np.int64)
                  for i, c in enumerate(L7_COLS)},
                 {c: np.asarray(hi[i]).astype(np.int64)
                  for i, c in enumerate(L7_COLS)},
                 np.asarray(seen).astype(np.int64))
                for lo, hi, seen in l7
            ]
        return stats.obs, stats.obs7

    def check(self, stats, control: bool = False) -> SimpleNamespace:
        """Every tuple of the last launch of each ring entry, through
        its (pool row, request), against the reference: the fused
        program's columns as the `replay` loop checks them, and the L7
        verdict and final verdict; each launch's telemetry, the
        window's counters and its drained L7 counts against the
        reference's fold.

        With `control`, the control is put in the program's place: the
        reference with every Headers constraint dropped, which breaks
        the configuration's header guarantee.  Its answers and counts
        go through the same checks."""
        from benchmark import compare as C
        from benchmark import l7_work
        from benchmark import reference as R
        from benchmark.l7gw_reference import L7Reference

        world = self.world
        pool = world.pool
        n_rows, n = len(pool["saddr"]), self.n_req
        obs, obs7 = self._observed(stats)
        t0 = time.perf_counter()
        ref = L7Reference(world)
        ct_keys = (ref.base.conntrack_after_seed(pool) if self.ct_seeded
                   else set())
        cols = ref.base.flows(pool, ct_keys)
        want7 = ref.verdicts(pool, cols)
        pool_x = {k: np.repeat(v, n) for k, v in pool.items()}
        cols_x = {k: np.repeat(v, n) for k, v in cols.items()}
        # how often each (pool row, request) appears in each ring
        # entry, per direction
        weights = [
            [np.bincount(np.concatenate([r[d] for r in reqs]),
                         minlength=n_rows * n) for d in (0, 1)]
            for _, _, reqs in self.ring
        ]
        launches = np.bincount(stats.entries, minlength=len(self.ring))
        total = [sum(launches[e] * weights[e][d] for e in range(len(weights)))
                 for d in (0, 1)]
        telem, acc = stats.telem_deltas, stats.acc.astype(np.int64)
        l7_counts = stats.l7_counts.astype(np.int64)
        got7 = want7
        if control:
            got7 = L7Reference(world, drop_headers=True).verdicts(pool, cols)
            obs = [({**lo, **{c: cols_x[c] for c in C.EXACT}},) * 2 + (s,)
                   for lo, _, s in obs]
            obs7 = [({c: got7[c].astype(np.int64) for c in L7_COLS},) * 2
                    + (s,) for _, _, s in obs7]
            l7_counts = l7_fold(got7, total)
        rows = C.compare_rows(cols_x, obs, pool_x)
        checks = {k: rows[k] for k in
                  ("rows_inconsistent", "rows_wrong", "names_not_one_to_one")}
        for lo, hi, seen in obs7:
            at = np.nonzero(seen > 0)[0]
            for c in L7_COLS:
                checks["rows_inconsistent"] += int(
                    (lo[c][at] != hi[c][at]).sum())
            checks["rows_wrong"] += int(sum(
                lo[c][at] != want7[c][at] for c in L7_COLS).astype(bool).sum())
        row_w = [[w.reshape(n_rows, n).sum(axis=1) for w in e]
                 for e in weights]
        want_telem = [R.telemetry_of(cols, w) for w in row_w]
        if control:
            telem = [want_telem[e] for e in stats.entries]
        checks["launches_telemetry_wrong"] = sum(
            int(not np.array_equal(delta, want_telem[e]))
            for e, delta in zip(stats.entries, telem)
        )
        kg = int(world.tables.policy.l4_meta.shape[2])

        def counters(c):
            return C.expected_counters(
                c, rows["_program"], pool_x, total, stats.acc.shape, kg
            ) % (1 << 32)

        if control:
            acc = counters(cols_x)
        checks["counter_cells_wrong"] = int((counters(cols_x) != acc).sum())
        want_counts = l7_fold(want7, total)
        checks["l7_counts_wrong"] = int(
            (want_counts[:3] % (1 << 32) != l7_counts[:3]).sum())
        checks["l7_overflow_wrong"] = int(abs(want_counts[3] - l7_counts[3]))
        self.launch_bytes = [
            l7_work.launch_bytes(world, want7["redirected"], w)
            for w in weights
        ]
        self.say(
            f"reference{' (control)' if control else ''}: {n_rows} pool "
            f"rows x {n} requests, ct keys {len(ct_keys)}, "
            f"{time.perf_counter() - t0:.2f} s; rows checked "
            f"{int(sum((s > 0).sum() for _, _, s in obs))}; window L7 "
            f"counts {l7_counts.tolist()} (reference "
            f"{want_counts.tolist()})"
        )
        return SimpleNamespace(
            checks={k: (v, 0) for k, v in checks.items()},
            attempted=stats.tuples, failed=0,
            e2e={"verdicts_per_s": stats.tuples / stats.window_s},
        )


def l7_fold(v7: dict, total) -> np.ndarray:
    """(received, forwarded, denied, overflow) over the window: each
    (pool row, request)'s verdicts as often as it was replayed."""
    w = np.asarray(total[0], np.int64) + np.asarray(total[1], np.int64)
    red = v7["redirected"]
    l7 = v7["l7_allowed"].astype(bool)
    return np.asarray([
        int(w[red].sum()), int(w[l7].sum()), int(w[red & ~l7].sum()),
        int(w[red & v7["flagged"]].sum()),
    ], np.int64)
