"""The `replay` loop: the fused datapath, closed loop.

Persistent fused-datapath launches (engine.datapath.
PersistentPairDispatcher) over a ring of host-packed launches; each
launch uploads its pairs, runs, and its counters and telemetry are
drained to the host before the next.  Every call into a layer runs
under a jax.profiler.TraceAnnotation named `replay.<call>`, so a
traced run can tell what the host was doing in each idle gap of the
device.

A loop module is found by the `loop` key of a traffic file
(benchmark/loops/<loop>.py) and gives `build(world, traffic, seed,
say)`, whose object has `window_span`, `warm()`, `run(seconds)` and
`check(stats, control)`.
"""

from __future__ import annotations

import gc
import time
from types import SimpleNamespace

import numpy as np

from benchmark import world as W


def build(world, traffic: dict, seed: int, say):
    if traffic["ct_seed"] and not getattr(world, "ct_seeded", False):
        say(f"conntrack seeded: {W.seed_conntrack(world)} entries in "
            f"{world.timings['ct_replay_s']:.3f} s replay + "
            f"{world.timings['ct_compile_s']:.3f} s snapshot")
        world.ct_seeded = True
    return Replay(world, traffic, seed, say)


class Replay:
    window_span = "replay.window"

    def __init__(self, world, traffic: dict, seed: int, say) -> None:
        import jax

        from cilium_tpu.engine.datapath import PersistentPairDispatcher

        self.world = world
        self.say = say
        self.k = int(traffic["pairs_per_launch"])
        self.half = int(traffic["tuples_per_direction"])
        self.ct_seeded = bool(traffic["ct_seed"])
        self.ring = []  # [(pairs [K x [2, 4, half]], picks)]
        prng = np.random.default_rng(np.random.SeedSequence([seed, 2]))
        kind = traffic["picks"]
        zipf_s = None if kind["kind"] == "uniform" else float(kind["s"])
        for _ in range(int(traffic["ring"])):
            self.ring.append(W.pack_pool_pairs(
                world.pool, prng, self.half, self.k, zipf_s
            ))
        self.tables = jax.device_put(W.headline_tables(world.tables)[0])
        self.pd = PersistentPairDispatcher(
            self.tables, self.k, *self._fresh_carry(),
            site="datapath.persistent",
        )

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

        outs = []
        with jax.profiler.TraceAnnotation("replay.dispatch"):
            for pair in self.ring[entry][0]:
                outs.extend(self.pd.submit(pair))
        with jax.profiler.TraceAnnotation("replay.drain"):
            acc = np.asarray(self.pd.acc)
            telem = np.asarray(self.pd.telem)
        return outs, acc, telem

    def warm(self) -> None:
        """Every ring entry launched once (compiles the program and the
        per-pair slices), then a fresh carry for the window."""
        for entry in range(len(self.ring)):
            self._launch(entry)
        self.pd.acc, self.pd.telem = self._fresh_carry()
        np.asarray(self.pd.telem)
        gc.collect()

    def run(self, seconds: float) -> SimpleNamespace:
        """Launches until `seconds` have passed; the window ends when
        the last launch's counters and telemetry are on the host."""
        import jax

        entries, telem_deltas = [], []
        last_outs = {}
        prev = np.zeros(np.asarray(self.pd.telem).shape, np.int64)
        acc = None
        with jax.profiler.TraceAnnotation(self.window_span):
            t0 = time.perf_counter()
            t_end = t0 + seconds
            i = 0
            while True:
                entry = i % len(self.ring)
                last_outs.pop(entry, None)
                outs, acc, telem = self._launch(entry)
                t_done = time.perf_counter()
                telem = telem.astype(np.int64)
                telem_deltas.append(telem - prev)
                prev = telem
                entries.append(entry)
                last_outs[entry] = outs
                i += 1
                if t_done >= t_end:
                    break
        return SimpleNamespace(
            window_s=t_done - t0, entries=entries,
            telem_deltas=telem_deltas, acc=acc, last_outs=last_outs,
            tuples=len(entries) * self.tuples_per_launch,
        )

    # -- correctness ---------------------------------------------------

    def _observed(self, stats):
        """Every tuple of the last launch of each ring entry, reduced
        on the device per pool row (compare.RowExtremes); once."""
        if getattr(stats, "obs", None) is None:
            import jax

            from benchmark import compare as C

            ex = C.RowExtremes(len(self.world.pool["saddr"]))
            for entry, outs in stats.last_outs.items():
                for (out_i, out_e), picks in zip(outs, self.ring[entry][1]):
                    for d, out in ((0, out_i), (1, out_e)):
                        ex.fold(d, out,
                                jax.device_put(picks[d].astype(np.int32)))
            stats.last_outs.clear()
            stats.obs = ex.host()
        return stats.obs

    def check(self, stats, control: bool = False) -> SimpleNamespace:
        """Every tuple of the last launch of each ring entry, through
        its pool row, against the reference; each launch's telemetry
        and the window's counters against the reference's fold.

        With `control`, the control is put in the program's place: the
        reference with the conntrack stage skipped (every flow NEW),
        which breaks the configuration's conntrack guarantee.  Its
        answers, telemetry and counters go through the same checks."""
        from benchmark import compare as C
        from benchmark import reference as R

        world = self.world
        pool = world.pool
        n_rows = len(pool["saddr"])
        obs = self._observed(stats)
        t0 = time.perf_counter()
        ref = R.Reference(world)
        ct_keys = (ref.conntrack_after_seed(pool) if self.ct_seeded
                   else set())
        cols = ref.flows(pool, ct_keys)
        # how often each pool row appears in each ring entry, per direction
        weights = [
            [np.bincount(np.concatenate([p[d] for p in picks]),
                         minlength=n_rows) for d in (0, 1)]
            for _, picks in self.ring
        ]
        telem, acc = stats.telem_deltas, stats.acc.astype(np.int64)
        if control:
            ctl = ref.flows(pool, set())
            obs = control_observed(ctl, obs)
            want = [R.telemetry_of(ctl, w) for w in weights]
            telem = [want[e] for e in stats.entries]
        rows = C.compare_rows(cols, obs, pool)
        checks = {k: rows[k] for k in
                  ("rows_inconsistent", "rows_wrong", "names_not_one_to_one")}
        want_telem = [R.telemetry_of(cols, w) for w in weights]
        checks["launches_telemetry_wrong"] = sum(
            int(not np.array_equal(delta, want_telem[e]))
            for e, delta in zip(stats.entries, telem)
        )
        launches = np.bincount(stats.entries, minlength=len(self.ring))
        total = [sum(launches[e] * weights[e][d] for e in range(len(weights)))
                 for d in (0, 1)]
        kg = int(world.tables.policy.l4_meta.shape[2])

        def counters(c):
            return C.expected_counters(
                c, rows["_program"], pool, total, stats.acc.shape, kg
            ) % (1 << 32)

        if control:
            acc = counters(ctl)
        checks["counter_cells_wrong"] = int((counters(cols) != acc).sum())
        self.say(
            f"reference{' (control)' if control else ''}: {n_rows} pool "
            f"rows, ct keys {len(ct_keys)}, "
            f"{time.perf_counter() - t0:.2f} s; rows checked "
            f"{int(sum((s > 0).sum() for _, _, s in obs))}"
        )
        return SimpleNamespace(
            checks={k: (v, 0) for k, v in checks.items()},
            attempted=stats.tuples, failed=0,
            e2e={"verdicts_per_s": stats.tuples / stats.window_s},
        )


def control_observed(ctl: dict, obs):
    """The program's per-row observations with the control's answers
    in every compared column (the numbering columns, sec_id, l4_slot
    and rev_nat, stay the program's)."""
    from benchmark import compare as C

    out = []
    for lo, _, seen in obs:
        lo = dict(lo)
        for c in C.EXACT:
            lo[c] = ctl[c]
        lo["proxy_port"] = np.where(
            ctl["redirect_key"] >= 0, np.maximum(lo["proxy_port"], 1), 0
        )
        out.append((lo, lo, seen))
    return out
