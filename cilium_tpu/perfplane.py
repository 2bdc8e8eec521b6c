"""Live performance plane: continuous hot-path self-profiling.

Every perf win since the hot/cold split is validated OFFLINE by
the benchmark and the byte model (engine/autotune.hot_bytes_per_tuple);
in production the agent was
blind to its own hot path.  This module is the always-on counterpart:
a low-overhead observability layer riding the existing dispatch seams
— nothing here adds a kernel, a sync, or a lock on the device path.

  * **Phase windows.**  Per coalesced batch, the serve loop feeds the
    pack / dispatch-enqueue / drain / fold / wall durations (lifted
    from AsyncBatchDispatcher's overlap bookkeeping plus the
    drain-side fold timing) into decaying windowed histograms: exact
    nearest-rank p50/p99/max over the last `window` batches AND the
    last `horizon_s` seconds, whichever is smaller — an idle plane's
    stale tail decays out instead of haunting the gauges.

  * **Ingest-starvation detector.**  Wall time the serve loop spends
    waiting with a NONEMPTY queue while NOTHING is in flight on the
    device accumulates into `cilium_serve_ingest_stall_seconds_total`
    — the line-rate-ingest item's headline symptom (the device idles
    because the host trickle-feeds it, not because there is no work).

  * **SLO compliance.**  Per tenant, deadline hit/miss counters plus
    an error-budget burn rate: the windowed miss fraction over the
    class's allowed miss fraction (1 - `objective`, default 0.99) —
    burn > 1 means the tenant is eating budget faster than its class
    allows.

  * **Live byte model.**  The autotune byte model evaluated
    against the PUBLISHED layout stamp and the OBSERVED cache-hit /
    dedup factors (Daemon.perf_snapshot assembles it): effective
    bytes-per-tuple and modeled GB/s as gauges, per-leaf breakdown on
    demand.

  * **Retune history.**  `engine.autotune.online_retune` records
    every layout swap here (trigger, knobs moved, layout stamps
    before/after) — the `/debug/perf` since-cursor surface replays
    what changed and why.

Everything windowed is exported to Prometheus at a bounded cadence
(every `EXPORT_EVERY` batches + at snapshot time), and the plane
accounts its OWN bookkeeping seconds (`overhead_s`) so its overhead
is measured inside the instrumented loop, the tracing_overhead_pct
discipline.

Simulation boundary: on this container the "device" is XLA's CPU
backend — absolute phase durations and modeled GB/s are only
meaningful on real hardware; the tier-1 suite pins the semantics
(window math, reset, stall accounting, SLO ledger, snapshot shape,
and that the plane's numbers agree with a harness's own wall clock).
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Dict, List, Optional

from cilium_tpu.metrics import registry as metrics

# serve-loop phases, in pipeline order.  "device" is the observable
# device-side lower bound (enqueue + drain block); true device-busy
# needs the overlap aggregates (a per-batch sync would cost the very
# overlap this plane observes).
PHASES = ("pack", "dispatch", "drain", "device", "fold", "wall")

EXPORT_EVERY = 16  # batches between Prometheus gauge pushes

_STATS = ("p50", "p99", "max")


class PhaseWindow:
    """Decaying window of raw observations: bounded by COUNT
    (`maxlen` most recent) and by AGE (`horizon_s`) — quantiles are
    exact nearest-rank over what survives both bounds."""

    __slots__ = (
        "_obs", "horizon_s", "count", "total", "lifetime_max",
    )

    def __init__(
        self, maxlen: int = 512, horizon_s: float = 60.0
    ) -> None:
        self._obs: deque = deque(maxlen=maxlen)  # (t, value)
        self.horizon_s = float(horizon_s)
        self.count = 0  # lifetime observations (survives decay)
        self.total = 0.0
        self.lifetime_max = 0.0

    def observe(self, value: float, now: float) -> None:
        self._obs.append((now, value))
        self.count += 1
        self.total += value
        if value > self.lifetime_max:
            self.lifetime_max = value

    def _prune(self, now: float) -> None:
        floor = now - self.horizon_s
        obs = self._obs
        while obs and obs[0][0] < floor:
            obs.popleft()

    def values(self, now: float) -> List[float]:
        self._prune(now)
        return [v for _, v in self._obs]

    def stats(self, now: float) -> Dict[str, float]:
        """{"p50", "p99", "max", "n"} over the decayed window
        (nearest-rank, the WindowedHistogram/quantile_ms estimator)."""
        vals = sorted(self.values(now))
        if not vals:
            return {"p50": 0.0, "p99": 0.0, "max": 0.0, "n": 0}

        def q(p: float) -> float:
            return vals[min(len(vals) - 1, int(p * len(vals)))]

        return {
            "p50": q(0.50),
            "p99": q(0.99),
            "max": vals[-1],
            "n": len(vals),
        }

    def reset(self) -> None:
        self._obs.clear()


class PerfPlane:
    """The daemon's always-on performance plane.  One instance per
    daemon; the serving plane feeds it per batch, the autotuner's
    online re-tune loop reads it for drift and writes its history
    back.  All methods are thread-safe and self-account their cost
    into `overhead_s`."""

    def __init__(
        self, window: int = 512, horizon_s: float = 60.0
    ) -> None:
        self._lock = threading.Lock()
        self.window = int(window)
        self.horizon_s = float(horizon_s)
        self.phases: Dict[str, PhaseWindow] = {
            p: PhaseWindow(window, horizon_s) for p in PHASES
        }
        self.fill = PhaseWindow(window, horizon_s)
        self.queue_delay = PhaseWindow(window, horizon_s)
        # ingest-starvation accumulator: (t, waited) pairs for the
        # windowed fraction + a lifetime total mirroring the counter
        self._stalls = PhaseWindow(window * 4, horizon_s)
        self.stall_seconds_total = 0.0
        # per-tenant SLO ledger: {tenant: {"slo_class", "hits",
        # "misses", "window": deque of 0/1 misses, "objective"}}
        self._slo: Dict[str, dict] = {}
        # monotone batch cursor: the /debug/perf since-cursor —
        # bumps once per observed batch
        self.seq = 0
        self.overhead_s = 0.0
        # throughput: EWMA of valid-tuples/batch-wall (the modeled
        # GB/s multiplier)
        self._vps_ewma: Optional[float] = None
        # retune plumbing (engine.autotune.online_retune)
        self.retunes: deque = deque(maxlen=64)
        self.baseline_p99_ms: Optional[float] = None
        self.last_retune_monotonic: Optional[float] = None
        self.batches_at_retune = 0

    # -- feeding (serve loop) -------------------------------------------------

    def observe_batch(
        self,
        *,
        pack_s: float = 0.0,
        dispatch_s: float = 0.0,
        drain_s: float = 0.0,
        fold_s: float = 0.0,
        wall_s: float = 0.0,
        fill_pct: float = 0.0,
        valid: int = 0,
    ) -> None:
        t0 = time.perf_counter()
        now = time.monotonic()
        with self._lock:
            ph = self.phases
            ph["pack"].observe(pack_s, now)
            ph["dispatch"].observe(dispatch_s, now)
            ph["drain"].observe(drain_s, now)
            ph["device"].observe(dispatch_s + drain_s, now)
            ph["fold"].observe(fold_s, now)
            ph["wall"].observe(wall_s, now)
            self.fill.observe(fill_pct, now)
            if wall_s > 0 and valid > 0:
                vps = valid / wall_s
                self._vps_ewma = (
                    vps
                    if self._vps_ewma is None
                    else 0.8 * self._vps_ewma + 0.2 * vps
                )
            self.seq += 1
            export = self.seq % EXPORT_EVERY == 0
        if export:
            self.export_gauges()
        self.overhead_s += time.perf_counter() - t0

    def observe_queue_delay(self, delay_s: float) -> None:
        t0 = time.perf_counter()
        now = time.monotonic()
        with self._lock:
            self.queue_delay.observe(delay_s, now)
        self.overhead_s += time.perf_counter() - t0

    def note_stall(self, waited_s: float) -> None:
        """Device-idle-while-queue-nonempty wall time (the serve
        loop's coalescing wait with nothing in flight)."""
        if waited_s <= 0:
            return
        t0 = time.perf_counter()
        now = time.monotonic()
        with self._lock:
            self._stalls.observe(waited_s, now)
            self.stall_seconds_total += waited_s
        metrics.serve_ingest_stall_seconds.inc(value=waited_s)
        self.overhead_s += time.perf_counter() - t0

    def note_deadline(
        self,
        tenant: str,
        slo_class: Optional[str],
        hit: bool,
        objective: float = 0.99,
    ) -> None:
        """One completed submission's deadline outcome, against the
        PR 15 slo_classes assignment."""
        t0 = time.perf_counter()
        cls = slo_class or "default"
        with self._lock:
            row = self._slo.get(tenant)
            if row is None:
                row = self._slo[tenant] = {
                    "slo_class": cls,
                    "hits": 0,
                    "misses": 0,
                    "objective": float(objective),
                    "window": deque(maxlen=256),
                }
            row["slo_class"] = cls
            row["objective"] = float(objective)
            if hit:
                row["hits"] += 1
            else:
                row["misses"] += 1
            row["window"].append(0 if hit else 1)
        metrics.serve_slo_deadline_total.inc(
            tenant, cls, "hit" if hit else "miss"
        )
        self.overhead_s += time.perf_counter() - t0

    def note_retune(self, record: dict) -> dict:
        """Append one online re-tune to the history (the since-cursor
        surface) and re-baseline the drift detector at the post-swap
        window."""
        with self._lock:
            record = dict(record)
            record["seq"] = self.seq
            self.retunes.append(record)
            self.last_retune_monotonic = time.monotonic()
            self.batches_at_retune = self.seq
            self.baseline_p99_ms = None  # re-learn after the swap
        return record

    # -- reading --------------------------------------------------------------

    def stall_fraction(self, now: Optional[float] = None) -> float:
        """Stalled fraction of the decay horizon: windowed stall
        seconds over `horizon_s` (1.0 = the device sat idle with a
        nonempty queue for the whole window)."""
        now = time.monotonic() if now is None else now
        with self._lock:
            stalled = sum(self._stalls.values(now))
        return min(1.0, stalled / self.horizon_s)

    def verdicts_per_sec(self) -> float:
        with self._lock:
            return float(self._vps_ewma or 0.0)

    def slo_burn(self, tenant: str) -> float:
        """Error-budget burn rate: windowed miss fraction over the
        class's allowed miss fraction (1 - objective).  > 1.0 = the
        tenant burns budget faster than its SLO class allows."""
        with self._lock:
            row = self._slo.get(tenant)
            if row is None or not row["window"]:
                return 0.0
            miss_rate = sum(row["window"]) / len(row["window"])
            budget = max(1.0 - row["objective"], 1e-9)
        return miss_rate / budget

    def export_gauges(self) -> None:
        """Push every windowed quantile to the Prometheus registry
        (bounded cadence: EXPORT_EVERY batches + snapshot time)."""
        now = time.monotonic()
        with self._lock:
            phase_stats = {
                p: w.stats(now) for p, w in self.phases.items()
            }
            fill_stats = self.fill.stats(now)
            delay_stats = self.queue_delay.stats(now)
            tenants = list(self._slo)
        for p, st in phase_stats.items():
            for stat in _STATS:
                metrics.serve_phase_seconds.set(
                    p, stat, value=st[stat]
                )
        for stat in _STATS:
            metrics.serve_batch_fill_window_pct.set(
                stat, value=fill_stats[stat]
            )
            metrics.serve_queue_delay_window_seconds.set(
                stat, value=delay_stats[stat]
            )
        for tenant in tenants:
            metrics.serve_slo_error_budget_burn.set(
                tenant, value=self.slo_burn(tenant)
            )

    def snapshot(self, since: Optional[int] = None) -> dict:
        """The plane's own state (the daemon layers the byte model /
        HBM / serving snapshot on top — Daemon.perf_snapshot).  With
        `since` (a previously returned `cursor`), `retunes` holds
        only the swaps that landed after it."""
        self.export_gauges()
        now = time.monotonic()
        with self._lock:
            phases = {
                p: {
                    **{
                        k: (v * 1000.0 if k != "n" else v)
                        for k, v in w.stats(now).items()
                    },
                    "total_s": w.total,
                    "count": w.count,
                }
                for p, w in self.phases.items()
            }
            fill = self.fill.stats(now)
            delay = self.queue_delay.stats(now)
            retunes = [
                dict(r)
                for r in self.retunes
                if since is None or r["seq"] > int(since)
            ]
            slo = {
                t: {
                    "slo_class": row["slo_class"],
                    "hits": row["hits"],
                    "misses": row["misses"],
                    "objective": row["objective"],
                }
                for t, row in self._slo.items()
            }
            cursor = self.seq
            overhead = self.overhead_s
            stall_total = self.stall_seconds_total
        for t in slo:
            slo[t]["error_budget_burn"] = self.slo_burn(t)
        return {
            "cursor": cursor,
            "window": self.window,
            "horizon_s": self.horizon_s,
            # phase quantiles in ms (the `top` view's unit); totals
            # in seconds for wall-clock agreement checks
            "phases_ms": phases,
            "batch_fill_pct": fill,
            "queue_delay_ms": {
                k: (v * 1000.0 if k != "n" else v)
                for k, v in delay.items()
            },
            "stall": {
                "seconds_total": stall_total,
                "fraction": self.stall_fraction(now),
            },
            "slo": slo,
            "verdicts_per_sec_ewma": self.verdicts_per_sec(),
            "retunes": retunes,
            "baseline_p99_ms": self.baseline_p99_ms,
            "overhead_s": overhead,
        }

    def reset(self) -> None:
        """The /debug/profile?reset=1 seam: clear every decaying
        window (phases, fill, queue delay, stall fraction, SLO burn
        windows) so before/after experiments don't bleed.  Lifetime
        counters and the retune history survive — they are counters,
        not windows."""
        with self._lock:
            for w in self.phases.values():
                w.reset()
            self.fill.reset()
            self.queue_delay.reset()
            self._stalls.reset()
            for row in self._slo.values():
                row["window"].clear()
            self.baseline_p99_ms = None
        self.export_gauges()


# ---------------------------------------------------------------------------
# `cilium-tpu top` rendering (shared by the CLI and bugtool)
# ---------------------------------------------------------------------------


def render_top(snap: dict) -> str:
    """One terminal frame of the live view: phase breakdown, batch
    fill, tenant SLO burn, stall fraction, modeled bytes.  Pure
    text — the CLI owns the clear-screen escapes."""
    lines: List[str] = []
    serving = snap.get("serving") or {}
    model = snap.get("byte_model") or {}
    lines.append(
        "cilium-tpu top — cursor {cursor}  batches {batches}  "
        "serving_p99 {p99:.2f} ms  vps {vps:,.0f}".format(
            cursor=snap.get("cursor", 0),
            batches=serving.get("batches", 0),
            p99=serving.get("serving_p99_ms", 0.0),
            vps=snap.get("verdicts_per_sec_ewma", 0.0),
        )
    )
    lines.append("")
    lines.append(
        f"{'phase':<10s} {'p50 ms':>10s} {'p99 ms':>10s} "
        f"{'max ms':>10s} {'n':>6s}"
    )
    for p in PHASES:
        st = (snap.get("phases_ms") or {}).get(p) or {}
        lines.append(
            f"{p:<10s} {st.get('p50', 0.0):>10.3f} "
            f"{st.get('p99', 0.0):>10.3f} "
            f"{st.get('max', 0.0):>10.3f} "
            f"{st.get('n', 0):>6d}"
        )
    fill = snap.get("batch_fill_pct") or {}
    delay = snap.get("queue_delay_ms") or {}
    stall = snap.get("stall") or {}
    lines.append("")
    lines.append(
        "batch fill   p50 {p50:6.1f}%  p99 {p99:6.1f}%".format(
            p50=fill.get("p50", 0.0), p99=fill.get("p99", 0.0)
        )
    )
    lines.append(
        "queue delay  p50 {p50:6.2f} ms  p99 {p99:6.2f} ms".format(
            p50=delay.get("p50", 0.0), p99=delay.get("p99", 0.0)
        )
    )
    lines.append(
        "ingest stall {tot:8.3f} s total   {frac:5.1%} of window".format(
            tot=stall.get("seconds_total", 0.0),
            frac=stall.get("fraction", 0.0),
        )
    )
    if model:
        lines.append(
            "byte model   hot {hot:.0f} B/tuple  effective "
            "{eff:.0f} B/tuple  modeled {gbps:.2f} GB/s "
            "(layout {layout})".format(
                hot=model.get("hot_bytes_per_tuple", 0.0),
                eff=model.get("effective_bytes_per_tuple", 0.0),
                gbps=model.get("modeled_gbps", 0.0),
                layout=model.get("layout_stamp", "?"),
            )
        )
    slo = snap.get("slo") or {}
    if slo:
        lines.append("")
        lines.append(
            f"{'tenant':<16s} {'class':<10s} {'hit':>8s} "
            f"{'miss':>8s} {'burn':>8s}"
        )
        for name in sorted(slo):
            row = slo[name]
            lines.append(
                f"{name:<16s} {row.get('slo_class', '-'):<10s} "
                f"{row.get('hits', 0):>8d} "
                f"{row.get('misses', 0):>8d} "
                f"{row.get('error_budget_burn', 0.0):>8.2f}"
            )
    hbm = snap.get("hbm") or {}
    chips = hbm.get("chip_bytes") or {}
    if chips:
        per = "  ".join(
            f"chip{c}={int(b) >> 20}MiB"
            for c, b in sorted(chips.items())
        )
        lines.append("")
        lines.append(f"hbm residency  {per}")
    retunes = snap.get("retunes") or []
    if retunes:
        last = retunes[-1]
        lines.append(
            "last retune  trigger={t} {knobs} @ seq {seq}".format(
                t=last.get("trigger", "?"),
                knobs=last.get("applied", {}),
                seq=last.get("seq", 0),
            )
        )
    return "\n".join(lines) + "\n"
