"""Continuous serving plane: streaming admission, SLO-aware dynamic
batching, and multi-tenant fair dispatch over the async/mesh pipeline.

Every dispatch path before this module is request→one batch→reply:
the headline verdicts/s only materializes when a caller hands the
daemon perfectly sized batches, but real traffic from millions of
users arrives as a stream of SMALL flows.  This is the steady-state
ingest pipeline the ROADMAP names — the continuous-batching insight
of PagedAttention/vLLM (arXiv:2309.06180) and the t5x partitioned
serving loop (arXiv:2203.17189) applied to policy verdicts:

  * **Streaming admission.**  `ServingPlane.submit()` decodes a
    flow-record buffer, runs the daemon's unknown-endpoint filter and
    XDP prefilter (shared `Daemon._prefilter_records` — prefiltered
    drops surface immediately, with the submitting tenant on the
    record), and queues the remainder on the tenant's ingest queue.
    A tenant whose backlog would exceed its bound is SHED, not
    queued: every shed flow carries the canonical Overload drop
    reason exactly once — a flow record naming the tenant, the
    shared shed_flows_total counter, and the per-tenant
    serve_shed_flows_total counter (backpressure is attribution,
    never buffering — the AdmissionGate contract).

  * **SLO-aware dynamic batching.**  One serve loop coalesces queued
    flows into device batches of ONE padded jit class (`batch_size`,
    by default the PR 6 autotuner's choice for the published
    tables): the batch grows while the oldest queued flow's deadline
    still allows a dispatch (an EWMA of recent batch walls estimates
    the cost), and dispatches early — partially filled — the moment
    it doesn't.  serve_batch_fill_pct / serve_queue_delay_seconds /
    serve_deadline_dispatch_total expose the trade.

  * **Multi-tenant fair dispatch.**  Batch composition is deficit
    round robin over the tenant queues (weights from
    `PATCH /config {"tenant_weights": ...}`): each round adds
    weight×quantum to a tenant's deficit and takes that many flows,
    so a noisy tenant flooding 10× cannot starve a compliant one —
    with equal weights each backlogged tenant holds ~half of every
    coalesced batch, and the flood sheds against ITS OWN backlog
    bound.

  * **The existing hot path end to end.**  Coalesced batches ride
    engine.publish.AsyncBatchDispatcher (the host pack of batch N+1
    overlaps device compute of batch N), dispatch through
    `Daemon._dispatch_or_degrade` — the breaker/retry/watchdog
    guard, the verdict-memoization plane, and the ChipFailoverRouter
    when a mesh is attached (the PR 8 remainder: the production
    dispatch loop now routes through the per-chip failure domain) —
    and results demux back to per-submission replies in stream
    order.  The monitor/flow/metrics folds per batch are the same
    calls the one-shot path makes, so verdict, counter, telemetry
    and flow surfaces are bit-identical to `process_flows` on the
    same tuples.

Simulation boundary: on this container the "device" is XLA's CPU
backend — absolute serving_p99_ms / sustained_verdicts_per_sec are
only meaningful on real hardware (the driver's bench box); what the
tier-1 suite pins here is the semantics — bit-identity, fairness
shares, exactly-once shed accounting, zero lost/duplicated
submissions across faults.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

import numpy as np

from cilium_tpu import option, tracing
from cilium_tpu.logging import get_logger
from cilium_tpu.metrics import registry as metrics

log = get_logger("serve")


def quantile_ms(latencies_s, p: float) -> float:
    """The ONE sorted-list latency quantile this plane and its
    harnesses share (serveprof asserts the plane's p99 against the
    harness's — they must be the same computation)."""
    lats = sorted(latencies_s)
    if not lats:
        return 0.0
    return lats[min(len(lats) - 1, int(p * len(lats)))] * 1000.0


def tenant_seed(seed: int, name: str) -> int:
    """Stable per-tenant RNG seed (hash() is randomized per process;
    a storm failure must reproduce under the same --seed)."""
    import zlib

    return seed + (zlib.crc32(name.encode()) & 0xFFFF)


class ServeResult:
    """Per-submission reply handle: verdict columns in the
    submission's own stream order, filled as its spans drain.
    ``shed_mask`` marks flows shed at dispatch time (admission
    gate); ``shed`` marks a whole submission refused at the tenant
    backlog bound.  ``wait()`` blocks until every flow is accounted
    (served or shed)."""

    def __init__(self, n: int, tenant: str) -> None:
        self.n = n
        self.tenant = tenant
        self.allowed = np.zeros(n, bool)
        self.match_kind = np.zeros(n, np.int32)
        self.proxy_port = np.zeros(n, np.int32)
        self.cache_hit = np.zeros(n, bool)
        self.shed_mask = np.zeros(n, bool)
        self.shed = False
        self.degraded_batches = 0
        self.batches = 0
        self.prefiltered = 0
        self.dropped_unknown = 0
        self.queue_delay_s = 0.0  # max span wait in this submission
        self.latency_s: Optional[float] = None
        self.error: Optional[BaseException] = None
        self._event = threading.Event()

    @property
    def done(self) -> bool:
        return self._event.is_set()

    def wait(self, timeout: Optional[float] = None) -> "ServeResult":
        if not self._event.wait(timeout):
            raise TimeoutError(
                f"submission of {self.n} flows not served within "
                f"{timeout}s"
            )
        if self.error is not None:
            raise self.error
        return self

    def verdict_columns(self) -> Dict[str, np.ndarray]:
        return {
            "allowed": self.allowed,
            "match_kind": self.match_kind,
            "proxy_port": self.proxy_port,
        }


class _Submission:
    __slots__ = (
        "rec", "tenant", "n", "cursor", "served", "t_enqueue",
        "deadline", "result",
    )

    def __init__(self, rec, tenant, deadline, result) -> None:
        self.rec = rec
        self.tenant = tenant
        self.n = len(rec["ep_id"])
        self.cursor = 0  # flows handed to a batch plan
        self.served = 0  # flows accounted at drain (served or shed)
        self.t_enqueue = time.monotonic()
        self.deadline = deadline
        self.result = result


class _Tenant:
    __slots__ = (
        "name", "weight", "queue", "backlog", "deficit",
        "admitted", "shed", "dispatched", "cache_hits", "served",
        "slo_class",
    )

    def __init__(self, name: str, weight: float = 1.0) -> None:
        self.name = name
        self.weight = float(weight)
        # named SLO class (PATCH /config {"tenant_slo": ...}); None
        # = the plane's default deadline/weight/shed behavior
        self.slo_class: Optional[str] = None
        self.queue: deque = deque()
        self.backlog = 0  # flows queued, not yet planned
        self.deficit = 0.0
        self.admitted = 0
        self.shed = 0
        self.dispatched = 0
        # verdict-cache hits among this tenant's SERVED flows (the
        # cross-tenant memo plane's per-tenant observability)
        self.cache_hits = 0
        self.served = 0


class ServingPlane:
    """The shared ingest queue + serve loop in front of a Daemon.

    One background thread owns batch composition and dispatch; any
    number of submitters feed it concurrently (the REST route's
    thread-per-connection model maps straight onto `submit`).
    """

    def __init__(
        self,
        daemon,
        *,
        batch_size: Optional[int] = None,
        slo_ms: float = 25.0,
        max_tenant_backlog: int = 1 << 16,
        async_depth: Optional[int] = None,
        tenant_weights: Optional[Dict[str, float]] = None,
        quantum: Optional[int] = None,
        fused: bool = False,
        slo_classes: Optional[Dict[str, Dict]] = None,
        tenant_slo: Optional[Dict[str, str]] = None,
    ) -> None:
        self.daemon = daemon
        # fused serving: coalesced batches carry the RAW 5-tuple
        # columns (saddr/daddr/sport ride the staged batch) and
        # dispatch through the attached ChipFailoverRouter's fused
        # datapath plane (router.dispatch_flows) — the FULL pipeline
        # (prefilter + LB/DNAT + CT + ipcache + lattice) served over
        # the partitioned N+1 tables, replica gathers and all.
        # Requires daemon.attach_mesh_router + router.attach_datapath.
        self.fused = bool(fused)
        self.batch_size = int(
            batch_size
            if batch_size is not None
            else self._autotuned_batch_size()
        )
        self.slo_s = float(slo_ms) / 1000.0
        self.max_tenant_backlog = int(max_tenant_backlog)
        self.async_depth = (
            daemon.dispatch_async_depth
            if async_depth is None
            else int(async_depth)
        )
        # DRR quantum (flows per round per unit weight): small
        # enough that one round never hands a single tenant the
        # whole batch, large enough to amortize the loop
        self.quantum = int(
            quantum
            if quantum is not None
            else max(64, self.batch_size // 8)
        )
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._tenants: Dict[str, _Tenant] = {}
        self._weights = dict(tenant_weights or {})
        # named SLO classes: {name: {"deadline_ms", "shed_priority",
        # "weight"}} + the tenant -> class assignment.  A class
        # bundles the deadline the EWMA batch-wall model protects
        # (per-class early dispatch), the DRR weight, and the shed
        # priority (HIGHER numbers shed FIRST under admission-gate
        # pressure).
        self._slo_classes: Dict[str, Dict] = dict(slo_classes or {})
        self._tenant_slo: Dict[str, str] = dict(tenant_slo or {})
        self._stop = False
        self._drain_on_stop = True
        self._thread: Optional[threading.Thread] = None
        # snapshot cache: endpoint-axis LUTs per published version
        self._lut_version = None
        self._luts = None
        # EWMA of recent coalesced-batch walls (pack→drain), the
        # dispatch-cost estimate behind "grow while the deadline
        # allows"; seeded pessimistically at slo/4 so the first
        # batches lean early rather than blow the SLO
        self._batch_wall_ewma: Optional[float] = None
        # rolling submission latencies → serving_p99_ms gauge; the
        # plane keeps its OWN window (the registry histogram is
        # process-global and may mix planes)
        self._completions = 0
        self._latency_window: deque = deque(maxlen=512)
        # stats
        self.batches = 0
        self.flows_served = 0
        self.early_dispatches = 0
        self.fill_sum = 0.0
        self.degraded_batches = 0
        # per-batch tenant composition ({tenant: flows}, newest
        # last): the fairness gate's evidence — batches where two
        # tenants were both backlogged must show the DRR shares
        self.batch_mix: deque = deque(maxlen=1024)
        # the loop's AsyncBatchDispatcher, exposed for the perf
        # plane (overlap aggregates + "anything in flight?" for the
        # ingest-stall detector)
        self._dispatcher = None
        # batch-boundary barriers (run_at_batch_boundary): callables
        # the serve loop runs BETWEEN dispatches, after draining the
        # in-flight overlap batch — the quiesce seam a reshard
        # cutover flips epochs through.  Admission never pauses;
        # queued flows simply land on whichever epoch is live when
        # their batch composes.
        self._barriers: deque = deque()

    # -- construction helpers -------------------------------------------------

    def _autotuned_batch_size(self) -> int:
        """Default device-batch jit class: the PR 6 autotuner's
        cached choice for the published tables' shape class when one
        exists, else a serving-friendly 4096."""
        try:
            from cilium_tpu.engine import autotune

            _, tables, _ = self.daemon.endpoint_manager.published()
            if tables is not None:
                hit = autotune.cached_choice(
                    autotune.shape_class_key(tables)
                )
                if hit is not None and hit.params.get("batch"):
                    return int(hit.params["batch"])
        except Exception:  # pragma: no cover - defensive default
            pass
        return 1 << 12

    # -- lifecycle ------------------------------------------------------------

    def start(self) -> "ServingPlane":
        if self._thread is not None:
            return self
        self._thread = threading.Thread(
            target=self._loop, name="serve-loop", daemon=True
        )
        self._thread.start()
        return self

    def stop(self, drain: bool = True) -> None:
        """Stop the serve loop.  With `drain` (default) every queued
        flow is dispatched first; without, queued flows are shed
        (Overload, exactly once each) so no submission ever hangs."""
        with self._cond:
            self._stop = True
            self._drain_on_stop = drain
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=60.0)
            self._thread = None

    def set_batch_size(self, batch_size: int) -> None:
        """Live batch-class swap (the online re-tune's batch knob):
        applies to batches COMPOSED from now on — an in-flight batch
        keeps the pad size snapshotted into its meta at stage time,
        so a swap never races the pack of a batch planned under the
        old class."""
        with self._cond:
            self.batch_size = int(batch_size)
            self.quantum = max(64, self.batch_size // 8)
            self._cond.notify_all()

    def set_tenant_weights(self, weights: Dict[str, float]) -> None:
        with self._cond:
            self._weights.update(
                {k: float(v) for k, v in weights.items()}
            )
            for name, t in self._tenants.items():
                t.weight = self._resolve_weight(name)

    def set_slo_classes(
        self,
        classes: Dict[str, Dict],
        tenant_slo: Optional[Dict[str, str]] = None,
    ) -> None:
        """Live-apply the named SLO class bundles + tenant
        assignments (PATCH /config): weights re-resolve immediately;
        deadlines apply to submissions from now on."""
        with self._cond:
            self._slo_classes = dict(classes)
            if tenant_slo is not None:
                self._tenant_slo = dict(tenant_slo)
            for name, t in self._tenants.items():
                t.slo_class = self._tenant_slo.get(name)
                t.weight = self._resolve_weight(name)

    def _class_of(self, tenant: str) -> Optional[Dict]:
        cls = self._tenant_slo.get(tenant)
        return self._slo_classes.get(cls) if cls else None

    def _resolve_weight(self, name: str) -> float:
        cls = self._class_of(name)
        if cls is not None and cls.get("weight") is not None:
            return float(cls["weight"])
        return float(self._weights.get(name, 1.0))

    def _shed_priority(self, tenant: str) -> int:
        cls = self._class_of(tenant)
        if cls is None:
            return 0
        return int(cls.get("shed_priority", 0))

    # -- admission ------------------------------------------------------------

    def _tenant(self, name: str) -> _Tenant:
        t = self._tenants.get(name)
        if t is None:
            t = _Tenant(name, self._resolve_weight(name))
            t.slo_class = self._tenant_slo.get(name)
            self._tenants[name] = t
        return t

    def submit(
        self,
        buf: Optional[bytes] = None,
        rec: Optional[dict] = None,
        tenant: str = "default",
        deadline_ms: Optional[float] = None,
        wait: bool = False,
        timeout: Optional[float] = 60.0,
    ) -> ServeResult:
        """Submit one flow-record buffer (or pre-decoded SoA) for a
        tenant.  Non-blocking by default: returns a ServeResult
        handle whose columns fill as the stream serves; `wait=True`
        blocks until the submission completes.  A malformed buffer
        raises ValueError (HTTP 400 at the REST seam); a tenant past
        its backlog bound gets the whole submission shed with
        exactly-once Overload accounting."""
        from cilium_tpu.native import decode_flow_records

        if rec is None:
            rec = decode_flow_records(buf)
        n_raw = len(rec["ep_id"])
        # filter against the submit-time snapshot (the same guards
        # process_flows applies before batching)
        version, _, index, _ = (
            self.daemon.endpoint_manager.published_with_states()
        )
        if index is None:
            index = {}
        local_ident_lut, _ = self._luts_for(version, index)
        known = np.isin(
            rec["ep_id"], np.fromiter(index, dtype=np.int64)
        )
        n_unknown = int((~known).sum())
        if n_unknown:
            rec = {k: v[known] for k, v in rec.items()}
        rec, n_prefiltered = self.daemon._prefilter_records(
            rec, index, local_ident_lut, tenant=tenant,
            trace_id=tracing.current_trace_id(),
        )
        n = len(rec["ep_id"])
        result = ServeResult(n, tenant)
        result.dropped_unknown = n_unknown
        result.prefiltered = n_prefiltered
        if deadline_ms is None:
            # the tenant's named SLO class (when assigned) carries
            # the default per-flow deadline the dynamic batcher's
            # EWMA batch-wall model protects
            cls = self._class_of(tenant)
            deadline_s = (
                float(cls["deadline_ms"]) / 1000.0
                if cls is not None and cls.get("deadline_ms")
                else self.slo_s
            )
        else:
            deadline_s = float(deadline_ms) / 1000.0
        deadline = time.monotonic() + deadline_s
        sub = _Submission(rec, tenant, deadline, result)
        if n == 0:
            result.latency_s = 0.0
            result._event.set()
            return result
        with self._cond:
            if self._stop:
                raise RuntimeError("serving plane is stopped")
            t = self._tenant(tenant)
            if t.backlog + n > self.max_tenant_backlog:
                # backpressure: shed the WHOLE submission, exactly
                # once per flow, against THIS tenant only
                t.shed += n
            else:
                t.queue.append(sub)
                t.backlog += n
                t.admitted += n
                metrics.serve_admitted_flows_total.inc(
                    tenant, value=n
                )
                metrics.serve_queue_depth.set(
                    tenant, value=t.backlog
                )
                self._cond.notify_all()
                sub = None  # queued — not shed below
        if sub is not None:
            self._shed_flows(sub.rec, tenant, 0, n)
            result.shed = True
            result.shed_mask[:] = True
            result.latency_s = 0.0
            result._event.set()
            if wait:
                return result.wait(timeout)
            return result
        if wait:
            return result.wait(timeout)
        return result

    def _shed_flows(
        self, rec, tenant, start, end, gate_counted: bool = False
    ) -> None:
        """Exactly-once Overload accounting for [start, end) of a
        submission's record SoA: the canonical drop counter, the
        shared + per-tenant shed counters, and one flow record per
        flow naming the tenant (capped at ring capacity — the rest
        charge the eviction counter, the capture_batch drop-storm
        rule).  `gate_counted` marks sheds the AdmissionGate's own
        reserve() refusal already charged to shed_total."""
        from cilium_tpu.flow.store import (
            VERDICT_DROPPED,
            FlowRecord,
        )
        from cilium_tpu.monitor.events import (
            DROP_OVERLOAD,
            drop_reason_name,
        )
        from cilium_tpu.replay import _ep_index_of

        n = end - start
        if n <= 0:
            return
        reason = drop_reason_name(DROP_OVERLOAD)
        dirs = rec["direction"][start:end]
        for dirv, dname in ((0, "INGRESS"), (1, "EGRESS")):
            count = int((dirs == dirv).sum())
            if count:
                metrics.drop_count.inc(reason, dname, value=count)
        metrics.shed_flows_total.inc(value=n)
        metrics.serve_shed_flows_total.inc(tenant, value=n)
        if not gate_counted:
            self.daemon.admission.charge_shed(n)
        tracing.add_event(
            "admission.shed", flows=n, tenant=tenant
        )
        store = self.daemon.flow_store
        build = min(n, store.capacity)
        truncated = n - build
        version, _, index, _ = (
            self.daemon.endpoint_manager.published_with_states()
        )
        local_ident_lut, _ = self._luts_for(version, index or {})
        sl = slice(end - build, end)
        ep_idx = _ep_index_of(
            {"ep_id": rec["ep_id"][sl]}, dict(index or {})
        )
        peer = rec["identity"][sl].astype(np.int64)
        local = local_ident_lut[ep_idx]
        dirs = rec["direction"][sl]
        src = np.where(dirs == 0, peer, local)
        dst = np.where(dirs == 0, local, peer)
        ts = time.time()
        records = [
            FlowRecord(
                ts=ts,
                chip=0,
                ep_id=int(rec["ep_id"][sl][i]),
                src_identity=int(src[i]),
                dst_identity=int(dst[i]),
                dport=int(rec["dport"][sl][i]),
                proto=int(rec["proto"][sl][i]),
                direction=int(dirs[i]),
                verdict=VERDICT_DROPPED,
                match_kind=0,
                drop_reason=reason,
                tenant=tenant,
            )
            for i in range(build)
        ]
        store.extend(records)
        store.charge_evicted(truncated)
        metrics.flow_records_captured_total.inc(
            VERDICT_DROPPED, value=n
        )
        metrics.flow_store_evicted.set(value=store.evicted)

    # -- batch composition (SLO-aware + DRR) ----------------------------------

    def _backlog(self) -> int:
        return sum(t.backlog for t in self._tenants.values())

    def _dispatch_estimate(self) -> float:
        return (
            self._batch_wall_ewma
            if self._batch_wall_ewma is not None
            else self.slo_s / 4.0
        )

    def _head_deadline(self) -> float:
        return self._head_deadline_info()[0]

    def _head_deadline_info(self):
        """(deadline, tenant) of the oldest queued flow — the
        submission whose SLO forces an early dispatch; its tenant's
        class labels serve_deadline_dispatch_total."""
        best = (float("inf"), None)
        for t in self._tenants.values():
            if t.queue and t.queue[0].deadline < best[0]:
                best = (t.queue[0].deadline, t.name)
        return best

    def _next_plan(self):
        """Block until a batch should dispatch.  Returns (spans,
        mix, early, early_class) or None at stop-with-empty-queue.
        `spans` is a list of (submission, sub_start, sub_end)
        totaling <= batch_size flows, composed by deficit round
        robin; `early_class` names the SLO class whose deadline
        forced an early dispatch ("default" for unclassed)."""
        with self._cond:
            while True:
                if self._barriers:
                    # a batch-boundary barrier is queued: hand the
                    # loop an empty plan so it reaches the boundary
                    # (flush + run barriers) even when the tenant
                    # queues are idle
                    return [], {}, False, None
                backlog = self._backlog()
                if backlog == 0:
                    if self._stop:
                        return None
                    self._cond.wait(timeout=0.05)
                    continue
                if self._stop or backlog >= self.batch_size:
                    # full batch (or draining): dispatch now
                    return self._compose_locked() + (False, None)
                now = time.monotonic()
                head, head_tenant = self._head_deadline_info()
                latest_start = head - self._dispatch_estimate()
                if now >= latest_start:
                    # SLO-forced early dispatch: growing further
                    # would blow the oldest flow's deadline
                    early_class = (
                        self._tenant_slo.get(head_tenant)
                        or "default"
                        if head_tenant is not None
                        else "default"
                    )
                    return self._compose_locked() + (
                        True, early_class,
                    )
                t_wait = time.monotonic()
                self._cond.wait(
                    timeout=max(
                        0.0005, min(latest_start - now, 0.05)
                    )
                )
                # ingest-starvation accumulator: this wait holds a
                # NONEMPTY queue (the coalescing-grow branch); when
                # nothing is in flight the device sat idle for it —
                # the line-rate-ingest symptom the perf plane counts
                perf = getattr(self.daemon, "perf", None)
                d = self._dispatcher
                if (
                    perf is not None
                    and d is not None
                    and not d._pending
                ):
                    perf.note_stall(time.monotonic() - t_wait)

    def _compose_locked(self):
        """Deficit round robin over the tenant queues: each round
        credits weight×quantum flows, each tenant drains whole or
        partial submissions against its deficit — one noisy tenant
        cannot hold more than its share of a contended batch, and
        flows WITHIN a submission stay in order.  Returns (spans,
        mix) where mix records, per tenant, the flows taken and the
        backlog LEFT BEHIND — the fairness gate's evidence that a
        small share meant a small offer, not starvation."""
        spans: List[Tuple[_Submission, int, int]] = []
        remaining = self.batch_size
        while remaining > 0:
            active = [
                t for t in self._tenants.values() if t.backlog > 0
            ]
            if not active:
                break
            for t in sorted(active, key=lambda x: x.name):
                t.deficit += t.weight * self.quantum
                while t.queue and t.deficit >= 1 and remaining > 0:
                    sub = t.queue[0]
                    take = min(
                        sub.n - sub.cursor,
                        remaining,
                        int(t.deficit),
                    )
                    if take <= 0:
                        break
                    spans.append(
                        (sub, sub.cursor, sub.cursor + take)
                    )
                    sub.cursor += take
                    t.backlog -= take
                    t.deficit -= take
                    t.dispatched += take
                    remaining -= take
                    if sub.cursor == sub.n:
                        t.queue.popleft()
                if not t.queue:
                    # classic DRR: an idle queue keeps no credit
                    t.deficit = 0.0
                metrics.serve_queue_depth.set(
                    t.name, value=t.backlog
                )
        mix: Dict[str, Dict[str, int]] = {}
        for sub, s, e in spans:
            row = mix.setdefault(
                sub.tenant, {"flows": 0, "left": 0}
            )
            row["flows"] += e - s
        for name, row in mix.items():
            row["left"] = self._tenants[name].backlog
        return spans, mix

    # -- the serve loop -------------------------------------------------------

    def _loop(self) -> None:
        from cilium_tpu.engine.publish import AsyncBatchDispatcher

        dispatcher = AsyncBatchDispatcher(
            pack_fn=self._pack,
            dispatch_fn=self._dispatch,
            depth=self.async_depth,
        )
        self._dispatcher = dispatcher
        try:
            while True:
                if self._barriers:
                    # batch boundary: the previous dispatch returned
                    # and the overlap batch drains on ITS epoch
                    # before the barrier runs — in-flight buffers
                    # are never swapped out from under a batch
                    for done in dispatcher.flush():
                        self._complete(*done)
                    while self._barriers:
                        self._run_barrier(self._barriers.popleft())
                plan = self._next_plan()
                if plan is None:
                    break
                spans, mix, early, early_class = plan
                if not spans:
                    continue
                if not self._drain_on_stop and self._stop:
                    # shed instead of dispatching the leftover
                    for sub, s, e in spans:
                        self._shed_span(sub, s, e)
                    continue
                meta = self._stage(spans, mix, early, early_class)
                if meta is None:
                    continue  # whole plan shed at the gate
                for done in dispatcher.submit(
                    (meta,), meta=meta
                ):
                    self._complete(*done)
                # overlap pays only under sustained load: when the
                # queue went idle there is no batch N+1 to pack, so
                # drain the in-flight batch NOW instead of holding
                # its replies hostage to the next arrival
                with self._cond:
                    idle = self._backlog() == 0
                if idle:
                    for done in dispatcher.flush():
                        self._complete(*done)
            for done in dispatcher.flush():
                self._complete(*done)
            # a barrier that raced stop still runs (the stream is
            # quiesced by definition here) so its submitter never
            # hangs on a dead loop
            while self._barriers:
                self._run_barrier(self._barriers.popleft())
        except Exception as loop_exc:  # last-resort guard: nothing
            # may hang — in-flight batches release their admission
            # units and every pending reply errors out instead of
            # blocking its submitter until the REST timeout
            log.exception("serve loop died")
            failed = set()
            for meta2, _res, _exc in dispatcher.flush():
                self.daemon.admission.release(meta2["valid"])
                so = meta2.get("shadow_out")
                if so is not None:  # in-flight shadow sample:
                    # refuse, exactly once
                    self.daemon.shadow.refuse(so.shadow_ticket)
                for sub, _s, _e in meta2["spans"]:
                    failed.add(id(sub))
                    sub.result.error = RuntimeError(
                        f"serve loop died: {loop_exc}"
                    )
                    sub.result._event.set()
            with self._cond:
                self._stop = True  # submit() must refuse from now on
                for t in self._tenants.values():
                    while t.queue:
                        sub = t.queue.popleft()
                        t.backlog -= sub.n - sub.cursor
                        if id(sub) not in failed:
                            sub.result.error = RuntimeError(
                                f"serve loop died: {loop_exc}"
                            )
                            sub.result._event.set()

    @staticmethod
    def _run_barrier(b: dict) -> None:
        try:
            b["result"] = b["fn"]()
        except BaseException as exc:  # surfaced to the submitter
            b["error"] = exc
        finally:
            b["event"].set()

    def run_at_batch_boundary(self, fn, timeout_s: float = 30.0):
        """Run `fn` on the serve loop BETWEEN batches: after the
        in-flight overlap batch drains on its own epoch, before the
        next plan composes.  The epoch-flip seam for a live reshard
        cutover — admission keeps accepting throughout (queued flows
        land on whichever epoch is live when their batch composes);
        nothing is drained except the one overlapped batch that was
        already dispatched.  Returns fn()'s result, re-raising its
        exception.  Called with no loop running (not started, or
        stopped), runs inline — the stream is trivially quiesced."""
        thread = self._thread
        if (
            thread is None
            or not thread.is_alive()
            or threading.current_thread() is thread
        ):
            return fn()
        box = {
            "fn": fn, "event": threading.Event(),
            "result": None, "error": None,
        }
        with self._cond:
            if self._stop and self._backlog() == 0:
                # loop may already be past its final flush
                return fn()
            self._barriers.append(box)
            self._cond.notify_all()
        if not box["event"].wait(timeout=timeout_s):
            raise TimeoutError(
                f"batch-boundary barrier not reached within "
                f"{timeout_s}s"
            )
        if box["error"] is not None:
            raise box["error"]
        return box["result"]

    def _stage(self, spans, mix, early, early_class=None):
        """Concatenate a plan's record slices into one host batch
        dict + bookkeeping meta.  Applies the AdmissionGate with
        SHED-PRIORITY ordering: when the gate refuses the full plan,
        whole tenants shed in DESCENDING shed priority (the SLO
        class bundle; higher numbers shed first, unclassed tenants
        are priority 0) until the remainder fits — so under gate
        pressure a gold-class tenant's flows survive the overload a
        noisy tenant created.  Every shed flow gets exactly-once
        Overload accounting; replies complete with shed_mask set."""
        keep = list(spans)
        shed_spans: List[Tuple[_Submission, int, int]] = []
        while keep:
            n_keep = sum(e - s for _sub, s, e in keep)
            if self.daemon.admission.reserve(n_keep, charge=False):
                break
            with self._lock:
                worst = max(
                    self._shed_priority(sub.tenant)
                    for sub, _s, _e in keep
                )
                shed_now = [
                    sp for sp in keep
                    if self._shed_priority(sp[0].tenant) == worst
                ]
            shed_spans.extend(shed_now)
            keep = [sp for sp in keep if sp not in shed_now]
        for sub, s, e in shed_spans:
            self._shed_span(sub, s, e)
        if not keep:
            return None
        spans = keep
        if shed_spans:
            # the fairness evidence must describe the batch that
            # actually dispatched
            for name in {sub.tenant for sub, _s, _e in shed_spans}:
                row = mix.get(name)
                if row is not None:
                    row["flows"] = sum(
                        e - s
                        for sub, s, e in spans
                        if sub.tenant == name
                    )
        fields = (
            "ep_id", "identity", "dport", "proto",
            "direction", "is_fragment",
        )
        if self.fused:
            # the fused pipeline consumes the raw 5-tuple: the
            # address/sport columns every decoded record already
            # carries ride the staged batch
            fields = fields + ("saddr", "daddr", "sport")
        cols = {
            f: np.concatenate(
                [sub.rec[f][s:e] for sub, s, e in spans]
            )
            for f in fields
        }
        valid = len(cols["ep_id"])
        tenants_col = np.concatenate(
            [
                np.full(e - s, sub.tenant, dtype=object)
                for sub, s, e in spans
            ]
        )
        if early:
            metrics.serve_deadline_dispatch_total.inc(
                early_class or "default"
            )
            self.early_dispatches += 1
        return {
            "spans": spans,
            "mix": mix,
            "cols": cols,
            "tenants": tenants_col,
            "valid": valid,
            "early": early,
            "t_plan": time.monotonic(),
            # the jit/pad class THIS batch dispatches under,
            # snapshotted so a live set_batch_size() swap never
            # races an in-flight batch's pack/redispatch (the max
            # guard covers a shrink landing mid-compose)
            "pad_b": max(self.batch_size, valid),
        }

    def _luts_for(self, version, index):
        with self._lock:
            if self._lut_version != version:
                self._luts = self.daemon._flow_luts(index)
                self._lut_version = version
            return self._luts

    def _pack(self, meta):
        """Host half (overlaps the previous batch's device
        compute): resolve the serving snapshot, translate endpoint
        ids, pad to the jit class, stage the TupleBatch."""
        from cilium_tpu.engine.verdict import TupleBatch
        from cilium_tpu.replay import _ep_index_of

        cols = meta["cols"]
        valid = meta["valid"]
        snap = self.daemon._resolve_serving_tables()
        version, tables, index, host_states = snap
        ep_idx = _ep_index_of(cols, dict(index))
        meta["snap"] = snap
        meta["ep_idx"] = ep_idx
        # endpoints deleted while the flows were QUEUED: the
        # submit-time filter passed them, but this snapshot no
        # longer knows them — _ep_index_of maps them to axis 0,
        # which would evaluate them under (and attribute them to)
        # whatever endpoint sits there.  Mask them: excluded from
        # every fold, reported as dropped_unknown on the reply —
        # the one-shot path's single-snapshot discipline, applied
        # across the queueing gap.
        stale = ~np.isin(
            cols["ep_id"], np.fromiter(index, dtype=np.int64)
        )
        meta["stale"] = stale if stale.any() else None
        if self.fused:
            # the fused router path packs/pads internally (its
            # batch re-split owns the padding); nothing to stage
            return (meta, tables, None)
        b = meta["pad_b"]

        def pad(a, fill=0):
            out = np.full(b, fill, dtype=a.dtype)
            out[:valid] = a
            return out

        batch = TupleBatch.from_numpy(
            ep_index=pad(ep_idx),
            identity=pad(cols["identity"]),
            dport=pad(cols["dport"].astype(np.int32)),
            proto=pad(cols["proto"].astype(np.int32)),
            direction=pad(cols["direction"].astype(np.int32)),
            is_fragment=pad(
                cols["is_fragment"].astype(bool), fill=False
            ),
        )
        return (meta, tables, batch)

    def _dispatch(self, meta, tables, batch):
        """Device half: the daemon's guarded dispatch — breaker +
        retry + watchdog, the memo plane, and the mesh router when
        one is attached (non-blocking enqueue on the single-chip
        path; the drain reads the columns one batch behind).  In
        fused mode the batch goes through the router's FULL fused
        pipeline instead (dispatch_flows: prefilter + LB/DNAT + CT +
        ipcache + lattice over the partitioned N+1 tables)."""
        cols = meta["cols"]
        ep_idx = meta["ep_idx"]
        host_states = meta["snap"][3]
        valid = meta["valid"]
        if self.fused:
            router = self.daemon.mesh_router
            if router is None or router.dp_store is None:
                raise RuntimeError(
                    "fused serving requires an attached mesh "
                    "router with a published datapath epoch "
                    "(attach_mesh_router + attach_datapath)"
                )
            res = router.dispatch_flows(
                ep_index=ep_idx,
                saddr=cols["saddr"],
                daddr=cols["daddr"],
                sport=cols["sport"].astype(np.int32),
                dport=cols["dport"].astype(np.int32),
                proto=cols["proto"].astype(np.int32),
                direction=cols["direction"].astype(np.int32),
                is_fragment=cols["is_fragment"].astype(bool),
            )
            meta["degraded"] = res.degraded
            meta["fused_result"] = res
            return (
                res.verdicts.allowed,
                res.verdicts.match_kind,
                res.verdicts.proxy_port,
                None,
                None,
            )

        def host_args():
            return (
                host_states,
                ep_idx,
                cols["identity"],
                cols["dport"],
                cols["proto"],
                cols["direction"],
                cols["is_fragment"].astype(bool),
            )

        def host_cols():
            return (
                ep_idx,
                cols["identity"],
                cols["dport"],
                cols["proto"],
                cols["direction"],
                cols["is_fragment"].astype(bool),
            )

        out, degraded = self.daemon._dispatch_or_degrade(
            tables, batch, host_args, meta["pad_b"],
            host_cols=host_cols,
        )
        meta["degraded"] = degraded
        # the (tables, batch) pair rides the meta so a drain-time
        # memo overflow refusal can re-dispatch THIS batch uncached
        meta["tables"] = tables
        meta["batch"] = batch
        # a shadow-sampled batch carries its ticket + lazy shadow
        # columns to the drain (cilium_tpu.shadow): folded or
        # refused exactly once in _complete
        meta["shadow_out"] = (
            out
            if getattr(out, "shadow_ticket", None) is not None
            else None
        )
        return (
            out.allowed,
            out.match_kind,
            out.proxy_port,
            getattr(out, "cache_hit", None),
            getattr(out, "cache_stats", None),
        )

    def _shed_span(
        self, sub, s, e, gate_counted: bool = False
    ) -> None:
        """Dispatch-time shed of one span (gate refusal / no-drain
        stop): exactly-once Overload accounting + reply completion
        bookkeeping."""
        self._shed_flows(
            sub.rec, sub.tenant, s, e, gate_counted=gate_counted
        )
        with self._lock:
            t = self._tenants.get(sub.tenant)
            if t is not None:
                t.shed += e - s
                t.dispatched -= e - s  # never reached the device
        sub.result.shed_mask[s:e] = True
        self._span_accounted(sub, e - s)

    def _span_accounted(self, sub, n) -> None:
        sub.served += n
        if sub.served >= sub.n:
            r = sub.result
            now = time.monotonic()
            r.latency_s = now - sub.t_enqueue
            metrics.serve_latency_seconds.observe(r.latency_s)
            with self._lock:
                self._latency_window.append(r.latency_s)
            self._completions += 1
            if self._completions % 32 == 0:
                metrics.serving_p99_ms.set(
                    value=self._window_p99_ms()
                )
            # SLO-class compliance ledger: a submission HITS its
            # deadline only when the reply landed in time with
            # nothing shed — shed flows failed their service even
            # though the reply completed early
            perf_plane = getattr(self.daemon, "perf", None)
            if perf_plane is not None:
                cls = self._tenant_slo.get(sub.tenant)
                bundle = (
                    self._slo_classes.get(cls) if cls else None
                ) or {}
                perf_plane.note_deadline(
                    sub.tenant,
                    cls,
                    hit=(
                        now <= sub.deadline
                        and not bool(r.shed_mask.any())
                        and r.error is None
                    ),
                    objective=float(
                        bundle.get("objective", 0.99)
                    ),
                )
            r._event.set()

    def _complete(self, meta, result, exc) -> None:
        """Drain one coalesced batch: failover on a drain-time
        device death, then the SAME per-batch fold the one-shot path
        runs (monitor events, flow records, metrics), then demux to
        the submissions in stream order."""
        from types import SimpleNamespace

        from cilium_tpu.flow import (
            allow_sample_for_level,
            capture_batch,
        )
        from cilium_tpu.monitor import verdicts_to_events

        cols = meta["cols"]
        spans = meta["spans"]
        valid = meta["valid"]
        ep_idx = meta.get("ep_idx")
        degraded = bool(meta.get("degraded"))
        shadow_out = meta.get("shadow_out")
        shadow_refuse = False
        t_fold0 = time.monotonic()  # the perf plane's fold phase
        try:
            if exc is not None and self.fused:
                # fused mode has no bit-identical host fold (the
                # lattice fold computes a DIFFERENT function than
                # the full pipeline) — error the replies instead of
                # silently serving lattice verdicts as fused ones
                for sub, _s, _e in spans:
                    if not sub.result.done:
                        sub.result.error = exc
                        sub.result._event.set()
                return
            if exc is not None:
                # pack/enqueue/drain failure: the in-flight batch
                # serves from the bit-identical host fold under the
                # breaker, same as the one-shot drain path.  Its
                # shadow columns (if sampled) came from the dead
                # dispatch — refuse the sample cleanly.
                shadow_refuse = True
                from cilium_tpu.engine.hostpath import (
                    lattice_fold_host,
                )
                from cilium_tpu.replay import _ep_index_of

                if self.daemon.verdict_cache is not None:
                    self.daemon.verdict_cache.flush(
                        reason="drain-failure"
                    )
                self.daemon.dispatch_breaker.record_failure(
                    str(exc)
                )
                log.warning(
                    "serve drain failed; serving in-flight batch "
                    "from host path",
                    extra={"fields": {"error": str(exc)}},
                )
                snap = meta.get("snap")
                if snap is None:
                    snap = self.daemon._resolve_serving_tables()
                    meta["snap"] = snap
                host_states = snap[3]
                if ep_idx is None:
                    ep_idx = _ep_index_of(cols, dict(snap[2]))
                    meta["ep_idx"] = ep_idx
                    stale_now = ~np.isin(
                        cols["ep_id"],
                        np.fromiter(snap[2], dtype=np.int64),
                    )
                    meta["stale"] = (
                        stale_now if stale_now.any() else None
                    )
                with tracing.tracer.span(
                    "engine.hostpath", site="engine.hostpath",
                    attrs={"failover": True, "drain": True},
                ):
                    host_out = lattice_fold_host(
                        host_states, ep_idx, cols["identity"],
                        cols["dport"], cols["proto"],
                        cols["direction"],
                        is_fragment=cols["is_fragment"].astype(bool),
                    )
                degraded = True
                self.daemon.degraded_batches += 1
                metrics.degraded_batches_total.inc()
                v = SimpleNamespace(
                    allowed=np.asarray(host_out.allowed)[:valid],
                    match_kind=np.asarray(
                        host_out.match_kind
                    )[:valid],
                    proxy_port=np.asarray(
                        host_out.proxy_port
                    )[:valid],
                    cache_hit=np.zeros(valid, bool),
                )
            else:
                (allowed, match_kind, proxy_port, cache_hit,
                 cache_stats) = result
                v = SimpleNamespace(
                    allowed=np.asarray(allowed)[:valid],
                    match_kind=np.asarray(match_kind)[:valid],
                    proxy_port=np.asarray(proxy_port)[:valid],
                    cache_hit=(
                        np.zeros(valid, bool)
                        if cache_hit is None
                        else np.asarray(cache_hit)[:valid]
                    ),
                )
                # deferred memo fold — THE shared drain seam
                # (Daemon._fold_memo_drain), applied to the
                # COALESCED multi-tenant batch: overflow refusal
                # re-dispatches uncached, hit/miss accounting lands
                # once corrected to the valid prefix
                if cache_stats is not None:

                    def _redispatch():
                        def _ha():
                            return (
                                meta["snap"][3],
                                ep_idx,
                                cols["identity"],
                                cols["dport"],
                                cols["proto"],
                                cols["direction"],
                                cols["is_fragment"].astype(bool),
                            )

                        return self.daemon._dispatch_or_degrade(
                            meta["tables"], meta["batch"], _ha,
                            meta["pad_b"], use_memo=False,
                            shadow_sample=False,
                        )

                    (
                        v, deg2, overflowed,
                    ) = self.daemon._fold_memo_drain(
                        cache_stats, v, valid,
                        int(np.asarray(allowed).shape[0]),
                        _redispatch,
                    )
                    degraded = degraded or deg2
                    shadow_refuse = shadow_refuse or overflowed
            # -- the shared fold (monitor + flow + metrics) -----------
            snap = meta["snap"]
            version, _, index, _ = snap
            local_ident_lut, rev_lut = self._luts_for(
                version, index
            )
            # flows whose endpoint vanished while queued are masked
            # out of every fold (their axis-0 evaluation is
            # meaningless) and reported as dropped_unknown below
            stale = meta.get("stale")
            k = slice(None) if stale is None else ~stale
            opts = option.Config.opts
            verdicts_to_events(
                self.daemon.monitor,
                SimpleNamespace(
                    allowed=v.allowed[k],
                    match_kind=v.match_kind[k],
                    proxy_port=v.proxy_port[k],
                ),
                ep_ids=rev_lut[ep_idx[k]],
                identities=cols["identity"][k],
                dports=cols["dport"][k],
                protos=cols["proto"][k],
                directions=cols["direction"][k],
                verdict_eps=(
                    self.daemon.verdict_notification_endpoints()
                ),
                emit_drops=opts.is_enabled(
                    option.DROP_NOTIFICATION
                ),
                emit_trace=(
                    opts.is_enabled(option.TRACE_NOTIFICATION)
                    and opts.level(option.MONITOR_AGGREGATION)
                    == option.MONITOR_AGG_NONE
                ),
            )
            # full-length identity orientation computed once: the
            # shadow fold consumes the whole valid batch, the
            # capture below slices the stale-masked view `k` off it
            dirs_full = cols["direction"]
            peer_full = cols["identity"].astype(np.int64)
            local_full = local_ident_lut[ep_idx]
            src_full = np.where(dirs_full == 0, peer_full, local_full)
            dst_full = np.where(dirs_full == 0, local_full, peer_full)
            dirs = dirs_full[k]
            # shadow verdict-diff fold (cilium_tpu.shadow), exactly
            # once per sampled coalesced batch; a batch holding
            # vanished-endpoint rows refuses (their axis-0 verdicts
            # would be a meaningless diff)
            diff_col = None
            if shadow_out is not None:
                diff_full = self.daemon._fold_shadow_drain(
                    shadow_out, v, valid,
                    ep_ids=rev_lut[ep_idx],
                    src_identities=src_full,
                    dst_identities=dst_full,
                    dports=cols["dport"],
                    protos=cols["proto"],
                    directions=dirs_full,
                    tenant=meta["tenants"],
                    trace_id="",
                    refuse=shadow_refuse or stale is not None,
                )
                if diff_full is not None:
                    diff_col = diff_full[k]
            capture_batch(
                self.daemon.flow_store,
                ep_ids=rev_lut[ep_idx[k]],
                src_identities=src_full[k],
                dst_identities=dst_full[k],
                dports=cols["dport"][k],
                protos=cols["proto"][k],
                directions=dirs,
                allowed=v.allowed[k],
                match_kind=v.match_kind[k],
                proxy_port=v.proxy_port[k],
                cache_hit=v.cache_hit[k],
                diff_status=diff_col,
                allow_sample=allow_sample_for_level(
                    opts.level(option.MONITOR_AGGREGATION)
                ),
                metrics_registry=metrics,
                tenant=meta["tenants"][k],
            )
            # -- bookkeeping ------------------------------------------
            now = time.monotonic()
            wall = now - meta["t_plan"]
            self._batch_wall_ewma = (
                wall
                if self._batch_wall_ewma is None
                else 0.8 * self._batch_wall_ewma + 0.2 * wall
            )
            self.batches += 1
            self.flows_served += valid
            fill = 100.0 * valid / meta["pad_b"]
            self.fill_sum += fill
            if degraded:
                self.degraded_batches += 1
            metrics.serve_batches_total.inc()
            metrics.serve_batch_fill_pct.set(value=fill)
            self.batch_mix.append(meta["mix"])
            # feed the perf plane: the dispatcher's per-batch phase
            # stamps (meta["perf"], written by the overlap
            # bookkeeping) + this fold's own wall — one call per
            # batch, windows + bounded-cadence gauge export inside
            perf_plane = getattr(self.daemon, "perf", None)
            if perf_plane is not None:
                pp = meta.get("perf") or {}
                perf_plane.observe_batch(
                    pack_s=pp.get("pack_s", 0.0),
                    dispatch_s=pp.get("enqueue_s", 0.0),
                    drain_s=pp.get("drain_s", 0.0),
                    fold_s=now - t_fold0,
                    wall_s=wall,
                    fill_pct=fill,
                    valid=valid,
                )
                # the online re-tune controller rides the serve
                # loop at a bounded cadence (no-op unless the
                # daemon enabled it)
                if self.batches % 64 == 0:
                    self.daemon.maybe_online_retune()
            # -- demux to per-submission replies ----------------------
            # per-tenant verdict-cache hits: the cross-tenant memo
            # plane's observability — batch_mix rows carry each
            # tenant's hit count beside its DRR share (one lock
            # acquisition for the whole batch, not one per span)
            off = 0
            mix = meta["mix"]
            tenant_stats: Dict[str, list] = {}
            for sub, s, e in spans:
                seg_hits = int(
                    v.cache_hit[off : off + (e - s)].sum()
                )
                row = mix.get(sub.tenant)
                if row is not None:
                    row["cache_hits"] = (
                        row.get("cache_hits", 0) + seg_hits
                    )
                agg = tenant_stats.setdefault(sub.tenant, [0, 0])
                agg[0] += seg_hits
                agg[1] += e - s
                off += e - s
            with self._lock:
                for name, (hits, served) in tenant_stats.items():
                    t = self._tenants.get(name)
                    if t is not None:
                        t.cache_hits += hits
                        t.served += served
            off = 0
            for sub, s, e in spans:
                n = e - s
                r = sub.result
                seg = slice(off, off + n)
                if stale is None or not stale[seg].any():
                    r.allowed[s:e] = v.allowed[seg]
                    r.match_kind[s:e] = v.match_kind[seg]
                    r.proxy_port[s:e] = v.proxy_port[seg]
                    r.cache_hit[s:e] = v.cache_hit[seg]
                else:
                    live = ~stale[seg]
                    r.allowed[s:e] = np.where(
                        live, v.allowed[seg], False
                    )
                    r.match_kind[s:e] = np.where(
                        live, v.match_kind[seg], 0
                    )
                    r.proxy_port[s:e] = np.where(
                        live, v.proxy_port[seg], 0
                    )
                    r.cache_hit[s:e] = np.where(
                        live, v.cache_hit[seg], False
                    )
                    r.dropped_unknown += int(stale[seg].sum())
                r.batches += 1
                if degraded:
                    r.degraded_batches += 1
                delay = meta["t_plan"] - sub.t_enqueue
                r.queue_delay_s = max(r.queue_delay_s, delay)
                metrics.serve_queue_delay_seconds.observe(delay)
                if perf_plane is not None:
                    perf_plane.observe_queue_delay(delay)
                off += n
                self._span_accounted(sub, n)
        except Exception as exc2:
            # a fold/demux failure must not leave submitters
            # blocked on replies that will never fill; an
            # unresolved shadow ticket refuses (idempotent — a
            # ticket that already folded stays folded)
            if shadow_out is not None:
                self.daemon.shadow.refuse(shadow_out.shadow_ticket)
            for sub, _s, _e in spans:
                if not sub.result.done:
                    sub.result.error = exc2
                    sub.result._event.set()
            raise
        finally:
            self.daemon.admission.release(valid)

    # -- introspection --------------------------------------------------------

    def reset_window(self) -> None:
        """Zero the rolling serving_p99_ms latency window (the
        /debug/profile?reset=1 seam applied to the serving plane):
        bench segments and before/after experiments must not bleed
        one load shape's tail into the next segment's p99.  The
        daemon's perf-plane windows (phase/fill/queue-delay/stall)
        reset alongside — one seam, every window."""
        with self._lock:
            self._latency_window.clear()
        metrics.serving_p99_ms.set(value=0.0)
        perf_plane = getattr(self.daemon, "perf", None)
        if perf_plane is not None:
            perf_plane.reset()

    def snapshot(self) -> Dict:
        with self._lock:
            tenants = {
                t.name: {
                    "weight": t.weight,
                    "slo_class": t.slo_class,
                    "backlog": t.backlog,
                    "admitted": t.admitted,
                    "dispatched": t.dispatched,
                    "shed": t.shed,
                    "cache_hits": t.cache_hits,
                    "cache_hit_rate": (
                        t.cache_hits / t.served if t.served else 0.0
                    ),
                }
                for t in self._tenants.values()
            }
        return {
            "batch_size": self.batch_size,
            "slo_classes": dict(self._slo_classes),
            "slo_ms": self.slo_s * 1000.0,
            "batches": self.batches,
            "flows_served": self.flows_served,
            "early_dispatches": self.early_dispatches,
            "degraded_batches": self.degraded_batches,
            "avg_batch_fill_pct": (
                self.fill_sum / self.batches if self.batches else 0.0
            ),
            "batch_wall_ewma_ms": (
                (self._batch_wall_ewma or 0.0) * 1000.0
            ),
            "serving_p99_ms": self._window_p99_ms(),
            "tenants": tenants,
        }

    def _window_p99_ms(self) -> float:
        with self._lock:
            lats = list(self._latency_window)
        return quantile_ms(lats, 0.99)


# ---------------------------------------------------------------------------
# sustained-QPS serving bench (open-loop arrivals)
# ---------------------------------------------------------------------------


def run_serve_bench(
    daemon,
    *,
    seconds: float = 5.0,
    qps: float = 200.0,
    flows_per_submit: int = 64,
    tenants: Optional[Dict[str, float]] = None,
    batch_size: int = 1 << 12,
    slo_ms: float = 50.0,
    make_records,
    seed: int = 7,
    poisson: bool = True,
) -> Dict:
    """Open-loop arrival driver over a ServingPlane: `tenants` maps
    tenant name → its share of the offered `qps` (submissions per
    second, each of `flows_per_submit` flows).  Arrivals are Poisson
    (exponential gaps) or uniform; the clock never waits for replies
    — open loop, so queue delay is real.  `make_records(rng, n)`
    returns a decoded record SoA of n flows.

    Returns the serving metrics:
    sustained_verdicts_per_sec, serving_p99_ms, queue-delay and
    batch-fill aggregates, and per-tenant admitted/shed counts."""
    rng = np.random.default_rng(seed)
    plane = daemon.serving_plane(
        batch_size=batch_size, slo_ms=slo_ms
    )
    # segment isolation: a previous bench segment's latency tail
    # must not bleed into THIS run's serving_p99_ms
    plane.reset_window()
    shares = tenants or {"default": 1.0}
    total_share = sum(shares.values())
    results: List[ServeResult] = []
    res_lock = threading.Lock()
    stop_at = time.monotonic() + seconds

    def arrivals(name, share):
        trng = np.random.default_rng(tenant_seed(seed, name))
        rate = qps * share / total_share
        if rate <= 0:
            return
        t_next = time.monotonic()
        while time.monotonic() < stop_at:
            rec = make_records(trng, flows_per_submit)
            r = plane.submit(rec=rec, tenant=name)
            with res_lock:
                results.append(r)
            gap = (
                trng.exponential(1.0 / rate)
                if poisson
                else 1.0 / rate
            )
            t_next += gap
            sleep = t_next - time.monotonic()
            if sleep > 0:
                time.sleep(sleep)
            else:
                t_next = time.monotonic()  # open loop: never bunch

    threads = [
        threading.Thread(
            target=arrivals, args=(name, share), daemon=True
        )
        for name, share in shares.items()
    ]
    t0 = time.monotonic()
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    for r in results:
        if not r.done:
            try:
                r.wait(timeout=60.0)
            except Exception:
                pass
    wall = time.monotonic() - t0
    # SERVED submissions only: a whole-submission shed completes at
    # latency ~0, which would bias the saturation p99 low exactly
    # when the metric matters
    lat = [
        r.latency_s
        for r in results
        if r.latency_s is not None and not r.shed
    ]
    served = sum(
        int((~r.shed_mask).sum()) for r in results if not r.shed
    )
    shed = sum(
        (r.n if r.shed else int(r.shed_mask.sum()))
        for r in results
    )

    def q(p):
        return quantile_ms(lat, p)

    snap = plane.snapshot()
    metrics.serving_p99_ms.set(value=q(0.99))
    return {
        "submissions": len(results),
        "offered_qps": qps,
        "wall_s": wall,
        "sustained_verdicts_per_sec": served / wall if wall else 0.0,
        "serving_p50_ms": q(0.50),
        "serving_p99_ms": q(0.99),
        "served_flows": served,
        "shed_flows": shed,
        "avg_batch_fill_pct": snap["avg_batch_fill_pct"],
        "batches": snap["batches"],
        "early_dispatches": snap["early_dispatches"],
        "degraded_batches": snap["degraded_batches"],
        "tenants": snap["tenants"],
    }


# ---------------------------------------------------------------------------
# self-contained demo world (serve-bench / serveprof / tenant storm)
# ---------------------------------------------------------------------------


def build_demo_daemon():
    """Two-endpoint world with an L4 + L3 policy — the canonical
    replay world, built self-contained so `cilium-tpu serve-bench`
    and tools/serveprof.py need no running agent.  Returns
    (daemon, client endpoint)."""
    from cilium_tpu.daemon import Daemon
    from cilium_tpu.labels import Label, LabelArray, Labels
    from cilium_tpu.policy.api import (
        EndpointSelector,
        IngressRule,
        PortProtocol,
        PortRule,
        Rule,
    )

    def k8s_labels(**kv):
        return Labels(
            {k: Label(k, v, "k8s") for k, v in kv.items()}
        )

    def es(**kv):
        return EndpointSelector(
            match_labels={f"k8s.{k}": v for k, v in kv.items()}
        )

    d = Daemon()
    d.create_endpoint(
        10, k8s_labels(app="server"), ipv4="10.0.0.10",
        name="server-0",
    )
    client = d.create_endpoint(
        11, k8s_labels(app="client"), ipv4="10.0.0.11",
        name="client-0",
    )
    d.policy_add(
        [
            Rule(
                endpoint_selector=es(app="server"),
                ingress=[
                    IngressRule(
                        from_endpoints=[es(app="client")],
                        to_ports=[
                            PortRule(
                                ports=[
                                    PortProtocol(
                                        port="80", protocol="TCP"
                                    )
                                ]
                            )
                        ],
                    )
                ],
                labels=LabelArray.parse("serve-bench-rule"),
            )
        ]
    )
    d.policy_trigger.close(wait=True)
    return d, client


def demo_record_maker(client_identity: int):
    """`make_records(rng, n)` for run_serve_bench over the demo
    world: a mixed allowed/denied stream against endpoint 10."""

    def make_records(rng, n):
        return {
            "ep_id": np.full(n, 10, np.uint32),
            "identity": rng.choice(
                [client_identity, 999999], size=n
            ).astype(np.uint32),
            "saddr": np.zeros(n, np.uint32),
            "daddr": np.zeros(n, np.uint32),
            "sport": np.full(n, 40000, np.uint16),
            "dport": rng.choice([80, 443], size=n).astype(
                np.uint16
            ),
            "proto": np.full(n, 6, np.uint8),
            "direction": np.zeros(n, np.uint8),
            "is_fragment": np.zeros(n, np.uint8),
        }

    return make_records
