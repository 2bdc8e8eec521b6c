"""The daemon: orchestration of every subsystem.

Re-design of /root/reference/daemon/daemon.go NewDaemon (daemon.go:1084)
and the policy API handlers (daemon/policy.go):

  bootstrap order (≙ §3.1 of SURVEY.md):
    config → repository → endpoint manager (builder pool) → identity
    allocator (kvstore-backed when a store is given) → ipcache (+
    device LPM listener) → kvstore watchers → clustermesh → proxy →
    endpoint restore from the state dir.

  PolicyAdd (daemon/policy.go:167): collect CIDR prefixes → prefix-
  length refcount → AllocateCIDRs (local identities + ipcache) →
  repo.AddList (revision++) → TriggerPolicyUpdates → regenerate all
  endpoints → publish fresh fleet tables.

  PolicyDelete (daemon/policy.go:240): delete by label, release CIDR
  identities, trigger regeneration.

The REST API of the reference (api/v1 swagger over a unix socket)
maps onto this object's methods one-to-one; cilium_tpu.cli drives
them in-process.
"""

from __future__ import annotations

import threading
from collections import Counter as _Counter
from typing import Dict, List, Optional, Tuple

from cilium_tpu import logging as logfields
from cilium_tpu.logging import get_logger

log = get_logger("daemon")

from cilium_tpu import option, tracing
from cilium_tpu.endpoint import Endpoint, EndpointManager
from cilium_tpu.endpoint.checkpoint import restore_endpoints, save_endpoint
from cilium_tpu.identity import IdentityAllocator
from cilium_tpu.ipcache import IPCache
from cilium_tpu.ipcache.cidr import allocate_cidrs, release_cidrs
from cilium_tpu.ipcache.lpm import LPMBuilder
from cilium_tpu.kvstore import IDENTITIES_PATH, KVStore
from cilium_tpu.kvstore.allocator import Allocator, IdentityBackendAdapter
from cilium_tpu.kvstore.clustermesh import ClusterMesh
from cilium_tpu.kvstore.ipsync import IPIdentityWatcher
from cilium_tpu.metrics import registry as metrics
from cilium_tpu.monitor import MonitorBus
from cilium_tpu.policy.repository import Repository
from cilium_tpu.policy.search import SearchContext
from cilium_tpu.policy.trace import trace_policy
from cilium_tpu.proxy import Proxy
from cilium_tpu.resilience import (
    STATE_CODES,
    AdmissionGate,
    CircuitBreaker,
    DispatchWatchdog,
)
from cilium_tpu.spanstat import SpanStats
from cilium_tpu.utils.controller import ControllerManager
from cilium_tpu.utils.trigger import Trigger


class EndpointConflict(ValueError):
    """Endpoint id already in use by a different workload."""


def get_cidr_prefixes(rules) -> List[str]:
    """policy.GetCIDRPrefixes: every CIDR string the rules reference."""
    out: List[str] = []
    for rule in rules:
        for ingress in rule.ingress:
            out.extend(str(c) for c in ingress.from_cidr)
            out.extend(str(c.cidr) for c in ingress.from_cidr_set)
        for egress in rule.egress:
            out.extend(str(c) for c in egress.to_cidr)
            out.extend(str(c.cidr) for c in egress.to_cidr_set)
    return out


class Daemon:
    def __init__(
        self,
        node_name: str = "node0",
        kvstore: Optional[KVStore] = None,
        state_dir: Optional[str] = None,
        num_workers: int = 4,
        dns_resolver=None,
        ipam_cidr: str = "10.200.0.0/16",
    ) -> None:
        self.node_name = node_name
        self.lock = threading.RLock()
        # host-scope endpoint IP allocation (pkg/ipam; daemon.go
        # ipam.Init) — create_endpoint without an explicit address
        # draws from this pool, the CNI ADD path
        from cilium_tpu.ipam import IPAM

        self.ipam = IPAM(ipam_cidr)

        # policy.NewPolicyRepository (daemon.go:1100)
        self.repo = Repository()
        # builder pool (daemon.go:235)
        self.endpoint_manager = EndpointManager(num_workers=num_workers)
        # identity allocator, kvstore-backed when distributed
        backend = None
        self.kvstore = kvstore
        if kvstore is not None:
            backend = IdentityBackendAdapter(
                Allocator(kvstore, IDENTITIES_PATH, node=node_name)
            )
        self.identity_allocator = IdentityAllocator(backend=backend)
        # ipcache + device LPM listener (§3.5 tail)
        self.ipcache = IPCache()
        self.lpm_builder = LPMBuilder()
        self.ipcache.add_listener(self.lpm_builder)
        if kvstore is not None:
            self._ip_watcher = IPIdentityWatcher(kvstore, self.ipcache)
        self.clustermesh = ClusterMesh(self.ipcache)
        # service model + conntrack, daemon-owned like the reference
        # (daemon/loadbalancer.go service BPF sync; endpointmanager
        # conntrack.go periodic GC).  Consumers assemble
        # DatapathTables with lb=compile_lb(self.services) /
        # ct=compile_ct(self.ct).
        from cilium_tpu.ct.table import CTMap
        from cilium_tpu.lb.service import ServiceManager

        self.services = ServiceManager()
        self.ct = CTMap()
        # tunnel/overlay map fed by node discovery (pkg/maps/tunnel ←
        # linuxNodeHandler NodeUpdate): remote nodes' pod CIDRs map to
        # their node IP; consumers assemble DatapathTables with
        # tunnel=self.tunnel_map.tables() to compile the overlay form
        from cilium_tpu.tunnel import TunnelMap

        self.tunnel_map = TunnelMap()
        if kvstore is not None:
            from cilium_tpu.kvstore.node import NodeWatcher

            def _tunnel_feed(kind, node):
                # the agent's OWN published Node comes back through
                # the watch; the local pod CIDR must stay direct
                # (linuxNodeHandler skips the local node)
                if getattr(node, "name", "") == self.node_name:
                    return
                self.tunnel_map.on_node(kind, node)

            self._node_watcher = NodeWatcher(
                kvstore, on_change=_tunnel_feed
            )
        # indexed selector -> identity-set resolution for the compiler
        from cilium_tpu.compiler.selectorcache import RuleIndex, SelectorCache

        self.selector_cache = SelectorCache()
        self.rule_index = RuleIndex()
        # Serializes whole regeneration sweeps.  The selector cache and
        # rule index are shared, version-keyed caches: a second sweep
        # starting mid-flight would re-sync them to a NEWER identity
        # universe than the first sweep's snapshot, so in-flight builds
        # could resolve selectors against identities absent from the
        # universe their tables are lowered onto (universe/table skew —
        # the reference serializes the equivalent via the trigger +
        # per-endpoint regeneration.Lock, policy.go:540-552).
        self._regen_lock = threading.Lock()
        # endpoint selectors of rules changed since the last sweep;
        # None = a non-policy reason forced a full sweep
        self._pending_rule_selectors: Optional[list] = []
        self.monitor = MonitorBus()
        # Hubble-style flow-record plane (cilium_tpu.flow): a bounded
        # ring of structured per-flow records fed by process_flows —
        # all drops plus head-sampled allows (the
        # MonitorAggregationLevel knob, shared with the monitor
        # fold) — served by GET /flows and `cilium-tpu observe`
        from cilium_tpu.flow import FlowStore

        self.flow_store = FlowStore()
        self.proxy = Proxy(monitor=self.monitor)
        # accumulated per-phase regeneration spans (pkg/spanstat; the
        # reference logs one SpanStat per phase, policy.go:689-699) —
        # served by GET /debug/profile
        self.regen_spans = SpanStats()
        # datapath-loop phase spans (host pack / dispatch / event
        # fold), fed by process_flows — the hot path's SpanStat
        # instrumentation, also served by GET /debug/profile
        self.datapath_spans = SpanStats()
        # XDP-style deny-by-CIDR prefilter (daemon/prefilter.go):
        # daemon-owned so trace_tuple, process_flows and datapath
        # assembly (prefilter.tables()) consult ONE authoritative
        # CIDR set
        from cilium_tpu.prefilter import PreFilter

        self.prefilter = PreFilter()
        self.controllers = ControllerManager()
        # a controller stuck failing on its background thread flips
        # node health to degraded at this many consecutive failures
        # (pkg/controller's failure bookkeeping surfaced, instead of
        # failing silently off the request path)
        self.controller_failure_threshold = 3
        # -- trace plane (cilium_tpu.tracing) --------------------------
        # the process-global tracer (the metrics-registry shape):
        # REST handlers open root spans, every serving-path phase
        # below nests under them via contextvars; `/debug/traces`
        # serves the ring
        self.tracer = tracing.tracer
        self._traced_evaluate = None  # jit-tracked evaluate_batch
        # -- resilience plane (cilium_tpu.resilience) ------------------
        # Device dispatch runs under retry + a circuit breaker; when
        # the breaker opens the serving plane degrades to the
        # bit-identical host lattice fold instead of erroring the
        # stream, and half-open probes restore TPU service.
        self.dispatch_retries = 2
        self.dispatch_retry_base = 0.002
        self.dispatch_breaker = CircuitBreaker(
            name="engine.dispatch",
            failure_threshold=3,
            recovery_timeout=1.0,
            on_transition=self._breaker_event,
        )
        # per-batch dispatch deadline (a wedged XLA launch must fail
        # the batch, not hang the stream); <=0 disables
        self.dispatch_watchdog = DispatchWatchdog(timeout=30.0)
        # double-buffered async dispatch depth: batches in flight
        # beyond the one being drained (process_flows overlaps the
        # host pack of batch N+1 with device compute of batch N);
        # 0 = fully synchronous per-batch serving
        self.dispatch_async_depth = 1
        # sub-word hot planes for the FUSED datapath world
        # (engine.datapath.subword_datapath_tables): opt-in default
        # of datapath_tables() — flip before attach_mesh_router /
        # ServingPlane(fused=True) so every fused epoch ships the
        # compact row layouts (planes whose ranges don't fit keep
        # the wide layout automatically)
        self.datapath_subword = False
        # fused-plane hot-lane overrides the online autotuner sweeps
        # (engine.autotune.retune_candidates): CT bucket-row width
        # for the compact layout, and a plane-scoped ipcache
        # sub-word toggle that applies without the global
        # datapath_subword transform.  Either change moves the
        # datapath layout stamp, so the DatapathStore refuses the
        # next cross-layout delta into exactly one full upload.
        self.datapath_ct_lanes = None
        self.datapath_ip_subword = None
        # device table-publication backoff (monotonic deadline): a
        # failed epoch publish must not be retried per batch
        self._device_publish_retry_at = 0.0
        # per-chip failure domain (engine/failover.py): when a mesh
        # router is attached, its ChipBreakerBank's transitions flow
        # through the same observability planes as the process-wide
        # breaker (cilium_chip_breaker_state{chip} gauge, AgentNotify
        # monitor events, health()/status() degraded reasons) — the
        # mesh refinement of the dispatch breaker above
        self.mesh_router = None
        # when a router is attached with route_dispatch=True (the
        # default), the production dispatch loop (process_flows +
        # the serving plane) sends each batch THROUGH the router's
        # per-chip failure domain instead of the single-chip
        # evaluate_batch — the PR 8 remainder closed.  Routing only
        # engages once the router holds a published epoch.
        self.mesh_route_dispatch = False
        # continuous serving plane (cilium_tpu.serve.ServingPlane):
        # lazy — POST /datapath/flows?stream=1 and `cilium-tpu
        # serve-bench` start it on first use.  Tenant fairness
        # weights live on the daemon so PATCH /config can set them
        # before (or after) the plane spins up.
        self.serving = None
        self.tenant_weights: Dict[str, float] = {}
        # verdict memoization (engine/memo.py): when enabled, the
        # serving dispatch dedups each batch's policy keys in-jit
        # and serves repeats from a device-resident verdict cache,
        # epoch-stamped so any publish flushes it; an overflowing
        # batch (more distinct keys than the compaction capacity)
        # falls back to the uncached program — bit-identity is
        # unconditional either way.  Off by default: PATCH /config
        # {"verdict_cache": true} turns it on.
        self.verdict_cache_enabled = False
        self.verdict_cache = None  # engine.memo.VerdictCache, lazy
        self.verdict_cache_rows = 1 << 12
        # rep/miss compaction capacity as a fraction of the batch
        # (1/4 keeps lattice-gather savings real while Zipf-skewed
        # batches virtually never overflow), floored at 1024 keys
        # (matching autotune.memo_candidates) so tiny batches don't
        # overflow on trivially small key sets
        self.verdict_cache_rep_shift = 2
        # overflow backoff: a workload whose distinct-key count
        # keeps exceeding the compaction capacity pays the memo
        # sort+probe AND the uncached re-dispatch per batch; after
        # `streak_limit` consecutive refusals the memo attempt is
        # skipped, re-probed once every `retry_period` batches
        self.verdict_cache_overflow_streak = 0
        self.verdict_cache_streak_limit = 8
        self.verdict_cache_retry_period = 64
        self._memo_batch_seq = 0
        # shadow policy rollout (cilium_tpu.shadow): dual-epoch
        # sampled evaluation + live verdict-diff canarying.  Armed
        # via POST /policy/shadow; disarmed windows cost one
        # attribute read per batch.
        from cilium_tpu.shadow import ShadowPlane

        self.shadow = ShadowPlane(self)
        # live performance plane (cilium_tpu.perfplane): always-on
        # per-batch phase windows, ingest-stall detector, SLO-class
        # compliance ledger and the retune history behind GET
        # /debug/perf and `cilium-tpu top`.  The serving plane feeds
        # it from the overlap bookkeeping it already keeps.
        from cilium_tpu.perfplane import PerfPlane

        self.perf = PerfPlane()
        # online re-tune (engine.autotune.online_retune): the serving
        # loop polls maybe_online_retune() every 64 batches; off by
        # default so steady-state daemons never swap layouts behind
        # the operator's back.  PATCH /config {"online_retune": true}
        # arms it; config overrides the hysteresis bounds.
        self.online_retune_enabled = False
        self.online_retune_config: Dict = {}
        self._retune_inflight = threading.Lock()
        # fused-tables byte model cache for perf_snapshot: keyed by
        # (generation, layout) so the gatherprof walk runs once per
        # publish, not per /debug/perf poll
        self._perf_model_cache = None
        # per-tenant named SLO classes (serving tier 2): name ->
        # {"deadline_ms", "shed_priority", "weight"} bundles and the
        # tenant -> class assignment, both live via PATCH /config
        # {"slo_classes": ..., "tenant_slo": ...}
        self.slo_classes: Dict[str, Dict] = {}
        self.tenant_slo: Dict[str, str] = {}
        # bounded admission: flows in flight across concurrent
        # process_flows calls; excess batches shed under the
        # canonical Overload drop reason (None = unbounded)
        self.admission = AdmissionGate(limit=None)
        self.degraded_batches = 0
        # CT occupancy watermarks → emergency GC with adaptive backoff
        self.ct_high_watermark = 0.90
        self.ct_low_watermark = 0.75
        self._ct_gc_backoff_base = 0.1
        self._ct_gc_backoff_max = 30.0
        self._ct_gc_backoff = self._ct_gc_backoff_base
        self._ct_gc_not_before = 0.0
        # periodic CT GC (pkg/maps/ctmap GC; endpointmanager
        # conntrack.go loop)
        from cilium_tpu.utils.controller import Controller

        self.controllers.update_controller(
            Controller(
                name="ct-gc",
                do_func=self._ct_gc,
                run_interval=30.0,
            )
        )
        # TriggerPolicyUpdates debouncing (daemon/policy.go:47)
        self.policy_trigger = Trigger(
            self._regenerate_for_reasons, name="policy_update"
        )
        # ToFQDNs poller (daemon.go NewDaemon: d.dnsPoller).  The
        # resolver defaults to the REAL host stack
        # (fqdn.system_resolver ≙ dnspoller.go LookupIPs); tests
        # inject deterministic resolvers.  Polling starts only when
        # ToFQDNs rules are marked, so hermetic runs never touch DNS.
        from cilium_tpu.fqdn import DNSPoller, system_resolver

        self.dns_poller = DNSPoller(
            policy_add=lambda rules: self.policy_add(rules, replace=True),
            resolver=dns_resolver or system_resolver,
        )
        # CIDR prefix-length refcounts (daemon.go createPrefixLengthCounter)
        self.prefix_lengths: _Counter = _Counter()

        self.state_dir = state_dir
        if state_dir:
            from cilium_tpu.ipcache.ipcache import (
                FROM_AGENT_LOCAL,
                IPIdentity,
            )
            from cilium_tpu.kvstore.ipsync import upsert_ip_mapping

            # schema migration FIRST (the init.sh cilium-map-migrate
            # moment): old-version checkpoints rewrite in place, then
            # restore parses only current-version docs
            from cilium_tpu.endpoint.checkpoint import migrate_state_dir

            migrated = migrate_state_dir(state_dir)
            if migrated:
                log.info(
                    "migrated endpoint checkpoints",
                    extra={"fields": {"count": migrated}},
                )
            import ipaddress as _ipaddress

            for endpoint in restore_endpoints(
                state_dir, self.identity_allocator
            ):
                self.endpoint_manager.insert(endpoint)
                # re-reserve the restored IP — a fresh pool would
                # hand the same address to the next CNI ADD
                if endpoint.ipv4 and (
                    _ipaddress.ip_address(endpoint.ipv4)
                    in self.ipam.cidr
                ):
                    try:
                        self.ipam.allocate(endpoint.ipv4)
                    except Exception:
                        log.warning(
                            "restored endpoint IP already reserved",
                            extra={"fields": {
                                logfields.ENDPOINT_ID: endpoint.id,
                                logfields.IP_ADDR: endpoint.ipv4,
                            }},
                        )
                # republish the endpoint's IP mapping — the reference
                # restores the ipcache from the pinned BPF map on
                # restart (daemon restoreOldEndpoints + ipcache
                # restore); without this, restored endpoints' traffic
                # would resolve to WORLD
                if (
                    endpoint.ipv4
                    and endpoint.security_identity is not None
                ):
                    self.ipcache.upsert(
                        endpoint.ipv4,
                        IPIdentity(
                            endpoint.security_identity.id,
                            FROM_AGENT_LOCAL,
                        ),
                    )
                    # and to the cluster: the old daemon's
                    # lease-scoped kvstore key died with its session,
                    # so remote nodes would otherwise resolve this
                    # endpoint to WORLD after our restart
                    if self.kvstore is not None:
                        upsert_ip_mapping(
                            self.kvstore,
                            endpoint.ipv4,
                            endpoint.security_identity.id,
                            node=self.node_name,
                        )
            if self.endpoint_manager.endpoints():
                self.trigger_policy_updates("restore", full=True)

    # -- identity snapshot ---------------------------------------------------

    def identity_cache(self):
        return self.identity_allocator.identity_cache()

    # -- policy API (daemon/policy.go) --------------------------------------

    def policy_add(self, rules, replace: bool = False) -> int:
        """PolicyAdd (daemon/policy.go:167).  Returns the new revision."""
        with self.lock:
            try:
                for rule in rules:
                    rule.sanitize()
            except Exception:
                metrics.policy_import_errors.inc()
                raise
            # MarkToFQDNRules (daemon/policy.go:172); the poll loop
            # spins up lazily on the first ToFQDNs rule, so hermetic
            # runs without such rules never touch DNS
            self.dns_poller.mark_to_fqdn_rules(rules)
            if self.dns_poller.managed and not self.dns_poller.running:
                self.dns_poller.start()
            prefixes = get_cidr_prefixes(rules)
            import ipaddress

            for prefix in prefixes:
                self.prefix_lengths[
                    ipaddress.ip_network(prefix, strict=False).prefixlen
                ] += 1
            if prefixes:
                allocate_cidrs(
                    self.ipcache, self.identity_allocator, prefixes
                )
            if replace:
                for rule in rules:
                    for old in self.repo.search(rule.labels):
                        self._note_rule_change(old.endpoint_selector)
                    self.repo.delete_by_labels(rule.labels)
            for rule in rules:
                self._note_rule_change(rule.endpoint_selector)
            revision = self.repo.add_list(list(rules))
            metrics.policy_count.set(value=self.repo.num_rules())
            metrics.policy_revision.set(value=revision)
            log.info(
                "policy rules imported",
                extra={"fields": {
                    logfields.POLICY_REVISION: revision,
                    "count": len(rules),
                }},
            )
        self.trigger_policy_updates("policy rules added")
        return revision

    def policy_delete(self, labels) -> Tuple[int, int]:
        """PolicyDelete (daemon/policy.go:240)."""
        with self.lock:
            deleted_rules = self.repo.search(labels)
            for old in deleted_rules:
                self._note_rule_change(old.endpoint_selector)
            prefixes = get_cidr_prefixes(deleted_rules)
            revision, n_deleted = self.repo.delete_by_labels(labels)
            if n_deleted:
                import ipaddress

                for prefix in prefixes:
                    plen = ipaddress.ip_network(
                        prefix, strict=False
                    ).prefixlen
                    self.prefix_lengths[plen] -= 1
                    if self.prefix_lengths[plen] <= 0:
                        del self.prefix_lengths[plen]
                release_cidrs(
                    self.ipcache, self.identity_allocator, prefixes
                )
            metrics.policy_count.set(value=self.repo.num_rules())
        if n_deleted:
            self.trigger_policy_updates("policy rules deleted")
        return revision, n_deleted

    def policy_resolve(self, ctx: SearchContext):
        """GET /policy/resolve (daemon/policy.go:66)."""
        return trace_policy(self.repo, ctx)

    def trace_tuple(self, **kwargs):
        """Single-tuple datapath explain (`cilium policy trace` made
        stage-accurate): rerun one tuple through prefilter → LB/DNAT
        → CT → ipcache → lattice → combine against THIS daemon's
        state, reporting each stage's decision and the matching
        rules.  See policy.trace.trace_tuple."""
        from cilium_tpu.policy.trace import trace_tuple

        return trace_tuple(self, **kwargs)

    # -- regeneration (daemon/policy.go:47 TriggerPolicyUpdates) ------------

    def _note_rule_change(self, endpoint_selector) -> None:
        """Record a changed rule's endpoint selector for delta-scoped
        regeneration (a rule affects only endpoints it selects)."""
        if self._pending_rule_selectors is not None:
            self._pending_rule_selectors.append(endpoint_selector)

    def trigger_policy_updates(self, reason: str, full: bool = False) -> None:
        if full:
            # non-policy reason (endpoint/identity/config change):
            # next sweep must not be delta-scoped
            self._pending_rule_selectors = None
        self.policy_trigger.trigger_with_reason(reason)

    def _accumulate_regen_span(
        self, stats: SpanStats, success: bool
    ) -> None:
        """Fold one run's spans into the lifetime accumulators served
        by GET /debug/profile (pkg/spanstat's success/failure split)."""
        for name, span in stats.items():
            acc = self.regen_spans.span(name)
            acc.success_total += span.success_total
            acc.failure_total += span.failure_total
            acc.num_success += span.num_success
            acc.num_failure += span.num_failure
        self._export_spans("regeneration", self.regen_spans)

    @staticmethod
    def _export_spans(scope: str, spans: SpanStats) -> None:
        """Mirror a SpanStats accumulator into the metrics registry
        (one gauge sample per phase, labels-first) so /debug/profile
        and /metrics/prometheus report the SAME numbers."""
        for name, span in spans.items():
            metrics.spanstat_seconds.set(
                scope, name, value=span.total()
            )
        metrics.trace_spans_total.set(
            value=tracing.tracer.finished_total
        )
        metrics.trace_spans_dropped.set(value=tracing.tracer.dropped)

    def reset_profile(self) -> None:
        """GET /debug/profile?reset=1: zero the cumulative SpanStat
        accumulators (regeneration + datapath) so before/after
        experiments don't need a daemon restart.  The mirrored
        spanstat_seconds gauges are zeroed too, so /metrics and
        /debug/profile keep agreeing."""
        for scope, spans in (
            ("regeneration", self.regen_spans),
            ("datapath", self.datapath_spans),
        ):
            for name in spans:
                metrics.spanstat_seconds.set(scope, name, value=0.0)
            spans.clear()
        # the serving plane's rolling serving_p99_ms window resets
        # with the same seam, so bench segments / before-after
        # experiments don't bleed one load shape's tail into the
        # next (the plane keeps its own window — see
        # ServingPlane.reset_window)
        if self.serving is not None:
            self.serving.reset_window()
        else:
            # no plane → reset_window can't do it for us: clear the
            # perf plane's phase/fill/stall windows directly so
            # /debug/perf experiments get the same seam
            self.perf.reset()

    def _regenerate_for_reasons(self, reasons: List[str]) -> None:
        self.regenerate_all(", ".join(reasons) or "trigger")

    def regenerate_all(self, reason: str = "") -> int:
        with self._regen_lock:
            # the regen sweep's root span: compile/publish pipeline
            # spans (FleetCompiler, DeviceTableStore) and proxy
            # upcalls nest under it
            with self.tracer.span(
                "daemon.regenerate", site="daemon",
                attrs={"reason": reason},
            ):
                return self._regenerate_all_locked(reason)

    def _regenerate_all_locked(self, reason: str = "") -> int:
        stats = SpanStats()  # fresh per run: the histogram observes
        # THIS run's duration; regen_spans accumulates across runs
        total_span = tracing.stat_span(
            stats, "total", site="daemon.regenerate", trc=self.tracer
        ).start()
        cache, cache_version = (
            self.identity_allocator.identity_cache_versioned()
        )
        prev_version = self.selector_cache.version
        universe_version = self.selector_cache.sync(
            cache, cache_version=cache_version
        )
        # Swap the pending set and snapshot the repo revision under
        # the daemon lock: a concurrent policy_add after the swap must
        # not be fast-forwarded past (its selector isn't in `pending`).
        with self.lock:
            pending, self._pending_rule_selectors = (
                self._pending_rule_selectors,
                [],
            )
            affected_revision = self.repo.get_revision()
        affected = None
        if pending is not None and universe_version == prev_version:
            affected = frozenset().union(
                *(
                    self.selector_cache.matches(sel)
                    for sel in pending
                ),
            ) if pending else frozenset()
        self.rule_index.build(self.repo, self.selector_cache)
        n = self.endpoint_manager.regenerate_all(
            self.repo,
            cache,
            reason,
            selector_cache=self.selector_cache,
            rule_index=self.rule_index,
            universe_version=universe_version,
            affected_identities=affected,
            affected_revision=affected_revision,
            identity_cache_token=cache_version,
        )
        # Two-phase redirect realization (pkg/endpoint/bpf.go:488 +
        # policy.go:157-166): the first pass computes desired L4
        # policy; redirects then get proxy ports allocated; endpoints
        # whose redirects changed recompute so the L4 entries carry
        # the allocated ports.  The L7 tables' identity axis MUST be
        # the fleet compiler's index space (the published tables'
        # id_direct), not a sorted rebuild.
        id_index, n_identities = self.endpoint_manager.identity_index()
        from cilium_tpu.utils.completion import WaitGroup

        wait_group = WaitGroup()
        dirty = False
        attempted = []  # (endpoint, realized map before this attempt)
        universe_unchanged = universe_version == prev_version
        upcall_failed = False
        for endpoint in self.endpoint_manager.endpoints():
            l4 = endpoint.desired_l4_policy
            if l4 is None or not l4.has_redirect():
                if endpoint.realized_redirects:
                    try:
                        self.proxy.update_endpoint_redirects(
                            endpoint, cache, id_index, n_identities,
                            self.selector_cache,
                        )
                    except Exception as exc:
                        # a failed proxy upcall (dead envoy, injected
                        # proxy.upcall fault) must not crash the
                        # sweep's thread: the endpoint keeps its old
                        # redirects and retries next trigger
                        upcall_failed = True
                        endpoint.force_policy_compute = True
                        log.warning(
                            "proxy upcall failed; keeping old "
                            "redirects",
                            extra={"fields": {
                                logfields.ENDPOINT_ID: endpoint.id,
                                "error": str(exc),
                            }},
                        )
                continue
            if (
                universe_unchanged
                and not endpoint.last_policy_changed
                and endpoint.realized_redirects
            ):
                # unchanged policy + unchanged identity universe ⇒ the
                # resolved matcher inputs are identical to the live
                # redirects' — skip even the re-resolution (the
                # fingerprint check would skip only the compile)
                continue
            before = dict(endpoint.realized_redirects)
            try:
                realized = self.proxy.update_endpoint_redirects(
                    endpoint, cache, id_index, n_identities,
                    self.selector_cache, wait_group=wait_group,
                )
            except Exception as exc:
                # same containment as above: roll this endpoint back
                # to its pre-attempt redirects, flag the retry, let
                # every other endpoint's regeneration proceed
                upcall_failed = True
                endpoint.realized_redirects = before
                endpoint.force_policy_compute = True
                log.warning(
                    "proxy upcall failed; keeping old redirects",
                    extra={"fields": {
                        logfields.ENDPOINT_ID: endpoint.id,
                        "error": str(exc),
                    }},
                )
                continue
            attempted.append((endpoint, before))
            if realized != before:
                endpoint.force_policy_compute = True
                dirty = True
        if upcall_failed:
            metrics.endpoint_regenerations.inc("fail")
        # ACK gate (pkg/completion + pkg/envoy/xds/ack.go): the table
        # flip below happens only once EVERY submitted matcher
        # compile — port change or not — has ACKed its version; on
        # timeout or NACK the regeneration FAILS: realized redirect
        # state rolls back so old redirects and old published tables
        # keep serving, and the retry flag makes the next trigger
        # re-attempt (pkg/endpoint/bpf.go:442, policy.go:770-775)
        if wait_group.pending and not wait_group.wait(
            timeout=option.Config.redirect_ack_timeout
        ):
            metrics.endpoint_regenerations.inc("fail")
            for endpoint, before in attempted:
                endpoint.realized_redirects = before
                endpoint.force_policy_compute = True
            total_span.end(success=False)
            self._accumulate_regen_span(stats, success=False)
            return n
        if dirty:
            self.endpoint_manager.regenerate_all(
                self.repo,
                cache,
                reason + " (redirects realized)",
                selector_cache=self.selector_cache,
                rule_index=self.rule_index,
                universe_version=universe_version,
                affected_revision=affected_revision,
                identity_cache_token=cache_version,
            )
        metrics.policy_regeneration_count.inc(value=n)
        total_span.end()
        metrics.endpoint_regeneration_seconds.observe(
            stats.span("total").total()
        )
        self._accumulate_regen_span(stats, success=True)
        return n

    # -- endpoint API (daemon/endpoint.go) ----------------------------------

    def create_endpoint(
        self, endpoint_id: int, labels, ipv4: Optional[str] = None,
        name: str = "", ip_reserved: bool = False,
    ) -> Endpoint:
        """PUT /endpoint/{id} (daemon/endpoint.go:138): allocate the
        identity from labels, publish the IP, regenerate.

        Idempotent for runtime retries: re-creating an id with the
        SAME name returns the existing endpoint untouched (CNI ADD is
        retried by runtimes); the same id under a DIFFERENT name is a
        conflict — silently replacing would leak the old endpoint's
        IP and leave its ipcache entry pointing at a dead identity."""
        import ipaddress as _ipaddress

        from cilium_tpu.endpoint.endpoint import (
            STATE_READY,
            STATE_WAITING_FOR_IDENTITY,
        )
        from cilium_tpu.ipcache.ipcache import FROM_AGENT_LOCAL, IPIdentity
        from cilium_tpu.kvstore.ipsync import upsert_ip_mapping

        with self.lock:
            # id 0 = "agent allocates": pick a free id instead of the
            # caller deriving one (a hash-derived id collides at
            # birthday rates and surfaces as a permanent ADD failure;
            # the reference's endpointmanager allocates too).
            # Idempotency still holds: a named re-create finds the
            # live endpoint by name before allocating.
            if endpoint_id == 0:
                if name:
                    existing = self.endpoint_manager.lookup_name(name)
                    if existing is not None:
                        return existing
                endpoint_id = self._allocate_endpoint_id()
            # check-then-act under the daemon lock: the API server is
            # thread-per-connection, and two concurrent ADD retries
            # racing past the existence guard would double-allocate
            # the IP and leak the losing endpoint's resources
            existing = self.endpoint_manager.lookup(endpoint_id)
            if existing is not None:
                # idempotent ONLY for a matching non-empty name (the
                # runtime-retry case); unnamed re-creates have no
                # identity to match on and must surface as conflicts
                # rather than silently discarding the new labels/IP
                if name and existing.name == name:
                    return existing
                raise EndpointConflict(
                    f"endpoint id {endpoint_id} in use by "
                    f"{existing.name!r}"
                )
            allocated_ip = None
            if ipv4 is None:
                ipv4 = allocated_ip = self.ipam.allocate()
            elif ip_reserved:
                # the caller already holds this address from the
                # agent's own IPAM (POST /ipam — the docker IpamDriver
                # flow); re-reserving would false-conflict
                pass
            elif _ipaddress.ip_address(ipv4) in self.ipam.cidr:
                # in-pool explicit address: a duplicate must FAIL
                # (the except-everything that was here swallowed the
                # conflict and brought two endpoints up on one IP);
                # out-of-pool addresses are the caller's own numbering
                self.ipam.allocate(ipv4)
                allocated_ip = ipv4
            try:
                endpoint = Endpoint(endpoint_id, ipv4=ipv4, name=name)
                # externally-reserved addresses (POST /ipam → docker
                # IpamDriver) are NOT returned to the pool on delete;
                # their ReleaseAddress call does that.  In-memory
                # only: a restart converts ownership to the agent
                # (restore re-reserves the address itself).
                endpoint.ip_externally_owned = ip_reserved
                endpoint.set_state(
                    STATE_WAITING_FOR_IDENTITY, "creating"
                )
                ident, _ = self.identity_allocator.allocate(labels)
                endpoint.set_identity(ident)
                endpoint.set_state(STATE_READY, "identity resolved")
                self.endpoint_manager.insert(endpoint)
            except BaseException:
                # a failed create must hand its address back — the
                # runtime retries, and each leaked IP would drain the
                # pool without ever serving an endpoint
                if allocated_ip is not None:
                    self.ipam.release(allocated_ip)
                raise
            if ipv4:
                self.ipcache.upsert(
                    ipv4, IPIdentity(ident.id, FROM_AGENT_LOCAL)
                )
        # the kvstore publish is network I/O — outside the daemon
        # lock, or one wedged store round trip stalls every
        # concurrent endpoint operation
        if ipv4 and self.kvstore is not None:
            upsert_ip_mapping(
                self.kvstore, ipv4, ident.id, node=self.node_name
            )
        self.trigger_policy_updates(
            f"endpoint {endpoint_id} created", full=True
        )
        return endpoint

    def update_endpoint_labels(self, endpoint_id: int, labels) -> bool:
        """EndpointUpdateLabels (pkg/endpoint + workloads docker.go:479):
        re-allocate the identity from the new label set, republish the
        IP mapping, release the old identity, regenerate."""
        from cilium_tpu.ipcache.ipcache import FROM_AGENT_LOCAL, IPIdentity
        from cilium_tpu.kvstore.ipsync import upsert_ip_mapping

        endpoint = self.endpoint_manager.lookup(endpoint_id)
        if endpoint is None:
            return False
        old = endpoint.security_identity
        ident, _ = self.identity_allocator.allocate(labels)
        if old is not None and ident.id == old.id:
            # same identity: drop the reference allocate() just took
            # (repeated runtime START events must not leak refs)
            self.identity_allocator.release(ident)
            return True
        endpoint.set_identity(ident)
        # the identity universe may be unchanged (another endpoint
        # already holds both identities), so the revision gate would
        # skip this endpoint — force its recompute
        endpoint.force_policy_compute = True
        if endpoint.ipv4:
            self.ipcache.upsert(
                endpoint.ipv4, IPIdentity(ident.id, FROM_AGENT_LOCAL)
            )
            if self.kvstore is not None:
                upsert_ip_mapping(
                    self.kvstore, endpoint.ipv4, ident.id,
                    node=self.node_name,
                )
        if old is not None:
            self.identity_allocator.release(old)
        self.trigger_policy_updates(
            f"endpoint {endpoint_id} relabeled", full=True
        )
        return True

    # next candidate for agent-allocated endpoint ids; ids live in
    # u16 space above the reserved low range, like the reference's
    # endpointmanager allocation (pkg/endpointmanager)
    _EP_ID_BASE = 256
    _next_ep_id = 256

    def _allocate_endpoint_id(self) -> int:
        """Pick a free endpoint id (caller holds self.lock)."""
        span = 65536 - self._EP_ID_BASE
        for _ in range(span):
            candidate = self._next_ep_id
            self._next_ep_id = (
                self._EP_ID_BASE
                + (candidate + 1 - self._EP_ID_BASE) % span
            )
            if self.endpoint_manager.lookup(candidate) is None:
                return candidate
        raise EndpointConflict("endpoint id space exhausted")

    def delete_endpoint(
        self, endpoint_id: int, expected_name: Optional[str] = None
    ) -> bool:
        """`expected_name` guards hash-derived callers (the CNI shim
        maps container ids onto endpoint ids): a DEL whose id collided
        with a DIFFERENT workload's endpoint must not tear that
        endpoint down."""
        from cilium_tpu.endpoint.endpoint import (
            STATE_DISCONNECTED,
            STATE_DISCONNECTING,
        )
        from cilium_tpu.kvstore.ipsync import delete_ip_mapping

        with self.lock:
            if endpoint_id == 0 and expected_name:
                # agent-allocated ids: the caller only knows the name
                endpoint = self.endpoint_manager.lookup_name(
                    expected_name
                )
            else:
                endpoint = self.endpoint_manager.lookup(endpoint_id)
            if endpoint is None:
                return False
            if (
                expected_name is not None
                and endpoint.name != expected_name
            ):
                raise EndpointConflict(
                    f"endpoint id {endpoint_id} belongs to "
                    f"{endpoint.name!r}, not {expected_name!r}"
                )
            endpoint.set_state(STATE_DISCONNECTING, "delete")
            if endpoint.ipv4:
                self.ipcache.delete(endpoint.ipv4)
                if not getattr(
                    endpoint, "ip_externally_owned", False
                ):
                    self.ipam.release(endpoint.ipv4)
            if endpoint.security_identity is not None:
                self.identity_allocator.release(
                    endpoint.security_identity
                )
            self.endpoint_manager.remove(endpoint)
            endpoint.set_state(STATE_DISCONNECTED, "deleted")
        # network I/O outside the lock (see create_endpoint)
        if endpoint.ipv4 and self.kvstore is not None:
            delete_ip_mapping(self.kvstore, endpoint.ipv4)
        return True

    # -- persistence ---------------------------------------------------------

    def checkpoint(self) -> int:
        if not self.state_dir:
            return 0
        n = 0
        for endpoint in self.endpoint_manager.endpoints():
            save_endpoint(endpoint, self.state_dir)
            n += 1
        return n

    # -- status (daemon/status.go) ------------------------------------------

    def _ct_gc(self) -> None:
        """Periodic CT garbage collection (pkg/maps/ctmap GC loop):
        expired entries leave the host map; gc() bumps the map's
        mutation counter, so the churn snapshot cache self-invalidates
        at its next use (replay._ChurnDriver gate) and the device CT
        resyncs.  Still sweeps a NON-EMPTY table while the Conntrack
        option is off: disabling flushed it, but replay harnesses may
        repopulate the daemon map afterwards — entries must never
        accumulate unbounded just because GC went dormant."""
        if (
            not option.Config.opts.is_enabled(option.CONNTRACK)
            and not self.ct.entries
        ):
            return
        self.ct.gc(now=self.ct.now())
        self._ct_pressure_check()

    def _ct_pressure_check(self) -> None:
        """CT occupancy watermarks (ctmap's pressure-scaled GC
        interval made explicit): past the high watermark run an
        emergency sweep — expiry GC first, then soonest-to-expire
        eviction down to the low watermark — with adaptive backoff so
        sustained pressure can't turn every batch into a GC storm."""
        import time as _time

        cap = self.ct.max_entries or 1
        occupancy = len(self.ct.entries) / cap
        metrics.ct_occupancy.set(value=occupancy)
        if occupancy < self.ct_high_watermark:
            self._ct_gc_backoff = self._ct_gc_backoff_base
            return
        now = _time.monotonic()
        if now < self._ct_gc_not_before:
            return
        expired = self.ct.gc(now=self.ct.now())
        target = int(cap * self.ct_low_watermark)
        evicted = self.ct.evict_to(target)
        metrics.ct_emergency_gc_total.inc()
        metrics.ct_occupancy.set(value=len(self.ct.entries) / cap)
        # adaptive backoff: each consecutive emergency sweep doubles
        # the spacing (an ineffective sweep repeated immediately only
        # burns the hot path); any drop below the high watermark
        # resets it
        self._ct_gc_not_before = now + self._ct_gc_backoff
        self._ct_gc_backoff = min(
            self._ct_gc_backoff * 2, self._ct_gc_backoff_max
        )
        from cilium_tpu.monitor.events import AgentNotify

        self.monitor.publish(
            AgentNotify(
                kind="ct-emergency-gc",
                text=(
                    f"occupancy {occupancy:.2f}: expired {expired}, "
                    f"evicted {evicted}"
                ),
            )
        )
        log.warning(
            "CT high watermark: emergency GC",
            extra={"fields": {
                "occupancy": round(occupancy, 3),
                "expired": expired,
                "evicted": evicted,
                "next_backoff_s": self._ct_gc_backoff,
            }},
        )

    # -- resilience (circuit breaker / degraded serving) ---------------------

    def _breaker_event(
        self, name: str, old: str, new: str, reason: str
    ) -> None:
        """CircuitBreaker transition listener: gauge + monitor event
        + log — breaker state is observable through every plane the
        telemetry PR wired (Prometheus, `cilium monitor`, agent
        log)."""
        from cilium_tpu.monitor.events import AgentNotify

        metrics.breaker_state.set(name, value=STATE_CODES[new])
        self.monitor.publish(
            AgentNotify(
                kind="circuit-breaker",
                text=f"{name}: {old} -> {new} ({reason})",
            )
        )
        log.warning(
            "circuit breaker transition",
            extra={"fields": {
                "breaker": name,
                "from": old,
                "to": new,
                "reason": reason,
            }},
        )

    def attach_mesh_router(
        self,
        router,
        route_dispatch: bool = True,
        auto_publish: bool = True,
    ) -> None:
        """Adopt a ChipFailoverRouter (engine/failover.py): per-chip
        breaker transitions publish AgentNotify monitor events beside
        the router's own gauge/span-event wiring, and health() gains
        per-chip degraded reasons — a mesh losing one chip reports
        WHICH ordinal is out, not just "degraded".

        With `route_dispatch` (default) the daemon's PRODUCTION
        dispatch loop also routes every batch through the router —
        survivor re-split, replica gathers and per-chip breakers
        serve the stream instead of the single-chip program — once
        the router holds a published epoch; until then, and on any
        router error, batches fall back to the single-chip path
        under the process-wide breaker.

        With `auto_publish` (default) the router's published tables
        TRACK daemon regenerates automatically: every device-epoch
        publish the endpoint manager performs also lands in the
        router's replica store, with a delta computed against the
        ROUTER store's own standby stamp (its epoch cadence differs
        from the manager store's) — no operator publish.  The
        current published tables, if any, are pushed immediately, so
        attaching to a warm daemon engages mesh routing on the very
        next batch."""
        from cilium_tpu.monitor.events import AgentNotify

        self.mesh_router = router
        self.mesh_route_dispatch = route_dispatch
        outer = router._on_chip_transition

        def _notify(ordinal, old, new, reason):
            self.monitor.publish(
                AgentNotify(
                    kind="chip-breaker",
                    text=(
                        f"chip {ordinal}: {old} -> {new} ({reason})"
                    ),
                )
            )
            if outer is not None:
                outer(ordinal, old, new, reason)

        router._on_chip_transition = _notify
        if not auto_publish:
            return

        def _sync_router(tables):
            """Publish a fresh host compile into the router's
            replica store, delta-scoped against ITS standby."""
            try:
                delta = self.endpoint_manager.delta_for(
                    router.store.spare_stamp(), tables
                )
            except Exception:  # pragma: no cover — compiler churn
                delta = None
            try:
                _, stats = router.publish(tables, delta)
                metrics.table_publish_total.inc(
                    f"router_{stats.mode}"
                )
            except Exception as exc:  # noqa: BLE001
                log.warning(
                    "router auto-publish failed; mesh routing will "
                    "serve the previous epoch",
                    extra={"fields": {"error": str(exc)}},
                )
                return
            if router.dp_store is None:
                return
            # the fused plane tracks regenerates too: rebuild the
            # datapath world from live daemon state (the new policy
            # tables + current ipcache/CT/LB) and republish — the
            # row-diff store keeps it a delta, so fused serving
            # never answers with pre-regenerate policy
            try:
                _, dstats = router.publish_datapath(
                    self.datapath_tables(policy=tables)
                )
                metrics.table_publish_total.inc(
                    f"datapath_{dstats.mode}"
                )
            except Exception as exc:  # noqa: BLE001
                log.warning(
                    "fused datapath auto-publish failed; fused "
                    "serving will use the previous epoch",
                    extra={"fields": {"error": str(exc)}},
                )

        self.endpoint_manager.on_device_publish = _sync_router
        version, tables, _index = self.endpoint_manager.published()
        if tables is not None:
            _sync_router(tables)

    def reshard_mesh(
        self,
        target_tp: int,
        step_bytes: Optional[int] = None,
        on_fault: str = "complete",
        plane=None,
        max_steps: int = 1 << 16,
    ) -> Dict:
        """Live elastic reshard of the attached mesh router's table
        axis to `target_tp` columns — stop-free: the live epoch
        serves throughout; moved rows stream into a staged
        target-layout epoch in bounded-byte steps
        (engine/reshard.ReshardPlan), and the cutover flips epochs
        between batches (via `plane.run_at_batch_boundary` when a
        ServingPlane is passed).  While the migration window is
        open, every auto-publish the endpoint manager performs is
        DUAL-APPLIED: the store's relayout-aware publish patches the
        live epoch in place (non-donated — zero drain) and the plan
        folds the same change into the staged target host, so churn
        never blocks a reshard and a reshard never loses churn.
        Returns the plan's stats dict ({outcome, steps, bytes_h2d,
        ms, restarts, dead_cols})."""
        from cilium_tpu.engine import reshard as reshard_mod

        router = self.mesh_router
        if router is None:
            raise RuntimeError(
                "no mesh router attached; call attach_mesh_router "
                "first"
            )
        target_mesh = reshard_mod.reshard_target_mesh(
            router, target_tp
        )
        dtables = (
            self.datapath_tables()
            if router.dp_store is not None else None
        )
        kwargs = {} if step_bytes is None else {
            "step_bytes": int(step_bytes)
        }
        plan = reshard_mod.ReshardPlan(
            router, target_mesh, on_fault=on_fault,
            dtables=dtables, shadow=self.shadow, **kwargs,
        )
        prev = self.endpoint_manager.on_device_publish

        def _dual_apply(tables):
            # live-epoch patch first (the relayout-aware store
            # path), then fold the same world into the staged target
            if prev is not None:
                prev(tables)
            if plan.state == "migrating":
                dt = (
                    self.datapath_tables(policy=tables)
                    if router.dp_store is not None else None
                )
                plan.on_publish(tables, dtables=dt)

        self.endpoint_manager.on_device_publish = _dual_apply
        try:
            plan.begin()
            steps = 0
            while plan.state == "migrating":
                if plan.pending():
                    plan.step()
                    steps += 1
                    if steps > max_steps:
                        plan.rollback(reason="max_steps exceeded")
                elif plane is not None:
                    plane.run_at_batch_boundary(plan.cutover)
                else:
                    plan.cutover()
        finally:
            self.endpoint_manager.on_device_publish = prev
        return dict(plan.stats)

    def _ensure_verdict_cache(self, tables):
        """The daemon's VerdictCache, stamped to the tables about to
        be dispatched: the stamp is (publish generation, table
        layout), so any publish / repack flushes before a stale
        verdict could be served.  Returns (cache, stamp) — the
        dispatch binds its probe AND its write-back to this stamp —
        or (None, None) when the feature was disabled after this
        batch's target selection: re-creating the cache here would
        silently undo `PATCH /config {"verdict_cache": false}`'s
        promise to drop the device buffer."""
        import numpy as np

        from cilium_tpu.compiler.tables import tables_layout_version
        from cilium_tpu.engine import memo as vm

        if not self.verdict_cache_enabled:
            return None, None
        if self.verdict_cache is None:
            self.verdict_cache = vm.VerdictCache(
                n_rows=self.verdict_cache_rows
            )
        gen = int(np.asarray(tables.generation)) & 0xFFFFFFFF
        stamp = (gen, tables_layout_version(tables))
        self.verdict_cache.ensure(stamp)
        return self.verdict_cache, stamp

    def _memo_evaluate(self, tables, batch):
        """Memoized lattice dispatch (engine/memo.py): intra-batch
        dedup + the device verdict cache in front of evaluate_batch,
        bit-identical by construction.  Returns a Verdicts-like
        namespace carrying the per-tuple `cache_hit` column the flow
        plane records and the DEVICE stats row (`cache_stats`).

        NO host read happens here — the double-buffered pipeline
        keeps its host-pack/device-compute overlap.  The drain (one
        batch behind, where the verdict columns sync anyway) folds
        the stats exactly once per served batch, corrects hit/miss
        accounting to the batch's valid prefix (padding rows all
        share one key and would drown the metrics in synthetic
        hits), and — when the kernel REFUSED the batch because it
        held more distinct policy keys than the compaction capacity
        — re-dispatches it through the uncached program.  The
        commit below is safe in that case: the kernel returns the
        carried cache unchanged on overflow by construction.

        Concurrency safety: the probe and the write-back are both
        bound to OUR tables' epoch stamp — `acquire()` reads
        (stamp, rows) atomically (a concurrent publish between
        ensure and the read hands us another epoch's cache, so we
        bypass memoization for this batch) and `commit()` refuses
        the write-back when a publish flushed mid-dispatch, so
        pre-publish entries can never resurrect under the new
        stamp."""
        from types import SimpleNamespace

        import numpy as np

        from cilium_tpu.engine import memo as vm

        b = int(batch.ep_index.shape[0])
        rep_cap = max(b >> self.verdict_cache_rep_shift, min(b, 1 << 10))
        self._memo_batch_seq += 1
        backoff = (
            self.verdict_cache_overflow_streak
            >= self.verdict_cache_streak_limit
            and self._memo_batch_seq % self.verdict_cache_retry_period
        )
        cache, stamp = self._ensure_verdict_cache(tables)
        if cache is None:  # disabled mid-flight
            out = self._traced_evaluate(tables, batch)
            return SimpleNamespace(
                allowed=out.allowed,
                proxy_port=out.proxy_port,
                match_kind=out.match_kind,
                cache_hit=np.zeros(b, bool),
            )
        cur_stamp, rows_in = cache.acquire()
        if backoff or cur_stamp != stamp:
            out = self._traced_evaluate(tables, batch)
            return SimpleNamespace(
                allowed=out.allowed,
                proxy_port=out.proxy_port,
                match_kind=out.match_kind,
                cache_hit=np.zeros(b, bool),
            )
        kernel = vm.memo_evaluate_kernel(rep_cap=rep_cap)
        v, rows, hit, stats = kernel(tables, batch, rows_in)
        # memo.insert fault seam — the write-back commit is the
        # verdict-cache insert path's host half.  The fault
        # PROPAGATES (never swallowed here): guarded_dispatch retries
        # re-run the memoized attempt (kernel not donated, carried
        # cache untouched), and a persistent schedule exhausts them
        # into the dispatch breaker whose host fold serves the batch
        # bit-identically; the dispatch-failure handler flushes the
        # cache, so no partial insert can outlive the fault.
        from cilium_tpu import faultinject

        try:
            faultinject.fire("memo.insert")
        except faultinject.FaultInjected:
            metrics.memo_insert_faults_total.inc()
            raise
        cache.commit(stamp, rows)
        return SimpleNamespace(
            allowed=v.allowed,
            proxy_port=v.proxy_port,
            match_kind=v.match_kind,
            cache_hit=hit,
            cache_stats=stats,
        )

    def _fold_memo_drain(
        self, cache_stats, v, valid, padded_len, redispatch
    ):
        """THE drain-time memo fold, shared by the one-shot drain
        (_process_flows_traced._drain_oldest) and the serving
        plane's drain (serve.ServingPlane._complete) so the two can
        never diverge: when the kernel REFUSED the batch (more
        distinct keys than the compaction capacity — its verdict
        columns are unspecified, carried cache state untouched) the
        batch re-dispatches through `redispatch()` (a thunk running
        the uncached program, returning (out, degraded)); otherwise
        hit/miss accounting lands exactly once, corrected to the
        batch's valid prefix (padding rows all share one key and
        would drown the metrics in synthetic hits).  Returns
        (v, extra_degraded, overflowed) — a caller holding a shadow
        sample REFUSES it when `overflowed` (the on-device diff was
        computed against the refused kernel's unspecified columns,
        so folding it would not be a two-pinned-worlds diff)."""
        from types import SimpleNamespace

        import numpy as np

        from cilium_tpu.engine import memo as vm

        s = np.asarray(cache_stats).astype(np.int64)
        deg = False
        overflowed = bool(int(s[vm.STAT_OVERFLOW]))
        if overflowed:
            self.verdict_cache_overflow_streak += 1
            out2, deg = redispatch()
            v = SimpleNamespace(
                allowed=np.asarray(out2.allowed)[:valid],
                match_kind=np.asarray(out2.match_kind)[:valid],
                proxy_port=np.asarray(out2.proxy_port)[:valid],
                cache_hit=np.zeros(valid, bool),
            )
        else:
            self.verdict_cache_overflow_streak = 0
            if valid < int(padded_len):
                s = s.copy()
                s[vm.STAT_HIT] = int(v.cache_hit.sum())
                s[vm.STAT_TUPLES] = int(valid)
        if self.verdict_cache is not None:
            self.verdict_cache.account(s)
        return v, deg, overflowed

    # -- shadow policy rollout (cilium_tpu.shadow) ----------------------------

    @staticmethod
    def _attach_shadow(out, ticket, scols):
        """Wrap a single-chip dispatch result with its shadow sample
        (lazy shadow columns + on-device diff codes): the drain folds
        or refuses the ticket exactly once."""
        from types import SimpleNamespace

        return SimpleNamespace(
            allowed=out.allowed,
            proxy_port=out.proxy_port,
            match_kind=out.match_kind,
            cache_hit=getattr(out, "cache_hit", None),
            cache_stats=getattr(out, "cache_stats", None),
            shadow_ticket=ticket,
            shadow_cols=scols,
        )

    def _attach_shadow_routed(self, out, res, ticket):
        """The mesh-path twin of _attach_shadow: the router already
        synced both legs' columns, so the diff codes fold host-side
        through the SAME diff_codes definition the device kernel
        jits."""
        from types import SimpleNamespace

        import numpy as np

        from cilium_tpu import shadow as shadow_mod

        sv = res.shadow_verdicts
        if sv is None:
            self.shadow.refuse(ticket)
            return out
        ca, cp, ck, trans = shadow_mod.diff_codes(
            np.asarray(out.allowed),
            np.asarray(out.proxy_port),
            np.asarray(out.match_kind),
            np.asarray(sv.allowed),
            np.asarray(sv.proxy_port),
            np.asarray(sv.match_kind),
            xp=np,
        )
        return SimpleNamespace(
            allowed=out.allowed,
            proxy_port=out.proxy_port,
            match_kind=out.match_kind,
            shadow_ticket=ticket,
            shadow_cols={
                "allowed": sv.allowed,
                "proxy_port": sv.proxy_port,
                "match_kind": sv.match_kind,
                "ca": ca,
                "cp": cp,
                "ck": ck,
                "trans": trans,
            },
        )

    def _fold_shadow_drain(
        self, out, v, valid, *, ep_ids, src_identities,
        dst_identities, dports, protos, directions, tenant,
        trace_id, refuse=False,
    ):
        """THE drain-time shadow fold, shared by the one-shot drain
        and the serving plane's drain: folds (or refuses) a sampled
        batch's ticket exactly once and returns the per-row
        transition codes (np.uint8, 0 = unchanged) for the flow
        plane's diff-status join — None when unsampled/refused.
        ``refuse`` forces a clean refusal (drain-time failover or a
        memo overflow re-dispatch invalidated the on-device diff)."""
        ticket = getattr(out, "shadow_ticket", None)
        if ticket is None:
            return None
        scols = getattr(out, "shadow_cols", None)
        if refuse or scols is None:
            self.shadow.refuse(ticket)
            return None
        try:
            return self.shadow.fold(
                ticket, v, scols, valid,
                ep_ids=ep_ids,
                src_identities=src_identities,
                dst_identities=dst_identities,
                dports=dports,
                protos=protos,
                directions=directions,
                tenant=tenant,
                trace_id=trace_id,
            )
        except Exception as exc:  # noqa: BLE001 — the shadow fold
            # must never take the live drain down
            log.warning(
                "shadow diff fold failed; sample refused",
                extra={"fields": {"error": str(exc)}},
            )
            self.shadow.refuse(ticket)
            return None

    def _dispatch_or_degrade(
        self, tables, batch, host_args, pad_to: int,
        use_memo: bool = True, host_cols=None,
        shadow_sample: bool = True,
    ):
        """One batch through the guarded device dispatch: the
        engine.dispatch fault seam fires first, the watchdog bounds
        the launch, retry_call absorbs transient failures (counted in
        dispatch_retries_total), and the circuit breaker decides
        admission.  On breaker-open or exhausted retries the batch is
        served by the bit-identical host lattice fold
        (engine.hostpath.lattice_fold_host) — the stream completes,
        degraded_batches_total counts the failover.

        Mesh routing: when a ChipFailoverRouter is attached with
        route_dispatch and holds a published epoch, the batch goes
        THROUGH the per-chip failure domain instead — `host_cols`
        (a thunk returning the UNPADDED host tuple columns) feeds
        router.dispatch, whose verdicts come back in stream order
        and bit-identical whatever the survivor set; a router error
        falls back to the single-chip path below.

        Returns (verdicts, degraded flag); verdicts satisfy the
        Verdicts contract (allowed/proxy_port/match_kind, padded on
        the single-chip path, exactly valid-length on the mesh
        path — callers slice [:valid] either way).

        Span-plane attribution: the device attempt runs under an
        `engine.dispatch` span (error status + breaker events when it
        fails); the failover fold
        runs under `engine.hostpath` — one trace shows which plane
        served the batch and why."""
        from cilium_tpu.engine.hostpath import lattice_fold_host
        from cilium_tpu.engine.verdict import evaluate_batch
        from cilium_tpu.resilience import guarded_dispatch

        if (
            self.mesh_router is not None
            and self.mesh_route_dispatch
            and host_cols is not None
            and self.mesh_router.store.current() is not None
        ):
            # shadow sampling on the routed path: the pinned-stamp
            # ticket is drawn against the manager's published epoch
            # (the stamp family the arm pinned); the shadow gather
            # rides the router's re-split batch through the routed
            # evaluators (dispatch(shadow=...)).  Drain-time
            # re-dispatches pass shadow_sample=False — their batch's
            # ticket already exists and must resolve exactly once.
            ticket = (
                self.shadow.sample_ticket(tables)
                if shadow_sample
                else None
            )
            shadow_args = None
            if ticket is not None:
                # the router serves ITS store's current epoch, which
                # the auto-publish hook advances independently of the
                # `tables` snapshot the ticket was drawn against: the
                # router stamp must match the pinned live stamp both
                # BEFORE and AFTER the dispatch, else the live leg
                # may have served a third world — refuse the sample
                # (stamps only move forward, so an equal bracket
                # pins the served epoch exactly)
                rstamp = self.mesh_router.store.current_stamp()
                if (
                    rstamp is None
                    or (int(rstamp) & 0xFFFFFFFF)
                    != ticket["live_gen"]
                ):
                    self.shadow.refuse(ticket)
                    ticket = None
            if ticket is not None:
                try:
                    shadow_args = self.shadow.routed_args(
                        self.mesh_router
                    )
                except Exception as exc:  # noqa: BLE001
                    log.warning(
                        "shadow routed epoch unavailable; sample "
                        "refused",
                        extra={"fields": {"error": str(exc)}},
                    )
                    self.shadow.refuse(ticket)
                    ticket = None
            try:
                res = self.mesh_router.dispatch(
                    *host_cols(), shadow=shadow_args
                )
            except Exception as exc:  # router unserviceable: fall
                # back to the single-chip path under the
                # process-wide breaker (the router's own terminal
                # fold only fires when it CAN host-fold)
                if ticket is not None:
                    self.shadow.refuse(ticket)
                log.warning(
                    "mesh router dispatch failed; serving batch "
                    "from the single-chip path",
                    extra={"fields": {"error": str(exc)}},
                )
            else:
                if res.degraded:
                    self.degraded_batches += 1
                out = res.verdicts
                if ticket is not None:
                    # the AFTER half of the stamp bracket: a publish
                    # that advanced the router mid-dispatch makes
                    # which epoch served ambiguous — refuse
                    rstamp = self.mesh_router.store.current_stamp()
                    if (
                        res.degraded
                        or rstamp is None
                        or (int(rstamp) & 0xFFFFFFFF)
                        != ticket["live_gen"]
                    ):
                        self.shadow.refuse(ticket)
                    else:
                        out = self._attach_shadow_routed(
                            out, res, ticket
                        )
                return out, res.degraded
        if self._traced_evaluate is None:
            # jit-cache hit/miss accounting on the serving entry
            # point (a fresh batch shape class = an XLA recompile the
            # stream waits for)
            self._traced_evaluate = tracing.track_jit(
                evaluate_batch, "engine.dispatch"
            )
        target = (
            self._memo_evaluate
            if (self.verdict_cache_enabled and use_memo)
            else self._traced_evaluate
        )
        if self.dispatch_breaker.allow():
            with self.tracer.span(
                "engine.dispatch", site="engine.dispatch"
            ) as sp:
                try:
                    out = guarded_dispatch(
                        target,
                        tables,
                        batch,
                        retries=self.dispatch_retries,
                        base_delay=self.dispatch_retry_base,
                        watchdog=self.dispatch_watchdog,
                    )
                    self.dispatch_breaker.record_success()
                    dispatched = out
                except Exception as exc:
                    sp.status = "error"
                    sp.attrs["error"] = str(exc)
                    # a memoized attempt may have committed lazy
                    # rows tied to the failed computation
                    if use_memo and self.verdict_cache is not None:
                        self.verdict_cache.flush(
                            reason="dispatch-failure"
                        )
                    self.dispatch_breaker.record_failure(str(exc))
                    log.warning(
                        "device dispatch failed; serving batch from "
                        "host path",
                        extra={"fields": {"error": str(exc)}},
                    )
                    dispatched = None
            if dispatched is not None:
                # shadow sampling (single-chip path): the SECOND
                # dispatch rides the already-staged TupleBatch
                # against the shadow epoch, diffed on device
                # (shadow.dispatch / shadow.diff spans nest under
                # this batch's dispatch span); columns stay lazy —
                # the drain folds them one batch behind.  Drain-time
                # re-dispatches never draw a second ticket.
                ticket = (
                    self.shadow.sample_ticket(tables)
                    if shadow_sample
                    else None
                )
                if ticket is not None:
                    scols = self.shadow.evaluate(
                        ticket, batch, dispatched
                    )
                    if scols is not None:
                        dispatched = self._attach_shadow(
                            dispatched, ticket, scols
                        )
                return dispatched, False
        with self.tracer.span(
            "engine.hostpath", site="engine.hostpath",
            attrs={"failover": True},
        ):
            states, ep_index, identity, dport, proto, direction, frag = (
                host_args()
            )
            out = lattice_fold_host(
                states, ep_index, identity, dport, proto, direction,
                is_fragment=frag, pad_to=pad_to,
            )
        self.degraded_batches += 1
        metrics.degraded_batches_total.inc()
        return out, True

    def service_upsert(
        self, frontend, backends
    ):
        """PUT /service (daemon/loadbalancer.go SVCAdd)."""
        with self.lock:
            svc = self.services.upsert(frontend, backends)
        return svc

    def service_delete(self, frontend) -> bool:
        with self.lock:
            return self.services.delete(frontend)

    def config_patch(self, changes: Dict) -> Dict:
        """PATCH /config (daemon config handler + pkg/option runtime
        options): apply named boolean option changes and the mutable
        enforcement mode; verdict-affecting changes trigger a full
        regeneration, exactly as the reference recompiles on config
        change (config IS part of the compiled program — the options
        feed the compiler cache key)."""
        from cilium_tpu import faultinject

        applied = 0
        verdict_affecting = False
        with self.lock:
            # validate EVERYTHING before mutating anything: a partial
            # apply followed by a 400 would silently diverge daemon
            # state from what the client believes.  Validation is the
            # option LIBRARY's parse+verify (option.go ParseOption):
            # booleans for most options, level names/ints for
            # MonitorAggregationLevel, NAT46's unsupported-gate, etc.
            raw_opts = changes.get("options") or {}
            for k, v in raw_opts.items():
                option.Config.opts.parse_validate(k, v)
            # fault-site arming ({"faults": {site: spec | null}}) —
            # the config_patch surface of the chaos framework;
            # validated up front like the options
            raw_faults = changes.get("faults") or {}
            parsed_faults = {}
            for site, spec in raw_faults.items():
                if site not in faultinject.SITES:
                    raise ValueError(
                        f"unknown fault site {site!r}"
                    )
                parsed_faults[site] = (
                    None
                    if spec is None
                    else faultinject.FaultSpec.parse(spec)
                )
            enforcement = changes.get("policy_enforcement")
            if enforcement is not None and enforcement not in (
                option.DEFAULT_ENFORCEMENT,
                option.ALWAYS_ENFORCE,
                option.NEVER_ENFORCE,
            ):
                raise ValueError(
                    f"unknown enforcement mode {enforcement!r}"
                )
            verdict_cache = changes.get("verdict_cache")
            if verdict_cache is not None and not isinstance(
                verdict_cache, bool
            ):
                raise ValueError(
                    "verdict_cache must be a boolean, got "
                    f"{verdict_cache!r}"
                )
            online_retune = changes.get("online_retune")
            if online_retune is not None and not isinstance(
                online_retune, bool
            ):
                raise ValueError(
                    "online_retune must be a boolean, got "
                    f"{online_retune!r}"
                )
            # serving-plane tenant fairness weights ({"tenant_
            # weights": {name: weight}}): validated up front like
            # the options; weight must be a positive number
            tenant_weights = changes.get("tenant_weights")
            if tenant_weights is not None:
                if not isinstance(tenant_weights, dict):
                    raise ValueError(
                        "tenant_weights must be an object of "
                        f"name: weight, got {tenant_weights!r}"
                    )
                for name, w in tenant_weights.items():
                    if (
                        isinstance(w, bool)
                        or not isinstance(w, (int, float))
                        or w <= 0
                    ):
                        raise ValueError(
                            f"tenant weight {name!r} must be a "
                            f"positive number, got {w!r}"
                        )
            # named SLO classes ({"slo_classes": {name: {deadline_ms,
            # shed_priority, weight} | null}}) + tenant assignment
            # ({"tenant_slo": {tenant: class | null}}): validated up
            # front; null deletes
            slo_classes = changes.get("slo_classes")
            if slo_classes is not None:
                if not isinstance(slo_classes, dict):
                    raise ValueError(
                        "slo_classes must be an object of name: "
                        f"bundle, got {slo_classes!r}"
                    )
                for cname, bundle in slo_classes.items():
                    if bundle is None:
                        continue
                    if not isinstance(bundle, dict):
                        raise ValueError(
                            f"slo class {cname!r} must be an "
                            f"object, got {bundle!r}"
                        )
                    unknown = set(bundle) - {
                        "deadline_ms", "shed_priority", "weight",
                    }
                    if unknown:
                        raise ValueError(
                            f"slo class {cname!r}: unknown keys "
                            f"{sorted(unknown)}"
                        )
                    dl = bundle.get("deadline_ms")
                    if dl is not None and (
                        isinstance(dl, bool)
                        or not isinstance(dl, (int, float))
                        or dl <= 0
                    ):
                        raise ValueError(
                            f"slo class {cname!r}: deadline_ms "
                            f"must be a positive number, got {dl!r}"
                        )
                    pr = bundle.get("shed_priority")
                    if pr is not None and (
                        isinstance(pr, bool)
                        or not isinstance(pr, int)
                        or pr < 0
                    ):
                        raise ValueError(
                            f"slo class {cname!r}: shed_priority "
                            f"must be an int >= 0, got {pr!r}"
                        )
                    w = bundle.get("weight")
                    if w is not None and (
                        isinstance(w, bool)
                        or not isinstance(w, (int, float))
                        or w <= 0
                    ):
                        raise ValueError(
                            f"slo class {cname!r}: weight must be "
                            f"a positive number, got {w!r}"
                        )
            tenant_slo = changes.get("tenant_slo")
            if tenant_slo is not None:
                if not isinstance(tenant_slo, dict):
                    raise ValueError(
                        "tenant_slo must be an object of tenant: "
                        f"class, got {tenant_slo!r}"
                    )
                future_classes = dict(self.slo_classes)
                for cname, bundle in (slo_classes or {}).items():
                    if bundle is None:
                        future_classes.pop(cname, None)
                    else:
                        future_classes[cname] = bundle
                for tname, cname in tenant_slo.items():
                    if cname is not None and (
                        not isinstance(cname, str)
                        or cname not in future_classes
                    ):
                        raise ValueError(
                            f"tenant {tname!r} references unknown "
                            f"slo class {cname!r}"
                        )
            if raw_opts:
                ct_before = option.Config.opts.is_enabled(
                    option.CONNTRACK
                )
                applied += option.Config.opts.apply(
                    dict(raw_opts), changed_hook=self._option_changed
                )
                # conntrack on/off changes verdict semantics
                # (REPLY/RELATED bypass exists only with CT) — and it
                # can flip via DEPENDENCY propagation (enabling
                # ConntrackAccounting enables Conntrack), so compare
                # states instead of checking the request keys
                if option.Config.opts.is_enabled(
                    option.CONNTRACK
                ) != ct_before:
                    verdict_affecting = True
            if enforcement is not None:
                if option.Config.policy_enforcement != enforcement:
                    option.Config.policy_enforcement = enforcement
                    applied += 1
                    verdict_affecting = True
            # verdict memoization toggle: bit-identical by
            # construction, so no regeneration sweep (counted after
            # the regen trigger below); disabling drops the cache
            # (and its HBM) immediately
            vc_applied = 0
            if (
                verdict_cache is not None
                and verdict_cache != self.verdict_cache_enabled
            ):
                self.verdict_cache_enabled = verdict_cache
                if not verdict_cache:
                    self.verdict_cache = None
                vc_applied = 1
            # online re-tune arming: verdict-neutral (the swap
            # itself is bit-identical by the layout-stamp seam)
            if (
                online_retune is not None
                and online_retune != self.online_retune_enabled
            ):
                self.online_retune_enabled = online_retune
                vc_applied += 1
            # fairness weights apply immediately to the live plane
            # (verdict-neutral — no regeneration)
            tw_applied = 0
            if tenant_weights is not None:
                for name, w in tenant_weights.items():
                    if self.tenant_weights.get(name) != float(w):
                        tw_applied += 1
                    self.tenant_weights[name] = float(w)
                if self.serving is not None:
                    self.serving.set_tenant_weights(
                        self.tenant_weights
                    )
            # SLO classes + tenant assignment: live-applied like the
            # weights (verdict-neutral)
            slo_applied = 0
            if slo_classes is not None:
                for cname, bundle in slo_classes.items():
                    if bundle is None:
                        if self.slo_classes.pop(cname, None):
                            slo_applied += 1
                    elif self.slo_classes.get(cname) != bundle:
                        self.slo_classes[cname] = dict(bundle)
                        slo_applied += 1
            if tenant_slo is not None:
                for tname, cname in tenant_slo.items():
                    if cname is None:
                        if self.tenant_slo.pop(tname, None):
                            slo_applied += 1
                    elif self.tenant_slo.get(tname) != cname:
                        self.tenant_slo[tname] = cname
                        slo_applied += 1
            if (
                (slo_classes is not None or tenant_slo is not None)
                and self.serving is not None
            ):
                self.serving.set_slo_classes(
                    self.slo_classes, self.tenant_slo
                )
            # fault arming applies last and never triggers a regen
            # sweep (it changes no compiled state)
            fault_applied = 0
            for site, spec in parsed_faults.items():
                if spec is None:
                    if faultinject.disarm(site):
                        fault_applied += 1
                else:
                    faultinject.arm(site, spec)
                    fault_applied += 1
        if applied:
            # enforcement changes alter verdicts → full sweep; pure
            # observability toggles (tracing, notifications) do not
            self.trigger_policy_updates(
                "configuration changed", full=verdict_affecting
            )
        applied += fault_applied + vc_applied + tw_applied
        applied += slo_applied
        return {
            "applied": applied,
            "policy_enforcement": option.Config.policy_enforcement,
            "options": dict(option.Config.opts),
            "faults": faultinject.armed(),
            "verdict_cache": self.verdict_cache_enabled,
            "online_retune": self.online_retune_enabled,
            "tenant_weights": dict(self.tenant_weights),
            "slo_classes": dict(self.slo_classes),
            "tenant_slo": dict(self.tenant_slo),
        }

    def _option_changed(self, name: str, value: int) -> None:
        """Behavioral hooks behind runtime options (the analog of the
        reference regenerating datapath programs whose #defines
        changed): logging levels flip immediately; disabling
        conntrack flushes the table the way the agent tears down CT
        state when CONNTRACK is compiled out."""
        import logging as _pylogging

        from cilium_tpu import logging as tpulog

        if name == option.DEBUG:
            tpulog.set_level(
                _pylogging.DEBUG if value else _pylogging.INFO
            )
        elif name == option.DEBUG_LB:
            tpulog.set_level(
                _pylogging.DEBUG if value else _pylogging.INFO,
                subsys="lb",
            )
        elif name == option.CONNTRACK_ACCOUNTING:
            self.ct.accounting = bool(value)
        elif name == option.CONNTRACK and not value:
            self.ct.entries.clear()
            self.ct.mutations += 1

    def endpoint_config_patch(
        self, endpoint_id: int, changes: Dict
    ) -> Dict:
        """`cilium endpoint config` (pkg/endpoint applyOptsLocked):
        apply per-endpoint option changes and queue THAT endpoint's
        regeneration — per-endpoint config is compiled state in the
        reference (it lands in the generated header)."""
        opts = changes.get("options") or {}
        for k, v in opts.items():
            option.Config.opts.parse_validate(k, v)
        with self.lock:
            endpoint = self.endpoint_manager.lookup(endpoint_id)
            if endpoint is None:
                raise KeyError(f"no endpoint {endpoint_id}")
            with endpoint.lock:
                applied = endpoint.opts.apply(dict(opts))
            if applied:
                # force THIS endpoint's recompute through the delta
                # sweep (the revision gate would skip it otherwise) —
                # a per-endpoint toggle must not recompile the fleet
                endpoint.force_policy_compute = True
        if applied:
            self.trigger_policy_updates(
                f"endpoint {endpoint_id} config changed"
            )
        return {
            "applied": applied,
            "options": dict(endpoint.opts),
        }

    def verdict_notification_endpoints(self) -> set:
        """Endpoint ids with per-endpoint PolicyVerdictNotification on
        (plus all when the global option is set): the monitor fold's
        allowed-verdict scope."""
        from cilium_tpu.option import POLICY_VERDICT_NOTIFICATION

        eps = self.endpoint_manager.endpoints()
        if option.Config.opts.is_enabled(POLICY_VERDICT_NOTIFICATION):
            return {ep.id for ep in eps}
        return {
            ep.id
            for ep in eps
            if ep.opts.is_enabled(POLICY_VERDICT_NOTIFICATION)
        }

    # -- serving-path building blocks (shared with cilium_tpu.serve) ---------

    def _resolve_serving_tables(self):
        """One serving snapshot: (version, dispatch tables, endpoint
        index, host map states) — the tables AND the states they were
        compiled from, read under one lock so the degraded host fold
        stays bit-identical to the device path whatever regenerations
        land mid-stream.  The dispatch tables are the device-resident
        epoch when publication succeeds (delta-scoped scatter for a
        policy change since the last call); a failed publication
        latches a 30 s backoff and dispatches the host arrays.
        Shared by process_flows and the serving plane's batch loop."""
        import time as _time

        version, tables, index, host_states = (
            self.endpoint_manager.published_with_states()
        )
        if tables is None:
            raise RuntimeError("no published tables")
        if _time.monotonic() >= self._device_publish_retry_at:
            try:
                # epoch lookup/publication under its own span: a
                # trace distinguishes "the batch was slow" from "the
                # batch paid a delta scatter / full upload first"
                with self.tracer.span(
                    "publish.epoch_lookup", site="engine.publish",
                    attrs={"version": version},
                ):
                    tables = self.endpoint_manager.device_tables_for(
                        tables
                    )
            except Exception as exc:  # device down → numpy tables
                self._device_publish_retry_at = (
                    _time.monotonic() + 30.0
                )
                log.warning(
                    "device table publication failed; dispatching "
                    "host arrays (retrying in 30s)",
                    extra={"fields": {"error": str(exc)}},
                )
        return version, tables, index, host_states

    def _flow_luts(self, index):
        """Endpoint-axis LUTs the verdict folds translate through:
        (local identity per axis slot, endpoint id per axis slot).
        Flow records orient each tuple as src→dst — the local
        endpoint is the DESTINATION of an ingress flow and the
        SOURCE of an egress one (the send_trace_notify convention).
        Shared by process_flows and the serving plane."""
        import numpy as np

        size = max(index.values(), default=0) + 1
        local_ident_lut = np.zeros(size, dtype=np.int64)
        rev_lut = np.zeros(size, dtype=np.int64)
        for ep_id, idx in index.items():
            rev_lut[idx] = ep_id
            ep = self.endpoint_manager.lookup(ep_id)
            if ep is not None and ep.security_identity is not None:
                local_ident_lut[idx] = ep.security_identity.id
        return local_ident_lut, rev_lut

    def _prefilter_records(
        self, rec, index, local_ident_lut, tenant="", trace_id="",
    ):
        """XDP prefilter over a decoded record SoA (the daemon-owned
        deny-by-CIDR set, bpf_xdp.c): flows from denied sources drop
        BEFORE the policy program, count under the canonical CIDR
        reason, and land in the flow plane as real drops.  Returns
        (filtered rec, n_prefiltered).  Shared by process_flows and
        the serving plane's submit path."""
        import numpy as np

        from cilium_tpu.flow import capture_batch
        from cilium_tpu.replay import _ep_index_of

        prefilter_cidrs = self.prefilter.dump()
        if not prefilter_cidrs:
            return rec, 0
        import ipaddress as _ipaddress

        from cilium_tpu.monitor.events import drop_reason_name

        hit = np.zeros(len(rec["saddr"]), bool)
        saddr = rec["saddr"].astype(np.uint64)
        for cidr in prefilter_cidrs:
            net = _ipaddress.ip_network(cidr, strict=False)
            if net.version != 4:
                continue
            hit |= (saddr & int(net.netmask)) == int(
                net.network_address
            )
        n_prefiltered = int(hit.sum())
        if not n_prefiltered:
            return rec, 0
        for dirv, dname in ((0, "INGRESS"), (1, "EGRESS")):
            count = int((hit & (rec["direction"] == dirv)).sum())
            if count:
                metrics.drop_count.inc(
                    drop_reason_name(-162), dname, value=count,
                )
        pre_idx = _ep_index_of(
            {"ep_id": rec["ep_id"][hit]}, dict(index)
        )
        pre_dirs = rec["direction"][hit]
        pre_peer = rec["identity"][hit].astype(np.int64)
        pre_local = local_ident_lut[pre_idx]
        capture_batch(
            self.flow_store,
            ep_ids=rec["ep_id"][hit],
            src_identities=np.where(
                pre_dirs == 0, pre_peer, pre_local
            ),
            dst_identities=np.where(
                pre_dirs == 0, pre_local, pre_peer
            ),
            dports=rec["dport"][hit],
            protos=rec["proto"][hit],
            directions=pre_dirs,
            allowed=np.zeros(n_prefiltered, bool),
            match_kind=np.zeros(n_prefiltered, np.int32),
            pre_dropped=np.ones(n_prefiltered, bool),
            allow_sample=0,
            metrics_registry=metrics,
            trace_id=trace_id,
            tenant=tenant,
        )
        rec = {k: v[~hit] for k, v in rec.items()}
        return rec, n_prefiltered

    def datapath_tables(self, policy=None, subword=None):
        """Assemble the FUSED DatapathTables from the daemon's
        current state — published policy tables + the ipcache
        listener's CIDR→identity view (idx-specialized) + the CT map
        snapshot + compiled services + the prefilter set.  This is
        the world ChipFailoverRouter.attach_datapath serves, and
        what the fused serving plane re-publishes on churn.

        `policy` pins the policy tables to an EXACT snapshot (the
        auto-publish listener passes the tables it just installed,
        so the router's lattice epoch and its fused epoch can never
        come from two different regenerates); None reads the current
        published tables.  The CT entry dict and the service map are
        shallow-snapshotted before compilation — the ct-gc
        controller thread mutates the live CTMap without the daemon
        lock, and iterating it directly would race.

        `subword` (default: the `datapath_subword` config option)
        applies the sub-word hot-lane transform
        (engine.datapath.subword_datapath_tables) to the assembled
        world — planes whose semantics don't fit their compact
        fields keep the wide layout.  The transform is a pure,
        deterministic function of the assembled tables, so the
        DatapathStore's row-diff delta still ships O(change) bytes
        through churn, and every width joins the layout stamp the
        store refuses cross-layout deltas on."""
        import copy

        from cilium_tpu.ct.device import compile_ct
        from cilium_tpu.engine.datapath import DatapathTables
        from cilium_tpu.ipcache.lpm import (
            build_ipcache,
            specialize_ipcache_to_idx,
        )
        from cilium_tpu.lb.device import compile_lb
        from cilium_tpu.prefilter import build_prefilter

        pol = policy
        if pol is None:
            _, pol, _ = self.endpoint_manager.published()
        if pol is None:
            raise RuntimeError("no published tables")
        with self.lock:
            mappings = dict(self.lpm_builder.mappings)
            prefilter_cidrs = self.prefilter.dump()
            services = copy.copy(self.services)
            services.by_frontend = dict(self.services.by_frontend)
        # dict() of the entries is atomic under the GIL; entry
        # values are only ever replaced, not mutated in the packed
        # fields, so the shallow snapshot is a consistent view
        ct_snap = copy.copy(self.ct)
        ct_snap.entries = dict(self.ct.entries)
        ipc = specialize_ipcache_to_idx(
            build_ipcache(mappings), pol
        )
        dt = DatapathTables(
            prefilter=build_prefilter(prefilter_cidrs),
            ipcache=ipc,
            ct=compile_ct(ct_snap),
            lb=compile_lb(services),
            policy=pol,
        )
        if subword is None:
            subword = bool(getattr(self, "datapath_subword", False))
        ct_lanes = getattr(self, "datapath_ct_lanes", None)
        if subword:
            from cilium_tpu.engine.datapath import (
                subword_datapath_tables,
            )

            dt, _report = subword_datapath_tables(
                dt, ct_lanes=ct_lanes
            )
        else:
            # plane-scoped lane overrides from the online autotuner
            # sweep (retune_candidates' CT/ipcache width grid) apply
            # without the global sub-word transform; a plane whose
            # semantics don't fit keeps its wide layout
            import dataclasses as _dc

            if ct_lanes:
                from cilium_tpu.ct.device import compact_ct_snapshot

                try:
                    dt = _dc.replace(
                        dt,
                        ct=compact_ct_snapshot(
                            dt.ct, lanes=int(ct_lanes)
                        ),
                    )
                except ValueError:
                    pass
            if getattr(self, "datapath_ip_subword", False):
                from cilium_tpu.ipcache.lpm import (
                    IPCacheDevice,
                    subword_ipcache,
                )

                if (
                    isinstance(dt.ipcache, IPCacheDevice)
                    and dt.ipcache.values_are_idx
                ):
                    try:
                        dt = _dc.replace(
                            dt, ipcache=subword_ipcache(dt.ipcache)
                        )
                    except ValueError:
                        pass
        return dt

    def serving_plane(self, **overrides):
        """The daemon's continuous serving plane
        (cilium_tpu.serve.ServingPlane), created and started on
        first use — the steady-state ingest pipeline behind
        `POST /datapath/flows?stream=1` and `cilium-tpu
        serve-bench`.  Constructor overrides apply only on first
        creation (the plane is one shared queue)."""
        with self.lock:
            if self.serving is None:
                from cilium_tpu.serve import ServingPlane

                self.serving = ServingPlane(
                    self,
                    tenant_weights=dict(self.tenant_weights),
                    slo_classes=dict(self.slo_classes),
                    tenant_slo=dict(self.tenant_slo),
                    **overrides,
                )
                self.serving.start()
            return self.serving

    def maybe_online_retune(self) -> "Optional[dict]":
        """The serving loop's retune poll (every 64 completed
        batches): delegate to engine.autotune.online_retune when the
        operator armed it, never concurrently, and never let a
        controller fault take down the serve loop — a missed retune
        is a performance bug, a dead plane is an outage."""
        if not self.online_retune_enabled:
            return None
        if not self._retune_inflight.acquire(blocking=False):
            return None  # one controller at a time
        try:
            from cilium_tpu.engine.autotune import online_retune

            return online_retune(
                self, config=self.online_retune_config
            )
        except Exception:
            log.exception("online retune failed (serve loop kept)")
            return None
        finally:
            self._retune_inflight.release()

    def _perf_byte_model(self, leaves: bool = False) -> Dict:
        """The gatherprof/autotune byte model evaluated LIVE: the
        published layout stamp's hot/cold bytes-per-tuple, shrunk by
        the OBSERVED verdict-cache dedup/hit factors, and priced
        into a modeled GB/s gauge at the perf plane's measured
        verdicts/s EWMA.  The per-leaf breakdown rides along on
        demand (`leaves=True` ≙ /debug/perf?leaves=1).  The static
        walk is cached per (generation, layout)."""
        from cilium_tpu.compiler.tables import tables_layout_version
        from cilium_tpu.engine import autotune

        gen, pol, _ = self.endpoint_manager.published()
        if pol is None:
            return {"published": False}
        layout = tables_layout_version(pol)
        cached = self._perf_model_cache
        if cached is None or cached[0] != (gen, layout):
            try:
                dt = self.datapath_tables(policy=pol)
            except Exception:
                return {"published": False}
            profile = autotune.hot_gather_profile(dt)
            hot = sum(
                r["bytes_per_tuple"] for r in profile
                if r["plane"] == "hot"
            )
            cold = sum(
                r["bytes_per_tuple"] for r in profile
                if r["plane"] == "cold"
            )
            cached = ((gen, layout), hot, cold, profile)
            self._perf_model_cache = cached
        _, hot, cold, profile = cached
        hits = metrics.verdict_cache_hits_total.get()
        misses = metrics.verdict_cache_misses_total.get()
        inserts = metrics.verdict_cache_insertions_total.get()
        lookups = hits + misses
        hit_rate = hits / lookups if lookups else 0.0
        # observed intra-batch dedup on the missed population:
        # tuples evaluated per representative inserted
        dedup = misses / inserts if inserts else 1.0
        effective = (
            hot / max(dedup, 1.0) * (1.0 - hit_rate)
            if lookups
            else hot
        )
        vps = self.perf.verdicts_per_sec()
        model = {
            "published": True,
            "generation": gen,
            "layout_stamp": layout,
            "hot_bytes_per_tuple": hot,
            "cold_bytes_per_tuple": cold,
            "effective_bytes_per_tuple": effective,
            "cache_hit_rate": hit_rate,
            "dedup_factor": max(dedup, 1.0),
            "modeled_gbps": effective * vps / 1e9,
        }
        metrics.perf_model_bytes_per_tuple.set("hot", value=hot)
        metrics.perf_model_bytes_per_tuple.set("cold", value=cold)
        metrics.perf_model_bytes_per_tuple.set(
            "effective", value=effective
        )
        metrics.perf_model_gbps.set(value=model["modeled_gbps"])
        if leaves:
            model["leaves"] = profile
        return model

    def perf_snapshot(
        self, since: "Optional[int]" = None, leaves: bool = False
    ) -> Dict:
        """GET /debug/perf — the live performance plane in one
        document: phase windows + stall/SLO ledger (PerfPlane
        .snapshot, since-cursor honored), the serving plane's own
        snapshot, the live byte model, dispatch-overlap bookkeeping
        and per-chip HBM via the store's chip_bytes seam.  Also the
        payload behind `cilium-tpu top` and bugtool's perf.json."""
        snap = self.perf.snapshot(since=since)
        snap["byte_model"] = self._perf_byte_model(leaves=leaves)
        plane = self.serving
        if plane is not None:
            snap["serving"] = plane.snapshot()
            d = getattr(plane, "_dispatcher", None)
            if d is not None:
                snap["overlap"] = {
                    "pack_s": d.pack_s,
                    "block_s": d.block_s,
                    "wall_s": d.wall_s,
                    "submitted": d.submitted,
                    "failed": d.failed,
                }
        store = self.endpoint_manager._device_store
        if store is not None:
            try:
                snap["hbm"] = {
                    "chip_bytes": {
                        str(k): int(v)
                        for k, v in (store.chip_bytes() or {}).items()
                    }
                }
            except Exception:  # pragma: no cover — defensive
                pass
        return snap

    def process_flows(
        self,
        buf: bytes,
        batch_size: int = 1 << 20,
        collect_verdicts: bool = False,
        async_depth: "Optional[int]" = None,
        tenant: str = "",
    ) -> "object":
        """Datapath execution under the agent with monitor folding —
        the production path behind `cilium monitor`: replay the
        record stream through the PUBLISHED lattice tables and fold
        every batch's verdicts into the monitor bus (drops always;
        allowed-verdict events for endpoints with
        PolicyVerdictNotification on, per-endpoint or global).

        This is the Hubble-style audit form (identity pre-resolved in
        the record); it reads verdict bits back per batch, which is
        the monitoring cost the reference pays through its perf ring.

        Resilience semantics (the graceful-degradation contract the
        chaos storm asserts): a malformed record buffer raises a
        clean ValueError (HTTP 400 at the API seam); device dispatch
        runs under retry + the dispatch circuit breaker and fails
        over per batch to the bit-identical host lattice fold —
        the verdict stream completes, bit-identical, with
        degraded_batches_total counting the failovers; bounded
        admission (self.admission) sheds whole batches under the
        canonical Overload drop reason instead of queueing
        unboundedly.

        With `collect_verdicts` the per-tuple verdict columns of
        every evaluated batch land in stats.verdicts (allowed /
        match_kind / proxy_port, stream order) — the chaos harness's
        bit-identity probe.

        Dispatch is double-buffered (`async_depth`, default
        self.dispatch_async_depth = 1): the host packs batch N+1
        while the device computes batch N, and results drain one
        batch behind in submission order — event/flow/telemetry
        folds see identical ordering and counts to synchronous
        serving (async_depth=0).  A device failure surfacing at
        drain time fails over that in-flight batch to the host fold
        under the breaker, same as a submit-time failure.

        Flow observability: every batch additionally folds into
        self.flow_store (cilium_tpu.flow) — ALL drops plus allows
        head-sampled per the MonitorAggregationLevel knob, classified
        through the same telemetry_masks definitions as the PR 1
        histogram.  Shed (Overload) flows are accounted in metrics
        only: building per-flow records under overload would amplify
        the overload being shed.  Returns ReplayStats.

        Tracing: the whole call runs under a `daemon.process_flows`
        span (a child of the REST request's root span when driven
        over the API); each phase/batch below opens child spans that
        SHARE their clock window with the SpanStat accumulators
        (tracing.StatSpan), so `/debug/profile` totals and
        `/debug/traces` durations agree; captured FlowRecords carry
        the trace id (GET /flows?trace-id=...)."""
        with self.tracer.span(
            "daemon.process_flows", site="daemon",
            attrs={"bytes": len(buf)},
        ) as proc_span:
            return self._process_flows_traced(
                buf, batch_size, collect_verdicts, proc_span,
                async_depth, tenant,
            )

    def _process_flows_traced(
        self, buf, batch_size, collect_verdicts, proc_span,
        async_depth=None, tenant="",
    ):
        import time as _time
        from types import SimpleNamespace

        import numpy as np

        from cilium_tpu.flow import allow_sample_for_level, capture_batch
        from cilium_tpu.monitor import verdicts_to_events
        from cilium_tpu.native import decode_flow_records
        from cilium_tpu.replay import (
            ReplayStats,
            _ep_index_of,
            read_batches_from_rec,
        )

        # tables AND the map-state snapshot they were compiled from,
        # read under one lock (see _resolve_serving_tables — the
        # block the serving plane shares)
        version, tables, index, host_states = (
            self._resolve_serving_tables()
        )
        # records for endpoints this node doesn't own are dropped up
        # front (the index→axis mapping sends unknown ids to axis 0,
        # which would evaluate them under — and attribute their
        # events to — the endpoint that happens to sit there).  ONE
        # decode pass: the filtered SoA feeds batching directly, and
        # the drop count is surfaced in stats.
        spans = self.datapath_spans
        host_pack = tracing.stat_span(
            spans, "host_pack", site="daemon", trc=self.tracer
        ).start()
        rec = decode_flow_records(buf)
        known = np.isin(
            rec["ep_id"], np.fromiter(index, dtype=np.int64)
        )
        n_dropped = int((~known).sum())
        if n_dropped:
            rec = {k: v[known] for k, v in rec.items()}
        # endpoint-axis LUTs (identity orientation + index→ep-id),
        # shared with the serving plane
        local_ident_lut, rev_lut = self._flow_luts(index)
        # allowed-flow record budget per batch — the SAME aggregation
        # knob that gates the monitor fold's per-packet traces; drops
        # are never sampled
        flow_allow_sample = allow_sample_for_level(
            option.Config.opts.level(option.MONITOR_AGGREGATION)
        )
        # XDP prefilter (shared _prefilter_records): denied sources
        # drop before the policy program, recorded as real drops —
        # keeps this audit path in agreement with trace_tuple's
        # prefilter stage
        rec, n_prefiltered = self._prefilter_records(
            rec, index, local_ident_lut, tenant=tenant,
            trace_id=tracing.current_trace_id(),
        )
        verdict_eps = self.verdict_notification_endpoints()
        # CT occupancy check on the serving path (the watermark
        # trigger must not wait for the 30 s GC controller tick)
        self._ct_pressure_check()
        # host-side endpoint-axis translation of the (filtered)
        # record stream — the degraded host fold and the shed
        # accounting read these slices without touching the device
        ep_idx_host = _ep_index_of(rec, dict(index))
        host_pack.end()
        stats = ReplayStats()
        stats.dropped = n_dropped
        # prefiltered flows received a verdict (deny) without
        # evaluation — they count toward the totals
        stats.total += n_prefiltered
        stats.denied += n_prefiltered
        collected = [] if collect_verdicts else None
        t0 = _time.perf_counter()
        offset = 0
        # Double-buffered async dispatch (engine/publish's epoch
        # ping-pong applied to BATCHES): the device computes batch N
        # while the host packs batch N+1 — read_batches_from_rec's
        # next() does the decode-slice + single-transfer upload after
        # _dispatch_or_degrade has merely ENQUEUED the previous
        # batch.  Results drain one batch behind, in submission
        # order, so the event fold / flow capture / tracing planes
        # keep their exact per-batch ordering and counts; admission
        # units stay reserved until their batch drains (the in-flight
        # accounting covers the whole pipeline, not just the
        # enqueue).  depth 0 restores fully synchronous serving.
        #
        # Kept inline rather than on AsyncBatchDispatcher: the
        # per-batch failover/span/admission interleaving (dispatch
        # span at submit, breaker + host-fold at drain, release in
        # the drain's finally) is daemon policy the generic pipeline
        # deliberately doesn't know about; the ordering semantics are
        # the same and pinned by tests/test_async_dispatch.py.
        #
        # batch_duration semantics under overlap: observed from
        # submit to drain-complete — the PIPELINE latency of the
        # batch, which at depth N includes up to N later batches'
        # pack+enqueue time.  depth 0 restores the historical
        # synchronous reading exactly.
        from collections import deque as _dq

        depth = (
            self.dispatch_async_depth
            if async_depth is None
            else async_depth
        )
        pending = _dq()
        trace_ctx = tracing.current_trace_id()

        def _host_args_for(s, e):
            return (
                host_states,
                ep_idx_host[s:e],
                rec["identity"][s:e],
                rec["dport"][s:e],
                rec["proto"][s:e],
                rec["direction"][s:e],
                rec["is_fragment"][s:e].astype(bool),
            )

        def _drain_oldest():
            from cilium_tpu.engine.hostpath import lattice_fold_host

            out, degraded, start, end, valid, batch_t0, dev_batch = (
                pending.popleft()
            )
            shadow_refuse = False
            try:
                drain_span = tracing.stat_span(
                    spans, "drain", site="daemon", trc=self.tracer,
                ).start()
                try:
                    hit_col = getattr(out, "cache_hit", None)
                    v = SimpleNamespace(
                        allowed=np.asarray(out.allowed)[:valid],
                        match_kind=np.asarray(out.match_kind)[:valid],
                        proxy_port=np.asarray(out.proxy_port)[:valid],
                        cache_hit=(
                            None
                            if hit_col is None
                            else np.asarray(hit_col)[:valid]
                        ),
                    )
                    # deferred memo fold (one per served batch — the
                    # dispatch target never syncs): correct hit/miss
                    # accounting to the valid prefix, and when the
                    # kernel REFUSED the batch (more distinct keys
                    # than the compaction capacity; its verdict
                    # columns are unspecified, carried cache state
                    # untouched) re-dispatch through the uncached
                    # program
                    cstats = getattr(out, "cache_stats", None)
                    if cstats is not None:

                        def _redispatch(s0=start, e0=end):
                            def _ha():
                                return _host_args_for(s0, e0)

                            return self._dispatch_or_degrade(
                                tables, dev_batch, _ha,
                                batch_size, use_memo=False,
                                shadow_sample=False,
                            )

                        v, deg2, overflowed = self._fold_memo_drain(
                            cstats, v, valid,
                            int(out.allowed.shape[0]),
                            _redispatch,
                        )
                        degraded = degraded or deg2
                        # an overflow re-dispatch replaced the live
                        # columns the device diff compared against
                        shadow_refuse = shadow_refuse or overflowed
                except Exception as exc:
                    # the overlapped batch died ON DEVICE after a
                    # successful enqueue: the breaker learns the
                    # failure and the in-flight batch drains through
                    # the bit-identical host fold instead of
                    # vanishing mid-pipeline.  A memoized dispatch
                    # committed its (lazy) output rows before the
                    # failure surfaced — drop them, or every later
                    # kernel feeds the poisoned buffer back in and
                    # serving stays degraded until an unrelated
                    # publish changes the stamp
                    if self.verdict_cache is not None:
                        self.verdict_cache.flush(
                            reason="drain-failure"
                        )
                    self.dispatch_breaker.record_failure(str(exc))
                    log.warning(
                        "async drain failed; serving in-flight "
                        "batch from host path",
                        extra={"fields": {"error": str(exc)}},
                    )
                    with self.tracer.span(
                        "engine.hostpath", site="engine.hostpath",
                        attrs={"failover": True, "drain": True},
                    ):
                        host_out = lattice_fold_host(
                            *_host_args_for(start, end),
                            pad_to=batch_size,
                        )
                    degraded = True
                    shadow_refuse = True  # the shadow columns came
                    # from the dead device dispatch; refuse cleanly
                    self.degraded_batches += 1
                    metrics.degraded_batches_total.inc()
                    v = SimpleNamespace(
                        allowed=np.asarray(host_out.allowed)[:valid],
                        match_kind=np.asarray(
                            host_out.match_kind
                        )[:valid],
                        proxy_port=np.asarray(
                            host_out.proxy_port
                        )[:valid],
                    )
                drain_span.end()
                n_allowed = int(v.allowed.sum())
                stats.total += int(valid)
                stats.allowed += n_allowed
                stats.denied += int(valid) - n_allowed
                stats.redirected += int((v.proxy_port > 0).sum())
                stats.batches += 1
                if degraded:
                    stats.degraded_batches += 1
                if collected is not None:
                    collected.append(v)
                event_fold = tracing.stat_span(
                    spans, "event_fold", site="daemon",
                    trc=self.tracer,
                ).start()
                ep_idx = ep_idx_host[start:end]
                opts = option.Config.opts
                verdicts_to_events(
                    self.monitor,
                    v,
                    ep_ids=rev_lut[ep_idx],
                    identities=rec["identity"][start:end],
                    dports=rec["dport"][start:end],
                    protos=rec["proto"][start:end],
                    directions=rec["direction"][start:end],
                    verdict_eps=verdict_eps,
                    emit_drops=opts.is_enabled(
                        option.DROP_NOTIFICATION
                    ),
                    emit_trace=(
                        opts.is_enabled(option.TRACE_NOTIFICATION)
                        and opts.level(option.MONITOR_AGGREGATION)
                        == option.MONITOR_AGG_NONE
                    ),
                )
                event_fold.end()
                # flow-record fold (the Hubble plane): all drops +
                # head-sampled allows, classified through the shared
                # telemetry_masks definitions
                flow_capture = tracing.stat_span(
                    spans, "flow_capture", site="daemon",
                    trc=self.tracer,
                ).start()
                dirs = rec["direction"][start:end]
                peer = rec["identity"][start:end].astype(np.int64)
                local = local_ident_lut[ep_idx]
                src_ids = np.where(dirs == 0, peer, local)
                dst_ids = np.where(dirs == 0, local, peer)
                # shadow verdict-diff fold (one per sampled batch,
                # exactly once): counters + diff records land in the
                # armed window; the returned transition codes join
                # the flow records (observe --diff-status)
                diff_col = self._fold_shadow_drain(
                    out, v, valid,
                    ep_ids=rev_lut[ep_idx],
                    src_identities=src_ids,
                    dst_identities=dst_ids,
                    dports=rec["dport"][start:end],
                    protos=rec["proto"][start:end],
                    directions=dirs,
                    tenant=tenant,
                    trace_id=trace_ctx,
                    refuse=shadow_refuse,
                )
                capture_batch(
                    self.flow_store,
                    ep_ids=rev_lut[ep_idx],
                    src_identities=src_ids,
                    dst_identities=dst_ids,
                    dports=rec["dport"][start:end],
                    protos=rec["proto"][start:end],
                    directions=dirs,
                    allowed=v.allowed,
                    match_kind=v.match_kind,
                    proxy_port=v.proxy_port,
                    cache_hit=getattr(v, "cache_hit", None),
                    diff_status=diff_col,
                    allow_sample=flow_allow_sample,
                    metrics_registry=metrics,
                    trace_id=trace_ctx,
                    tenant=tenant,
                )
                flow_capture.end()
            finally:
                self.admission.release(valid)
            metrics.batch_duration.observe(
                _time.perf_counter() - batch_t0
            )

        try:
            for batch, valid in read_batches_from_rec(
                rec, batch_size, ep_index=ep_idx_host
            ):
                start, end = offset, offset + valid
                offset = end
                batch_t0 = _time.perf_counter()
                # bounded admission: a batch the gate refuses is
                # SHED — counted under the canonical Overload drop
                # reason, never queued (backpressure on the datapath
                # is attribution, not buffering)
                if not self.admission.reserve(valid):
                    stats.shed += valid
                    metrics.shed_flows_total.inc(value=valid)
                    from cilium_tpu.monitor.events import (
                        DROP_OVERLOAD,
                        drop_reason_name,
                    )

                    for dirv, dname in ((0, "INGRESS"), (1, "EGRESS")):
                        count = int(
                            (rec["direction"][start:end] == dirv).sum()
                        )
                        if count:
                            metrics.drop_count.inc(
                                drop_reason_name(DROP_OVERLOAD),
                                dname, value=count,
                            )
                    continue
                try:
                    dispatch_span = tracing.stat_span(
                        spans, "dispatch", site="daemon",
                        attrs={
                            "batch": stats.batches + len(pending),
                            "rows": valid,
                        },
                        trc=self.tracer,
                    ).start()

                    def _host_args(s=start, e=end):
                        return _host_args_for(s, e)

                    def _host_cols(s=start, e=end):
                        # the UNPADDED host tuple columns the mesh
                        # router re-splits across survivors
                        return (
                            ep_idx_host[s:e],
                            rec["identity"][s:e],
                            rec["dport"][s:e],
                            rec["proto"][s:e],
                            rec["direction"][s:e],
                            rec["is_fragment"][s:e].astype(bool),
                        )

                    out, degraded = self._dispatch_or_degrade(
                        tables, batch, _host_args, batch_size,
                        host_cols=_host_cols,
                    )
                    dispatch_span.end(success=not degraded)
                except Exception:
                    self.admission.release(valid)
                    raise
                # the device batch rides `pending` so a drain-time
                # overflow refusal can re-dispatch it uncached
                pending.append(
                    (out, degraded, start, end, valid, batch_t0,
                     batch)
                )
                while len(pending) > depth:
                    _drain_oldest()
            while pending:
                _drain_oldest()
        finally:
            # an exception escaping mid-stream (decode, drain-side
            # fold, host-fold failure) must not leak the reserved
            # admission units of batches still in flight — the gate's
            # outstanding count would stay inflated forever and later
            # calls would spuriously shed.  In-flight shadow tickets
            # refuse (exactly-once accounting) rather than dangle.
            while pending:
                dropped = pending.popleft()
                tk = getattr(dropped[0], "shadow_ticket", None)
                if tk is not None:
                    self.shadow.refuse(tk)
                self.admission.release(dropped[4])
        stats.seconds = _time.perf_counter() - t0
        stats.spans = spans
        proc_span.attrs.update(
            total=stats.total, batches=stats.batches,
            allowed=stats.allowed, denied=stats.denied,
            dropped=stats.dropped, shed=stats.shed,
            degraded_batches=stats.degraded_batches,
        )
        self._export_spans("datapath", spans)
        if collected is not None:
            stats.verdicts = {
                field: np.concatenate(
                    [np.asarray(getattr(c, field)) for c in collected]
                )
                if collected
                else np.zeros(0)
                for field in ("allowed", "match_kind", "proxy_port")
            }
        if stats.seconds > 0:
            metrics.verdict_throughput.set(
                value=stats.total / stats.seconds
            )
        return stats

    def health(self) -> Dict:
        """Node health rollup (status.go's aggregate): degraded when
        the dispatch breaker is not closed (serving from the host
        path) or any controller is stuck failing past the threshold —
        background-thread failures must surface, not rot silently."""
        reasons = []
        breaker_state = self.dispatch_breaker.state
        if breaker_state != "closed":
            reasons.append(
                f"dispatch breaker {breaker_state}: device verdicts "
                f"degraded to host path"
            )
        chip_states = {}
        if self.mesh_router is not None:
            chip_states = self.mesh_router.chip_states()
            for ordinal, state in chip_states.items():
                if state != "closed":
                    reasons.append(
                        f"chip {ordinal} breaker {state}: its batch "
                        f"shard re-splits across survivors and its "
                        f"table rows serve from replicas"
                    )
        for name, s in self.controllers.statuses().items():
            if (
                s.consecutive_failures
                >= self.controller_failure_threshold
            ):
                reasons.append(
                    f"controller {name} failing "
                    f"({s.consecutive_failures} consecutive: "
                    f"{s.last_error})"
                )
        out = {
            "status": "degraded" if reasons else "ok",
            "reasons": reasons,
            "breaker": {
                **self.dispatch_breaker.snapshot(),
                "state": breaker_state,
            },
            "degraded_batches": self.degraded_batches,
            "shed_flows": self.admission.shed_total,
        }
        if self.mesh_router is not None:
            out["chips"] = {
                str(o): s for o, s in chip_states.items()
            }
        return out

    def status(self) -> Dict:
        version, tables, index = self.endpoint_manager.published()
        build_fail_count, build_fail_last = (
            self.endpoint_manager.build_failure_snapshot()
        )
        health = self.health()
        out = {
            "node": self.node_name,
            "health": health["status"],
            "health_reasons": health["reasons"],
            "breaker": health["breaker"],
            "degraded_batches": self.degraded_batches,
            "shed_flows": self.admission.shed_total,
            "policy_revision": self.repo.get_revision(),
            "num_rules": self.repo.num_rules(),
            "num_endpoints": len(self.endpoint_manager.endpoints()),
            "num_identities": len(self.identity_cache()),
            "ipcache_entries": len(self.ipcache.ip_to_identity),
            "tables_version": version,
            "table_endpoints": len(index),
            "kvstore": "connected" if self.kvstore else "disabled",
            "clustermesh_clusters": self.clustermesh.num_connected(),
            "build_failures": build_fail_count,
            "last_build_failures": [
                {"endpoint": e, "reason": r, "error": err}
                for e, r, err in build_fail_last
            ],
            "controllers": {
                name: {
                    "success": s.success_count,
                    "failure": s.failure_count,
                    "consecutive_failures": s.consecutive_failures,
                    "last_error": s.last_error,
                }
                for name, s in self.controllers.statuses().items()
            },
        }
        if self.serving is not None:
            out["serving"] = self.serving.snapshot()
        return out
