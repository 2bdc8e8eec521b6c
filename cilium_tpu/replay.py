"""Flow-replay harness: binary flow records → streamed device verdicts.

The framework's data-loader (SURVEY §7 step 5: "flow-replay harness,
Hubble-tuple reader"): reads fixed 24-byte flow records (decoded by
the native C++ decoder at memory bandwidth), streams fixed-size padded
batches through the FUSED datapath step — prefilter → LB/DNAT → CT →
ipcache LPM → policy lattice in one jit (engine/datapath.py, the
analog of bpf_lxc.c:440/899 being ONE program) — with pipelined
dispatch (the double-buffered H2D pattern of SURVEY §7 hard part 6),
accumulates per-entry counters back into the endpoints' realized map
states, and optionally applies CT writeback between batches so NEW
flows become ESTABLISHED mid-replay (sustained-churn mode).

`replay_lattice` keeps the bare policy-lattice path for callers that
have only compiled PolicyTables (no CT/LB/ipcache state) — identity
comes pre-resolved from the record, as in a Hubble post-hoc replay.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Iterator, Optional, Tuple

import numpy as np

from cilium_tpu.engine.verdict import (
    TupleBatch,
    _verdict_kernel_with_counters,
)
from cilium_tpu.maps.policymap import PolicyKey
from cilium_tpu.native import decode_flow_records

# fold the carried u32 counter buffers into host u64 sums before any
# cell could have gained 2^31 increments (each batch adds ≤ batch_size
# to a cell), leaving 2× headroom below the u32 wrap
_COUNTER_FOLD_MAX_INCR = 1 << 31

def _guarded_dispatch(fn, *args, donated=False):
    """One jitted dispatch under the shared guard
    (resilience.guarded_dispatch): engine.dispatch fault seam +
    bounded retry; the Daemon's breaker + host-path failover handles
    anything persistent.  `donated=True` for the accumulator-carrying
    steps whose jit donates buffers — those retry only the
    pre-launch injected fault (see guarded_dispatch)."""
    from cilium_tpu.resilience import guarded_dispatch

    return guarded_dispatch(fn, *args, donated=donated)

# churn-mode intent compaction capacity: create/delete intents per
# batch round that travel device→host (only deduped flagged rows
# move; burstier rounds spill into extra convergence passes)
_CT_INTENT_CAP = 1 << 16
# claim-table slots for the on-device intent dedup (scatter-min);
# larger = fewer convergence re-runs from slot collisions
_CT_CLAIM_SLOTS = 1 << 19
# intent-fetch slice buckets: the fetch moves the smallest
# power-of-two column slice covering the round's intent count instead
# of the full [12, cap] buffer (3.1 MB); the D2H cost per fetch is not
# measured on the local chip.  Static sizes keep the slice kernels in
# the jit cache.
_CT_FETCH_BUCKETS = (1 << 10, 1 << 13, _CT_INTENT_CAP)


def _churn_compact(out, flows, valid):
    """Dedup + compact a batch's create/delete intents on device: a
    scatter-min claim table keeps the FIRST flagged row per flow-hash
    slot (distinct flows sharing a slot lose the round and surface in
    the header's `remaining`, which drives a convergence re-run), so
    the D2H transfer is O(unique intents), never O(batch).

    Returns (header u32 [4] = count/allowed/redirected/remaining,
    intents u32 [12, cap]) as SEPARATE outputs so the caller can pull
    the 16-byte header alone on quiet rounds: the intent buffer only
    moves when the header says something is in it."""
    import jax.numpy as jnp

    from cilium_tpu.engine.hashtable import fnv1a_device

    cap = _CT_INTENT_CAP
    claim_m = _CT_CLAIM_SLOTS
    b = out.ct_create.shape[0]
    flag = out.ct_create.astype(bool) | out.ct_delete.astype(bool)
    in_valid = jnp.arange(b, dtype=jnp.int32) < valid
    flag = flag & in_valid

    h = fnv1a_device(
        jnp.stack(
            [
                out.final_daddr.astype(jnp.uint32),
                flows.saddr.astype(jnp.uint32),
                (out.final_dport.astype(jnp.uint32) << 16)
                | (flows.sport.astype(jnp.uint32) & 0xFFFF),
                (flows.proto.astype(jnp.uint32) << 8)
                | flows.direction.astype(jnp.uint32),
            ],
            axis=1,
        )
    )
    slot = (h & jnp.uint32(claim_m - 1)).astype(jnp.int32)
    row_id = jnp.arange(b, dtype=jnp.int32)
    claim = jnp.full(claim_m, b, jnp.int32).at[slot].min(
        jnp.where(flag, row_id, b)
    )
    winner_row = claim[slot]
    winner = flag & (winner_row == row_id)
    # losers whose full hash equals their slot winner's are (almost
    # surely) later packets of the SAME flow — the winner's create
    # covers them, no convergence re-run needed.  A 32-bit-hash
    # collision between distinct flows defers that flow's create to
    # its next appearance in the stream, the same race the per-packet
    # kernel datapath has (conntrack.h ct_create4 is best-effort too).
    wr = jnp.clip(winner_row, 0, b - 1)
    true_loser = flag & ~winner & (h[wr] != h)

    # compaction via argsort, NOT scatter: a scatter routing millions
    # of non-winner rows at one trash index is pathologically slow on
    # TPU (duplicate-index collision handling); sorting 'winner-first'
    # and slicing the head is a single O(B log B) sort + tiny gathers
    take = min(cap, b)
    order = jnp.argsort(jnp.where(winner, row_id, jnp.int32(b)))[:take]
    keep = winner[order]  # mask off the tail when < cap win
    cols = jnp.stack(
        [
            out.ct_create.astype(jnp.uint32),
            out.ct_delete.astype(jnp.uint32),
            out.final_daddr.astype(jnp.uint32),
            out.final_dport.astype(jnp.uint32),
            flows.saddr.astype(jnp.uint32),
            flows.sport.astype(jnp.uint32),
            flows.proto.astype(jnp.uint32),
            flows.direction.astype(jnp.uint32),
            out.rev_nat.astype(jnp.uint32),
            out.lb_slave.astype(jnp.uint32),
            # pre-DNAT frontend, for service-entry creation and
            # dual-homed bucket placement (apply_ct_writeback_host)
            flows.daddr.astype(jnp.uint32),
            flows.dport.astype(jnp.uint32),
        ]
    )  # [12, B]
    intents = jnp.zeros((12, cap), jnp.uint32)
    intents = intents.at[:, :take].set(
        jnp.where(keep[None, :], cols[:, order], 0)
    )
    n_tx = jnp.minimum(winner.sum(dtype=jnp.uint32), jnp.uint32(take))
    allowed = jnp.sum(
        out.allowed.astype(jnp.uint32) * in_valid, dtype=jnp.uint32
    )
    redirected = jnp.sum(
        (out.proxy_port > 0) & in_valid, dtype=jnp.uint32
    )
    overflow = winner.sum(dtype=jnp.uint32) - n_tx
    remaining = true_loser.sum(dtype=jnp.uint32) + overflow
    header = jnp.stack([n_tx, allowed, redirected, remaining])
    return header, intents


_CHURN_FNS = None


def _flows_from_pool(pool_packed, picks):
    """Device-side flow materialization: gather pool rows by pick
    index inside the fused program, split via the shared
    FLOW_COLUMNS contract.  The pool-mode data loader moves 4
    bytes/tuple (the pick) instead of ~88 (decode read + pack write +
    record upload), keeping host decode and pack off the churn loop."""
    from cilium_tpu.engine.datapath import flow_batch_from_packed

    return flow_batch_from_packed(pool_packed[:, picks])


_POOL_PACK_KEY = "__device_pack__"


def pack_flow_pool(pool: Dict[str, np.ndarray]) -> np.ndarray:
    """Flow-universe dict → [8, P] u32 pack (one upload, device
    gathers per batch).  Row order is datapath.FLOW_COLUMNS — the
    same contract FlowBatch.from_numpy packs with."""
    from cilium_tpu.engine.datapath import FLOW_COLUMNS

    p = len(pool["saddr"])
    packed = np.empty((len(FLOW_COLUMNS), p), dtype=np.uint32)
    for j, k in enumerate(FLOW_COLUMNS):
        packed[j] = np.asarray(pool[k]).astype(np.uint32, copy=False)
    return packed


def _churn_fns():
    """Jitted fused churn programs: datapath step + intent compaction
    in ONE dispatch (the churn loop's critical path is serial —
    step → header D2H → CT fold → snapshot delta — so every extra
    dispatch adds a host↔device round trip).  Returns
    (step, step_accum, step_pool); step_pool additionally fuses the
    pool-row gather (see _flows_from_pool)."""
    global _CHURN_FNS
    if _CHURN_FNS is None:
        import jax

        from cilium_tpu.engine.datapath import (
            _datapath_kernel,
            _datapath_kernel_accum,
        )

        def step(tables, flows, valid):
            out = _datapath_kernel(tables, flows)
            return _churn_compact(out, flows, valid)

        def step_accum(tables, flows, valid, acc):
            out, acc = _datapath_kernel_accum(tables, flows, acc)
            header, intents = _churn_compact(out, flows, valid)
            return header, intents, acc

        def step_pool(tables, pool_packed, picks, valid):
            flows = _flows_from_pool(pool_packed, picks)
            out = _datapath_kernel(tables, flows)
            return _churn_compact(out, flows, valid)

        def step_pool_rand(tables, pool_packed, key, batch_size, valid):
            # device-side pick generation: an 8-byte PRNG key per
            # round replaces the [B] index upload on the serial churn
            # loop (uniform picks, same distribution the host sampler
            # draws)
            import jax.numpy as jnp
            import jax.random as jrandom

            picks = jrandom.randint(
                key,
                (batch_size,),
                0,
                pool_packed.shape[1],
                dtype=jnp.uint32,
            )
            flows = _flows_from_pool(pool_packed, picks)
            out = _datapath_kernel(tables, flows)
            return _churn_compact(out, flows, valid)

        _CHURN_FNS = (
            jax.jit(step),
            jax.jit(step_accum, donate_argnums=(3,)),
            jax.jit(step_pool),
            jax.jit(step_pool_rand, static_argnums=(3,)),
        )
    return _CHURN_FNS


_FETCH_SLICE = {}


def _fetch_intents(intents_dev, k: int) -> np.ndarray:
    """Pull the first k intent columns via the smallest static slice
    bucket (each bucket is one tiny cached jit program), so a quiet
    round moves kilobytes, not the full 2.6 MB buffer."""
    import jax

    bucket = next(
        (b for b in _CT_FETCH_BUCKETS if k <= b), _CT_INTENT_CAP
    )
    fn = _FETCH_SLICE.get(bucket)
    if fn is None:
        fn = jax.jit(lambda x, n=bucket: x[:, :n])
        _FETCH_SLICE[bucket] = fn
    return np.asarray(fn(intents_dev))[:, :k]


class _ChurnDriver:
    """Shared churn-mode machinery for replay()/replay_pool(): the
    bucket-index + device-snapshot cache, and the per-round drain
    (header parse → bucketed intent fetch → host CT fold → per-bucket
    device delta).

    The bucket index (O(entries) host hash placement) and the
    full-snapshot upload are the churn path's fixed setup cost — both
    cache on the CTMap across calls.  Validity gate: the CTMap
    mutation counter (bumped by create/probe/gc — catches host-side
    lookups between replays that mutate lifetime/closing flags in
    place) plus the exact key set (catches direct `entries` dict
    manipulation).  The only remaining bypass is mutating a CTEntry
    object's fields directly without touching the map; such callers
    must `del ct_map._device_churn_cache`.  Within the loop every
    mutation flows through ct_index.apply, keeping all three (map,
    index, device snapshot) in lockstep.
    """

    def __init__(self, ct_map) -> None:
        import jax

        from cilium_tpu.ct.device import CTBucketIndex

        self.ct_map = ct_map
        self._delta_jit = _delta_fn()
        cached = getattr(ct_map, "_device_churn_cache", None)
        if (
            cached is not None
            and cached[2] == getattr(ct_map, "mutations", -1)
            and cached[0].key_home.keys() == ct_map.entries.keys()
        ):
            self.ct_index, self.dev_snap = cached[:2]
        else:
            self.ct_index = CTBucketIndex(ct_map)
            self.dev_snap = jax.device_put(
                self.ct_index.full_snapshot()
            )

    def drain(
        self, header_d, intents_d, stats: "ReplayStats",
        valid: int, first_pass: bool,
    ) -> int:
        """One convergence round: fold the round's intents into the
        host CT + device snapshot, update stats on the first pass.
        Returns the header's `remaining` count (>0 ⇒ the caller must
        re-run the batch against the updated snapshot)."""
        from cilium_tpu.engine.datapath import apply_ct_writeback_host

        header = np.asarray(header_d)
        k = int(header[0])
        remaining = int(header[3])
        if first_pass:
            stats.total += valid
            allowed = int(header[1])
            stats.allowed += allowed
            stats.denied += valid - allowed
            stats.redirected += int(header[2])
            stats.batches += 1
        if k:
            packed = _fetch_intents(intents_d, k)
            created_keys, deleted_keys = apply_ct_writeback_host(
                self.ct_map,
                packed[0].astype(bool),
                packed[1].astype(bool),
                *(packed[j] for j in range(2, 10)),
                orig_daddr=packed[10],
                orig_dport=packed[11],
                # stamp lifetimes on the MAP's clock: the daemon's GC
                # runs on ct.now() (map age), and a now=0 stamp would
                # read as already-expired once uptime passes the
                # timeout
                now=self.ct_map.now(),
            )
            stats.ct_created += len(created_keys)
            stats.ct_deleted += len(deleted_keys)
            if created_keys or deleted_keys:
                idx, rows, new_stash = self.ct_index.apply(
                    created_keys, deleted_keys
                )
                if len(idx) or new_stash is not None:
                    self.dev_snap = self._delta_jit(
                        self.dev_snap, idx, rows, new_stash
                    )
        return remaining

    def stash(self) -> None:
        self.ct_map._device_churn_cache = (
            self.ct_index,
            self.dev_snap,
            self.ct_map.mutations,
        )


_DELTA_FN = None


def _delta_fn():
    """Module-level cached jit of apply_bucket_delta (donated
    snapshot) — per-driver jits would re-trace on every replay call."""
    global _DELTA_FN
    if _DELTA_FN is None:
        import jax

        from cilium_tpu.ct.device import apply_bucket_delta

        _DELTA_FN = jax.jit(apply_bucket_delta, donate_argnums=(0,))
    return _DELTA_FN


@dataclass
class ReplayStats:
    total: int = 0
    allowed: int = 0
    denied: int = 0
    redirected: int = 0
    batches: int = 0
    seconds: float = 0.0
    ct_created: int = 0
    ct_deleted: int = 0
    # records discarded BEFORE evaluation (e.g. unknown endpoint ids
    # filtered by Daemon.process_flows) — totals must account for
    # every input record
    dropped: int = 0
    # flows shed by bounded admission (Daemon.process_flows overload
    # shedding; like `dropped`, NOT part of `total`)
    shed: int = 0
    # batches served by the host-path fallback while the dispatch
    # circuit breaker was open/failing (verdicts bit-identical)
    degraded_batches: int = 0
    # per-tuple verdict columns in stream order (process_flows
    # collect_verdicts=True): {"allowed", "match_kind", "proxy_port"}
    verdicts: object = None
    # per-phase wall-time accumulators (SpanStats: host_pack /
    # dispatch / drain), populated by replay()'s instrumented loop
    spans: object = None
    # [2, TELEM_COLS] u64 stage/drop histogram of the replayed
    # traffic (replay(collect_telemetry=True))
    telemetry: object = None

    @property
    def verdicts_per_sec(self) -> float:
        return self.total / self.seconds if self.seconds else 0.0


def _ep_index_of(rec, ep_map: Optional[Dict[int, int]]) -> np.ndarray:
    # int64: a u32 ep_id near 2^32 must not wrap negative pre-LUT
    ep_index = rec["ep_id"].astype(np.int64)
    if ep_map is not None:
        lut = np.zeros(max(ep_map.keys(), default=0) + 1, dtype=np.int32)
        for ep_id, idx in ep_map.items():
            lut[ep_id] = idx
        in_range = ep_index < len(lut)
        ep_index = np.where(
            in_range, lut[np.minimum(ep_index, len(lut) - 1)], 0
        )
    return ep_index.astype(np.int32)


def _batch_slices(n: int, batch_size: int):
    for start in range(0, n, batch_size):
        yield start, min(start + batch_size, n)


def _padded(a: np.ndarray, start: int, end: int, size: int, fill=0):
    chunk = a[start:end]
    pad = size - (end - start)
    if pad:
        chunk = np.concatenate(
            [chunk, np.full(pad, fill, dtype=a.dtype)]
        )
    return chunk


def read_batches(
    buf: bytes, batch_size: int, ep_map: Optional[Dict[int, int]] = None
) -> Iterator[Tuple[TupleBatch, int]]:
    """Decode flow records and yield padded TupleBatches (identity
    pre-resolved from the record).  `ep_map` translates record
    endpoint ids to table endpoint-axis indices (unknown endpoints map
    to 0 — callers should pre-filter)."""
    return read_batches_from_rec(
        decode_flow_records(buf), batch_size, ep_map
    )


def read_batches_from_rec(
    rec: Dict[str, np.ndarray],
    batch_size: int,
    ep_map: Optional[Dict[int, int]] = None,
    ep_index: Optional[np.ndarray] = None,
) -> Iterator[Tuple[TupleBatch, int]]:
    """read_batches over an ALREADY-decoded record SoA — callers that
    pre-filter records (Daemon.process_flows) avoid a second decode
    pass over the buffer.  `ep_index` supplies an already-computed
    endpoint-axis translation (callers that keep one host-side for
    event folding skip the second O(n) LUT pass)."""
    n = len(rec["ep_id"])
    if ep_index is None:
        ep_index = _ep_index_of(rec, ep_map)
    for start, end in _batch_slices(n, batch_size):
        p = lambda a, fill=0: _padded(a, start, end, batch_size, fill)
        yield (
            TupleBatch.from_numpy(
                ep_index=p(ep_index),
                identity=p(rec["identity"]),
                dport=p(rec["dport"].astype(np.int32)),
                proto=p(rec["proto"].astype(np.int32)),
                direction=p(rec["direction"].astype(np.int32)),
                is_fragment=p(rec["is_fragment"].astype(bool), fill=False),
            ),
            end - start,
        )


def read_flow_batches(
    buf: bytes, batch_size: int, ep_map: Optional[Dict[int, int]] = None
) -> Iterator[tuple]:
    """Decode flow records and yield padded FlowBatches (raw 5-tuples
    with addresses — identity resolution happens on device via the
    ipcache LPM inside the fused step)."""
    from cilium_tpu.engine.datapath import FlowBatch

    rec = decode_flow_records(buf)
    n = len(rec["ep_id"])
    ep_index = _ep_index_of(rec, ep_map)
    for start, end in _batch_slices(n, batch_size):
        p = lambda a, fill=0: _padded(a, start, end, batch_size, fill)
        yield (
            FlowBatch.from_numpy(
                ep_index=p(ep_index),
                saddr=p(rec["saddr"]),
                daddr=p(rec["daddr"]),
                sport=p(rec["sport"].astype(np.int32)),
                dport=p(rec["dport"].astype(np.int32)),
                proto=p(rec["proto"].astype(np.int32)),
                direction=p(rec["direction"].astype(np.int32)),
                is_fragment=p(rec["is_fragment"].astype(bool), fill=False),
            ),
            end - start,
        )


def replay(
    tables,
    buf: bytes,
    batch_size: int = 1 << 20,
    accumulate_counters: bool = True,
    ep_map: Optional[Dict[int, int]] = None,
    manager=None,
    ct_map=None,
    collect_telemetry: bool = False,
    flow_store=None,
    chip: int = 0,
) -> tuple:
    """Run all records through the FULL fused datapath step
    (engine/datapath.datapath_step_accum — counters scatter into
    carried, donated device buffers) with pipelined dispatch.

    `tables` is a DatapathTables (prefilter/ipcache/CT/LB/policy).
    With `ct_map` (the authoritative host CTMap) replay runs in
    sustained-churn mode: batches are drained in order, CT writeback
    (create/delete intents) is applied after each batch, and the
    device CT snapshot is recompiled whenever it changed — so a flow
    created by batch i is ESTABLISHED from batch i+1 on, mirroring the
    kernel datapath seeing its own CT writes.  Without it batches
    evaluate against the fixed snapshot and stay pipelined.

    With `collect_telemetry` the fused dispatch additionally carries
    the [2, TELEM_COLS] stage/drop accumulator
    (datapath_step_accum_telem); the folded histogram lands in
    stats.telemetry AND increments the process metrics registry
    (cilium_drop_count_total / policy_verdict_total / ...).  Not
    offered in churn mode (the churn programs fuse intent compaction
    instead).

    Phase wall times (host_pack / dispatch / drain) accumulate into
    stats.spans, and per-iteration wall time feeds the registry's
    batch-duration histogram — the SpanStat instrumentation the
    reference hangs off its regeneration phases, applied to the
    datapath loop.

    With `flow_store` (a cilium_tpu.flow.FlowStore) every drained
    batch folds flow records into the ring — all drops plus allows
    head-sampled per the MonitorAggregationLevel knob — tagged with
    `chip` and classified through the shared telemetry_masks
    definitions; the peer identity rides src/dst per direction, the
    local side is 0 (replay has no endpoint-identity context).  Not
    offered in churn mode, like collect_telemetry.

    Returns (ReplayStats, l4_counts, l3_counts); the counter arrays
    are u64 sums across batches with shapes [E, 2, Kg] and [E, 2, N]
    (policy_entry packets, bpf/lib/policy.h:66-68), or (stats, None,
    None) when `accumulate_counters` is False.
    """
    import time

    import jax

    from cilium_tpu.engine.datapath import (
        DatapathTables,
        datapath_step,
        datapath_step_accum,
        datapath_step_accum_telem,
    )
    from cilium_tpu.engine.verdict import make_counter_buffers
    from cilium_tpu.metrics import registry as _metrics
    from cilium_tpu.spanstat import SpanStats

    if manager is not None:
        # stale-table guard at the layer that actually reads the
        # stacked per-endpoint rows: tables 2+ publishes old have had
        # those rows rewritten in place (FleetCompiler double
        # buffering) and would return wrong verdicts silently
        manager.check_tables_current(tables.policy)
    if flow_store is not None and ct_map is not None:
        raise ValueError(
            "flow capture is not offered in churn mode (the churn "
            "programs fuse intent compaction instead of returning "
            "per-tuple verdict columns)"
        )

    stats = ReplayStats()
    spans = SpanStats()
    stats.spans = spans
    # pin every table on device once — jitted steps re-upload host
    # numpy leaves on EVERY call otherwise (268 MB of policy tables
    # per batch at config5 scale)
    tables = jax.device_put(tables)
    # counters scatter into a carried u32 device buffer, donated
    # across batches — one D2H fold per _COUNTER_FOLD_BATCHES into
    # host u64 sums (a cell can gain ≤ batch_size per batch, so u32
    # can't wrap within a fold interval), instead of [E, 2, N]
    # tensors per batch
    acc = None
    acc_total = None
    batches_since_fold = 0
    fold_every = max(1, _COUNTER_FOLD_MAX_INCR // max(batch_size, 1))
    if accumulate_counters:
        acc = jax.device_put(make_counter_buffers(tables.policy))
    telem_dev = None
    telem_total = None
    if collect_telemetry and ct_map is None:
        from cilium_tpu.engine.verdict import (
            TELEM_COLS,
            make_telemetry_buffers,
        )

        telem_total = np.zeros((2, TELEM_COLS), np.uint64)
        if accumulate_counters:
            telem_dev = jax.device_put(make_telemetry_buffers())

    def _fold_counters():
        nonlocal acc, acc_total, batches_since_fold, telem_dev
        nonlocal telem_total
        host = np.asarray(acc).astype(np.uint64)
        acc_total = host if acc_total is None else acc_total + host
        acc = jax.device_put(make_counter_buffers(tables.policy))
        if telem_dev is not None:
            # the telemetry buffer wraps at the same u32 horizon as
            # the counter buffer — fold it on the same cadence
            from cilium_tpu.engine.verdict import (
                make_telemetry_buffers,
            )

            telem_total = telem_total + np.asarray(telem_dev).astype(
                np.uint64
            )
            telem_dev = jax.device_put(make_telemetry_buffers())
        batches_since_fold = 0

    churn = None
    if ct_map is not None:
        # incremental churn machinery (_ChurnDriver): a host mirror
        # of the device bucket layout, a donated device snapshot, and
        # a two-phase D2H per batch (16-byte header always; intent
        # columns only on rounds that flagged any).  The kernel owns
        # the map, the agent folds writes back — with per-bucket row
        # updates instead of full-snapshot rebuilds
        # (bpf/lib/conntrack.h's map writes are per-bucket too).
        churn = _ChurnDriver(ct_map)
        tables = DatapathTables(
            prefilter=tables.prefilter,
            ipcache=tables.ipcache,
            ct=churn.dev_snap,
            lb=tables.lb,
            policy=tables.policy,
            tunnel=tables.tunnel,
        )
        churn_step, churn_step_accum = _churn_fns()[:2]

    id_table_host = (
        np.asarray(tables.policy.id_table)
        if flow_store is not None
        else None
    )
    # out.sec_id is a raw identity INDEX only when BOTH hold: the
    # dispatch was the emit_sec_id=False telem program AND the
    # ipcache is idx-form (the hash-form branch emits the real id
    # regardless of emit_sec_id) — see _datapath_core
    ipcache_idx_form = False
    if flow_store is not None:
        from cilium_tpu.ipcache.lpm import IPCacheDevice

        ipcache_idx_form = bool(
            isinstance(tables.ipcache, IPCacheDevice)
            and tables.ipcache.values_are_idx
        )
    # record ep_ids must be ENDPOINT ids: invert the record→axis
    # translation the loader applied (the daemon path's rev_lut)
    ep_rev_lut = None
    if flow_store is not None and ep_map:
        ep_rev_lut = np.zeros(
            max(ep_map.values()) + 1, dtype=np.int64
        )
        for rev_ep_id, rev_idx in ep_map.items():
            ep_rev_lut[rev_idx] = rev_ep_id

    def _drain_item(item):
        """Drain one pending batch; host-fold its telemetry when the
        dispatch couldn't carry the device accumulator (partial tail
        batches, or the no-counter audit path), and fold flow records
        when a flow_store rides along."""
        nonlocal telem_total
        out, valid, fold_direction, flows_ref, sec_is_idx = item
        spans.span("drain").start()
        _drain_fused((out, valid), stats)
        if fold_direction is not None:
            from cilium_tpu.telemetry import telemetry_from_outputs

            telem_total = telem_total + telemetry_from_outputs(
                out, np.asarray(fold_direction), valid=valid
            )
        if flow_store is not None:
            _capture_replay_flows(
                flow_store, out, flows_ref, int(valid), sec_is_idx,
                id_table_host, chip, ep_rev_lut,
            )
        spans.span("drain").end()

    pending = []  # pipelined dispatch, bounded depth
    t0 = time.perf_counter()
    batch_iter = iter(read_flow_batches(buf, batch_size, ep_map))
    while True:
        # host pack phase: record decode + pad + H2D upload of the
        # next batch (read_flow_batches does all three in next())
        spans.span("host_pack").start()
        item = next(batch_iter, None)
        spans.span("host_pack").end(success=item is not None)
        if item is None:
            break
        flows, valid = item
        iter_t0 = time.perf_counter()
        if ct_map is not None:
            # sustained churn: the compaction runs FUSED with the
            # datapath step (one dispatch per round), the 16-byte
            # header is the only unconditional D2H, and intent
            # columns travel in the smallest slice bucket covering
            # the round's count.  Claim-table losers (distinct flows
            # sharing a dedup slot, or >cap unique intents) drive
            # convergence re-runs of the same batch against the
            # updated snapshot, so the next batch sees every flow
            # this one created (up to the documented
            # 32-bit-hash-collision deferral in _churn_compact).
            first_pass = True
            while True:
                tables = DatapathTables(
                    prefilter=tables.prefilter,
                    ipcache=tables.ipcache,
                    ct=churn.dev_snap,
                    lb=tables.lb,
                    policy=tables.policy,
                    tunnel=tables.tunnel,
                )
                spans.span("dispatch").start()
                if first_pass and accumulate_counters:
                    header_d, intents_d, acc = _guarded_dispatch(
                        churn_step_accum, tables, flows, valid, acc,
                        donated=True,
                    )
                    batches_since_fold += 1
                    if batches_since_fold >= fold_every:
                        _fold_counters()
                else:
                    # convergence passes skip counter accumulation —
                    # the first pass already counted this batch
                    header_d, intents_d = _guarded_dispatch(
                        churn_step, tables, flows, valid
                    )
                spans.span("dispatch").end()
                spans.span("drain").start()
                remaining = churn.drain(
                    header_d, intents_d, stats, int(valid), first_pass
                )
                spans.span("drain").end()
                first_pass = False
                if remaining == 0:
                    break
            _metrics.batch_duration.observe(
                time.perf_counter() - iter_t0
            )
            continue
        fold_direction = None
        sec_is_idx = False
        spans.span("dispatch").start()
        if accumulate_counters:
            # BOTH accum kernels run emit_sec_id=False: with an
            # idx-form ipcache their sec output is the raw identity
            # index, which flow capture translates through id_table
            # host-side (the non-counter datapath_step emits the
            # real id, so it stays False)
            sec_is_idx = ipcache_idx_form
            if telem_dev is not None and valid == batch_size:
                out, acc, telem_dev = _guarded_dispatch(
                    datapath_step_accum_telem,
                    tables, flows, acc, telem_dev,
                    donated=True,
                )
            else:
                out, acc = _guarded_dispatch(
                    datapath_step_accum, tables, flows, acc,
                    donated=True,
                )
                if telem_total is not None:
                    # partial tail batch: the device accumulator
                    # would count the padding rows, so this batch's
                    # histogram folds host-side on the valid prefix
                    fold_direction = flows.direction
            batches_since_fold += 1
            if batches_since_fold >= fold_every:
                _fold_counters()
        else:
            out = _guarded_dispatch(datapath_step, tables, flows)
            if telem_total is not None:
                fold_direction = flows.direction
        spans.span("dispatch").end()
        pending.append(
            (
                out,
                valid,
                fold_direction,
                flows if flow_store is not None else None,
                sec_is_idx,
            )
        )
        stats.batches += 1
        if len(pending) >= 4:
            _drain_item(pending.pop(0))
        _metrics.batch_duration.observe(time.perf_counter() - iter_t0)
    while pending:
        _drain_item(pending.pop(0))
    if churn is not None:
        churn.stash()
    if telem_total is not None:
        from cilium_tpu.telemetry import fold_telemetry

        if telem_dev is not None:
            telem_total = telem_total + np.asarray(telem_dev).astype(
                np.uint64
            )
            telem_dev = None  # consumed; the trailing counter fold
            # must not fold this buffer a second time
        stats.telemetry = telem_total
        fold_telemetry(telem_total)
    stats.seconds = time.perf_counter() - t0

    if not accumulate_counters:
        return stats, None, None
    _fold_counters()
    kg = tables.policy.l4_meta.shape[2]
    return stats, acc_total[:, :, :kg], acc_total[:, :, kg:]


def _capture_replay_flows(
    flow_store, out, flows, valid: int, sec_is_idx: bool,
    id_table_host: np.ndarray, chip: int,
    ep_rev_lut: "Optional[np.ndarray]" = None,
) -> None:
    """Fold one drained batch's DatapathVerdicts into the flow ring
    (replay's Hubble feed): the full fused-path columns — CT state,
    prefilter attribution, post-DNAT dport — are available here,
    unlike the lattice-only audit path.  The derived peer identity
    (out.sec_id: src of an ingress flow, dst of an egress one) rides
    the matching side of the pair; the other side is 0 (replay has
    no endpoint-identity context)."""
    from cilium_tpu import option as _option
    from cilium_tpu.flow import allow_sample_for_level, capture_batch

    sec = np.asarray(out.sec_id)[:valid].astype(np.int64)
    if sec_is_idx:
        sec = id_table_host[
            np.minimum(sec, len(id_table_host) - 1)
        ].astype(np.int64)
    dirs = np.asarray(flows.direction)[:valid]
    zeros = np.zeros(valid, np.int64)
    ep_ids = np.asarray(flows.ep_index)[:valid]
    if ep_rev_lut is not None:
        ep_ids = ep_rev_lut[
            np.minimum(ep_ids, len(ep_rev_lut) - 1)
        ]
    capture_batch(
        flow_store,
        ep_ids=ep_ids,
        src_identities=np.where(dirs == 0, sec, zeros),
        dst_identities=np.where(dirs == 0, zeros, sec),
        dports=np.asarray(out.final_dport)[:valid],
        protos=np.asarray(flows.proto)[:valid],
        directions=dirs,
        allowed=np.asarray(out.allowed)[:valid],
        match_kind=np.asarray(out.match_kind)[:valid],
        proxy_port=np.asarray(out.proxy_port)[:valid],
        pre_dropped=np.asarray(out.pre_dropped)[:valid],
        ct_result=np.asarray(out.ct_result)[:valid],
        ct_delete=np.asarray(out.ct_delete)[:valid],
        lb_slave=np.asarray(out.lb_slave)[:valid],
        ipcache_miss=np.asarray(out.ipcache_miss)[:valid],
        chip=chip,
        allow_sample=allow_sample_for_level(
            _option.Config.opts.level(_option.MONITOR_AGGREGATION)
        ),
    )


def replay_pool(
    tables,
    pool: Dict[str, np.ndarray],
    picks: "np.ndarray | int",
    batch_size: int = 1 << 21,
    *,
    ct_map,
) -> ReplayStats:
    """Sustained-churn replay over a FLOW-UNIVERSE loader: the pool
    (unique flows, as real traffic repeats flows) uploads once and
    each batch moves only its u32 pick indices; the fused program
    gathers the flow columns on device (_flows_from_pool) before the
    datapath step + intent compaction.

    `picks` is either an explicit index array (caller-chosen flow
    order, one [B] u32 upload per batch) or an INT — "this many
    uniform picks, generated on device from an 8-byte PRNG key per
    batch", which spares the serial churn loop a per-batch index
    upload.

    Identical verdict/CT semantics to replay() with a record buffer of
    pool[picks] — only the loader changes: 4 bytes/tuple instead of
    decoding+packing+uploading 24-byte records.  `ct_map` is required: pool mode
    IS the churn loader (for churn-free pool replay, pre-stage device
    batches as engine/datapath.PersistentPairDispatcher's callers
    do).  Counter accumulation is not offered here for the same
    reason.
    """
    import time

    import jax

    from cilium_tpu.engine.datapath import DatapathTables

    stats = ReplayStats()
    tables = jax.device_put(tables)
    # the packed device copy caches ON the pool dict itself (seed +
    # timed churn reuse one universe; a dict-id-keyed cache would go
    # stale when CPython recycles a freed dict's id).  The dunder key
    # keeps consumers that iterate pool.items() for FLOW COLUMNS from
    # picking up the [8, P] device array as a bogus column; helpers
    # that take the pool dict should iterate FLOW_COLUMNS, not items.
    # The pool arrays are treated as immutable once replayed —
    # callers that mutate them must drop the cache key or pass a
    # fresh dict.
    pool_dev = pool.get(_POOL_PACK_KEY)
    if pool_dev is None:
        pool_dev = jax.device_put(pack_flow_pool(pool))
        pool[_POOL_PACK_KEY] = pool_dev
    churn_pool = _churn_fns()[2]
    churn_pool_rand = _churn_fns()[3]
    churn = _ChurnDriver(ct_map)

    # `picks` as an INT means "n uniform picks, generated on device":
    # an 8-byte PRNG key per batch replaces the [B] index upload.  An
    # explicit array keeps the caller-chosen flow order.
    if isinstance(picks, (int, np.integer)):
        import jax.random as jrandom

        n = int(picks)
        base_key = jrandom.PRNGKey(len(ct_map.entries) ^ n)
        t0 = time.perf_counter()
        batch_idx = 0
        for start in range(0, n, batch_size):
            valid = min(batch_size, n - start)
            key = jrandom.fold_in(base_key, batch_idx)
            batch_idx += 1
            first_pass = True
            while True:
                t = DatapathTables(
                    prefilter=tables.prefilter,
                    ipcache=tables.ipcache,
                    ct=churn.dev_snap,
                    lb=tables.lb,
                    policy=tables.policy,
                    tunnel=tables.tunnel,
                )
                header_d, intents_d = churn_pool_rand(
                    t, pool_dev, key, batch_size, valid
                )
                remaining = churn.drain(
                    header_d, intents_d, stats, valid, first_pass
                )
                first_pass = False
                if remaining == 0:
                    break
        churn.stash()
        stats.seconds = time.perf_counter() - t0
        return stats

    picks = np.asarray(picks).astype(np.uint32, copy=False)
    t0 = time.perf_counter()
    for start in range(0, len(picks), batch_size):
        chunk = picks[start : start + batch_size]
        valid = len(chunk)
        if valid < batch_size:
            chunk = np.concatenate(
                [
                    chunk,
                    np.zeros(batch_size - valid, dtype=np.uint32),
                ]
            )
        picks_dev = jax.device_put(chunk)
        first_pass = True
        while True:
            t = DatapathTables(
                prefilter=tables.prefilter,
                ipcache=tables.ipcache,
                ct=churn.dev_snap,
                lb=tables.lb,
                policy=tables.policy,
                tunnel=tables.tunnel,
            )
            header_d, intents_d = churn_pool(
                t, pool_dev, picks_dev, valid
            )
            remaining = churn.drain(
                header_d, intents_d, stats, valid, first_pass
            )
            first_pass = False
            if remaining == 0:
                break
    churn.stash()
    stats.seconds = time.perf_counter() - t0
    return stats


def replay_lattice(
    tables,
    buf: bytes,
    batch_size: int = 1 << 20,
    accumulate_counters: bool = True,
    ep_map: Optional[Dict[int, int]] = None,
    manager=None,
) -> tuple:
    """Replay through the bare policy lattice (PolicyTables only,
    identity pre-resolved from the record) — the post-hoc Hubble
    audit path.  Same return shape as replay()."""
    import time

    if manager is not None:
        manager.check_tables_current(tables)
    step = _replay_step()
    stats = ReplayStats()
    acc = _CounterAccumulator() if accumulate_counters else None

    pending = []  # pipelined dispatch, bounded depth
    t0 = time.perf_counter()
    for batch, valid in read_batches(buf, batch_size, ep_map):
        out = _guarded_dispatch(step, tables, batch)
        pending.append((out, valid))
        stats.batches += 1
        if len(pending) >= 4:
            _drain(pending.pop(0), stats, acc)
    while pending:
        _drain(pending.pop(0), stats, acc)
    stats.seconds = time.perf_counter() - t0

    if acc is None:
        return stats, None, None
    return stats, acc.l4, acc.l3


class _CounterAccumulator:
    l4: Optional[np.ndarray] = None
    l3: Optional[np.ndarray] = None

    def add(self, l4_counts, l3_counts) -> None:
        if self.l4 is None:
            self.l4 = np.zeros(l4_counts.shape, dtype=np.uint64)
            self.l3 = np.zeros(l3_counts.shape, dtype=np.uint64)
        self.l4 += np.asarray(l4_counts).astype(np.uint64)
        self.l3 += np.asarray(l3_counts).astype(np.uint64)


def _tally(verdicts, valid, stats: ReplayStats) -> None:
    allowed = np.asarray(verdicts.allowed)[:valid]
    proxy = np.asarray(verdicts.proxy_port)[:valid]
    stats.total += int(valid)
    stats.allowed += int(allowed.sum())
    stats.denied += int(valid - allowed.sum())
    stats.redirected += int((proxy > 0).sum())


def _drain(item, stats: ReplayStats, acc: Optional[_CounterAccumulator]) -> None:
    (verdicts, l4_counts, l3_counts), valid = item
    _tally(verdicts, valid, stats)
    if acc is not None:
        acc.add(l4_counts, l3_counts)


def _drain_fused(item, stats: ReplayStats) -> None:
    """Fused-path drain: counters live in the carried device
    accumulators, so the item is just (verdicts, valid)."""
    verdicts, valid = item
    _tally(verdicts, valid, stats)


_REPLAY_STEP = None


def _replay_step():
    """Module-level jitted lattice step (one compilation cache across
    replay_lattice() calls, like engine.verdict.evaluate_batch)."""
    global _REPLAY_STEP
    if _REPLAY_STEP is None:
        import jax

        _REPLAY_STEP = jax.jit(_verdict_kernel_with_counters)
    return _REPLAY_STEP


def slot_keys_from_tables(tables) -> Dict[int, Tuple[int, int]]:
    """Recover global L4 slot → (dport, proto) from the compiled
    port_slot table (the inverse of lower_map_state's slot_of)."""
    from cilium_tpu.compiler.tables import NO_SLOT

    port_slot = np.asarray(tables.port_slot)
    protos, dports = np.nonzero(port_slot != NO_SLOT)
    slots = port_slot[protos, dports]
    return {
        int(j): (int(dport), int(proto))
        for j, dport, proto in zip(slots, dports, protos)
    }


def sync_counters_to_endpoints(
    l4_counts: Optional[np.ndarray],
    l3_counts: Optional[np.ndarray],
    manager,
    tables=None,
    index: Optional[Dict[int, int]] = None,
) -> int:
    """Fold accumulated device counters back into the endpoints'
    realized map states (the packets field of policy_entry the agent
    reads back from the datapath, pkg/maps/policymap PolicyEntry).

    Pass the `tables`/`index` the counters were computed against; a
    republish between replay() and sync would otherwise shift the
    identity/slot indexing and misattribute counts.  Falls back to the
    currently-published version when not given.  Returns entries
    updated."""
    if tables is None or index is None:
        _, tables, index = manager.published()
    if tables is None:
        return 0
    # NOTE: no staleness guard needed here — this function reads only
    # tables.id_table (freshly allocated per rebuild) and
    # tables.port_slot (write-once cells), both of which stay valid in
    # arbitrarily old snapshots.  The in-place-mutation hazard is the
    # stacked per-endpoint rows, guarded at replay()/evaluation time.
    updated = 0
    rev_index = {v: k for k, v in index.items()}
    id_table = np.asarray(tables.id_table)
    if l3_counts is not None:
        # L3 counters are indexed by identity index.  Re-read the
        # realized state under the endpoint lock per update: a
        # concurrent sync_policy_map publishes a NEW array-backed
        # state (copy-on-write), and an increment applied through a
        # pre-sync view would land in the superseded snapshot.
        for e, d, idx in zip(*np.nonzero(l3_counts)):
            ep = manager.lookup(rev_index.get(int(e), -1))
            if ep is None:
                continue
            key = PolicyKey(int(id_table[idx]), 0, 0, int(d))
            with ep.lock:
                entry = ep.realized_map_state.get(key)
                if entry is not None:
                    entry.packets += int(l3_counts[e, d, idx])
                    updated += 1
    if l4_counts is not None:
        # L4 counters are indexed by global slot; a slot hit covers
        # every (identity, dport, proto) entry of that filter — the
        # wildcard entry takes the count (exact-entry attribution
        # would need per-(slot, identity) counters; the reference
        # bumps the entry the probe hit, which for MATCH_L4 is the
        # exact key and for MATCH_L4_WILD the wildcard — we fold both
        # into the slot's wildcard-or-first entry, preserving totals).
        slot_keys = slot_keys_from_tables(tables)
        for e, d, j in zip(*np.nonzero(l4_counts)):
            ep = manager.lookup(rev_index.get(int(e), -1))
            if ep is None or int(j) not in slot_keys:
                continue
            dport, proto = slot_keys[int(j)]
            count = int(l4_counts[e, d, j])
            wild = PolicyKey(0, dport, proto, int(d))
            with ep.lock:
                entry = ep.realized_map_state.get(wild)
                if entry is None:
                    for key, cand in ep.realized_map_state.items():
                        if (
                            key.dest_port == dport
                            and key.nexthdr == proto
                            and key.traffic_direction == int(d)
                        ):
                            entry = cand
                            break
                if entry is not None:
                    entry.packets += count
                    updated += 1
    return updated
