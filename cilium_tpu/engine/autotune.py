"""Batch-size / jit-class autotuning + gather-byte accounting.

The fused dispatch has two knobs that trade dispatch overhead against
latency and bytes-moved-per-tuple:

  * the BATCH SIZE — bigger batches amortize the per-dispatch floor
    but push p99 batch latency up linearly;
  * the HOT-PLANE PACK WIDTH — the hashed L4 entry tables' row lane
    count (compiler.tables.L4H_LANES): narrower rows halve the
    dominant per-tuple gather and lane-compare work, wider rows halve
    the bucket count (compiler.tables.repack_hash_lanes re-places the
    entries at any width without recompiling policy).

`autotune` runs a caller-supplied measurement over a small candidate
grid and picks the highest verdicts/s whose p99 batch latency stays
under the bound.  The choice is cached per TABLE SHAPE CLASS (the jit
cache key the dispatch programs compile against), so a long-running
server tunes once per layout instead of per publish — recompile
storms would otherwise show up in the existing
`cilium_jit_cache_*{site}` metrics this module deliberately rides.

`hot_gather_profile` is the bytes-moved model behind the tuner and
`hot_bytes_per_tuple`: per-leaf bytes GATHERED per
tuple by the fused per-direction pipeline, split into the hot plane
(leaves the hashed-probe kernels actually gather) and the cold plane
(dense-fallback leaves a hot-only publication never ships).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np


@dataclass
class Trial:
    params: dict
    verdicts_per_sec: float
    p99_batch_ms: float
    admitted: bool


@dataclass
class TuneChoice:
    params: dict
    verdicts_per_sec: float
    p99_batch_ms: float
    trials: List[Trial] = field(default_factory=list)
    cached: bool = False


# shape-class key → TuneChoice (process-lifetime: the jit caches the
# tuned programs live exactly as long)
_CHOICES: Dict[tuple, TuneChoice] = {}


def shape_class_key(policy_tables) -> tuple:
    """The table shape class a tuned choice is valid for — the same
    axes that key the dispatch programs' jit cache entries."""
    rows = getattr(policy_tables, "l4_hash_rows", None)
    wrows = getattr(policy_tables, "l4_wild_rows", None)
    return (
        tuple(policy_tables.l4_meta.shape),
        int(policy_tables.id_table.shape[0]),
        None if rows is None else tuple(rows.shape),
        None if wrows is None else tuple(wrows.shape),
    )


def cached_choice(key: tuple) -> Optional[TuneChoice]:
    return _CHOICES.get(key)


def autotune(
    candidates: Sequence[dict],
    run_candidate: Callable[[dict], Tuple[float, float]],
    p99_bound_ms: float = float("inf"),
    cache_key: Optional[tuple] = None,
    log: Optional[Callable[[str], None]] = None,
) -> TuneChoice:
    """Measure every candidate (`run_candidate(params)` →
    (verdicts_per_sec, p99_batch_ms)) and pick the fastest admitted
    one; candidates over the p99 bound are rejected unless nothing
    fits (then the lowest-latency candidate wins — a serving plane
    must pick SOMETHING).  With `cache_key` the choice is memoized
    per table shape class."""
    if cache_key is not None:
        hit = _CHOICES.get(cache_key)
        if hit is not None:
            return hit
    trials: List[Trial] = []
    for params in candidates:
        vps, p99 = run_candidate(dict(params))
        admitted = p99 <= p99_bound_ms
        trials.append(Trial(dict(params), vps, p99, admitted))
        if log is not None:
            log(
                f"autotune candidate {params}: "
                f"{vps / 1e6:.1f}M verdicts/s, p99 {p99:.0f} ms"
                f"{'' if admitted else ' (over p99 bound)'}"
            )
    admitted = [t for t in trials if t.admitted]
    if admitted:
        best = max(admitted, key=lambda t: t.verdicts_per_sec)
    else:
        # bound unsatisfiable on this hardware: throughput wins
        # (shrinking the batch further only lowers BOTH)
        best = max(trials, key=lambda t: t.verdicts_per_sec)
    choice = TuneChoice(
        params=best.params,
        verdicts_per_sec=best.verdicts_per_sec,
        p99_batch_ms=best.p99_batch_ms,
        trials=trials,
    )
    if cache_key is not None:
        _CHOICES[cache_key] = choice
        choice.cached = True
    return choice


def measure_dispatch(
    step: Callable,
    make_args: Callable[[], tuple],
    n_tuples_per_call: int,
    reps: int = 4,
    outstanding: int = 2,
    sync_reps: int = 3,
) -> Tuple[float, float]:
    """One candidate measurement: a short pipelined loop for
    sustained verdicts/s (dispatch overlap, like the serving loop)
    plus a few synchronous calls for the per-batch latency tail.
    `make_args()` returns fresh call args per rep (carried/donated
    buffers must be re-made by the caller's closure)."""
    import jax

    # warmup/compile
    out = step(*make_args())
    jax.block_until_ready(out)
    outs = []
    t0 = time.perf_counter()
    for _ in range(reps):
        outs.append(step(*make_args()))
        if len(outs) > outstanding:
            jax.block_until_ready(outs.pop(0))
    jax.block_until_ready(outs)
    vps = reps * n_tuples_per_call / (time.perf_counter() - t0)
    lat = []
    for _ in range(sync_reps):
        t1 = time.perf_counter()
        jax.block_until_ready(step(*make_args()))
        lat.append(time.perf_counter() - t1)
    # p99 over a handful of sync reps is the max — the honest tail
    # estimate at this sample count
    return vps, max(lat) * 1000.0


# ---------------------------------------------------------------------------
# Gather-byte accounting (the bytes-moved model)
# ---------------------------------------------------------------------------


def hot_gather_profile(tables, packed_io: bool = True) -> List[dict]:
    """Per-leaf bytes gathered per tuple by the fused per-direction
    pipeline, with the plane ('hot'/'cold') and pipeline stage of
    each.  `tables` is an engine.datapath.DatapathTables; cold rows
    are reported at ZERO bytes when the policy tables carry the
    hashed entry pair (the kernel never gathers them) and at their
    dense-probe cost otherwise.

    Broadcast compares (stashes, prefilter ranges) move no
    gather bytes — they are compute, priced separately — so they do
    not appear here."""
    rows: List[dict] = []
    pol = tables.policy

    def add(stage, leaf, plane, nbytes, note=""):
        rows.append(
            {
                "stage": stage,
                "leaf": leaf,
                "plane": plane,
                "bytes_per_tuple": float(nbytes),
                "note": note,
            }
        )

    # CT: one bucket-row gather serves the service + flow probes
    ct_lanes = int(np.asarray(tables.ct.buckets).shape[1])
    ct_ew = int(getattr(tables.ct, "entry_words", 5))
    add(
        "ct", "ct.buckets", "hot", ct_lanes * 4,
        "1 row gather"
        + (f", sub-word {ct_ew}-word entries" if ct_ew != 5 else ""),
    )
    # LB: service bucket row gather (egress only — averaged at 1/2);
    # the inline layout keys+backends in one row, the classic layout
    # pays a second backend-row gather on service hits (rare, priced
    # at the key row only)
    lb_rows = getattr(tables.lb, "rows", None)
    if lb_rows is None:
        lb_rows = getattr(tables.lb, "buckets", None)
    if lb_rows is not None:
        lb_lanes = int(np.asarray(lb_rows).shape[1])
        add(
            "lb", "lb.rows", "hot", lb_lanes * 4 / 2,
            "egress half-batches only",
        )
    # ipcache: the bucketized form pays one bucket-row gather plus
    # one range-class row gather per distinct non-/32 prefix length
    # (the hashed table that replaced the [B, P] broadcast scan);
    # the DIR-24-8 fallback is two element gathers
    from cilium_tpu.ipcache.lpm import IPCacheDevice

    ipc = getattr(tables, "ipcache", None)
    if isinstance(ipc, IPCacheDevice):
        ip_lanes = int(np.asarray(ipc.buckets).shape[1])
        sub_note = ""
        if getattr(ipc, "bucket_entries", 0):
            sub_note = (
                f", sub-word val{ipc.value_width}/"
                f"l3w{ipc.l3_width}"
            )
        add(
            "ipcache", "ipcache.buckets", "hot", ip_lanes * 4,
            "1 bucket-row gather" + sub_note,
        )
        if ipc.range_rows is not None:
            n_classes = len(ipc.range_class_plens)
            rw = int(np.asarray(ipc.range_rows).shape[1])
            add(
                "ipcache", "ipcache.range_rows", "hot",
                n_classes * rw * 4,
                f"{n_classes} prefix-length class gathers",
            )
        else:
            add(
                "ipcache", "ipcache.ranges", "hot", 0,
                "[B, P] broadcast scan (compute, not gathers)",
            )
    else:
        add("ipcache", "ipcache.dir24_8", "hot", 8, "2 element gathers")
    hash_rows = getattr(pol, "l4_hash_rows", None)
    if hash_rows is not None:
        from cilium_tpu.compiler.tables import l4_entry_words

        lanes = int(np.asarray(hash_rows).shape[1])
        wlanes = int(np.asarray(pol.l4_wild_rows).shape[1])
        ew = l4_entry_words(pol)
        add(
            "lattice", "l4_hash_rows", "hot", lanes * 4,
            f"pack width {lanes}"
            + (", sub-word 2-word entries" if ew == 2 else ""),
        )
        add(
            "lattice", "l4_wild_rows", "hot", wlanes * 4,
            f"pack width {wlanes}"
            + (", sub-word 2-word entries" if ew == 2 else ""),
        )
        if ew == 2:
            # the compact form drops the per-entry proxy copy and
            # reconstructs it with ONE l4_meta element gather at the
            # combined slot index — priced honestly
            add(
                "lattice", "l4_meta", "hot", 4,
                "compact-entry proxy reconstruction",
            )
        # identity index rides the idx-form ipcache when present;
        # otherwise one id_direct element gather
        add("lattice", "id_direct", "hot", 4, "skipped w/ idx ipcache")
        for leaf in ("port_slot", "l4_allow_bits"):
            add("lattice", leaf, "cold", 0, "hashed probe active")
        add("lattice", "l3_allow_bits", "hot", 0, "l3-plane ipcache")
    else:
        add("lattice", "port_slot", "cold", 2, "dense slot probe")
        add("lattice", "l4_allow_bits", "cold", 4, "dense bit probe")
        add("lattice", "l4_meta", "cold", 4, "dense meta probe")
        add("lattice", "l3_allow_bits", "hot", 4, "l3 word gather")
        add("lattice", "id_direct", "hot", 4, "identity index")
    # batch IO: packed flow columns in, packed verdict words out
    add(
        "io", "flow_batch", "hot", 16 if packed_io else 32,
        "H2D packed columns" if packed_io else "H2D u32 columns",
    )
    return rows


def hot_bytes_per_tuple(tables, packed_io: bool = True) -> float:
    """Total HOT-plane bytes gathered per tuple (the headline
    `hot_bytes_per_tuple` bench metric)."""
    return sum(
        r["bytes_per_tuple"]
        for r in hot_gather_profile(tables, packed_io=packed_io)
        if r["plane"] == "hot"
    )


def cold_bytes_per_tuple(tables) -> float:
    return sum(
        r["bytes_per_tuple"]
        for r in hot_gather_profile(tables)
        if r["plane"] == "cold"
    )


# ---------------------------------------------------------------------------
# Verdict memoization (engine/memo.py) tuning
# ---------------------------------------------------------------------------


MEMO_ROWS_MIN = 1 << 10
MEMO_ROWS_MAX = 1 << 20


def memo_rows_for_headroom(
    headroom_bytes: int,
    entries: int = 8,
    headroom_frac: float = 0.25,
) -> int:
    """Largest pow2 verdict-cache row count whose device buffer fits
    within `headroom_frac` of the given HBM headroom (the ROADMAP's
    lever (d): size the cache for the access pattern AND the budget,
    not a fixed list).  Row cost mirrors engine/memo.py's layout:
    CACHE_WORDS * entries + 1 u32 words per row, one scratch row.
    Clamped to [MEMO_ROWS_MIN, MEMO_ROWS_MAX]; returns 0 when even
    the minimum doesn't fit (the tuner then keeps memo off)."""
    from cilium_tpu.engine.memo import CACHE_WORDS

    row_bytes = (CACHE_WORDS * int(entries) + 1) * 4
    budget = max(int(headroom_bytes * headroom_frac), 0)
    rows = MEMO_ROWS_MIN
    if (rows + 1) * row_bytes > budget:
        return 0
    while (
        rows < MEMO_ROWS_MAX
        and (rows * 2 + 1) * row_bytes <= budget
    ):
        rows <<= 1
    return rows


def memo_candidates(
    batch: int,
    include_off: bool = True,
    rows_options: "Optional[Sequence[int]]" = None,
    rep_shifts: Sequence[int] = (2,),
    store=None,
    hbm_bytes: int = 16 << 30,
    headroom_frac: float = 0.25,
    rows_cap: Optional[int] = None,
) -> List[dict]:
    """Verdict-memoization candidates for the tuner: cache row counts ×
    rep/miss compaction capacities (batch >> shift, so the lattice
    gather chain shrinks when the workload's key skew covers it).
    `{"memo": False}` is the ENABLE THRESHOLD: when the sort+probe
    overhead beats the gathers saved on this workload the tuner
    keeps the uncached program — the choice is cached per table
    shape class like the batch/pack-width choice, so a long-running
    server decides once per layout.

    Capacity is HBM-aware: with a `store` (any object exposing
    chip_bytes() → {ordinal: resident bytes}, e.g. the daemon's
    DeviceTableStore or the router's DatapathStore), the candidate
    row counts derive from the MEASURED per-shard headroom —
    hbm_bytes minus the worst chip's resident table bytes — instead
    of a fixed list, so the cache never competes with the sharded
    table planes for the same HBM.  An explicit `rows_options`
    overrides."""
    if rows_options is None:
        if store is not None:
            try:
                per_chip = store.chip_bytes() or {}
            except Exception:  # pragma: no cover — defensive
                per_chip = {}
            worst = max(per_chip.values()) if per_chip else 0
            rows = memo_rows_for_headroom(
                max(hbm_bytes - worst, 0),
                headroom_frac=headroom_frac,
            )
            if rows and rows_cap:
                rows = min(rows, int(rows_cap))
            rows_options = (rows,) if rows else ()
        else:
            rows_options = (1 << 14,)
    cands: List[dict] = [{"memo": False}] if include_off else []
    for rows in rows_options:
        for shift in rep_shifts:
            cands.append(
                {
                    "memo": True,
                    "rows": int(rows),
                    "rep_cap": max(int(batch) >> shift, 1 << 10),
                }
            )
    return cands


# ---------------------------------------------------------------------------
# Online re-tune: the telemetry-driven layout loop (perf plane consumer)
# ---------------------------------------------------------------------------


# hysteresis bounds: drift must exceed these before a re-tune fires,
# and the cooldown gates how often one may fire at all — the
# README's "retune hysteresis contract"
RETUNE_DEFAULTS = {
    # serving-window p99 above factor x the post-swap baseline
    "p99_factor": 1.5,
    # batch-fill p50 below this while the plane is actually batching
    "fill_low_pct": 30.0,
    # windowed ingest-stall fraction above this
    "stall_frac": 0.25,
    # wall-clock + batch-count cooldown between swaps
    "cooldown_s": 30.0,
    "min_batches": 64,
    # windows thinner than this can't witness drift
    "min_window": 32,
}


def retune_trigger(perf, plane, config=None):
    """The drift detector: reads the perf plane's serving_p99 /
    batch-fill / stall windows against the hysteresis bounds and
    returns a trigger name ('p99_drift' | 'fill_low' | 'stall') or
    None.  Pure read — no side effects, so tests can drive it with
    injected telemetry."""
    cfg = dict(RETUNE_DEFAULTS)
    cfg.update(config or {})
    now = time.monotonic()
    # cooldown: wall clock AND batch count since the last swap
    if perf.last_retune_monotonic is not None:
        if now - perf.last_retune_monotonic < cfg["cooldown_s"]:
            return None
        if perf.seq - perf.batches_at_retune < cfg["min_batches"]:
            return None
    wall = perf.phases["wall"].stats(now)
    if wall["n"] < cfg["min_window"]:
        return None
    p99_ms = (
        plane._window_p99_ms() if plane is not None else 0.0
    )
    if perf.baseline_p99_ms is None:
        # first full window since start/swap: learn the baseline,
        # never fire on it
        perf.baseline_p99_ms = p99_ms
        return None
    if (
        perf.baseline_p99_ms > 0
        and p99_ms > cfg["p99_factor"] * perf.baseline_p99_ms
    ):
        return "p99_drift"
    fill = perf.fill.stats(now)
    if fill["n"] >= cfg["min_window"] and (
        fill["p50"] < cfg["fill_low_pct"]
    ):
        return "fill_low"
    if perf.stall_fraction(now) > cfg["stall_frac"]:
        return "stall"
    return None


def _datapath_lane_options(daemon):
    """(ct_opts, ip_opts) for the fused-plane hot-lane sweep: CT
    bucket-row widths (compact_ct_snapshot's lanes seam, priced at
    lanes*4 bytes/tuple like every bucketized gather) and the
    ipcache plane's wide-vs-sub-word row widths.  Each option is
    None ("keep the current layout") or a dict of candidate params;
    worlds with no assemblable fused datapath sweep nothing."""
    ct_opts: List[Optional[dict]] = [None]
    ip_opts: List[Optional[dict]] = [None]
    try:
        dt = daemon.datapath_tables()
    except Exception:
        return ct_opts, ip_opts
    from cilium_tpu.ct.device import compact_ct_snapshot
    from cilium_tpu.ipcache.lpm import (
        IPCacheDevice,
        subword_ipcache,
    )

    ct_now = int(np.asarray(dt.ct.buckets).shape[1])
    for lanes in (32, 64):
        if lanes == ct_now:
            continue
        try:  # only offer widths this snapshot can actually pack to
            compact_ct_snapshot(dt.ct, lanes=lanes)
        except ValueError:
            continue
        ct_opts.append({"ct_lanes": lanes})
    ipc = dt.ipcache
    if isinstance(ipc, IPCacheDevice) and hasattr(ipc, "buckets"):
        ip_now = int(np.asarray(ipc.buckets).shape[1])
        if getattr(ipc, "bucket_entries", 0):
            # currently sub-word: nothing narrower to offer; the
            # wide layout is not reachable through a lane knob
            pass
        elif ipc.values_are_idx:
            try:
                packed = subword_ipcache(ipc)
                ip_packed = int(
                    np.asarray(packed.buckets).shape[1]
                )
                if ip_packed != ip_now:
                    ip_opts.append({
                        "ip_lanes": ip_packed,
                        "ip_subword": True,
                    })
            except ValueError:
                pass
    return ct_opts, ip_opts


def retune_candidates(daemon, plane):
    """The online candidate grid: batch class (half/same/double),
    hot-plane pack width (the repack_hash_lanes widths), memo
    capacity (HBM-aware via the store's chip_bytes seam), and the
    fused plane's CT / ipcache hot-lane widths (the
    subword_datapath_tables ct_lanes seam + the ipcache sub-word
    toggle), all scored by the same hot_bytes_per_tuple byte model."""
    batch = plane.batch_size if plane is not None else 1 << 12
    batches = sorted(
        {max(batch // 2, 256), batch, min(batch * 2, 1 << 15)}
    )
    compiler = daemon.endpoint_manager._fleet_compiler
    lanes_now = compiler.hash_lanes
    lanes_opts = sorted({lanes_now, 32, 64})
    store = getattr(
        daemon.endpoint_manager, "_device_store", None
    )
    memo_rows = [daemon.verdict_cache_rows]
    if store is not None:
        for c in memo_candidates(
            batch, include_off=False, store=store
        ):
            memo_rows.append(c["rows"])
    memo_rows = sorted(set(memo_rows))
    ct_opts, ip_opts = _datapath_lane_options(daemon)
    cands = []
    for b in batches:
        for lanes in lanes_opts:
            for rows in memo_rows:
                for ct in ct_opts:
                    for ip in ip_opts:
                        cand = {
                            "batch": b, "hash_lanes": lanes,
                            "memo_rows": rows,
                        }
                        if ct:
                            cand.update(ct)
                        if ip:
                            cand.update(ip)
                        cands.append(cand)
    return cands


def _model_run_candidate(daemon, plane):
    """Default candidate scorer when no measured `run_candidate` is
    supplied: rank by the hot_bytes_per_tuple byte model at each candidate's
    pack width, scaled by the plane's measured verdicts/s EWMA —
    deterministic and sweep-free, so the serve loop never pays a
    device measurement campaign mid-stream.  Callers wanting a
    MEASURED sweep pass their own run_candidate."""
    _, tables, _ = daemon.endpoint_manager.published()
    base_vps = max(daemon.perf.verdicts_per_sec(), 1.0)
    base_lanes = daemon.endpoint_manager._fleet_compiler.hash_lanes
    base_batch = plane.batch_size if plane is not None else 1 << 12
    base_bpt = None
    base_ct_lanes = base_ip_lanes = None
    if tables is not None:
        try:
            dt = daemon.datapath_tables(policy=tables)
            base_bpt = hot_bytes_per_tuple(dt)
            base_ct_lanes = int(
                np.asarray(dt.ct.buckets).shape[1]
            )
            if hasattr(dt.ipcache, "buckets"):
                base_ip_lanes = int(
                    np.asarray(dt.ipcache.buckets).shape[1]
                )
        except Exception:
            base_bpt = None

    def run(params):
        lanes = int(params.get("hash_lanes", base_lanes))
        batch = int(params.get("batch", base_batch))
        # modeled bytes scale with the dominant hashed-pair lanes;
        # throughput ~ 1/bytes, p99 ~ batch/vps
        if base_bpt:
            # the hashed pair contributes lanes*4 + wlanes*4; scale
            # only that share of the model
            delta_b = (lanes - base_lanes) * 4 * 2
            # the fused plane's bucketized gathers each price one
            # row at lanes*4 (hot_gather_profile): a candidate CT /
            # ipcache width moves exactly that share
            if base_ct_lanes and params.get("ct_lanes"):
                delta_b += (
                    int(params["ct_lanes"]) - base_ct_lanes
                ) * 4
            if base_ip_lanes and params.get("ip_lanes"):
                delta_b += (
                    int(params["ip_lanes"]) - base_ip_lanes
                ) * 4
            bpt = max(base_bpt + delta_b, 1.0)
            vps = base_vps * base_bpt / bpt
        else:
            vps = base_vps
        vps *= batch / max(base_batch, 1)  # amortized floor
        p99_ms = batch / max(vps, 1.0) * 1000.0
        return vps, p99_ms

    return run


def online_retune(
    daemon,
    *,
    trigger=None,
    force: bool = False,
    candidates=None,
    run_candidate=None,
    p99_bound_ms: Optional[float] = None,
    config=None,
) -> Optional[dict]:
    """The serve-loop-driven re-tune controller: watch the perf
    plane's serving_p99 / batch-fill / stall windows, and when drift
    exceeds the hysteresis bounds re-run the cached autotuner over
    the candidate grid (batch class, pack width, memo capacity) and
    apply the choice through the existing seams —

      * pack width: FleetCompiler.set_hash_lanes + regenerate_all →
        new layout stamp → the device store refuses the delta,
        full-uploads, deltas resume (bit-identity by construction);
      * batch class: ServingPlane.set_batch_size (in-flight batches
        keep their meta-snapshotted pad class);
      * memo capacity: verdict_cache_rows + drop the device buffer
        (the lazy _ensure_verdict_cache recreates it at the new
        size, stamp-checked as ever).

    Returns the retune record (also appended to perf.retunes and
    counted in cilium_retune_total{trigger}), or None when the
    hysteresis said "hold"."""
    from cilium_tpu import tracing
    from cilium_tpu.metrics import registry as metrics

    perf = daemon.perf
    plane = getattr(daemon, "serving", None)
    if trigger is None:
        if force:
            trigger = "forced"
        else:
            trigger = retune_trigger(perf, plane, config)
            if trigger is None:
                return None
    if candidates is None:
        candidates = retune_candidates(daemon, plane)
    if run_candidate is None:
        run_candidate = _model_run_candidate(daemon, plane)
    if p99_bound_ms is None:
        p99_bound_ms = (
            plane.slo_s * 1000.0 if plane is not None
            else float("inf")
        )
    _, tables, _ = daemon.endpoint_manager.published()
    cache_key = None
    if tables is not None:
        cache_key = shape_class_key(tables) + ("online",)
    compiler = daemon.endpoint_manager._fleet_compiler
    before = {
        "batch": plane.batch_size if plane is not None else None,
        "hash_lanes": compiler.hash_lanes,
        "memo_rows": daemon.verdict_cache_rows,
        "layout_stamp": (
            tables_layout_stamp(tables)
            if tables is not None else None
        ),
    }
    with tracing.tracer.span(
        "autotune.retune", site="autotune",
        attrs={"trigger": trigger},
    ) as sp:
        choice = autotune(
            candidates, run_candidate,
            p99_bound_ms=p99_bound_ms, cache_key=cache_key,
        )
        params = choice.params
        applied = {}
        if (
            plane is not None
            and params.get("batch")
            and int(params["batch"]) != plane.batch_size
        ):
            plane.set_batch_size(int(params["batch"]))
            applied["batch"] = int(params["batch"])
        rows = params.get("memo_rows")
        if rows and int(rows) != daemon.verdict_cache_rows:
            daemon.verdict_cache_rows = int(rows)
            with daemon.lock:
                daemon.verdict_cache = None  # lazy re-create
            applied["memo_rows"] = int(rows)
        lanes = params.get("hash_lanes")
        if lanes and int(lanes) != compiler.hash_lanes:
            compiler.set_hash_lanes(int(lanes))
            daemon.regenerate_all(f"online retune ({trigger})")
            applied["hash_lanes"] = int(lanes)
        # fused-plane hot-lane widths: the CT row width / ipcache
        # sub-word knobs feed daemon.datapath_tables, so the NEXT
        # datapath publish ships the new layout and the store's
        # cross-layout refusal turns it into exactly one full
        # upload (candidates only carry these keys when they differ
        # from the current layout — see retune_candidates)
        dp_changed = False
        ct_l = params.get("ct_lanes")
        if ct_l and int(ct_l) != getattr(
            daemon, "datapath_ct_lanes", None
        ):
            daemon.datapath_ct_lanes = int(ct_l)
            applied["ct_lanes"] = int(ct_l)
            dp_changed = True
        if "ip_subword" in params and bool(
            params["ip_subword"]
        ) != bool(getattr(daemon, "datapath_ip_subword", False)):
            daemon.datapath_ip_subword = bool(params["ip_subword"])
            applied["ip_subword"] = bool(params["ip_subword"])
            dp_changed = True
        if dp_changed:
            router = getattr(daemon, "mesh_router", None)
            if router is not None and router.dp_store is not None:
                try:
                    router.publish_datapath(
                        daemon.datapath_tables()
                    )
                except Exception:  # noqa: BLE001 — next churn
                    pass  # publish re-ships the new layout
        _, tables_after, _ = daemon.endpoint_manager.published()
        after_stamp = (
            tables_layout_stamp(tables_after)
            if tables_after is not None else None
        )
        sp.attrs["applied"] = dict(applied)
        metrics.retune_total.inc(trigger)
        record = perf.note_retune(
            {
                "trigger": trigger,
                "choice": dict(params),
                "applied": applied,
                "before": before,
                "layout_stamp_after": after_stamp,
            }
        )
    return record


def tables_layout_stamp(tables) -> Optional[int]:
    """The published tables' layout stamp (compiler.tables
    .tables_layout_version) — None for tables without the hashed
    pair (the stamp would not gate a delta anyway)."""
    try:
        from cilium_tpu.compiler.tables import (
            tables_layout_version,
        )

        return int(tables_layout_version(tables))
    except Exception:
        return None


def effective_hot_bytes_per_tuple(
    tables, dedup_factor: float, packed_io: bool = True
) -> float:
    """The gather-byte model under intra-batch dedup:
    hot_bytes_per_tuple divided by the measured dedup factor — the
    bytes the lattice ACTUALLY moves per tuple once duplicates
    collapse onto one representative.  Cache hits shrink it further
    (a hit gathers one cache row instead of the lattice rows); this
    line deliberately prices only the dedup level so the model stays
    conservative."""
    return hot_bytes_per_tuple(tables, packed_io=packed_io) / max(
        float(dedup_factor), 1.0
    )
