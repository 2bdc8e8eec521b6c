"""Device-resident table epochs: versioned double-buffered publication.

The serving path used to re-upload every PolicyTables leaf after each
control-plane publish (device_put of ~hundreds of MB of numpy per
flip).  This store keeps TWO device-resident epochs ping-ponging, the
device analog of the realized/backup map shuffle
(pkg/datapath/ipcache/listener.go:167):

  * `publish(tables, delta)` installs the new generation into the
    SPARE epoch.  With a TableDelta covering the spare's stamp, the
    update is a compact jitted scatter (`tables.at[idx].set(rows)`,
    donate_argnums on the spare pytree so XLA patches the resident
    buffers in place) — bytes shipped are proportional to the CHANGE,
    not the world.  Without a delta (shape-class change, stale spare)
    it falls back to a full upload.
  * in-flight batches dispatched against the CURRENT epoch finish on
    it untouched; only the spare's buffers are donated.
  * `check_current` raises for tables whose epoch has since been
    donated — the device-side extension of
    FleetCompiler.check_tables_current's one-flip window.

Replication: pass `shardings` (a PolicyTables pytree of NamedSharding)
and every chip of a mesh receives the same scatter — tables are
replicated across the mesh (engine/sharded.py), so one delta updates
the whole fleet of chips.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import numpy as np

from cilium_tpu import faultinject, tracing
from cilium_tpu.compiler.delta import TableDelta, tables_nbytes
from cilium_tpu.compiler.tables import (
    COLD_LEAVES,
    PolicyTables,
    split_hot,
    tables_layout_version,
)
from cilium_tpu.logging import get_logger
from cilium_tpu.metrics import registry as metrics

log = get_logger("publish")

# low bits of a layout stamp carrying the hashed-table pack widths;
# the high bits are the hot/cold coldness mask (see
# tables_layout_version) — the store compares pack widths across the
# delta/epoch seam and owns the coldness decision itself
_LAYOUT_LANES_MASK = (1 << 22) - 1


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (>= 1): the single size-class
    rounding every scatter/repair/re-split payload shares, so the
    jit caches keyed on padded shapes can never drift apart."""
    size = 1
    while size < n:
        size <<= 1
    return size


def _pad_pow2(update):
    """Pad scatter payloads to the next power of two by repeating the
    last entry (duplicate identical writes are deterministic), so the
    jitted updater recompiles per size CLASS instead of per size."""
    k = len(update.values)
    size = next_pow2(k)
    if size == k:
        return update.idx, update.values
    pad = size - k
    idx = tuple(
        np.concatenate([i, np.repeat(i[-1:], pad)]) for i in update.idx
    )
    values = np.concatenate(
        [update.values, np.repeat(update.values[-1:], pad, axis=0)]
    )
    return idx, values


def _chip_resident_bytes(dev_tables) -> Dict[int, int]:
    """Actual per-device resident bytes of a device table pytree,
    summed from each leaf's addressable shards — the measured (not
    modeled) per-chip HBM footprint of one epoch.  On a sharded
    store the identity-major leaves contribute 1/num_shards per
    chip; replicated leaves contribute their full size everywhere."""
    import jax

    per: Dict[int, int] = {}
    for leaf in jax.tree.leaves(dev_tables):
        shards = getattr(leaf, "addressable_shards", None)
        if shards is None:
            continue
        for sh in shards:
            ordinal = int(sh.device.id)
            per[ordinal] = per.get(ordinal, 0) + int(sh.data.nbytes)
    return per


@dataclass
class PublishStats:
    epoch: int
    mode: str  # "full" | "delta"
    bytes_h2d: int
    seconds: float
    scatter_leaves: int = 0
    replaced_leaves: int = 0


class StaleEpochError(ValueError):
    pass


class DeviceTableStore:
    """Two device table epochs with scatter-delta publication.

    With `hot_only=True` every published epoch carries only the HOT
    leaf plane (compiler.tables.HOT_LEAVES — the words the fused
    hashed-probe kernels can ever gather); the COLD leaves (the 32 MB
    port_slot and the dense allow bitmap, the two largest tables by
    an order of magnitude) never reach the device, and deltas
    touching them are filtered before the scatter.  Epochs carry a
    layout stamp (tables_layout_version): a delta recorded against a
    different pack width or leaf split than the resident spare is
    refused and the publish falls back to a full upload."""

    def __init__(
        self,
        shardings: Optional[PolicyTables] = None,
        hot_only: bool = False,
        shardings_fn=None,
        partition_digest: int = 0,
        transform_fn=None,
        delta_transform_fn=None,
    ) -> None:
        self._lock = threading.Lock()
        # each slot: dict(tables=<device pytree>, stamp=int,
        # epoch=int, layout=int, chip_bytes={ordinal: bytes},
        # host=<the transformed host pytree the epoch was placed
        # from — retained as the repair scatter's value source>)
        self._slots = [None, None]
        self._cur = 0
        self._epoch = 0
        self._shardings = shardings
        # shape-aware sharding resolver (tables → NamedShardings
        # pytree, e.g. compiler.partition.table_shardings bound to a
        # mesh): the partition rules are declarative but leaf
        # divisibility depends on the published shapes, so the
        # resolved pytree is recomputed per publish
        self._shardings_fn = shardings_fn
        self._hot_only = hot_only
        # rule-table digest (compiler.partition.partition_digest),
        # folded into every epoch's layout stamp: a delta recorded
        # against one partitioning can never scatter into an epoch
        # laid out under another
        self.partition_digest = int(partition_digest)
        # device-layout transform (e.g. the N+1 replica augmentation,
        # compiler.partition.replicate_table_leaves): applied to the
        # host tables before placement; `delta_transform_fn(delta,
        # pre_transform_tables)` rewrites a delta recorded against
        # the un-transformed layout into device coordinates so the
        # scatter path keeps every copy bit-identical
        self._transform_fn = transform_fn
        self._delta_transform_fn = delta_transform_fn
        # the repair scatter (repair_rows) reads its values from the
        # epoch's retained host pytree — only stores with a device
        # layout seam (replica stores) have that consumer; a plain
        # store must not pin two extra full host copies in RAM
        self._retain_host = (
            transform_fn is not None or delta_transform_fn is not None
        )
        # per-chip outage ledger (the re-admission rebalance feed):
        # ordinal -> {"epoch": epoch at mark-out, "missed":
        # [transformed TableDelta...], "needs_full": bool} — every
        # publish that lands while a chip's breaker is open records
        # what that chip missed, so readmission can replay exactly
        # those rows through the delta-scatter path instead of a
        # full upload
        self._out_chips: Dict[int, Dict] = {}
        self._missed_cap = 32
        self._apply_cache: Dict[tuple, object] = {}
        self._apply_nodonate_cache: Dict[tuple, object] = {}
        self._repair_cache: Dict[tuple, object] = {}
        # open relayout window (engine/reshard.py): while set, the
        # SPARE slot holds the migration target epoch (laid out
        # under the NEW digest) and publish() must not consume it —
        # churn deltas patch the LIVE slot in place (non-donated),
        # full publishes replace the live slot AND mark the window
        # broken (the plan's deterministic full-upload-into-target
        # restart)
        self._relayout: Optional[Dict] = None

    # -- device placement ----------------------------------------------------

    def _put(self, value, leaf: Optional[str] = None):
        import jax

        if self._shardings is None:
            return jax.device_put(value)
        sharding = (
            getattr(self._shardings, leaf)
            if leaf is not None and hasattr(self._shardings, leaf)
            else None
        )
        if sharding is None:
            # payload arrays replicate (every chip applies the same
            # scatter); use any leaf's mesh via the generation spec
            sharding = self._shardings.generation
        return jax.device_put(value, sharding)

    def _put_tables(self, tables: PolicyTables):
        import jax

        if self._shardings is None:
            return jax.device_put(tables)
        return jax.tree.map(
            lambda leaf, s: (
                None if leaf is None else jax.device_put(leaf, s)
            ),
            tables,
            self._shardings,
            is_leaf=lambda x: x is None,
        )

    # -- scatter updater -----------------------------------------------------

    def _apply_fn(self, fields: Tuple[str, ...], donate: bool = True):
        """Jitted scatter: patch `fields` of an epoch and stamp the
        new generation.  Cached per field set (payload shapes are
        pow2-padded, so the per-set jit cache stays small).  With
        `donate=False` the input pytree's buffers are NOT consumed —
        the publish-during-relayout path patches the LIVE epoch into
        a fresh pytree while batches may still be in flight against
        the old one (the zero-drain seam)."""
        import jax

        cache = self._apply_cache if donate else self._apply_nodonate_cache
        fn = cache.get(fields)
        if fn is not None:
            return fn

        def apply(tables, payloads, generation):
            kw = {}
            for name, (idx, values) in zip(fields, payloads):
                kw[name] = getattr(tables, name).at[idx].set(values)
            kw["generation"] = generation
            return dataclasses.replace(tables, **kw)

        # jit-cache observability rides the scatter entry point: a
        # payload outside the known pow2 classes shows up as a miss +
        # compile seconds in the same scrape as the publish bytes
        fn = tracing.track_jit(
            jax.jit(apply, donate_argnums=(0,) if donate else ()),
            "publish.scatter" if donate else "publish.scatter_live",
        )
        cache[fields] = fn
        return fn

    # -- publication ---------------------------------------------------------

    def publish(
        self, tables: PolicyTables, delta: Optional[TableDelta] = None
    ) -> Tuple[PolicyTables, PublishStats]:
        """Install `tables` (host arrays) as the new current epoch.
        `delta` must describe every change from the SPARE slot's stamp
        to `tables` (see FleetCompiler.delta_for); anything else —
        or delta=None — forces a full upload."""
        import jax

        with self._lock, tracing.tracer.span(
            "publish.epoch", site="engine.publish"
        ) as sp:
            t0 = time.perf_counter()
            if self._hot_only:
                tables = split_hot(tables)
            pre_transform = tables
            if self._transform_fn is not None:
                tables = self._transform_fn(tables)
            if self._shardings_fn is not None:
                self._shardings = self._shardings_fn(tables)
            layout = tables_layout_version(tables) | (
                self.partition_digest << 32
            )
            spare_i = self._cur ^ 1
            spare = self._slots[spare_i]
            stamp = int(np.asarray(tables.generation))
            if (
                self._relayout is not None
                and not self._relayout.get("broken")
            ):
                # the spare slot is the staged migration target —
                # churn must not consume it (engine/reshard.py keeps
                # the target current through the plan's dual-apply)
                return self._publish_relayout_locked(
                    tables, pre_transform, delta, layout, stamp,
                    sp, t0,
                )
            use_delta = (
                delta is not None
                and spare is not None
                and spare["stamp"] == delta.base_stamp
                and stamp == delta.new_stamp
                # layout guard: a delta's scatter indices are only
                # meaningful against the exact hot/cold + pack-width
                # layout the spare epoch holds (pack widths must
                # match end to end; coldness is the store's own
                # setting, already applied to both sides)
                and spare["layout"] == layout
                and (delta.layout & _LAYOUT_LANES_MASK)
                == (layout & _LAYOUT_LANES_MASK)
            )
            if use_delta and self._delta_transform_fn is not None:
                # rewrite into device coordinates (the delta was
                # recorded against the un-transformed layout; the
                # geometry it maps from is the pre-transform pytree)
                delta = self._delta_transform_fn(delta, pre_transform)
            if use_delta:
                try:
                    dev, stats = self._publish_delta(
                        spare["tables"], tables, delta
                    )
                except faultinject.FaultInjected as exc:
                    # the publish.scatter seam fired: the scatter is
                    # poisoned before the donated apply runs, but the
                    # spare's row bookkeeping can no longer be
                    # trusted either way — de-register the slot and
                    # serve THIS publish through the full-upload
                    # path.  The control plane degrades to bytes
                    # spent, never to a half-patched epoch: the
                    # fallback is the refusal path the chaos/fuzz
                    # schedules assert bit-identity across.
                    self._slots[spare_i] = None
                    use_delta = False
                    metrics.publish_fallback_total.inc()
                    sp.attrs["fallback"] = str(exc)
                    log.warning(
                        "delta publish scatter faulted; falling "
                        "back to full upload",
                        extra={"fields": {"error": str(exc)}},
                    )
                except Exception:
                    # the donated scatter may have consumed the spare
                    # epoch's buffers before failing — de-register the
                    # slot so the next publish full-uploads instead of
                    # scattering into deleted arrays forever
                    self._slots[spare_i] = None
                    self._sample_bytes()
                    raise
                else:
                    # the standby's resident buffers were donated
                    # (patched in place) — HBM reused, not reallocated
                    metrics.device_table_retired_bytes.inc(
                        value=spare.get("nbytes", 0)
                    )
            if not use_delta:
                dev = self._put_tables(tables)
                jax.block_until_ready(dev)
                stats = PublishStats(
                    epoch=0, mode="full", bytes_h2d=tables_nbytes(tables),
                    seconds=0.0,
                )
            self._epoch += 1
            self._slots[spare_i] = {
                "tables": dev, "stamp": stamp, "epoch": self._epoch,
                "nbytes": tables_nbytes(tables), "layout": layout,
                "chip_bytes": _chip_resident_bytes(dev),
                "host": tables if self._retain_host else None,
                "shardings": self._shardings,
            }
            self._cur = spare_i
            stats.epoch = self._epoch
            stats.seconds = time.perf_counter() - t0
            # outage ledger: record what every marked-out chip just
            # missed — a delta publish is replayable row-by-row at
            # readmission; a full upload (or an overflowing miss
            # list) downgrades the rebalance to a whole-slice replay
            for rec in self._out_chips.values():
                if (
                    use_delta
                    and not rec["needs_full"]
                    and len(rec["missed"]) < self._missed_cap
                ):
                    rec["missed"].append(delta)
                else:
                    rec["needs_full"] = True
            self._sample_bytes()
            sp.attrs.update(
                mode=stats.mode, epoch=stats.epoch,
                bytes_h2d=stats.bytes_h2d,
                scatter_leaves=stats.scatter_leaves,
                replaced_leaves=stats.replaced_leaves,
            )
            return dev, stats

    def _publish_relayout_locked(
        self, tables, pre_transform, delta, layout, stamp, sp, t0
    ):
        """Publish while a relayout window is open (caller holds the
        lock).  The spare slot is the staged migration target and
        must not be consumed, so churn lands on the LIVE slot:

          * a valid delta against the live epoch patches it through a
            NON-donated scatter — the previous pytree's buffers stay
            intact for every batch still in flight against them (the
            zero-drain seam; the old pytree is simply dropped when
            the last reference goes);
          * anything else (stale delta, shape-class change, a fault
            on the scatter seam) full-uploads into the live slot and
            marks the window BROKEN: the migration plan observes the
            flag and deterministically restarts as a full upload into
            the target layout.
        """
        import jax

        live_i = self._cur
        live = self._slots[live_i]
        use_delta = (
            delta is not None
            and live is not None
            and live["stamp"] == delta.base_stamp
            and stamp == delta.new_stamp
            and live["layout"] == layout
            and (delta.layout & _LAYOUT_LANES_MASK)
            == (layout & _LAYOUT_LANES_MASK)
        )
        if use_delta and self._delta_transform_fn is not None:
            delta = self._delta_transform_fn(delta, pre_transform)
        if use_delta:
            try:
                dev, stats = self._publish_delta(
                    live["tables"], tables, delta, donate=False
                )
            except faultinject.FaultInjected as exc:
                # nothing was donated — the live epoch is intact,
                # but the scatter path is poisoned: serve this
                # publish as a full upload (which breaks the window
                # below, the plan's deterministic restart trigger)
                use_delta = False
                metrics.publish_fallback_total.inc()
                sp.attrs["fallback"] = str(exc)
                log.warning(
                    "delta publish scatter faulted during relayout; "
                    "falling back to full upload",
                    extra={"fields": {"error": str(exc)}},
                )
        if not use_delta:
            dev = self._put_tables(tables)
            jax.block_until_ready(dev)
            stats = PublishStats(
                epoch=0, mode="full",
                bytes_h2d=tables_nbytes(tables), seconds=0.0,
            )
            self._relayout["broken"] = True
            sp.attrs["relayout_broken"] = True
        self._epoch += 1
        self._slots[live_i] = {
            "tables": dev, "stamp": stamp, "epoch": self._epoch,
            "nbytes": tables_nbytes(tables), "layout": layout,
            "chip_bytes": _chip_resident_bytes(dev),
            "host": tables if self._retain_host else None,
            "shardings": self._shardings,
        }
        stats.epoch = self._epoch
        stats.seconds = time.perf_counter() - t0
        for rec in self._out_chips.values():
            if (
                use_delta
                and not rec["needs_full"]
                and len(rec["missed"]) < self._missed_cap
            ):
                rec["missed"].append(delta)
            else:
                rec["needs_full"] = True
        self._sample_bytes()
        sp.attrs.update(
            mode=stats.mode, epoch=stats.epoch,
            bytes_h2d=stats.bytes_h2d,
            scatter_leaves=stats.scatter_leaves,
            replaced_leaves=stats.replaced_leaves, relayout=True,
        )
        return dev, stats

    # -- live elastic resharding (engine/reshard.py drives these) ------------

    def begin_relayout(
        self, host_aug, moved_rows, shardings, partition_digest
    ) -> Tuple[int, int]:
        """Open a relayout window: install the migration TARGET
        epoch (already transformed/augmented for the target mesh)
        into the SPARE slot while the live epoch keeps serving.

        `moved_rows` ({leaf: (axis, index array)} from
        compiler.partition.reshard_moved_rows) names every augmented
        row whose bytes are NOT device-resident under the source
        column assignment.  The staged device epoch is seeded from
        `host_aug` with those rows ZEROED — the epoch only becomes
        correct as the migration scatters (repair_rows(spare=True))
        stream them in, so cutover bit-identity proves the streamed
        bytes rather than the seed.  The TRUE target host is
        retained on the slot as the scatter's value source.

        Returns (epoch, layout) — the pins every subsequent
        migration step must present."""
        import jax

        with self._lock, tracing.tracer.span(
            "publish.begin_relayout", site="engine.publish"
        ) as sp:
            if self._relayout is not None:
                raise RuntimeError("relayout window already open")
            if self._slots[self._cur] is None:
                raise RuntimeError("no live epoch to reshard from")
            layout = tables_layout_version(host_aug) | (
                int(partition_digest) << 32
            )
            kw = {}
            for name, (axis, idx) in moved_rows.items():
                arr = np.array(np.asarray(getattr(host_aug, name)))
                idx = np.asarray(idx, np.int64)
                if idx.size:
                    arr[(slice(None),) * int(axis) + (idx,)] = 0
                kw[name] = arr
            seed = (
                dataclasses.replace(host_aug, **kw) if kw else host_aug
            )
            dev = jax.tree.map(
                lambda leaf, s: (
                    None if leaf is None else jax.device_put(leaf, s)
                ),
                seed, shardings,
                is_leaf=lambda x: x is None,
            )
            jax.block_until_ready(dev)
            self._epoch += 1
            spare_i = self._cur ^ 1
            self._slots[spare_i] = {
                "tables": dev,
                "stamp": int(np.asarray(host_aug.generation)),
                "epoch": self._epoch,
                "nbytes": tables_nbytes(host_aug),
                "layout": layout,
                "chip_bytes": _chip_resident_bytes(dev),
                "host": host_aug,
                "shardings": shardings,
            }
            self._relayout = {
                "epoch": self._epoch, "layout": layout,
                "broken": False, "shardings": shardings,
                "digest": int(partition_digest),
            }
            self._sample_bytes()
            sp.attrs.update(epoch=self._epoch, layout=layout)
            return self._epoch, layout

    def relayout_state(self) -> Optional[Dict]:
        """{"epoch", "layout", "broken"} of the open relayout
        window, or None — the plan's restart detector."""
        with self._lock:
            rel = self._relayout
            if rel is None:
                return None
            return {
                "epoch": rel["epoch"], "layout": rel["layout"],
                "broken": bool(rel.get("broken")),
            }

    def relayout_update_host(self, host_aug) -> Tuple[int, int]:
        """Replace the staged target epoch's retained host — the
        churn dual-apply: migration scatters issued after this read
        the NEW values (the plan re-queues rows whose contents
        changed), and the staged epoch's generation leaf is
        re-placed on device so its stamp tracks the live world.
        Refused when no window is open or the window broke."""
        import jax

        with self._lock:
            rel = self._relayout
            if rel is None or rel.get("broken"):
                raise RuntimeError(
                    "no open relayout window to update"
                )
            spare_i = self._cur ^ 1
            slot = self._slots[spare_i]
            if slot is None or slot["epoch"] != rel["epoch"]:
                raise RuntimeError("staged relayout epoch is gone")
            stamp = int(np.asarray(host_aug.generation))
            gen_dev = jax.device_put(
                np.uint64(np.asarray(host_aug.generation)),
                rel["shardings"].generation,
            )
            # non-donated replace: only the generation leaf is
            # re-placed; the table leaves stay resident and the
            # migration scatters keep patching them
            slot["tables"] = dataclasses.replace(
                slot["tables"], generation=gen_dev
            )
            layout = tables_layout_version(host_aug) | (
                rel["digest"] << 32
            )
            slot["host"] = host_aug
            slot["stamp"] = stamp
            slot["nbytes"] = tables_nbytes(host_aug)
            slot["layout"] = layout
            rel["layout"] = layout
            return slot["epoch"], layout

    def cutover_relayout(
        self,
        shardings_fn=None,
        partition_digest=None,
        transform_fn=None,
        delta_transform_fn=None,
    ) -> int:
        """Flip the staged target epoch live — the reshard cutover.
        Zero-drain by construction: the previous live epoch's
        buffers are never donated or touched; it remains resident as
        the source-layout spare, whose next delta publish is
        layout-refused (the digests differ by ntp) into exactly one
        full upload, after which deltas resume.  Rebinds the store's
        partition seams (sharding resolver, digest, augmentation,
        delta rewrite) so subsequent publishes land under the NEW
        layout.  Refused while broken — the migration must restart
        instead of cutting over to a stale target."""
        with self._lock, tracing.tracer.span(
            "publish.cutover_relayout", site="engine.publish"
        ) as sp:
            rel = self._relayout
            if rel is None:
                raise RuntimeError("no open relayout window")
            if rel.get("broken"):
                raise RuntimeError(
                    "relayout window broken by a full publish; "
                    "cutover refused — restart the migration"
                )
            spare_i = self._cur ^ 1
            slot = self._slots[spare_i]
            if slot is None or slot["epoch"] != rel["epoch"]:
                raise RuntimeError(
                    "staged relayout epoch is gone; cutover refused"
                )
            self._cur = spare_i
            self._relayout = None
            self._shardings = rel["shardings"]
            if shardings_fn is not None:
                self._shardings_fn = shardings_fn
            if partition_digest is not None:
                self.partition_digest = int(partition_digest)
            if transform_fn is not None:
                self._transform_fn = transform_fn
            if delta_transform_fn is not None:
                self._delta_transform_fn = delta_transform_fn
            self._retain_host = (
                self._transform_fn is not None
                or self._delta_transform_fn is not None
            )
            # jit entries traced against the source mesh would pin
            # stale executables (and their donated-buffer shapes)
            self._apply_cache.clear()
            self._apply_nodonate_cache.clear()
            self._repair_cache.clear()
            self._sample_bytes()
            sp.attrs.update(
                epoch=slot["epoch"], layout=slot["layout"]
            )
            return slot["epoch"]

    def rollback_relayout(self) -> bool:
        """Abandon the staged target epoch: the spare slot is
        dropped (nothing was ever donated from the live epoch, so
        the fully-consistent source layout keeps serving untouched)
        and the next publish full-uploads into the freed slot.
        Returns True when a window was open."""
        with self._lock:
            rel = self._relayout
            if rel is None:
                return False
            spare_i = self._cur ^ 1
            slot = self._slots[spare_i]
            if slot is not None and slot["epoch"] == rel["epoch"]:
                self._slots[spare_i] = None
            self._relayout = None
            self._sample_bytes()
            return True

    def _sample_bytes(self) -> None:
        """cilium_device_table_bytes{epoch}: per-slot resident bytes,
        sampled at every publish (caller holds the lock) — the HBM
        line of the device-resource accounting plane."""
        cur = self._slots[self._cur]
        spare = self._slots[self._cur ^ 1]
        metrics.device_table_bytes.set(
            "live", value=(cur or {}).get("nbytes", 0)
        )
        metrics.device_table_bytes.set(
            "standby", value=(spare or {}).get("nbytes", 0)
        )
        # cilium_device_table_bytes_per_chip{chip}: per-shard
        # resident bytes over both epoch slots — identity-sharded
        # leaves divide across chips, replicated ones repeat, so the
        # per-chip line is what the universe headroom model bounds
        for ordinal, nbytes in sorted(self._chip_bytes_locked().items()):
            metrics.device_table_bytes_per_chip.set(
                str(ordinal), value=nbytes
            )

    def _publish_delta(
        self,
        spare_dev: PolicyTables,
        tables: PolicyTables,
        delta: TableDelta,
        donate: bool = True,
    ):
        import jax

        # the publish.scatter fault seam, probed once per device
        # ordinal holding a slice of the spare epoch (chip-scoped
        # schedules poison the scatter only when their chip is a
        # recipient; unscoped schedules fire on the first probe).
        # publish() catches the FaultInjected and falls back to a
        # full upload — the spare's buffers are still intact here,
        # but its bookkeeping is de-registered conservatively.
        # Nothing-armed (production churn) must not pay the ordinal
        # enumeration: the whole setup gates on the same lock-free
        # emptiness read the fault verbs use.
        if faultinject.any_armed():
            ordinals = sorted(_chip_resident_bytes(spare_dev))
            if ordinals:
                for ordinal in ordinals:
                    faultinject.fire("publish.scatter", chip=ordinal)
            else:
                faultinject.fire("publish.scatter")

        n_scatter = 0
        n_replace = 0
        bytes_h2d = 0
        # hot-only epochs never receive cold-plane payloads — their
        # leaves are None on device and the host arrays are the
        # authority for the cold plane anyway
        skip = set(COLD_LEAVES) if self._hot_only else ()
        # whole-leaf replacements land outside the jit: fresh uploads
        # swapped into the donated pytree (the old leaf is dropped)
        replaced = {}
        for name, arr in delta.replace.items():
            if name in skip:
                continue
            replaced[name] = self._put(arr, name)
            bytes_h2d += np.asarray(arr).nbytes
            n_replace += 1
        base = spare_dev
        if replaced:
            base = dataclasses.replace(base, **replaced)
        fields = tuple(
            sorted(n for n in delta.updates if n not in skip)
        )
        gen_dev = self._put(np.uint64(np.asarray(tables.generation)))
        if fields:
            payloads = []
            for name in fields:
                idx, values = _pad_pow2(delta.updates[name])
                payloads.append(
                    (
                        tuple(self._put(i) for i in idx),
                        self._put(values),
                    )
                )
                bytes_h2d += delta.updates[name].nbytes
                n_scatter += 1
            dev = self._apply_fn(fields, donate=donate)(
                base, tuple(payloads), gen_dev
            )
        else:
            dev = dataclasses.replace(base, generation=gen_dev)
        jax.block_until_ready(dev)
        return dev, PublishStats(
            epoch=0, mode="delta", bytes_h2d=bytes_h2d,
            seconds=0.0, scatter_leaves=n_scatter,
            replaced_leaves=n_replace,
        )

    # -- consumers -----------------------------------------------------------

    def current(self) -> Optional[Tuple[int, PolicyTables]]:
        with self._lock:
            slot = self._slots[self._cur]
            if slot is None:
                return None
            return slot["epoch"], slot["tables"]

    def current_stamp(self) -> Optional[int]:
        with self._lock:
            slot = self._slots[self._cur]
            return None if slot is None else slot["stamp"]

    def get(self, stamp: int) -> Optional[PolicyTables]:
        """The live epoch carrying `stamp`, if still resident (a
        reader that snapshotted an older publish reuses its epoch
        instead of flipping the store backward)."""
        with self._lock:
            for slot in self._slots:
                if slot is not None and slot["stamp"] == stamp:
                    return slot["tables"]
            return None

    def spare_stamp(self) -> Optional[int]:
        """Stamp held by the standby epoch — the base the next delta
        must cover."""
        with self._lock:
            spare = self._slots[self._cur ^ 1]
            return None if spare is None else spare["stamp"]

    def live_stamps(self) -> Tuple[int, ...]:
        with self._lock:
            return tuple(
                s["stamp"] for s in self._slots if s is not None
            )

    def chip_bytes(self) -> Dict[int, int]:
        """Measured per-chip resident bytes over both epoch slots —
        the numbers behind cilium_device_table_bytes_per_chip."""
        with self._lock:
            return self._chip_bytes_locked()

    def _chip_bytes_locked(self) -> Dict[int, int]:
        per: Dict[int, int] = {}
        for slot in self._slots:
            for ordinal, nbytes in (
                (slot or {}).get("chip_bytes", {}) or {}
            ).items():
                per[ordinal] = per.get(ordinal, 0) + nbytes
        return per

    # -- per-chip outage / re-admission rebalance ----------------------------

    def mark_chip_out(self, ordinal: int) -> None:
        """Start the outage ledger for a chip whose breaker opened:
        every publish from now on records what this chip missed.
        Idempotent — a re-open after a failed half-open probe keeps
        the original ledger (the chip still misses everything since
        its first failure)."""
        with self._lock:
            self._out_chips.setdefault(
                int(ordinal),
                {"epoch": self._epoch, "missed": [],
                 "needs_full": False},
            )

    def chip_outage(self, ordinal: int) -> Optional[Dict]:
        with self._lock:
            rec = self._out_chips.get(int(ordinal))
            if rec is None:
                return None
            return {
                "epoch": rec["epoch"],
                "missed": list(rec["missed"]),
                "needs_full": rec["needs_full"],
            }

    def readmit_chip(self, ordinal: int) -> Optional[Dict]:
        """Close the outage ledger and return it (the failover
        router converts it into the owned-row repair scatter).  A
        SPARE epoch published during the outage is semantically
        stale on the chip's slice; when the slot retains its host
        pytree (replica stores do — it is the repair value source)
        the record comes back with ``spare_stale`` set and the
        router REPAIRS the chip's whole owned regions of the spare
        from that retained snapshot (`repair_rows(..., spare=True)`)
        — bytes proportional to one chip's slice, not a full
        upload.  Only a plain store without a retained host still
        de-registers the spare (the next publish full-uploads)."""
        with self._lock:
            rec = self._out_chips.pop(int(ordinal), None)
            if rec is None:
                return None
            live = self._slots[self._cur]
            spare = self._slots[self._cur ^ 1]
            # layout pins for the repair scatters: a readmission
            # racing an in-flight migration must repair each epoch
            # against the layout THAT slot actually holds — the
            # caller computed its owned-row sets under one column
            # assignment, and scattering them into an epoch laid out
            # under another (e.g. the staged reshard target) would
            # plant source-layout rows in a target-layout spare
            rec["live_layout"] = (
                None if live is None else live["layout"]
            )
            rec["spare_layout"] = (
                None if spare is None else spare["layout"]
            )
            if spare is not None and spare["epoch"] > rec["epoch"]:
                if spare.get("host") is not None:
                    rec["spare_stale"] = True
                    # the repair must land on THIS epoch: a publish
                    # interleaved before the repair flips the slots
                    # (repair_rows verifies the epoch and refuses);
                    # the store's own counter, not the table stamp —
                    # distinct epochs can share a stamp
                    rec["spare_epoch"] = spare["epoch"]
                else:
                    self._slots[self._cur ^ 1] = None
            return rec

    def restore_outage(self, ordinal: int, rec: Dict) -> None:
        """Put a popped ledger back after a FAILED repair: the
        scatter may have partially landed, so the restored record is
        downgraded to needs_full — the next readmission replays the
        chip's whole owned regions instead of trusting row-level
        bookkeeping the failure invalidated.  Merges with any record
        a concurrent re-open already created."""
        rec["needs_full"] = True
        with self._lock:
            existing = self._out_chips.get(int(ordinal))
            if existing is None:
                self._out_chips[int(ordinal)] = rec
            else:
                existing["epoch"] = min(
                    existing["epoch"], rec["epoch"]
                )
                existing["needs_full"] = True

    def _repair_fn(self, fields: Tuple[str, ...],
                   axes: Tuple[int, ...]):
        """Jitted donated scatter rewriting whole index slices along
        one axis per leaf — the re-admission rebalance's engine
        (same machinery as _apply_fn, but indexing a single interior
        axis so a chip's owned rows repair in one scatter each)."""
        import jax

        key = (fields, axes)
        fn = self._repair_cache.get(key)
        if fn is not None:
            return fn

        def apply(tables, payloads):
            kw = {}
            for name, axis, (idx, values) in zip(
                fields, axes, payloads
            ):
                index = (slice(None),) * axis + (idx,)
                kw[name] = getattr(tables, name).at[index].set(values)
            return dataclasses.replace(tables, **kw)

        fn = tracing.track_jit(
            jax.jit(apply, donate_argnums=(0,)), "publish.repair"
        )
        self._repair_cache[key] = fn
        return fn

    def repair_rows(
        self,
        row_sets: Dict[str, Tuple[int, object]],
        spare: bool = False,
        expect_epoch: Optional[int] = None,
        expect_layout: Optional[int] = None,
    ) -> int:
        """Rewrite `row_sets` ({leaf: (axis, index array)}) of the
        LIVE epoch from its retained host arrays — the re-admission
        rebalance: the rows a chip missed while its breaker was open
        land back on device through the delta-scatter path, bytes
        proportional to the missed change (never a full upload).
        With `spare=True` the STANDBY epoch repairs instead, from
        ITS retained host snapshot — the spare-epoch repair at chip
        readmission that keeps the next publish on the delta path
        (a de-registered spare would cost one full upload).
        `expect_epoch` pins the repair to the slot fill the caller
        observed (readmit_chip's `spare_epoch` — the store's own
        monotonic counter, since distinct epochs can share a table
        stamp): a publish interleaved since then flipped the slots,
        and scattering into whatever occupies the slot NOW would
        leave the stale epoch live-and-unrepaired — the repair
        refuses instead, and the caller's recovery path replays the
        whole slice on the next probe.

        The repaired epoch's buffers are DONATED to the scatter, so
        the caller must not have batches in flight against it (the
        failover router rebalances at stream boundaries, before the
        probe dispatch that re-admits the chip).  Returns bytes
        shipped host→device (also accumulated in
        cilium_rebalance_bytes_h2d_total)."""
        import jax

        with self._lock:
            slot = self._slots[self._cur ^ 1 if spare else self._cur]
            which = "spare" if spare else "live"
            if slot is None:
                raise RuntimeError(f"no {which} epoch to repair")
            if (
                expect_epoch is not None
                and slot["epoch"] != expect_epoch
            ):
                raise RuntimeError(
                    f"{which} epoch changed since readmission "
                    f"(epoch {slot['epoch']} != expected "
                    f"{expect_epoch}); repair refused"
                )
            if (
                expect_layout is not None
                and slot["layout"] != expect_layout
            ):
                # the caller's index arithmetic assumed a different
                # column assignment / pack layout than this epoch
                # actually holds (an in-flight reshard re-laid the
                # slot out) — scattering would plant rows computed
                # under one layout into an epoch keyed by another
                raise RuntimeError(
                    f"{which} epoch layout changed since "
                    f"readmission (layout {slot['layout']:#x} != "
                    f"expected {int(expect_layout):#x}); repair "
                    "refused"
                )
            host = slot.get("host")
            if host is None:
                raise RuntimeError(
                    f"{which} epoch retains no host source; repair "
                    "requires a publish through this store"
                )
            # payloads must land on the SLOT's mesh, not the store's
            # current one: during a relayout the staged epoch lives
            # on the target mesh while self._shardings still resolves
            # against the source — mixing meshes in one jit call is
            # an error, so each slot remembers its own shardings
            slot_sh = slot.get("shardings", self._shardings)

            def put(value):
                import jax as _jax

                if slot_sh is None:
                    return _jax.device_put(value)
                return _jax.device_put(value, slot_sh.generation)

            fields, axes, payloads = [], [], []
            bytes_h2d = 0
            for name in sorted(row_sets):
                axis, idx = row_sets[name]
                idx = np.asarray(idx, np.int64)
                if idx.size == 0:
                    continue
                # pow2-pad by repeating the last index (duplicate
                # identical writes are deterministic) so the repair
                # jit recompiles per size class, like _pad_pow2
                size = next_pow2(idx.size)
                if size != idx.size:
                    idx = np.concatenate(
                        [idx, np.repeat(idx[-1:], size - idx.size)]
                    )
                values = np.take(
                    np.asarray(getattr(host, name)), idx, axis=axis
                )
                fields.append(name)
                axes.append(int(axis))
                payloads.append((put(idx), put(values)))
                bytes_h2d += idx.nbytes + values.nbytes
            if not fields:
                return 0
            dev = self._repair_fn(tuple(fields), tuple(axes))(
                slot["tables"], tuple(payloads)
            )
            jax.block_until_ready(dev)
            slot["tables"] = dev
            metrics.rebalance_bytes_h2d_total.inc(value=bytes_h2d)
            return bytes_h2d

    @staticmethod
    def _norm(stamp: int) -> int:
        # without jax x64 the device generation leaf truncates to its
        # low 32 bits (the publish counter); stamps are store-scoped,
        # so comparing the counter bits stays unambiguous
        return int(stamp) & 0xFFFFFFFF

    def holds(self, tables) -> bool:
        """True when `tables` IS one of the live (undonated) epoch
        pytrees.  Object identity, not stamp comparison: a HOST
        snapshot can share a stamp with a lagging device epoch while
        its own stacked buffers have been rewritten — such tables
        must fall through to the compiler's staleness check."""
        with self._lock:
            return any(
                slot is not None and slot["tables"] is tables
                for slot in self._slots
            )

    def check_current(self, tables) -> None:
        """Raise unless `tables` is one of the two live epochs: older
        epochs' buffers have been donated to a newer publish and may
        have been overwritten in place."""
        raw = getattr(tables, "generation", None)
        stamp = self._norm(
            int(np.asarray(raw)) if raw is not None else 0
        )
        live = self.live_stamps()
        if not live or stamp in {self._norm(s) for s in live}:
            return
        raise StaleEpochError(
            f"stale device epoch: generation {stamp} is no longer "
            f"resident (live epochs: {live}) — its buffers were "
            f"donated to a newer publish"
        )


# ---------------------------------------------------------------------------
# Double-buffered async batch dispatch
# ---------------------------------------------------------------------------


class AsyncBatchDispatcher:
    """The epoch ping-pong machinery applied to BATCHES instead of
    tables: a bounded staging pipeline that overlaps the host pack of
    batch N+1 with the device compute of batch N.

      * `submit(host_args, meta)` runs `pack_fn` (encode + H2D
        staging — the host half) and `dispatch_fn` (a non-blocking
        jit enqueue — the device half), then drains AT MOST the
        batches beyond `depth` in FIFO order, so at any time up to
        `depth + 1` batches are in flight: one computing, one being
        packed.
      * results come back ONE BATCH BEHIND through the values
        returned from submit()/flush(): `(meta, result, exc)` tuples
        in exact submission order — consumers that fold events /
        flow records / telemetry per batch keep their ordering and
        per-batch counts unchanged relative to synchronous dispatch.
      * a failure at pack/enqueue time OR at drain (readback) time is
        captured as `exc` on that batch's tuple instead of poisoning
        the pipeline — the caller decides failover (the daemon serves
        the batch from the bit-identical host path).

    Overlap accounting: `pack_s` (host-side staging time), `block_s`
    (time spent blocked waiting on device results) and `wall_s`
    (first submit → flush) let callers derive the device-busy
    fraction during sustained dispatch."""

    def __init__(self, pack_fn, dispatch_fn, depth: int = 1) -> None:
        from collections import deque

        self.pack_fn = pack_fn
        self.dispatch_fn = dispatch_fn
        self.depth = max(int(depth), 0)
        self._pending = deque()
        self.pack_s = 0.0
        self.block_s = 0.0
        self._t_first = None
        self._t_last = None
        self.submitted = 0
        self.failed = 0

    def _drain_one(self):
        import jax

        meta, out, exc = self._pending.popleft()
        if exc is None:
            t0 = time.perf_counter()
            try:
                jax.block_until_ready(out)
            except Exception as drain_exc:  # device died mid-compute
                out, exc = None, drain_exc
                self.failed += 1
            dt = time.perf_counter() - t0
            self.block_s += dt
            if isinstance(meta, dict):
                meta.setdefault("perf", {})["drain_s"] = dt
        self._t_last = time.perf_counter()
        return meta, out, exc

    def submit(self, host_args: tuple, meta=None) -> list:
        """Stage + enqueue one batch; returns the drained (meta,
        result, exc) tuples that completed (possibly empty).

        Per-batch phase stamps: when `meta` is a dict, the pack /
        enqueue / drain durations this dispatcher already measures
        for the overlap aggregates are ALSO written into
        `meta["perf"]` — the perf plane's per-batch phase windows
        ride the existing bookkeeping instead of re-timing."""
        if self._t_first is None:
            self._t_first = time.perf_counter()
        self.submitted += 1
        out, exc = None, None
        t0 = time.perf_counter()
        try:
            dev_args = self.pack_fn(*host_args)
        except Exception as pack_exc:
            exc = pack_exc
            self.failed += 1
        dt_pack = time.perf_counter() - t0
        self.pack_s += dt_pack
        if isinstance(meta, dict):
            meta.setdefault("perf", {})["pack_s"] = dt_pack
        if exc is None:
            t1 = time.perf_counter()
            try:
                out = self.dispatch_fn(*dev_args)
            except Exception as disp_exc:
                out, exc = None, disp_exc
                self.failed += 1
            if isinstance(meta, dict):
                meta["perf"]["enqueue_s"] = (
                    time.perf_counter() - t1
                )
        self._pending.append((meta, out, exc))
        done = []
        while len(self._pending) > self.depth:
            done.append(self._drain_one())
        return done

    def flush(self) -> list:
        """Drain every in-flight batch, in order."""
        done = []
        while self._pending:
            done.append(self._drain_one())
        return done

    @property
    def wall_s(self) -> float:
        if self._t_first is None or self._t_last is None:
            return 0.0
        return self._t_last - self._t_first

    def overlap_efficiency_pct(self, device_seconds: float) -> float:
        """Device-busy fraction during sustained dispatch, given an
        independently measured estimate of pure device seconds for
        the submitted batches (e.g. sync per-batch latency × count).
        100% = the host pack was fully hidden behind device compute."""
        if self.wall_s <= 0:
            return 0.0
        return min(100.0, 100.0 * device_seconds / self.wall_s)
