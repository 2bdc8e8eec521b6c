"""Host half of the datapath telemetry plane.

The instrumented device kernels (engine/datapath.py
``datapath_step_telem``, ``datapath_step_accum_telem`` and the
fused-pair programs) carry a [2, TELEM_COLS] u32 stage/drop
accumulator alongside the per-entry counter buffer — one masked-sum
reduction set fused into the verdict dispatch, no extra launches.
This module folds that accumulator (or, equivalently, per-tuple
DatapathVerdicts columns host-side) into:

  * ``metrics.Registry`` — cilium_drop_count_total{reason,direction},
    cilium_forward_count_total, cilium_policy_verdict_total and
    cilium_datapath_stage_total, the same metric surface
    pkg/metrics exposes for the kernel datapath;
  * summary dicts for bench/status output.

Both folds derive from the ONE mask definition set
(engine.verdict.telemetry_masks), so the on-device histogram and the
host per-tuple fold are bit-identical by construction — the property
chip_smoke.py and tests/test_telemetry.py assert.
"""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from cilium_tpu.engine.verdict import (
    TELEM_COLS,
    TELEM_CT_BYPASS_ALLOW,
    TELEM_CT_DELETE,
    TELEM_CT_ESTABLISHED,
    TELEM_CT_NEW,
    TELEM_CT_RELATED,
    TELEM_CT_REPLY,
    TELEM_DENIED,
    TELEM_DROP_FRAG,
    TELEM_DROP_POLICY,
    TELEM_DROP_PREFILTER,
    TELEM_FORWARDED,
    TELEM_IPCACHE_WORLD,
    TELEM_LB_DNAT,
    TELEM_MATCH_FRAG,
    TELEM_MATCH_L3,
    TELEM_MATCH_L4,
    TELEM_MATCH_L4_WILD,
    TELEM_MATCH_NONE,
    TELEM_NAMES,
    TELEM_PROXY_REDIRECT,
    TELEM_TOTAL,
    telemetry_masks,
)
from cilium_tpu.monitor.events import drop_reason_name

DIRECTION_NAMES = ("INGRESS", "EGRESS")

# drop-column → canonical bpf/lib/common.h reason string (the same
# names `cilium monitor` prints, via monitor.events.DROP_REASONS)
DROP_COLUMN_REASONS = {
    TELEM_DROP_PREFILTER: drop_reason_name(-162),  # Policy denied (CIDR)
    TELEM_DROP_POLICY: drop_reason_name(-133),  # Policy denied (L3)
    TELEM_DROP_FRAG: drop_reason_name(-157),  # Fragmentation needed
}

# match-column → (match label, action label) of
# cilium_policy_verdict_total; the lattice verdict is implied by the
# match kind (hits allow, none/frag deny)
MATCH_COLUMN_LABELS = {
    TELEM_MATCH_L4: ("l4", "allowed"),
    TELEM_MATCH_L3: ("l3", "allowed"),
    TELEM_MATCH_L4_WILD: ("l4_wild", "allowed"),
    TELEM_MATCH_NONE: ("none", "denied"),
    TELEM_MATCH_FRAG: ("frag", "denied"),
}

# stage-column → cilium_datapath_stage_total{stage} label
STAGE_COLUMN_LABELS = {
    TELEM_LB_DNAT: "lb_dnat",
    TELEM_CT_NEW: "ct_new",
    TELEM_CT_ESTABLISHED: "ct_established",
    TELEM_CT_REPLY: "ct_reply",
    TELEM_CT_RELATED: "ct_related",
    TELEM_CT_BYPASS_ALLOW: "ct_bypass_allow",
    TELEM_CT_DELETE: "ct_delete",
    TELEM_IPCACHE_WORLD: "ipcache_world",
    TELEM_PROXY_REDIRECT: "proxy_redirect",
}


def telemetry_from_outputs(
    out, directions, valid: Optional[int] = None
) -> np.ndarray:
    """Fold per-tuple DatapathVerdicts columns into the same
    [2, TELEM_COLS] u64 stage histogram the device accumulator
    carries — the host side of the bit-identity gate, and the fold
    non-instrumented callers (replay audit paths, tests) use.

    ``directions``: per-tuple direction array (required —
    DatapathVerdicts does not carry the direction column).  ``valid``
    truncates padded batches to their live prefix."""
    if directions is None:
        raise ValueError(
            "telemetry_from_outputs needs the per-tuple direction "
            "array (DatapathVerdicts does not carry it)"
        )
    cols = {
        name: np.asarray(getattr(out, name))
        for name in (
            "pre_dropped", "ct_result", "match_kind", "allowed",
            "ct_delete", "proxy_port", "lb_slave", "ipcache_miss",
        )
    }
    directions = np.asarray(directions)
    if valid is not None:
        cols = {k: a[:valid] for k, a in cols.items()}
        directions = directions[:valid]
    masks = telemetry_masks(
        cols["pre_dropped"], cols["ct_result"], cols["match_kind"],
        cols["allowed"], cols["ct_delete"], cols["proxy_port"],
        cols["lb_slave"], cols["ipcache_miss"], xp=np,
    )
    telem = np.zeros((2, TELEM_COLS), np.uint64)
    for d in (0, 1):
        in_dir = directions == d
        for c, mask in enumerate(masks):
            telem[d, c] = int(np.sum(mask & in_dir))
    return telem


def fold_telemetry(telem, registry=None) -> None:
    """Fold a [2, TELEM_COLS] stage histogram DELTA into the metrics
    registry (process-global by default).  Callers pass the amount
    accumulated since their last fold — the counters are cumulative,
    so refolding the same buffer double-counts."""
    if registry is None:
        from cilium_tpu.metrics import registry as registry_
        registry = registry_
    telem = np.asarray(telem)
    for d, dname in enumerate(DIRECTION_NAMES):
        row = telem[d]
        if int(row[TELEM_FORWARDED]):
            registry.forward_count.inc(
                dname, value=int(row[TELEM_FORWARDED])
            )
        for col, reason in DROP_COLUMN_REASONS.items():
            if int(row[col]):
                registry.drop_count.inc(
                    reason, dname, value=int(row[col])
                )
        for col, (match, action) in MATCH_COLUMN_LABELS.items():
            if int(row[col]):
                registry.policy_verdict_total.inc(
                    dname, match, action, value=int(row[col])
                )
        for col, stage in STAGE_COLUMN_LABELS.items():
            if int(row[col]):
                registry.datapath_stage_total.inc(
                    stage, dname, value=int(row[col])
                )


def fold_telemetry_per_chip(per_chip, registry=None) -> np.ndarray:
    """Fold an all-gathered [n_chips, 2, TELEM_COLS] per-chip stage
    histogram DELTA (engine.sharded.make_mesh_evaluator with
    collect_telemetry) into the registry: the chip-summed mesh total
    goes through fold_telemetry — so ONE /metrics/prometheus scrape
    covers the whole mesh — and each chip's rows land under the
    `chip` label in cilium_datapath_telemetry_per_chip_total for
    imbalance debugging.  Summing a column over `chip` equals the
    mesh-total counters by construction.  Returns the mesh-total
    [2, TELEM_COLS] u64 histogram."""
    if registry is None:
        from cilium_tpu.metrics import registry as registry_
        registry = registry_
    per_chip = np.asarray(per_chip).astype(np.uint64)
    total = per_chip.sum(axis=0)
    fold_telemetry(total, registry=registry)
    for chip in range(per_chip.shape[0]):
        for d, dname in enumerate(DIRECTION_NAMES):
            row = per_chip[chip, d]
            for col, name in enumerate(TELEM_NAMES):
                if int(row[col]):
                    registry.telemetry_per_chip.inc(
                        str(chip), name, dname,
                        value=int(row[col]),
                    )
    return total


def telemetry_summary(telem) -> Dict[str, Dict[str, int]]:
    """{direction: {column name: count}} rendering of a stage
    histogram, for bench JSON lines and `cilium status`-style dumps
    (zero columns omitted)."""
    telem = np.asarray(telem)
    out: Dict[str, Dict[str, int]] = {}
    for d, dname in enumerate(DIRECTION_NAMES):
        row = {
            name: int(v)
            for name, v in zip(TELEM_NAMES, telem[d])
            if int(v)
        }
        out[dname.lower()] = row
    return out


def telemetry_consistent(telem) -> bool:
    """Internal-consistency invariants of one histogram: the final
    outcomes partition the batch, and the drop columns partition the
    denials.  The tests assert this on the device buffer before
    comparing against the host fold."""
    telem = np.asarray(telem)
    ok = True
    for d in (0, 1):
        row = telem[d]
        ok &= int(row[TELEM_TOTAL]) == int(row[TELEM_FORWARDED]) + int(
            row[TELEM_DENIED]
        )
        ok &= int(row[TELEM_DENIED]) == (
            int(row[TELEM_DROP_PREFILTER])
            + int(row[TELEM_DROP_POLICY])
            + int(row[TELEM_DROP_FRAG])
        )
        ok &= int(row[TELEM_TOTAL]) == (
            int(row[TELEM_CT_NEW])
            + int(row[TELEM_CT_ESTABLISHED])
            + int(row[TELEM_CT_REPLY])
            + int(row[TELEM_CT_RELATED])
        )
    return bool(ok)
