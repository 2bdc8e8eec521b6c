"""Shadow policy rollout smoke: the full canary lifecycle in one
process, gated against the host oracle.

    arm (candidate)  -> live traffic  -> on-device diff == the host
    oracle's diff of the two worlds (counters + record multiset)
    -> churn          -> the window closes with an explicit `stale`
    -> re-arm, promote -> counters zeroed, and the promoted world
       re-armed against itself diffs to ZERO.

Drives the same REST-contract operations the CLI uses (DaemonAPI:
POST /policy/shadow, GET /policy/diff) over a self-contained demo
daemon — no agent socket needed.  Prints one JSON line; asserts are
the gate.

Usage:
    python tools/policydiff.py [--flows 512] [--seed 11]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from collections import Counter

import numpy as np

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


CANDIDATE = [{
    "endpointSelector": {"matchLabels": {"app": "server"}},
    "ingress": [{
        "fromEndpoints": [{"matchLabels": {"app": "client"}}],
        "toPorts": [{
            "ports": [{"port": "443", "protocol": "TCP"}]
        }],
    }],
    "labels": ["serve-bench-rule"],
}]

EXTRA_RULE = [{
    "endpointSelector": {"matchLabels": {"app": "server"}},
    "ingress": [{
        "fromEndpoints": [{"matchLabels": {"app": "client"}}],
        "toPorts": [{
            "ports": [{"port": "8080", "protocol": "TCP"}]
        }],
    }],
    "labels": ["policydiff-churn-rule"],
}]


def oracle_diff(d, rec, shadow_states):
    """The host oracle's two-world diff for one record SoA."""
    from cilium_tpu.engine.hostpath import lattice_fold_host
    from cilium_tpu.replay import _ep_index_of
    from cilium_tpu.shadow import diff_codes

    _, _, index, live_states = (
        d.endpoint_manager.published_with_states()
    )
    ep_idx = _ep_index_of(rec, dict(index))
    frag = rec["is_fragment"].astype(bool)

    def fold(states):
        return lattice_fold_host(
            states, ep_idx, rec["identity"], rec["dport"],
            rec["proto"], rec["direction"], is_fragment=frag,
        )

    lv, sv = fold(live_states), fold(shadow_states)
    return lv, sv, diff_codes(
        lv.allowed, lv.proxy_port, lv.match_kind,
        sv.allowed, sv.proxy_port, sv.match_kind, xp=np,
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--flows", type=int, default=512)
    ap.add_argument("--seed", type=int, default=11)
    args = ap.parse_args(argv)

    from cilium_tpu.api.server import DaemonAPI
    from cilium_tpu.native import encode_flow_records
    from cilium_tpu.policy.api import rules_from_json
    from cilium_tpu.serve import build_demo_daemon, demo_record_maker
    from cilium_tpu.shadow import TRANS_NAMES, TRANS_NONE

    d, client = build_demo_daemon()
    api = DaemonAPI(d)
    make = demo_record_maker(client.security_identity.id)
    rng = np.random.default_rng(args.seed)
    rec = make(rng, args.flows)
    buf = encode_flow_records(**rec)

    # ---- arm + traffic --------------------------------------------------
    st = api.policy_shadow(
        {"action": "arm", "rules": CANDIDATE, "sample_rate": 1.0}
    )
    assert st["state"] == "armed", st
    api.process_flows(buf)
    out = api.policy_diff({"last": "0"})
    w = out["window"]
    assert w["sampled"] == args.flows, w

    # ---- the on-device diff vs the host oracle --------------------------
    with d.shadow._lock:
        shadow_states = list(d.shadow._window["states"])
    lv, sv, (ca, cp, ck, trans) = oracle_diff(d, rec, shadow_states)
    assert w["changed"]["allowed"] == int(ca.sum()), w
    assert w["changed"]["proxy_port"] == int(cp.sum()), w
    assert w["changed"]["match_kind"] == int(ck.sum()), w
    got_ms = Counter(
        (f["ep_id"], f["dport"], f["transition"])
        for f in out["flows"]
    )
    want_ms = Counter(
        (
            int(rec["ep_id"][i]),
            int(rec["dport"][i]),
            TRANS_NAMES[int(trans[i])],
        )
        for i in range(args.flows)
        if int(trans[i]) != TRANS_NONE
    )
    assert got_ms == want_ms, (got_ms, want_ms)
    n_changed = int((trans != TRANS_NONE).sum())
    assert n_changed > 0, "the candidate produced no diff at all"

    # ---- churn: a publish closes the window stale -----------------------
    d.policy_add(rules_from_json(json.dumps(EXTRA_RULE)))
    d.regenerate_all("policydiff churn")
    assert api.policy_diff({})["state"] == "stale"

    # ---- re-arm, promote: counters zero, candidate goes live ------------
    api.policy_shadow(
        {"action": "arm", "rules": CANDIDATE, "sample_rate": 1.0}
    )
    api.process_flows(buf)
    assert api.policy_diff({})["window"]["sampled"] == args.flows
    promoted = api.policy_shadow({"action": "promote"})
    assert promoted["promoted"]["promoted_revision"] > 0
    d.regenerate_all("policydiff promote")
    post = api.policy_diff({})
    assert post["state"] == "disarmed", post
    # the promoted world re-armed against itself: ZERO diff, and the
    # fresh window's counters start from zero
    api.policy_shadow(
        {"action": "arm", "rules": CANDIDATE, "sample_rate": 1.0}
    )
    assert api.policy_diff({})["window"]["sampled"] == 0
    api.process_flows(buf)
    w2 = api.policy_diff({})["window"]
    assert w2["changed"] == {
        "allowed": 0, "proxy_port": 0, "match_kind": 0,
    }, w2

    print(json.dumps({
        "smoke": "ok",
        "flows": args.flows,
        "sampled": w["sampled"],
        "changed": w["changed"],
        "allow_to_deny": w["allow_to_deny"],
        "deny_to_allow": w["deny_to_allow"],
        "diff_records": n_changed,
        "stale_fired": True,
        "promoted": True,
        "post_promote_diff_zero": True,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
