"""Benchmark ladder: BASELINE.md configs 1-5 on one chip.

Config 5 (headline, printed LAST so the driver's tail-parse picks it
up) is the real workload end-to-end: a ≥50k-rule mixed L3/L4/L7 policy
compiled through the actual control plane (policy_add → regeneration →
FleetCompiler), then ≥10M Hubble-style raw 5-tuple flows replayed
through the FUSED datapath step (prefilter → LB/DNAT → CT → ipcache
LPM → policy lattice in ONE jit, engine/datapath.py — the analog of
bpf_lxc.c:440/899 being one program).  A composed-host-oracle
bit-identity gate runs on a subsample before timing; divergence aborts
the bench.

Config 5 also emits:
  * config5_combined_verdicts_per_sec — the fused datapath PLUS
    inline fleet-L7 matching of redirected flows in ONE measured
    pipeline (the kernel-datapath+Envoy system), with its own
    composed oracle incl. L7;
  * incremental_update_ms — one rule added to the full world →
    delta-scoped regenerate → freshly published tables;
  * ct_churn / lattice / control-plane compile supporting lines.

Configs 1-4, 6 (one JSON line each):
  1. L3/L4 identity-pair allowlist from real rules, 1k tuples — the
     minimum end-to-end slice, oracle-gated.
  2. CIDR ruleset: DIR-24-8 ipcache LPM identity derivation feeding
     the lattice, 100k-unique-tuple replay (plus a supplementary
     1M-batch line showing the dispatch-amortized device rate).
  3. HTTP L7: regex→DFA device matching, 1M requests, host re.fullmatch
     oracle subsample.
  4. Kafka L7: field-equality tensors, 1M requests, MatchesRule host
     oracle subsample.
  6. The fused IPv6 program (prefilter6 → lb6/DNAT → CT6 → ipcache6
     → shared lattice), 1M tuples, composed-oracle subsample.

Output: one JSON line per config; the final line is
{"metric": "verdicts_per_sec_per_chip", ...} for config 5 through the
fused path.  vs_baseline is against the driver target of 100M
verdicts/sec aggregate on v5e-8, i.e. 12.5M verdicts/sec/chip.
"""

from __future__ import annotations

import argparse
import ipaddress
import json
import sys
import time

import numpy as np

BASELINE_PER_CHIP = 100e6 / 8  # driver target spread over v5e-8

# the headline config5 line, kept for re-emission as the LAST line
_HEADLINE = None


def emit(metric: str, value, unit: str, vs_baseline=None, **extra) -> None:
    global _HEADLINE
    line = {"metric": metric, "value": value, "unit": unit}
    if vs_baseline is not None:
        line["vs_baseline"] = vs_baseline
    line.update(extra)
    if metric == "verdicts_per_sec_per_chip":
        # the mid-run emission is a crash-safety copy (config 5 runs
        # first so a budget kill can't lose the headline); it is
        # LABELED provisional so trajectory parsers see exactly one
        # canonical record — the clean re-emission at exit
        _HEADLINE = {k: v for k, v in line.items() if k != "provisional"}
        line["provisional"] = True
    print(json.dumps(line), flush=True)


def ip_u32(s: str) -> int:
    return int(ipaddress.ip_address(s))


from cilium_tpu.engine.hostpath import HostLPM, composed_oracle  # noqa: E402


# ---------------------------------------------------------------------------
# config 5: full control plane + fused datapath
# ---------------------------------------------------------------------------


def build_rules(rng, n_rules, n_endpoints, n_teams):
    """A mixed 50k-rule policy: plain L4 (84%), L3-only (8%), CIDR
    (4%), HTTP L7 (3%), Kafka L7 (1%) — every rule selects one app
    (endpoint) and allows one team (identity group)."""
    from cilium_tpu.labels import LabelArray
    from cilium_tpu.policy.api import (
        EndpointSelector,
        IngressRule,
        PortProtocol,
        PortRule,
        Rule,
    )
    from cilium_tpu.policy.api.rule import (
        CIDRRule,
        L7Rules,
        PortRuleHTTP,
        PortRuleKafka,
    )

    def es(key, value):
        return EndpointSelector(match_labels={f"k8s.{key}": value})

    plain_ports = rng.choice(
        np.arange(1000, 30000), size=224, replace=False
    )
    http_ports = list(range(8000, 8016))
    kafka_ports = list(range(9090, 9098))

    rules = []
    l7_pairs = []  # (endpoint_idx, dport, team_idx) of L7 rules
    for i in range(n_rules):
        app = f"app{i % n_endpoints}"
        team_idx = int(rng.integers(0, n_teams))
        team = f"t{team_idx}"
        kind = rng.random()
        sel = es("app", app)
        src = es("team", team)
        if kind < 0.84:
            port = int(plain_ports[int(rng.integers(0, len(plain_ports)))])
            proto = "TCP" if rng.random() < 0.7 else "UDP"
            ingress = IngressRule(
                from_endpoints=[src],
                to_ports=[
                    PortRule(
                        ports=[PortProtocol(port=str(port), protocol=proto)]
                    )
                ],
            )
        elif kind < 0.92:
            ingress = IngressRule(from_endpoints=[src])  # L3-only
        elif kind < 0.96:
            block = int(rng.integers(0, 256))
            ingress = IngressRule(
                from_cidr_set=[CIDRRule(cidr=f"198.18.{block}.0/24")]
            )
        elif kind < 0.99:
            port = http_ports[int(rng.integers(0, len(http_ports)))]
            l7_pairs.append((i % n_endpoints, port, team_idx))
            ingress = IngressRule(
                from_endpoints=[src],
                to_ports=[
                    PortRule(
                        ports=[
                            PortProtocol(port=str(port), protocol="TCP")
                        ],
                        rules=L7Rules(
                            http=[
                                PortRuleHTTP(
                                    method="GET",
                                    path=f"/api/v{i % 4}/[a-z]+",
                                )
                            ]
                        ),
                    )
                ],
            )
        else:
            port = kafka_ports[int(rng.integers(0, len(kafka_ports)))]
            l7_pairs.append((i % n_endpoints, port, team_idx))
            ingress = IngressRule(
                from_endpoints=[src],
                to_ports=[
                    PortRule(
                        ports=[
                            PortProtocol(port=str(port), protocol="TCP")
                        ],
                        rules=L7Rules(
                            kafka=[
                                PortRuleKafka(topic=f"topic{i % 32}")
                            ]
                        ),
                    )
                ],
            )
        rules.append(
            Rule(
                endpoint_selector=sel,
                ingress=[ingress],
                labels=LabelArray.parse(f"bench-rule-{i}"),
            )
        )
    all_ports = (
        [(int(p), 6) for p in plain_ports if True]
        + [(int(p), 17) for p in plain_ports]
        + [(p, 6) for p in http_ports]
        + [(p, 6) for p in kafka_ports]
    )
    return rules, all_ports, l7_pairs


def build_config5(args, rng):
    """Returns (daemon, DatapathTables, index, flow pool arrays,
    oracle context, timings)."""
    from cilium_tpu.ct.device import compile_ct
    from cilium_tpu.ct.table import CTMap
    from cilium_tpu.daemon import Daemon
    from cilium_tpu.engine.datapath import DatapathTables
    from cilium_tpu.ipcache.ipcache import IPIdentity
    from cilium_tpu.labels import Label, Labels
    from cilium_tpu.lb.device import compile_lb
    from cilium_tpu.lb.service import L3n4Addr, ServiceManager

    timings = {}

    d = Daemon(num_workers=8)
    d.policy_trigger.close(wait=True)  # explicit sweeps

    # endpoints: one per app
    t0 = time.perf_counter()
    ep_ip = {}
    for i in range(args.endpoints):
        ip = f"10.250.{i // 256}.{i % 256}"
        ep_ip[100 + i] = ip_u32(ip)
        d.create_endpoint(
            100 + i,
            Labels({"app": Label("app", f"app{i}", "k8s")}),
            ipv4=ip,
            name=f"ep{i}",
        )

    # identity universe: n_identities cluster-scope ids in teams of
    # ~identities/teams; each gets one /32 in the ipcache
    n_teams = max(args.identities // 16, 1)
    id_ips = []
    ids = []
    for i in range(args.identities - args.endpoints):
        labels = Labels(
            {
                "team": Label("team", f"t{i % n_teams}", "k8s"),
                "svc": Label("svc", f"s{i}", "k8s"),
            }
        )
        ident, _ = d.identity_allocator.allocate(labels)
        ip = 0x0A000000 | (i + 1)  # 10.0.0.0/8, dense
        id_ips.append(ip)
        ids.append(ident.id)
        d.ipcache.upsert(
            str(ipaddress.ip_address(ip)),
            IPIdentity(ident.id, "kvstore"),
        )
    timings["identity_setup_s"] = time.perf_counter() - t0

    # policy: n_rules mixed rules through the real policy_add path
    t0 = time.perf_counter()
    rules, all_ports, l7_pairs = build_rules(
        rng, args.rules, args.endpoints, n_teams
    )
    d.policy_add(rules)
    timings["policy_add_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    d.regenerate_all("bench import")
    timings["regenerate_s"] = time.perf_counter() - t0

    _, policy_tables, index = d.endpoint_manager.published()

    # prefilter: one denied CIDR
    prefilter_map = {"203.0.113.0/24": 1}
    from cilium_tpu.prefilter import build_prefilter

    # services: VIPs load-balancing onto endpoint IPs
    mgr = ServiceManager()
    vips = []
    for i in range(16):
        vip = f"172.16.0.{i + 1}"
        backends = [
            L3n4Addr(
                str(ipaddress.ip_address(ep_ip[100 + int(b)])),
                int(all_ports[i][0]),
                6,
            )
            for b in rng.choice(args.endpoints, size=2, replace=False)
        ]
        mgr.upsert(L3n4Addr(vip, 80, 6), backends)
        vips.append(ip_u32(vip))

    ct = CTMap()
    from cilium_tpu.ipcache.lpm import specialize_ipcache_to_idx

    ipcache_tables = specialize_ipcache_to_idx(
        d.lpm_builder.tables(), policy_tables
    )
    tables = DatapathTables(
        prefilter=build_prefilter(prefilter_map),
        ipcache=ipcache_tables,
        ct=compile_ct(ct),
        lb=compile_lb(mgr),
        policy=policy_tables,
    )

    oracle_ctx = {
        "prefilter": HostLPM(prefilter_map),
        "ipcache": HostLPM(dict(d.lpm_builder.mappings)),
        "ct": ct,
        "mgr": mgr,
        "daemon": d,
        "index": index,
    }
    pool = make_flow_pool(
        args, rng, ep_ip, np.asarray(id_ips, np.uint32), vips, all_ports,
        index, l7_pairs=l7_pairs, n_teams=n_teams,
    )
    return d, tables, index, pool, oracle_ctx, timings, ct, mgr


def make_flow_pool(args, rng, ep_ip, id_ips, vips, all_ports, index,
                   l7_pairs=None, n_teams=1):
    """A pool of unique flows (CT-friendly: 10M replay tuples sample
    from `pool_size` unique flows, like real traffic repeats flows).

    2.5% of flows are PROXY-BOUND L7 traffic: real clients of the
    policy's HTTP/Kafka rules (an allowed team member hitting the
    rule's port at the rule's endpoint) — the mixed L3/L4/L7 traffic
    shape BASELINE config 5 describes.  Uncorrelated random flows
    virtually never redirect (team × port joint probability ~1e-5),
    which would leave the proxy path unmeasured."""
    n = args.pool
    ep_ids = np.asarray(sorted(ep_ip), np.int64)
    ep_axis = np.asarray([index[int(e)] for e in ep_ids], np.int32)
    ep_addr = np.asarray([ep_ip[int(e)] for e in ep_ids], np.uint32)

    pick_ep = rng.integers(0, len(ep_ids), size=n)
    direction = (rng.random(n) < 0.5).astype(np.uint8)  # 0=in 1=eg
    peer_ip = id_ips[rng.integers(0, len(id_ips), size=n)]
    # 2% prefiltered sources, 3% world (unknown) sources
    pre = rng.random(n) < 0.02
    world = rng.random(n) < 0.03
    peer_ip = np.where(
        pre,
        ip_u32("203.0.113.0") + rng.integers(0, 256, size=n),
        np.where(
            world,
            ip_u32("8.8.0.0") + rng.integers(0, 1 << 16, size=n),
            peer_ip,
        ),
    ).astype(np.uint32)
    # egress: 10% of destinations are service VIPs (LB DNAT)
    to_vip = (direction == 1) & (rng.random(n) < 0.10)
    vip_arr = np.asarray(vips, np.uint32)
    vip_pick = vip_arr[rng.integers(0, len(vip_arr), size=n)]

    saddr = np.where(direction == 0, peer_ip, ep_addr[pick_ep])
    daddr = np.where(
        direction == 0,
        ep_addr[pick_ep],
        np.where(to_vip, vip_pick, peer_ip),
    )
    ports = np.asarray([p for p, _ in all_ports], np.int64)
    protos = np.asarray([pr for _, pr in all_ports], np.int64)
    pick_port = rng.integers(0, len(ports), size=n)
    dport = ports[pick_port]
    proto = protos[pick_port]
    # 10% junk ports (miss the slot table), VIP flows probe port 80
    junk = rng.random(n) < 0.10
    dport = np.where(junk, rng.integers(30000, 65536, size=n), dport)
    dport = np.where(to_vip, 80, dport).astype(np.uint16)
    proto = np.where(junk, 6, proto)
    proto = np.where(to_vip, 6, proto).astype(np.uint8)
    sport = rng.integers(1024, 65536, size=n).astype(np.uint16)
    frag = (rng.random(n) < 0.02).astype(np.uint8)

    ep_index = ep_axis[pick_ep].astype(np.uint32)
    if l7_pairs:
        # overlay LAST so junk/VIP/prefilter mixing can't clobber the
        # L7 flows' defining fields
        l7 = np.nonzero(rng.random(n) < 0.025)[0]
        pick_rule = rng.integers(0, len(l7_pairs), size=len(l7))
        for row, r in zip(l7, pick_rule):
            app_i, port, team_idx = l7_pairs[int(r)]
            # an identity of that team: id_ips[i] belongs to team
            # (i % n_teams)
            member = int(rng.integers(0, len(id_ips) // n_teams))
            i_id = member * n_teams + team_idx
            if i_id >= len(id_ips):
                i_id = team_idx
            direction[row] = 0  # ingress at the serving endpoint
            ep_index[row] = index[100 + app_i]
            saddr[row] = id_ips[i_id]
            daddr[row] = ep_ip[100 + app_i]
            dport[row] = port
            proto[row] = 6
            frag[row] = 0

    return {
        "ep_index": ep_index,
        "saddr": saddr.astype(np.uint32),
        "daddr": daddr.astype(np.uint32),
        "sport": sport,
        "dport": dport,
        "proto": proto,
        "direction": direction,
        "is_fragment": frag,
    }


def zipf_picks(prng, n: int, size: int, s: float) -> np.ndarray:
    """Ranked-Zipf sample of pool rows: rank r (1-based) drawn with
    probability ∝ r^-s, ranks mapped through a per-prng random
    permutation so the head flows are arbitrary pool rows, not row 0.
    s≈1.1 is the trace-skew shape real identity-pair/port traffic
    shows (millions of tuples, few distinct policy keys); s=0 is
    uniform.  Shared with tools/cacheprof.py so the hit-rate curve
    and the bench's effective line sample the same distribution."""
    ranks = np.arange(1, n + 1, dtype=np.float64)
    w = ranks ** -float(s)
    w /= w.sum()
    perm = prng.permutation(n)
    return perm[prng.choice(n, size=size, p=w)]


def encode_pool_sample(pool, picks):
    from cilium_tpu.native import encode_flow_records

    n = len(picks)
    return encode_flow_records(
        ep_id=pool["ep_index"][picks],
        identity=np.zeros(n, np.uint32),
        saddr=pool["saddr"][picks],
        daddr=pool["daddr"][picks],
        sport=pool["sport"][picks],
        dport=pool["dport"][picks],
        proto=pool["proto"][picks],
        direction=pool["direction"][picks],
        is_fragment=pool["is_fragment"][picks],
    )


def pack_pool_pairs(pool, prng, half: int, k: int):
    """k host-staged [2, 4, half] u32 packed4 pairs from the
    per-direction pool subsets (the host half of the headline
    staging; ONE array per pair = one device_put per batch).
    Returns (pairs, picks), picks[i] = (ingress rows, egress rows)
    of the pool behind pair i."""
    from cilium_tpu.engine.datapath import pack_flow_records4

    subsets = [np.nonzero(pool["direction"] == d)[0] for d in (0, 1)]
    pairs, picks = [], []
    for _ in range(k):
        pair = np.empty((2, 4, half), np.uint32)
        rows = []
        for row, subset in enumerate(subsets):
            p = subset[prng.integers(0, len(subset), size=half)]
            pair[row] = pack_flow_records4(
                ep_index=pool["ep_index"][p],
                saddr=pool["saddr"][p],
                daddr=pool["daddr"][p],
                sport=pool["sport"][p],
                dport=pool["dport"][p],
                proto=pool["proto"][p],
                direction=pool["direction"][p],
                is_fragment=pool["is_fragment"][p],
            )
            rows.append(p)
        pairs.append(pair)
        picks.append(tuple(rows))
    return pairs, picks


def add_one_rule(
    d, port: int, app: str = "app0", team: str = "t0",
    label_prefix: str = "bench-incr",
) -> None:
    """The one-rule churn unit shared by the incremental/delta bench
    sections and tools/churnprof.py: allow `team` → `app` on one TCP
    port.  Keeping ONE builder means every churn metric measures the
    same rule shape."""
    from cilium_tpu.labels import LabelArray
    from cilium_tpu.policy.api import (
        EndpointSelector,
        IngressRule,
        PortProtocol,
        PortRule,
        Rule,
    )

    d.policy_add(
        [
            Rule(
                endpoint_selector=EndpointSelector(
                    match_labels={"k8s.app": app}
                ),
                ingress=[
                    IngressRule(
                        from_endpoints=[
                            EndpointSelector(
                                match_labels={"k8s.team": team}
                            )
                        ],
                        to_ports=[
                            PortRule(ports=[
                                PortProtocol(
                                    port=str(port), protocol="TCP"
                                )
                            ])
                        ],
                    )
                ],
                labels=LabelArray.parse(f"{label_prefix}-{port}"),
            )
        ]
    )


def run_config5(args) -> None:
    import jax

    from cilium_tpu.ct.device import compile_ct
    from cilium_tpu.engine.datapath import DatapathTables
    from cilium_tpu.replay import read_flow_batches, replay_pool

    rng = np.random.default_rng(7)
    t_build = time.perf_counter()
    (d, tables, index, pool, oracle_ctx, timings, ct, mgr) = (
        build_config5(args, rng)
    )
    timings["total_build_s"] = time.perf_counter() - t_build
    # pin the compiled tables on device ONCE — replay()'s own
    # device_put then no-ops instead of re-uploading 24 leaves per
    # replay call
    tables = jax.device_put(tables)
    n_entries = sum(
        len(e.realized_map_state)
        for e in d.endpoint_manager.endpoints()
    )
    emit(
        "control_plane_compile_seconds",
        round(timings["total_build_s"], 2),
        "s",
        rules=args.rules,
        endpoints=args.endpoints,
        identities=args.identities,
        map_entries=n_entries,
        phases={k: round(v, 2) for k, v in timings.items()},
    )

    # --- seed CT: one churn pass over 2 batches of the pool ----------------
    # 2M-tuple churn batches: the loop's critical path is serial
    # (step → 16-byte header D2H → CT fold → snapshot delta), so the
    # per-batch host↔device round trip (not measured on the local
    # chip) amortizes over more tuples; bigger still and the
    # convergence re-runs on bursty rounds cost more than it saves.
    # Pool-mode loader (replay_pool): the flow universe uploads once,
    # each batch moves only u32 pick indices, and the fused program
    # gathers the flow columns on device.  The record-buffer loader
    # (replay) stays the generic path.
    seed_batch = min(args.batch, 1 << 21)
    # picks generate ON DEVICE (int = count): an 8-byte PRNG key per
    # batch replaces an [B] index upload — same uniform pool sampling
    seed_stats = replay_pool(
        tables, pool, 2 * seed_batch, batch_size=seed_batch, ct_map=ct
    )
    # sustained-churn metric: a SECOND pass at the same batch shape —
    # the seed pass paid the jit compiles and created most of the
    # pool's flows, so this measures the steady-state loop (dispatch
    # + 16-byte header D2H + bucketed intent fetch + per-bucket
    # delta) the way a running agent experiences it
    churn_stats = replay_pool(
        tables, pool, 4 * seed_batch, batch_size=seed_batch, ct_map=ct
    )
    # stats.seconds starts after the per-call fixed setup (pool
    # pack+upload, snapshot-cache check) — that's per-call overhead
    # the seed already paid, not the churn loop being measured
    churn_s = churn_stats.seconds
    tables = DatapathTables(
        prefilter=tables.prefilter,
        ipcache=tables.ipcache,
        ct=compile_ct(ct),
        lb=tables.lb,
        policy=tables.policy,
    )
    emit(
        "ct_churn_tuples_per_sec",
        round(churn_stats.total / churn_s),
        "tuples/s",
        ct_created=seed_stats.ct_created + churn_stats.ct_created,
        note=(
            "sustained fused replay, incremental device CT: "
            "compacted intent D2H + per-bucket row deltas"
        ),
    )

    # --- bit-identity gate vs composed host oracle -------------------------
    states = [None] * len(index)
    for ep in d.endpoint_manager.endpoints():
        states[index[ep.id]] = ep.realized_map_state
    sample = rng.integers(0, args.pool, size=args.oracle_sample)
    got_buf = encode_pool_sample(pool, sample)
    flows = next(read_flow_batches(got_buf, len(sample)))[0]
    from cilium_tpu.engine.datapath import datapath_step

    got = datapath_step(tables, flows)
    want_allow, want_proxy, want_sec, want_stages = composed_oracle(
        oracle_ctx, states, pool, list(sample), return_stages=True
    )
    assert (np.asarray(got.allowed) == want_allow).all(), (
        "fused datapath diverges from composed oracle (allow)"
    )
    assert (np.asarray(got.proxy_port) == want_proxy).all(), (
        "fused datapath diverges from composed oracle (proxy)"
    )
    assert (np.asarray(got.sec_id) == want_sec).all(), (
        "fused datapath diverges from composed oracle (sec_id)"
    )
    # per-stage bit-identity: the telemetry plane's stage columns
    # must agree with the oracle's intermediate decisions per tuple
    for col, key in (
        ("pre_dropped", "pre_drop"),
        ("ct_result", "ct_res"),
        ("match_kind", "match_kind"),
        ("ipcache_miss", "ipcache_miss"),
    ):
        assert (
            np.asarray(getattr(got, col)).astype(np.int64)
            == want_stages[key].astype(np.int64)
        ).all(), f"stage divergence vs composed oracle ({col})"
    assert (
        (np.asarray(got.lb_slave) > 0) == want_stages["lb_hit"]
    ).all(), "stage divergence vs composed oracle (lb_hit)"

    # --- timed fused replay: args.tuples sampled from the pool -------------
    tables = jax.device_put(tables)
    n_batches = max(args.tuples // args.batch, 1)
    from cilium_tpu.engine.datapath import (
        datapath_step_accum_pair,
        datapath_step_accum_pair_telem,
    )
    from cilium_tpu.engine.verdict import (
        make_counter_buffers,
        make_telemetry_buffers,
    )
    from cilium_tpu.metrics import registry as metrics_registry
    from cilium_tpu.spanstat import SpanStats
    from cilium_tpu.telemetry import (
        fold_telemetry,
        telemetry_consistent,
        telemetry_from_outputs,
        telemetry_summary,
    )

    bench_spans = SpanStats()
    bench_spans.span("host_pack").start()

    # The datapath is direction-specialized (bpf_lxc's separate
    # ingress/egress programs): sample each timed batch as one
    # half-batch per direction from the pool's per-direction subsets
    # — the same flow distribution, already partitioned the way real
    # packets arrive at the two hooks.
    half = args.batch // 2
    idx_ingress = np.nonzero(pool["direction"] == 0)[0]
    idx_egress = np.nonzero(pool["direction"] == 1)[0]
    flow_batches = []
    for _ in range(min(n_batches, 4)):
        pair = []
        for subset in (idx_ingress, idx_egress):
            picks = subset[rng.integers(0, len(subset), size=half)]
            pair.append(
                jax.device_put(
                    next(
                        read_flow_batches(
                            encode_pool_sample(pool, picks), half
                        )
                    )[0]
                )
            )
        flow_batches.append(tuple(pair))
    bench_spans.span("host_pack").end()
    # warmup/compile both forms: the INSTRUMENTED pair program (the
    # headline pipeline — counters + the [2, T] telemetry reductions
    # ride the one dispatch) and the bare pair program (the
    # telemetry_overhead_pct reference)
    acc = jax.device_put(make_counter_buffers(tables.policy))
    telem = jax.device_put(make_telemetry_buffers())
    out_i, out_e, acc, telem = datapath_step_accum_pair_telem(
        tables, flow_batches[0][0], flow_batches[0][1], acc, telem
    )
    jax.block_until_ready((out_i, out_e, acc, telem))
    acc_bare = jax.device_put(make_counter_buffers(tables.policy))
    out_i, out_e, acc_bare = datapath_step_accum_pair(
        tables, flow_batches[0][0], flow_batches[0][1], acc_bare
    )
    jax.block_until_ready((out_i, out_e, acc_bare))

    # --- telemetry gate: on-device stage counters bit-identical to the
    # host fold of per-tuple outputs on one ≥1M-tuple batch pair -----------
    gate_in, gate_eg = flow_batches[0]
    out_full_in = datapath_step(tables, gate_in)
    out_full_eg = datapath_step(tables, gate_eg)
    want_telem = telemetry_from_outputs(
        out_full_in, np.zeros(half, np.int64)
    ) + telemetry_from_outputs(out_full_eg, np.ones(half, np.int64))
    acc_gate = jax.device_put(make_counter_buffers(tables.policy))
    telem_gate = jax.device_put(make_telemetry_buffers())
    _, _, acc_gate, telem_gate = datapath_step_accum_pair_telem(
        tables, gate_in, gate_eg, acc_gate, telem_gate
    )
    got_telem = np.asarray(telem_gate).astype(np.uint64)
    assert (got_telem == want_telem).all(), (
        "device telemetry diverges from host per-stage fold:\n"
        f"device={got_telem}\nhost={want_telem}"
    )
    assert telemetry_consistent(got_telem), got_telem
    del acc_gate, telem_gate, out_full_in, out_full_eg

    # --- hot/cold + packed4 staging gate: the headline program (hot
    # policy plane only, [4, B] u32 staged columns unpacked in-jit)
    # computes bit-identical verdict columns, counters AND telemetry
    # to the u32-column pair program on the full tables ---------------------
    from cilium_tpu.compiler.tables import split_hot
    from cilium_tpu.engine.datapath import pack_flow_records4

    def _packed4_of(fb):
        return pack_flow_records4(
            ep_index=np.asarray(fb.ep_index),
            saddr=np.asarray(fb.saddr),
            daddr=np.asarray(fb.daddr),
            sport=np.asarray(fb.sport),
            dport=np.asarray(fb.dport),
            proto=np.asarray(fb.proto),
            direction=np.asarray(fb.direction),
            is_fragment=np.asarray(fb.is_fragment),
        )

    tables_hot = DatapathTables(
        prefilter=tables.prefilter,
        ipcache=tables.ipcache,
        ct=tables.ct,
        lb=tables.lb,
        policy=split_hot(tables.policy),
    )
    from cilium_tpu.engine.datapath import (
        datapath_step_accum_pair_telem_packed4_stacked,
    )

    acc_p = jax.device_put(make_counter_buffers(tables.policy))
    telem_p = jax.device_put(make_telemetry_buffers())
    acc_r = jax.device_put(make_counter_buffers(tables.policy))
    telem_r = jax.device_put(make_telemetry_buffers())
    pk_pair = jax.device_put(
        np.stack([_packed4_of(gate_in), _packed4_of(gate_eg)])
    )
    got_i, got_e, acc_p, telem_p = (
        datapath_step_accum_pair_telem_packed4_stacked(
            tables_hot, pk_pair, acc_p, telem_p
        )
    )
    ref_i, ref_e, acc_r, telem_r = datapath_step_accum_pair_telem(
        tables, gate_in, gate_eg, acc_r, telem_r
    )
    for got, ref in ((got_i, ref_i), (got_e, ref_e)):
        for col in (
            "allowed", "proxy_port", "match_kind", "sec_id",
            "ct_result", "pre_dropped", "final_daddr", "final_dport",
            "rev_nat", "lb_slave", "ct_create", "ct_delete",
            "l4_slot", "ipcache_miss",
        ):
            assert np.array_equal(
                np.asarray(getattr(got, col)),
                np.asarray(getattr(ref, col)),
            ), f"packed4/hot-split divergence in verdict column {col}"
    assert np.array_equal(np.asarray(acc_p), np.asarray(acc_r)), (
        "packed4/hot-split counter divergence"
    )
    assert np.array_equal(np.asarray(telem_p), np.asarray(telem_r)), (
        "packed4/hot-split telemetry divergence"
    )
    del acc_p, telem_p, acc_r, telem_r, got_i, got_e, ref_i, ref_e
    del pk_pair

    # --- instrumented reference loop (device-resident batches): the
    # telemetry A/B substrate — the same pairs the bare loop below
    # replays, through the instrumented program.  The HEADLINE number
    # now comes from the autotuned async staging loop further down;
    # this loop only prices the instrumentation.
    acc = jax.device_put(make_counter_buffers(tables.policy))
    telem = jax.device_put(make_telemetry_buffers())
    bench_spans.span("dispatch").start()
    t0 = time.perf_counter()
    outs = []
    for i in range(n_batches):
        fin, feg = flow_batches[i % len(flow_batches)]
        out_i, out_e, acc, telem = datapath_step_accum_pair_telem(
            tables, fin, feg, acc, telem
        )
        outs.append((out_i, out_e))
        if len(outs) > 4:
            jax.block_until_ready(outs.pop(0))
    bench_spans.span("dispatch").end()
    bench_spans.span("device").start()
    jax.block_until_ready(outs)
    jax.block_until_ready((acc, telem))
    dt = time.perf_counter() - t0
    bench_spans.span("device").end()

    # --- bare reference loop: the same batches through the
    # uninstrumented pair program → telemetry_overhead_pct ------------------
    t0 = time.perf_counter()
    outs = []
    for i in range(n_batches):
        fin, feg = flow_batches[i % len(flow_batches)]
        out_i, out_e, acc_bare = datapath_step_accum_pair(
            tables, fin, feg, acc_bare
        )
        outs.append((out_i, out_e))
        if len(outs) > 4:
            jax.block_until_ready(outs.pop(0))
    jax.block_until_ready(outs)
    jax.block_until_ready(acc_bare)
    dt_bare = time.perf_counter() - t0
    del acc_bare
    overhead_pct = (dt - dt_bare) / dt_bare * 100.0
    total_ref = n_batches * 2 * half
    emit(
        "telemetry_overhead_pct",
        round(overhead_pct, 2),
        "%",
        instrumented_verdicts_per_sec=round(total_ref / dt),
        bare_verdicts_per_sec=round(total_ref / dt_bare),
        note=(
            "instrumented headline pipeline (counters + [2, T] "
            "stage reductions fused into the pair dispatch) vs the "
            "bare pair program over identical batches"
        ),
    )

    # --- flow-capture reference loop: the same instrumented batches
    # with the Hubble flow fold riding each drain → the flow plane's
    # hot-path cost (flow_capture_overhead_pct).  On the fused bench
    # loop capture runs under the monitor fold's head-sample budget
    # (a bounded window per direction; the ring is bounded anyway) —
    # the every-drop guarantee is the audit path's contract, gated by
    # tools/flow_tail.py, not a property bought on this loop --------------
    from cilium_tpu.flow import FlowStore, capture_batch

    flow_store = FlowStore()
    flow_window = 2048  # tuples examined per direction per batch
    flow_allow_cap = 512
    flow_id_table = np.asarray(tables.policy.id_table)
    flow_capture_s = [0.0]

    # ONE fused head-window slice per direction (a single tiny cached
    # program + one D2H) instead of a dozen per-column slices
    @jax.jit
    def _flow_slice(out_last):
        import jax.numpy as jnp

        w = flow_window
        return jnp.stack(
            [
                out_last.sec_id[:w].astype(jnp.uint32),
                out_last.final_dport[:w].astype(jnp.uint32),
                out_last.allowed[:w].astype(jnp.uint32),
                out_last.match_kind[:w].astype(jnp.uint32),
                out_last.proxy_port[:w].astype(jnp.uint32),
                out_last.pre_dropped[:w].astype(jnp.uint32),
                out_last.ct_result[:w].astype(jnp.uint32),
                out_last.ct_delete[:w].astype(jnp.uint32),
                out_last.lb_slave[:w].astype(jnp.uint32),
                out_last.ipcache_miss[:w].astype(jnp.uint32),
            ]
        )

    def _capture_pair(pair):
        cap_t0 = time.perf_counter()
        _capture_pair_inner(pair)
        flow_capture_s[0] += time.perf_counter() - cap_t0

    def _capture_pair_inner(pair):
        for dirv, out_last in ((0, pair[0]), (1, pair[1])):
            cols = np.asarray(_flow_slice(out_last))
            sec_idx = cols[0].astype(np.int64)
            ident = flow_id_table[
                np.minimum(sec_idx, len(flow_id_table) - 1)
            ].astype(np.int64)
            zeros_ = np.zeros(len(sec_idx), np.int64)
            capture_batch(
                flow_store,
                ep_ids=zeros_,
                src_identities=ident if dirv == 0 else zeros_,
                dst_identities=zeros_ if dirv == 0 else ident,
                dports=cols[1],
                protos=np.full(len(sec_idx), 6),
                directions=np.full(len(sec_idx), dirv),
                allowed=cols[2],
                match_kind=cols[3],
                proxy_port=cols[4].astype(np.int32),
                pre_dropped=cols[5],
                ct_result=cols[6],
                ct_delete=cols[7],
                lb_slave=cols[8],
                ipcache_miss=cols[9],
                allow_sample=flow_allow_cap,
            )

    # warm/compile the capture path like every other timed program,
    # then reset the accounting so the measurement excludes compile
    _capture_pair((out_i, out_e))
    flow_store = FlowStore()
    flow_capture_s[0] = 0.0

    acc_cap = jax.device_put(make_counter_buffers(tables.policy))
    telem_cap = jax.device_put(make_telemetry_buffers())
    t0 = time.perf_counter()
    outs = []
    for i in range(n_batches):
        fin, feg = flow_batches[i % len(flow_batches)]
        out_i, out_e, acc_cap, telem_cap = (
            datapath_step_accum_pair_telem(
                tables, fin, feg, acc_cap, telem_cap
            )
        )
        outs.append((out_i, out_e))
        if len(outs) > 4:
            done = outs.pop(0)
            jax.block_until_ready(done)
            _capture_pair(done)
    while outs:
        done = outs.pop(0)
        jax.block_until_ready(done)
        _capture_pair(done)
    jax.block_until_ready((acc_cap, telem_cap))
    dt_cap = time.perf_counter() - t0
    del acc_cap, telem_cap
    # the overhead is the capture work MEASURED inside the timed loop
    # over the pipeline time without it — a wall-clock A/B of two
    # whole loops would be dominated by run-to-run dispatch variance
    # at this batch count (the telemetry A/B above shows its size),
    # while the added host cost is what the flow fold actually
    # charges the hot path
    flow_overhead_pct = (
        flow_capture_s[0] / max(dt_cap - flow_capture_s[0], 1e-9)
    ) * 100.0
    emit(
        "flow_capture_overhead_pct",
        round(flow_overhead_pct, 2),
        "%",
        flow_capture_seconds=round(flow_capture_s[0], 4),
        pipeline_seconds=round(dt_cap, 3),
        flow_records_captured=flow_store.captured_total,
        flow_ring_evicted=flow_store.evicted,
        capture_window_per_direction=flow_window,
        allow_sample_cap=flow_allow_cap,
        note=(
            "per-batch Hubble flow fold (drops + sampled allows "
            "from a bounded head window riding the existing drain) "
            "measured inside the instrumented pair pipeline"
        ),
    )

    # --- tracing reference loop: the same instrumented batches with
    # span-plane bookkeeping riding each dispatch (a root span per
    # stream + per-batch dispatch spans with per-chip children — the
    # daemon's process_flows span shape at this batch cadence).  The
    # overhead is the tracer's OWN accounted bookkeeping seconds
    # (Tracer.overhead_s: begin/finish/ring-append time measured
    # inside the tracer) over the pipeline time without it — the same
    # measured-inside-the-loop discipline as flow_capture_overhead_pct,
    # immune to run-to-run dispatch variance ------------------------------
    from cilium_tpu import tracing as _tracing

    bench_tracer = _tracing.Tracer(
        seed=0, sample_rate=args.trace_sample_rate
    )
    acc_tr = jax.device_put(make_counter_buffers(tables.policy))
    telem_tr = jax.device_put(make_telemetry_buffers())
    t0 = time.perf_counter()
    outs = []
    with bench_tracer.span(
        "bench.process_flows", site="bench",
        attrs={"batches": n_batches},
    ):
        for i in range(n_batches):
            fin, feg = flow_batches[i % len(flow_batches)]
            with bench_tracer.span(
                "dispatch", site="bench", attrs={"batch": i}
            ) as bsp:
                out_i, out_e, acc_tr, telem_tr = (
                    datapath_step_accum_pair_telem(
                        tables, fin, feg, acc_tr, telem_tr
                    )
                )
            _tracing.record_chip_spans(
                bench_tracer, bsp, 1, 2 * half, "bench"
            )
            outs.append((out_i, out_e))
            if len(outs) > 4:
                jax.block_until_ready(outs.pop(0))
        jax.block_until_ready(outs)
        jax.block_until_ready((acc_tr, telem_tr))
    dt_trace = time.perf_counter() - t0
    del acc_tr, telem_tr
    trace_overhead_pct = (
        bench_tracer.overhead_s
        / max(dt_trace - bench_tracer.overhead_s, 1e-9)
    ) * 100.0
    assert trace_overhead_pct < 3.0, (
        f"tracing overhead {trace_overhead_pct:.3f}% breaches the "
        f"3% gate at sample rate {args.trace_sample_rate}"
    )
    emit(
        "tracing_overhead_pct",
        round(trace_overhead_pct, 4),
        "%",
        trace_sample_rate=args.trace_sample_rate,
        tracer_seconds=round(bench_tracer.overhead_s, 6),
        pipeline_seconds=round(dt_trace, 3),
        spans_exported=bench_tracer.finished_total,
        spans_dropped=bench_tracer.dropped,
        note=(
            "span-plane bookkeeping (root + per-batch dispatch "
            "spans + per-chip children) measured inside the "
            "instrumented pair pipeline; gate < 3% at the default "
            "sample rate"
        ),
    )

    # --- autotune: pow2 batch sizes × hot-plane pack widths ----------------
    # A small measured search (cached per table shape class) picks
    # the jit class the headline loop runs: candidates maximize
    # verdicts/s subject to the p99 batch-latency bound.  Pack-width
    # candidates re-place the hashed entry tables via
    # repack_hash_lanes — no policy recompile, and the layout stamp
    # keeps delta publication honest about the changed layout.
    from cilium_tpu.engine import autotune as at
    from cilium_tpu.compiler.tables import repack_hash_lanes

    cur_lanes = int(np.asarray(tables.policy.l4_hash_rows).shape[1])
    lane_tables = {cur_lanes: tables_hot}

    def _tables_for(lanes):
        if lanes not in lane_tables:
            lane_tables[lanes] = jax.device_put(
                DatapathTables(
                    prefilter=tables.prefilter,
                    ipcache=tables.ipcache,
                    ct=tables.ct,
                    lb=tables.lb,
                    policy=split_hot(
                        repack_hash_lanes(tables.policy, lanes)
                    ),
                )
            )
        return lane_tables[lanes]

    def _run_candidate(params):
        t_c = _tables_for(params["hash_lanes"])
        half_c = params["batch"] // 2
        pairs, _ = pack_pool_pairs(
            pool, np.random.default_rng(31), half_c, 2
        )
        state = {
            "acc": jax.device_put(
                make_counter_buffers(tables.policy)
            ),
            "telem": jax.device_put(make_telemetry_buffers()),
            "i": 0,
        }

        def step(pair):
            o_i, o_e, state["acc"], state["telem"] = (
                datapath_step_accum_pair_telem_packed4_stacked(
                    t_c, jnp_dev(pair),
                    state["acc"], state["telem"],
                )
            )
            return o_i.allowed, o_e.allowed

        def make_args():
            state["i"] += 1
            return (pairs[state["i"] % len(pairs)],)

        return at.measure_dispatch(
            step, make_args, params["batch"], reps=3,
            outstanding=2, sync_reps=2,
        )

    import jax.numpy as _jnp

    def jnp_dev(a):
        return _jnp.asarray(a)

    if args.no_autotune:
        choice = at.TuneChoice(
            params={"batch": args.batch, "hash_lanes": cur_lanes},
            verdicts_per_sec=0.0, p99_batch_ms=0.0,
        )
    else:
        cands = []
        for lanes in dict.fromkeys((cur_lanes, 128)):
            for bs in dict.fromkeys(
                (max(args.batch >> 1, 1 << 20), args.batch)
            ):
                cands.append({"batch": bs, "hash_lanes": lanes})
        choice = at.autotune(
            cands,
            _run_candidate,
            p99_bound_ms=args.autotune_p99_ms,
            cache_key=at.shape_class_key(tables.policy),
            log=lambda msg: print(f"# {msg}", file=sys.stderr),
        )
    chosen_bs = choice.params["batch"]
    chosen_lanes = choice.params["hash_lanes"]
    tables_chosen = _tables_for(chosen_lanes)
    emit(
        "autotune_choice",
        chosen_bs,
        "tuples/batch",
        hash_lanes=chosen_lanes,
        p99_bound_ms=args.autotune_p99_ms,
        trials=[
            {
                "batch": t.params["batch"],
                "hash_lanes": t.params["hash_lanes"],
                "verdicts_per_sec": round(t.verdicts_per_sec),
                "p99_batch_ms": round(t.p99_batch_ms, 1),
                "admitted": t.admitted,
            }
            for t in choice.trials
        ],
        note=(
            "pow2 batch sizes x hot-plane pack widths, cached per "
            "table shape class (jit classes bounded; see "
            "cilium_jit_cache_* metrics)"
        ),
    )

    from cilium_tpu.engine.publish import AsyncBatchDispatcher

    # --- sub-word hot planes: one layout stamp, gated ----------------------
    # The headline world shrinks every hot gathered row to the bits
    # the verdict actually reads (compact 2-word L4 entries, 4-word
    # CT lanes, packed ipcache idx/l3/prefix-class planes) — applied
    # where semantics allow, full-surface bit-identity gated below
    # before a single timed tuple.
    from cilium_tpu.engine.datapath import (
        PersistentPairDispatcher,
        subword_datapath_tables,
    )

    persist_k = max(int(args.persist_pairs), 1)
    host_headline = DatapathTables(
        prefilter=tables.prefilter,
        ipcache=tables.ipcache,
        ct=tables.ct,
        lb=tables.lb,
        policy=split_hot(
            tables.policy if chosen_lanes == cur_lanes
            else repack_hash_lanes(tables.policy, chosen_lanes)
        ),
    )
    subword_report = {"disabled": "--no-subword"}
    if not args.no_subword:
        host_headline, subword_report = subword_datapath_tables(
            host_headline
        )
    tables_headline = jax.device_put(host_headline)

    # --- HEADLINE: persistent fused-pair program ---------------------------
    # ONE launch evaluates --persist-pairs staged pair batches via a
    # donated-carry lax.scan (zero per-pair dispatch, no
    # per-direction launches); the counter/telemetry carry stays
    # device-resident and commits once per drain.  The host stages
    # super-batch N+1 while the device computes N (jax async
    # dispatch — the launch returns immediately, the only sync is
    # the final drain).
    half_h = chosen_bs // 2
    n_batches_h = max(args.tuples // chosen_bs, 1)
    host_pairs, _ = pack_pool_pairs(
        pool, np.random.default_rng(41), half_h, min(n_batches_h, 6)
    )

    # bit-identity gate: the sub-word + persistent program against
    # the reference per-pair program on the SAME pairs — all 14
    # verdict columns + counters + telemetry, before any timing
    gate_pairs = host_pairs[: min(len(host_pairs), persist_k + 1)]
    acc_g = jax.device_put(make_counter_buffers(tables.policy))
    tel_g = jax.device_put(make_telemetry_buffers())
    pd_gate = PersistentPairDispatcher(
        tables_headline, persist_k, acc_g, tel_g,
        site="datapath.persistent",
    )
    got_pairs = []
    for p in gate_pairs:
        got_pairs.extend(pd_gate.submit(p))
    rem, acc_g, tel_g = pd_gate.flush()
    got_pairs.extend(rem)
    acc_r = jax.device_put(make_counter_buffers(tables.policy))
    tel_r = jax.device_put(make_telemetry_buffers())
    ref_pairs = []
    for p in gate_pairs:
        r_i, r_e, acc_r, tel_r = (
            datapath_step_accum_pair_telem_packed4_stacked(
                tables_chosen, jax.device_put(p), acc_r, tel_r
            )
        )
        ref_pairs.append((r_i, r_e))
    for (g_i, g_e), (r_i, r_e) in zip(got_pairs, ref_pairs):
        for got, ref in ((g_i, r_i), (g_e, r_e)):
            for col in (
                "allowed", "proxy_port", "match_kind", "sec_id",
                "ct_result", "pre_dropped", "final_daddr",
                "final_dport", "rev_nat", "lb_slave", "ct_create",
                "ct_delete", "l4_slot", "ipcache_miss",
            ):
                assert np.array_equal(
                    np.asarray(getattr(got, col)),
                    np.asarray(getattr(ref, col)),
                ), f"sub-word/persistent divergence in {col}"
    assert np.array_equal(np.asarray(pd_gate.acc), np.asarray(acc_r))
    assert np.array_equal(np.asarray(pd_gate.telem), np.asarray(tel_r))
    del pd_gate, acc_g, tel_g, acc_r, tel_r, got_pairs, ref_pairs

    # fresh carry so counter_hits/telemetry reflect exactly the
    # timed tuples (the gate warmed both jit classes)
    pdisp = PersistentPairDispatcher(
        tables_headline, persist_k,
        jax.device_put(make_counter_buffers(tables.policy)),
        jax.device_put(make_telemetry_buffers()),
        site="datapath.persistent",
    )
    hstate = {"last": None}
    bench_spans.span("async_dispatch").start()
    t0 = time.perf_counter()
    for i in range(n_batches_h):
        drained = pdisp.submit(host_pairs[i % len(host_pairs)])
        if drained:
            hstate["last"] = drained[-1]
    rem, acc, telem = pdisp.flush()
    if rem:
        hstate["last"] = rem[-1]
    jax.block_until_ready((acc, telem))
    dt = time.perf_counter() - t0
    bench_spans.span("async_dispatch").end()
    total = n_batches_h * chosen_bs
    vps = total / dt
    out_i, out_e = hstate["last"]

    # --- windowed batch latency + overlap efficiency -----------------------
    # Synchronous segment at the chosen class with PRE-STAGED device
    # args: per-batch device latency (p50/p99) and the device-busy
    # estimate behind overlap_efficiency_pct (device seconds that
    # the async wall clock must at least cover; 100% = staging fully
    # hidden behind device compute).
    dev_pair = jax.device_put(host_pairs[0])
    acc_s = jax.device_put(make_counter_buffers(tables.policy))
    telem_s = jax.device_put(make_telemetry_buffers())
    sync_lat = []
    for i in range(8):
        b0 = time.perf_counter()
        s_i, s_e, acc_s, telem_s = (
            datapath_step_accum_pair_telem_packed4_stacked(
                tables_headline, dev_pair, acc_s, telem_s,
            )
        )
        jax.block_until_ready((s_i, s_e))
        lat = time.perf_counter() - b0
        sync_lat.append(lat)
        metrics_registry.batch_duration.observe(lat)
    del acc_s, telem_s
    p50_batch_s = metrics_registry.batch_duration.window_quantile(0.5)
    p99_batch_s = metrics_registry.batch_duration.window_quantile(0.99)
    device_est_s = float(np.median(sync_lat)) * n_batches_h
    overlap_pct = min(100.0, 100.0 * device_est_s / max(dt, 1e-9))

    # gather-byte accounting: the bytes-moved model behind the
    # sub-word split (per-width per-leaf breakdown)
    profile = at.hot_gather_profile(tables_headline, packed_io=True)
    hot_bpt = at.hot_bytes_per_tuple(tables_headline, packed_io=True)
    cold_bpt = at.cold_bytes_per_tuple(tables_headline)

    # --- scatter fold: device accumulators → host registry -----------------
    bench_spans.span("scatter_fold").start()
    counter_total = int(np.asarray(acc).sum())
    telem_host = np.asarray(telem).astype(np.uint64)
    fold_telemetry(telem_host)
    bench_spans.span("scatter_fold").end()

    # --- event fold: sampled DropNotify/PolicyVerdictNotify from the
    # last pair's outputs onto a monitor bus --------------------------------
    bench_spans.span("event_fold").start()
    from types import SimpleNamespace

    from cilium_tpu.metrics import Registry
    from cilium_tpu.monitor import MonitorBus, verdicts_to_events

    bus = MonitorBus()
    # the timed traffic was already folded into the process registry
    # from the device accumulator; the sampled event fold counts into
    # a throwaway registry so nothing double-counts
    event_registry = Registry()
    sample_cap = 4096
    id_table_host = np.asarray(tables.policy.id_table)
    n_events = 0
    for dirv, out_last in ((0, out_i), (1, out_e)):
        sl = slice(0, 1 << 16)  # head slice: event fold is sampled
        sec_idx = np.asarray(out_last.sec_id[sl]).astype(np.int64)
        n_events += verdicts_to_events(
            bus,
            SimpleNamespace(
                allowed=np.asarray(out_last.allowed[sl]),
                match_kind=np.asarray(out_last.match_kind[sl]),
                proxy_port=np.asarray(out_last.proxy_port[sl]),
            ),
            ep_ids=np.zeros(sec_idx.shape, np.int64),
            identities=id_table_host[
                np.minimum(sec_idx, len(id_table_host) - 1)
            ],
            dports=np.asarray(out_last.final_dport[sl]),
            protos=np.full(sec_idx.shape, 6),
            directions=np.full(sec_idx.shape, dirv),
            sample=sample_cap,
            metrics_registry=event_registry,
        )
    bench_spans.span("event_fold").end()

    # secondary: the bare lattice on the same tables (round 1/2 metric)
    from cilium_tpu.engine.verdict import TupleBatch, evaluate_batch

    lrng = np.random.default_rng(1)
    lat_batch = jax.device_put(
        TupleBatch.from_numpy(
            ep_index=lrng.integers(0, args.endpoints, size=args.batch),
            identity=lrng.integers(
                256, 256 + args.identities, size=args.batch
            ).astype(np.uint32),
            dport=lrng.integers(1, 65535, size=args.batch),
            proto=lrng.choice([6, 17], size=args.batch),
            direction=lrng.integers(0, 2, size=args.batch),
        )
    )
    jax.block_until_ready(evaluate_batch(tables.policy, lat_batch))
    t0 = time.perf_counter()
    louts = [
        evaluate_batch(tables.policy, lat_batch) for _ in range(8)
    ]
    jax.block_until_ready(louts)
    lat_vps = 8 * args.batch / (time.perf_counter() - t0)
    emit(
        "lattice_verdicts_per_sec_per_chip",
        round(lat_vps),
        "verdicts/s",
        vs_baseline=round(lat_vps / BASELINE_PER_CHIP, 3),
    )

    # --- combined datapath + inline L7 (the full serving system) -----------
    run_config5_combined(args, d, tables, pool, oracle_ctx, states)

    # --- incremental update: one rule added to the 50k world ---------------
    # The reference's regeneration is revision-gated per endpoint
    # (pkg/endpoint/policy.go:540-552): adding one rule re-lowers only
    # the endpoints it selects.  Measured: policy_add → delta-scoped
    # regenerate → fresh published tables.
    ver_before = d.endpoint_manager.published()[0]
    t0 = time.perf_counter()
    add_one_rule(d, 4242, label_prefix="bench-incremental")
    d.regenerate_all("incremental-update bench")
    incr_ms = (time.perf_counter() - t0) * 1000
    assert d.endpoint_manager.published()[0] > ver_before
    emit(
        "incremental_update_ms",
        round(incr_ms, 1),
        "ms",
        note=(
            "one rule added to the full world -> delta-scoped "
            "regenerate -> new published tables"
        ),
    )

    # --- delta DEVICE publication: one rule -> in-place epoch scatter ------
    # The reference updates individual policymap entries in place
    # (pkg/maps/policymap) — here the compiler diffs the lowered rows
    # and the device store patches the standby epoch with
    # `.at[idx].set(rows)` instead of re-uploading every table.
    from cilium_tpu.compiler.delta import tables_nbytes

    em = d.endpoint_manager

    def _one_rule(port: int) -> None:
        add_one_rule(d, port, label_prefix="bench-delta")
        d.regenerate_all("delta-update bench")
        em.published_device()

    # prime both epochs + the scatter jit's payload shape classes so
    # the timed update measures the steady-state delta path
    em.published_device()
    for port in (4301, 4302, 4303):
        _one_rule(port)
    t0 = time.perf_counter()
    _one_rule(4304)
    delta_ms = (time.perf_counter() - t0) * 1000
    st = em.last_publish_stats
    assert st is not None and st.mode == "delta", (
        f"steady-state update did not take the delta path: {st}"
    )
    # bit-identity gate: every device-epoch leaf equals the host
    # compile it was scattered from
    _, host_tables, _, _ = em.published_with_states()
    _, dev_tables, _ = em.published_device()
    for leaf in (
        "id_table", "id_direct", "id_lo_len", "port_slot", "l4_meta",
        "l4_allow_bits", "l3_allow_bits", "l4_hash_rows",
        "l4_hash_stash", "l4_wild_rows", "l4_wild_stash",
    ):
        assert np.array_equal(
            np.asarray(getattr(dev_tables, leaf)),
            np.asarray(getattr(host_tables, leaf)),
        ), f"delta-built device epoch diverged from host ({leaf})"
    full_bytes = tables_nbytes(host_tables)
    emit(
        "delta_update_ms",
        round(delta_ms, 1),
        "ms",
        note=(
            "one rule added to the full world -> delta-scoped "
            "regenerate -> in-place device epoch scatter "
            "(bit-identical to the host compile)"
        ),
    )
    emit(
        "delta_update_bytes_h2d",
        int(st.bytes_h2d),
        "bytes",
        full_upload_bytes=int(full_bytes),
        reduction=round(full_bytes / max(int(st.bytes_h2d), 1), 1),
        scatter_leaves=st.scatter_leaves,
        note=(
            "bytes shipped host->device per delta publish vs "
            "re-uploading every table"
        ),
    )

    # achieved gather traffic of the headline loop (roofline context
    # for regressions): the per-leaf bytes-moved model of the
    # hot/cold split (engine.autotune.hot_gather_profile) — hot-plane
    # bytes are what the fused kernel actually gathers per tuple
    emit(
        "hot_bytes_per_tuple",
        round(hot_bpt, 1),
        "bytes",
        cold_bytes_per_tuple=round(cold_bpt, 1),
        per_leaf=[
            {
                "stage": r["stage"], "leaf": r["leaf"],
                "plane": r["plane"],
                "bytes_per_tuple": round(r["bytes_per_tuple"], 1),
            }
            for r in profile
        ],
        note=(
            "bytes gathered per tuple by the fused per-direction "
            "pipeline; cold-plane leaves are never gathered (and "
            "never shipped by a hot-only publication)"
        ),
    )

    # sharded-table scale headroom: the partition-rule model
    # (compiler/partition.py) over the REAL config-5 tables — what
    # partitioning the identity-major leaves across a mesh buys.
    # tools/shardprof.py measures the same numbers on a live mesh;
    # cilium_device_table_bytes_per_chip reports them at publish.
    from cilium_tpu.compiler import partition as pt_rules

    n_chips = max(len(jax.devices()), 1)
    _, per_chip_b, repl_b = pt_rules.shard_bytes_model(
        tables.policy, n_chips
    )
    emit(
        "table_bytes_per_chip",
        int(per_chip_b),
        "bytes",
        num_shards=n_chips,
        replicated_bytes_per_chip=int(tables_nbytes(tables.policy)),
        replicated_leaf_overhead=int(repl_b),
        note=(
            "per-chip HBM under the identity-sharded partition "
            "rules; the replicated layout pays "
            "replicated_bytes_per_chip on EVERY chip"
        ),
    )
    emit(
        "universe_max_identities",
        int(
            pt_rules.universe_max_identities(tables.policy, n_chips)
        ),
        "identities",
        num_shards=n_chips,
        curve={
            str(ns): int(
                pt_rules.universe_max_identities(tables.policy, ns)
            )
            for ns in (1, 8, 64)
        },
        note=(
            "identity-universe cap at 16 GB HBM/chip under the "
            "partition rules — the scale headroom table sharding "
            "buys (num_shards=1 is the replicated cap)"
        ),
    )
    emit(
        "alltoall_bytes_per_tuple",
        pt_rules.alltoall_bytes_per_tuple(n_chips),
        "bytes",
        num_shards=n_chips,
        note=(
            "collective bytes per tuple the routed-gather evaluator "
            "moves along the identity axis (one psum pair: exact-"
            "probe verdict column + L3 word bit)"
        ),
    )
    # the WHOLE-datapath extension: CT/ipcache/LB planes sharded
    # under the family rules + the N+1 replica placement
    # (engine/datapath_mesh.py) — per-chip HBM and universe headroom
    # now honest for the FULL fused pipeline, not just the lattice
    try:
        _dp_rows, dp_per_chip, dp_repl, dp_ovh = (
            pt_rules.datapath_bytes_model(tables, n_chips)
        )
        dp_full = sum(
            int(
                getattr(leaf, "nbytes", None)
                or np.asarray(leaf).nbytes
            )
            for leaf in jax.tree.leaves(tables)
        )
        emit(
            "datapath_table_bytes_per_chip",
            int(dp_per_chip),
            "bytes",
            num_shards=n_chips,
            replicated_bytes_per_chip=int(dp_full),
            replicated_leaf_overhead=int(dp_repl),
            replica_overhead_per_chip=int(dp_ovh),
            note=(
                "per-chip HBM of the WHOLE fused datapath "
                "(policy + CT/ipcache/LB planes) under the family "
                "partition rules with N+1 replicas"
            ),
        )
        emit(
            "datapath_universe_max_identities",
            int(
                pt_rules.datapath_universe_max_identities(
                    tables, n_chips
                )
            ),
            "identities",
            num_shards=n_chips,
            curve={
                str(ns): int(
                    pt_rules.datapath_universe_max_identities(
                        tables, ns
                    )
                )
                for ns in (1, 8, 64)
            },
            note=(
                "identity-universe cap at 16 GB HBM/chip for the "
                "WHOLE datapath footprint (ipcache buckets scale "
                "with the universe; CT/LB planes divide as "
                "constants)"
            ),
        )
        n_range_classes = len(
            getattr(tables.ipcache, "range_class_plens", ()) or ()
        )
        emit(
            "datapath_alltoall_bytes_per_tuple",
            pt_rules.datapath_alltoall_bytes_per_tuple(
                n_chips, range_classes=n_range_classes
            ),
            "bytes",
            num_shards=n_chips,
            note=(
                "collective bytes per tuple of the fused routed "
                "pipeline (CT svc+flow probes, LB resolution, "
                "ipcache exact + range classes, lattice psums)"
            ),
        )
    except Exception as dp_exc:  # pragma: no cover — model only
        print(f"# datapath bytes model skipped: {dp_exc}",
              file=sys.stderr)
    emit(
        "verdicts_per_sec_per_chip",
        round(vps),
        "verdicts/s",
        vs_baseline=round(vps / BASELINE_PER_CHIP, 3),
        tuples=total,
        batch=chosen_bs,
        hash_lanes=chosen_lanes,
        p50_batch_ms=round(p50_batch_s * 1000, 1),
        p99_batch_ms=round(p99_batch_s * 1000, 1),
        counter_hits=counter_total,
        telemetry_overhead_pct=round(overhead_pct, 2),
        tracing_overhead_pct=round(trace_overhead_pct, 4),
        telemetry=telemetry_summary(telem_host),
        telemetry_spans_s={
            name: round(s.total(), 3)
            for name, s in bench_spans.items()
        },
        monitor_events_sampled=n_events,
        hot_bytes_per_tuple=round(hot_bpt, 1),
        gathered_gb_per_sec=round(vps * hot_bpt / 1e9, 1),
        overlap_efficiency_pct=round(overlap_pct, 1),
        pair_mode="persistent",
        persist_pairs=persist_k,
        persistent_launches=pdisp.launches,
        subword=subword_report,
        pipeline=(
            "sub-word hot planes (compact 2-word L4 entries, 4-word "
            "CT lanes, packed ipcache idx/l3/prefix-class words) "
            "through the PERSISTENT fused-pair program: one "
            "donated-carry lax.scan launch per --persist-pairs pair "
            "batches (zero per-pair dispatch, no per-direction "
            "launches), carry committed once at drain; packed4 "
            "staged columns, merged counter scatter, fused [2, T] "
            "telemetry"
        ),
    )

    # --- verdict memoization: intra-batch dedup + device verdict cache -----
    # (engine/memo.py).  The headline verdicts_per_sec_per_chip above
    # stays the skew-INDEPENDENT baseline (uniform pool replay through
    # the uncached program); this section measures what the two-level
    # memo plane buys on Zipf/trace-skewed traffic — bit-identity
    # gated first on the FULL verdict/counter/telemetry surface, on
    # uniform AND Zipf flows, across an interleaved churn publish.
    from cilium_tpu.compiler.tables import tables_layout_version
    from cilium_tpu.engine import memo as vm

    half_m = chosen_bs // 2
    memo_verdict_cols = (
        "allowed", "proxy_port", "match_kind", "sec_id", "ct_result",
        "pre_dropped", "final_daddr", "final_dport", "rev_nat",
        "lb_slave", "ct_create", "ct_delete", "l4_slot",
        "ipcache_miss",
    )

    def _host_pairs_zipf(prng, half_c, k, s):
        """Zipf-skewed sibling of pack_pool_pairs: per-direction
        pool rows drawn rank-Zipf(s) instead of uniform."""
        pairs = []
        for _ in range(k):
            pair = np.empty((2, 4, half_c), np.uint32)
            for row, subset in enumerate((idx_ingress, idx_egress)):
                picks = subset[
                    zipf_picks(prng, len(subset), half_c, s)
                ]
                pair[row] = pack_flow_records4(
                    ep_index=pool["ep_index"][picks],
                    saddr=pool["saddr"][picks],
                    daddr=pool["daddr"][picks],
                    sport=pool["sport"][picks],
                    dport=pool["dport"][picks],
                    proto=pool["proto"][picks],
                    direction=pool["direction"][picks],
                    is_fragment=pool["is_fragment"][picks],
                )
            pairs.append(pair)
        return pairs

    def _memo_stamp(t):
        return (
            int(np.asarray(t.policy.generation)) & 0xFFFFFFFF,
            tables_layout_version(t.policy),
        )

    memo_cache = vm.VerdictCache(n_rows=1 << 14)
    memo_cache.ensure(_memo_stamp(tables_chosen))
    # the GATE kernel runs at full compaction capacity (rep_cap ==
    # half-batch): overflow is impossible, so bit-identity there is
    # unconditional — the tuned-down capacity class is gated
    # separately below on the Zipf pair it will actually serve
    gate_kern = vm.memo_pair_packed4_kernel(rep_cap=half_m)

    def _memo_gate(t_full, pair_host):
        """One pair through the memoized kernel AND the uncached
        reference: every verdict column + counters + telemetry must
        be bit-identical.  Folds the batch's stats into memo_cache
        and returns the host stats row."""
        k = gate_kern
        pair_dev = jax.device_put(pair_host)
        acc_m = jax.device_put(make_counter_buffers(tables.policy))
        tel_m = jax.device_put(make_telemetry_buffers())
        g_i, g_e, acc_m, tel_m, rows, hit_i, hit_e, st = k(
            t_full, pair_dev, memo_cache.rows, acc_m, tel_m
        )
        row = memo_cache.account(st)
        assert row["overflow"] == 0, (
            f"memo gate overflowed: {row} (rep_cap {half_m})"
        )
        memo_cache.rows = rows
        acc_u = jax.device_put(make_counter_buffers(tables.policy))
        tel_u = jax.device_put(make_telemetry_buffers())
        r_i, r_e, acc_u, tel_u = (
            datapath_step_accum_pair_telem_packed4_stacked(
                t_full, pair_dev, acc_u, tel_u
            )
        )
        for got, ref in ((g_i, r_i), (g_e, r_e)):
            for col in memo_verdict_cols:
                assert np.array_equal(
                    np.asarray(getattr(got, col)),
                    np.asarray(getattr(ref, col)),
                ), f"memoized pipeline diverges in {col}"
        assert np.array_equal(np.asarray(acc_m), np.asarray(acc_u)), (
            "memoized pipeline counter divergence"
        )
        assert np.array_equal(np.asarray(tel_m), np.asarray(tel_u)), (
            "memoized pipeline telemetry divergence"
        )
        # per-tuple hit flags must be consistent with the stats row
        nh = int(np.asarray(hit_i).sum()) + int(np.asarray(hit_e).sum())
        assert nh == row["hits"], (nh, row)
        return row

    # uniform flows: cold pass then warm pass (repeats must hit)
    row0 = _memo_gate(tables_chosen, host_pairs[0])
    assert row0["hits"] == 0, "cold cache served a hit"
    row1 = _memo_gate(tables_chosen, host_pairs[0])
    assert row1["hits"] > 0, "warm cache served no hits"

    # Zipf flows at the bench skew — the base seed mixes in
    # --seed so a failing Zipf run reproduces from its logged seed
    # alone (the fuzz satellite's seed-determinism contract)
    zrng = np.random.default_rng(53 + args.seed)
    zpairs = _host_pairs_zipf(
        zrng, half_m, min(max(args.tuples // chosen_bs, 1), 4),
        args.zipf_s,
    )
    _memo_gate(tables_chosen, zpairs[0])
    zrow = _memo_gate(tables_chosen, zpairs[0])
    assert zrow["hits"] > 0

    # interleaved churn publish: a delta publish through the real
    # control plane changes the epoch stamp; the cache MUST flush and
    # the first post-publish batch must serve zero (stale) hits while
    # staying bit-identical to the uncached program on the NEW tables
    flushes_before = memo_cache.flushes
    add_one_rule(d, 4311, label_prefix="bench-memo")
    d.regenerate_all("verdict-memo bench churn")
    em.published_device()
    _, host_pol, _, _ = em.published_with_states()
    tables_pub = jax.device_put(
        DatapathTables(
            prefilter=tables.prefilter,
            ipcache=tables.ipcache,
            ct=tables.ct,
            lb=tables.lb,
            policy=split_hot(
                repack_hash_lanes(host_pol, chosen_lanes)
            ),
        )
    )
    assert _memo_stamp(tables_pub) != _memo_stamp(tables_chosen), (
        "delta publish did not change the epoch stamp"
    )
    assert memo_cache.ensure(_memo_stamp(tables_pub)), (
        "stamp change did not flush the verdict cache"
    )
    assert memo_cache.flushes == flushes_before + 1
    prow = _memo_gate(tables_pub, zpairs[0])
    assert prow["hits"] == 0, (
        "post-publish batch served hits from a flushed cache"
    )
    prow2 = _memo_gate(tables_pub, zpairs[0])
    assert prow2["hits"] > 0, "hit rate did not recover post-publish"

    # back to the bench world for the timed section (flushes again)
    memo_cache.ensure(_memo_stamp(tables_chosen))

    # --- tuner: cache capacity + enable threshold join the autotuned
    # shape class — None (uncached) is a candidate, so a workload
    # whose sort+probe overhead beats the gathers saved keeps the
    # uncached program -----------------------------------------------------
    def _run_memo_candidate(params):
        if not params.get("memo"):
            state = {
                "acc": jax.device_put(
                    make_counter_buffers(tables.policy)
                ),
                "telem": jax.device_put(make_telemetry_buffers()),
                "i": 0,
            }

            def step(pair):
                o_i, o_e, state["acc"], state["telem"] = (
                    datapath_step_accum_pair_telem_packed4_stacked(
                        tables_chosen, jnp_dev(pair),
                        state["acc"], state["telem"],
                    )
                )
                return o_i.allowed, o_e.allowed
        else:
            kern_c = vm.memo_pair_packed4_kernel(
                rep_cap=params["rep_cap"]
            )
            state = {
                "acc": jax.device_put(
                    make_counter_buffers(tables.policy)
                ),
                "telem": jax.device_put(make_telemetry_buffers()),
                "cache": jax.device_put(
                    vm.make_cache_rows(params["rows"])
                ),
                "i": 0,
            }

            def step(pair):
                (
                    o_i, o_e, state["acc"], state["telem"],
                    state["cache"], _, _, _,
                ) = kern_c(
                    tables_chosen, jnp_dev(pair),
                    state["cache"], state["acc"], state["telem"],
                )
                return o_i.allowed, o_e.allowed

        def make_args():
            state["i"] += 1
            return (zpairs[state["i"] % len(zpairs)],)

        return at.measure_dispatch(
            step, make_args, chosen_bs, reps=3,
            outstanding=2, sync_reps=2,
        )

    memo_rep_cap = max(half_m >> 2, 1 << 10)

    # ROADMAP lever (d): cache capacity bounded by the measured
    # per-chip HBM headroom (resident table bytes subtracted from
    # the HBM budget) instead of a fixed list; rows_cap keeps the
    # single candidate proportionate to the batch's key universe so
    # smoke-scale runs don't allocate a 1M-row buffer for nothing
    from cilium_tpu.engine.publish import next_pow2 as _np2

    class _ResidentBytes:
        def chip_bytes(self):
            import jax as _jax

            return {
                0: sum(
                    int(np.asarray(leaf).nbytes)
                    for leaf in _jax.tree.leaves(tables_chosen)
                )
            }

    memo_cands = at.memo_candidates(
        half_m,
        store=_ResidentBytes(),
        rows_cap=max(1 << 14, _np2(4 * half_m)),
    )
    memo_choice = at.autotune(
        memo_cands,
        _run_memo_candidate,
        p99_bound_ms=args.autotune_p99_ms,
        cache_key=("memo", round(float(args.zipf_s), 3), args.seed)
        + at.shape_class_key(tables_chosen.policy),
        log=lambda msg: print(f"# {msg}", file=sys.stderr),
    )
    uncached_zipf = next(
        (
            t.verdicts_per_sec
            for t in memo_choice.trials
            if not t.params.get("memo")
        ),
        0.0,
    )

    # --- timed memoized loop on Zipf traffic (the effective line):
    # the headline's double-buffered async staging loop with the
    # tuned memo class in front of the lattice ------------------------------
    timed_kern = vm.memo_pair_packed4_kernel(rep_cap=memo_rep_cap)
    mstate = {
        "acc": jax.device_put(make_counter_buffers(tables.policy)),
        "telem": jax.device_put(make_telemetry_buffers()),
        "cache": jax.device_put(vm.make_cache_rows(1 << 14)),
        "last": None,
    }
    memo_stats_rows = []

    def _m_dispatch(pair_dev):
        (
            o_i, o_e, mstate["acc"], mstate["telem"],
            mstate["cache"], h_i, h_e, st,
        ) = timed_kern(
            tables_chosen, pair_dev,
            mstate["cache"], mstate["acc"], mstate["telem"],
        )
        memo_stats_rows.append(st)
        mstate["last"] = (o_i, o_e)
        return (o_i, o_e)

    mdisp = AsyncBatchDispatcher(
        pack_fn=lambda pair: (jax.device_put(pair),),
        dispatch_fn=_m_dispatch,
        depth=max(args.async_depth, 0),
    )
    n_batches_m = max(args.tuples // chosen_bs, 1)
    # warmup (compile the timed class + first-touch the cache), then
    # fresh stats so the measured hit rate is the steady state
    _m_dispatch(jax.device_put(zpairs[0]))
    jax.block_until_ready(mstate["last"])
    memo_stats_rows.clear()
    t0 = time.perf_counter()
    for i in range(n_batches_m):
        for _, _, exc in mdisp.submit((zpairs[i % len(zpairs)],)):
            if exc is not None:
                raise exc
    for _, _, exc in mdisp.flush():
        if exc is not None:
            raise exc
    jax.block_until_ready((mstate["acc"], mstate["telem"]))
    dt_m = time.perf_counter() - t0
    eff_vps = n_batches_m * chosen_bs / dt_m
    folded = np.zeros(vm.STATS, np.int64)
    for st in memo_stats_rows:
        folded += np.asarray(st).astype(np.int64)
    overflow_batches = sum(
        1
        for st in memo_stats_rows
        if int(np.asarray(st)[vm.STAT_OVERFLOW])
    )
    hit_rate = float(folded[vm.STAT_HIT]) / max(
        int(folded[vm.STAT_TUPLES]), 1
    )
    dedup = float(folded[vm.STAT_TUPLES]) / max(
        int(folded[vm.STAT_UNIQUE]), 1
    )
    emit(
        "verdict_cache_hit_rate",
        round(hit_rate, 4),
        "fraction",
        zipf_s=args.zipf_s,
        seed=args.seed,
        insertions=int(folded[vm.STAT_INSERT]),
        overflow_batches=overflow_batches,
        cache_rows=1 << 14,
        cache_bytes=int((1 << 14) + 1) * (vm.CACHE_WORDS * 8 + 1) * 4,
        flushes=memo_cache.flushes,
        note=(
            "tuples served from the device verdict cache on the "
            "timed Zipf loop (distinct policy keys evaluated once "
            "per epoch; any publish flushes)"
        ),
    )
    emit(
        "dedup_factor",
        round(dedup, 2),
        "x",
        zipf_s=args.zipf_s,
        unique_keys_per_batch=int(
            folded[vm.STAT_UNIQUE] / max(len(memo_stats_rows), 1)
        ),
        effective_hot_bytes_per_tuple=round(
            at.effective_hot_bytes_per_tuple(tables_chosen, dedup), 1
        ),
        hot_bytes_per_tuple=round(hot_bpt, 1),
        note=(
            "batch tuples per distinct policy key (intra-batch "
            "dedup): the lattice gather chain runs once per key, so "
            "effective gathered bytes/tuple = hot_bytes_per_tuple / "
            "dedup_factor"
        ),
    )
    emit(
        "effective_verdicts_per_sec_per_chip",
        round(eff_vps),
        "verdicts/s",
        vs_baseline=round(eff_vps / BASELINE_PER_CHIP, 3),
        zipf_s=args.zipf_s,
        verdict_cache_hit_rate=round(hit_rate, 4),
        dedup_factor=round(dedup, 2),
        rep_cap=memo_rep_cap,
        uncached_zipf_verdicts_per_sec=round(uncached_zipf),
        memo_enabled=bool(memo_choice.params.get("memo")),
        tuner_trials=[
            {
                "params": t.params,
                "verdicts_per_sec": round(t.verdicts_per_sec),
                "p99_batch_ms": round(t.p99_batch_ms, 1),
            }
            for t in memo_choice.trials
        ],
        note=(
            "double-buffered async staging loop with the two-level "
            "verdict memo plane (intra-batch dedup + epoch-stamped "
            "device cache) on Zipf-skewed flows; "
            "verdicts_per_sec_per_chip above stays the "
            "skew-independent uncached baseline"
        ),
    )


# ---------------------------------------------------------------------------
# per-chip failover bench: degraded throughput + re-admission cost
# ---------------------------------------------------------------------------


def run_failover_bench(args) -> None:
    """The per-chip failure domain's two bench lines:

      * degraded_verdicts_per_sec_per_chip — sustained throughput per
        SURVIVING chip with one chip's breaker open (its batch shard
        re-split across survivors, its table rows served from the
        N+1 replicas); the companion fields carry the healthy
        baseline so the trajectory shows the retention ratio, which
        should sit near (N-1)/N of healthy per-chip throughput;
      * readmit_rebalance_ms — wall time of the half-open
        re-admission rebalance (replaying the rows the chip missed
        through the delta-scatter path), with its bytes_h2d against
        the full-upload comparator.

    Runs on whatever mesh the process sees at bench startup (the
    driver's multi-chip box).  A single-device environment has no
    chip to lose and emits a skip marker — on a plain CPU box that
    is the expected outcome: jax is already initialized by the
    config-5 headline before this runs, so the chaos tools'
    xla_force_host_platform_device_count virtual mesh cannot take
    effect here (use tools/chaos_storm.py --mesh, a fresh process,
    for the virtual-mesh exercise)."""
    import jax

    from cilium_tpu import faultinject
    from cilium_tpu.compiler.delta import tables_nbytes
    from cilium_tpu.engine.failover import ChipFailoverRouter
    from cilium_tpu.engine.oracle import evaluate_batch_oracle
    from cilium_tpu.maps.policymap import (
        INGRESS,
        PolicyKey,
        PolicyMapStateEntry,
    )
    from cilium_tpu.resilience import ChipBreakerBank
    from tools.chaos_storm import _mesh_tuples, _mesh_world

    devs = jax.devices()
    n = len(devs)
    if n < 2 or n % 2:
        emit(
            "degraded_verdicts_per_sec_per_chip", 0, "verdicts/s",
            skipped=f"{n} device(s): no chip to lose",
        )
        return
    tp = 2
    dp = n // tp
    mesh = jax.sharding.Mesh(
        np.array(devs).reshape(dp, tp), ("batch", "table")
    )
    rng = np.random.default_rng(3)
    states, ids, fc, compile_eps = _mesh_world(
        seed=3, n_eps=8, identity_pad=1024
    )
    tables = compile_eps()
    bank = ChipBreakerBank(
        recovery_timeout=0.05, failure_threshold=1
    )
    router = ChipFailoverRouter(mesh, tables, bank=bank)
    router.publish(tables)
    router.publish(compile_eps())
    b = 1 << 14
    tuples = _mesh_tuples(rng, b, len(states), ids)
    reps = 6

    def loop():
        t0 = time.perf_counter()
        for _ in range(reps):
            res = router.dispatch(**tuples)
        return reps * b / (time.perf_counter() - t0), res

    router.dispatch(**tuples)  # warmup (jit)
    healthy_vps, res = loop()
    # bit-identity gate before timing means anything
    want = evaluate_batch_oracle(
        [dict(s) for s in states], **tuples
    )
    assert np.array_equal(res.verdicts.allowed, want[0])

    victim = int(router.ordinals[dp - 1, tp - 1])
    faultinject.arm("engine.dispatch", f"raise:chip={victim}")
    try:
        router.dispatch(**tuples)  # trips the breaker + retrace
        degraded_vps, res_deg = loop()
    finally:
        faultinject.disarm("engine.dispatch")
    assert np.array_equal(res_deg.verdicts.allowed, want[0])
    survivors = n - 1
    emit(
        "degraded_verdicts_per_sec_per_chip",
        round(degraded_vps / survivors),
        "verdicts/s",
        chips=n,
        survivors=survivors,
        healthy_verdicts_per_sec_per_chip=round(healthy_vps / n),
        retention_pct=round(
            100.0 * (degraded_vps / survivors)
            / max(healthy_vps / n, 1e-9),
            1,
        ),
        replica_hits=res_deg.replica_hits,
        note=(
            "per-surviving-chip throughput with one chip's breaker "
            "open: batch shard re-split across survivors, table "
            "rows served from N+1 replicas, verdicts bit-identical "
            "to the healthy mesh"
        ),
    )

    # churn one delta while the chip is out, then time re-admission
    base = router.store.spare_stamp()
    states[0][
        PolicyKey(int(ids[0]), 7321, 6, INGRESS)
    ] = PolicyMapStateEntry()
    fresh = compile_eps()
    delta = fc.delta_for(base, fresh)
    router.publish(fresh, delta)
    time.sleep(bank.recovery_timeout * 2)
    res_back = router.dispatch(**tuples)
    assert victim in res_back.rebalanced_chips, (
        "re-admission did not rebalance the victim chip"
    )
    full = tables_nbytes(fresh)
    emit(
        "readmit_rebalance_ms",
        round(res_back.rebalance_ms, 2),
        "ms",
        rebalance_bytes_h2d=res_back.rebalance_bytes,
        full_upload_bytes=int(full),
        missed_deltas=1,
        note=(
            "half-open re-admission: the rows the chip missed "
            "while out replay through the delta-scatter path "
            "(bytes strictly below a full upload)"
        ),
    )


def run_serving_bench(args) -> None:
    """The continuous serving plane's sustained-QPS lines
    (cilium_tpu/serve.py): open-loop arrivals through the shared
    ingest queue — SLO-aware dynamic batching + DRR fair dispatch —
    against the ONE-SHOT async path on the SAME daemon/tables as
    the comparator.

      * sustained_verdicts_per_sec — flows served per wall second
        at saturation (offered load ~2x the one-shot rate, uniform
        arrivals; excess sheds at the backlog bound, which IS
        saturation).  Acceptance wants >= 0.9x the one-shot async
        rate on the same tables — the ratio rides the line.
      * serving_p99_ms — p99 submission-to-reply latency under
        that load.

    Both gates ride first: the streamed verdict stream must be
    np.array_equal to the one-shot path on identical tuples, and —
    when the process sees >= 2 devices — identical again with a
    chip killed mid-stream and the daemon's dispatch loop routed
    through the ChipFailoverRouter.

    Container honesty: this box's CPU "device" shares 2 cores with
    the Python ingest threads, so the ABSOLUTE rates (and the
    sustained/one-shot ratio) are only meaningful on the driver's
    bench box; the bit-identity gates hold anywhere."""
    import jax

    from cilium_tpu import faultinject
    from cilium_tpu.engine.failover import ChipFailoverRouter
    from cilium_tpu.engine.hostpath import lattice_fold_host
    from cilium_tpu.native import encode_flow_records
    from cilium_tpu.resilience import ChipBreakerBank
    from cilium_tpu.serve import (
        build_demo_daemon,
        demo_record_maker,
        run_serve_bench,
    )

    batch = args.serve_batch
    seconds = args.serve_seconds
    d, client = build_demo_daemon()
    make = demo_record_maker(client.security_identity.id)
    rng = np.random.default_rng(11)

    # ---- one-shot async baseline (same tables) ----------------------
    n_flows = batch * 8
    buf = encode_flow_records(**make(rng, n_flows))
    d.process_flows(buf, batch_size=batch)  # warm/compile
    stats = d.process_flows(buf, batch_size=batch, async_depth=2)
    oneshot_vps = stats.total / max(stats.seconds, 1e-9)
    emit(
        "oneshot_async_verdicts_per_sec",
        round(oneshot_vps),
        "verdicts/s",
        batch=batch,
        note="the serving plane's same-tables comparator",
    )

    # ---- shadow-eval overhead (dual-epoch verdict-diff canarying) ---
    # arm a restricting candidate at sample rate 0.1 and re-measure
    # the SAME one-shot loop: the marginal cost is the sampled
    # batches' second lattice gather (the staged batch, H2D and all
    # folds are shared).  The < 5% gate is judged on real hardware
    # (this container's 2-CPU noise swamps a 10%-of-batches second
    # gather); the DETERMINISTIC byte-model gate lives in
    # tools/gatherprof.py (shadow second-gather priced against the
    # hot total).
    import json as _json

    shadow_candidate = [{
        "endpointSelector": {"matchLabels": {"app": "server"}},
        "ingress": [{
            "fromEndpoints": [{"matchLabels": {"app": "client"}}],
            "toPorts": [{
                "ports": [{"port": "443", "protocol": "TCP"}]
            }],
        }],
        "labels": ["serve-bench-rule"],
    }]

    def _oneshot_wall():
        s = d.process_flows(buf, batch_size=batch, async_depth=2)
        return s.seconds

    base_wall = min(_oneshot_wall() for _ in range(3))
    bench_seed = getattr(args, "seed", None)
    d.shadow.arm(
        rules_json=_json.dumps(shadow_candidate),
        sample_rate=0.1,
        seed=11 if bench_seed is None else int(bench_seed),
    )
    _oneshot_wall()  # compile the shadow program outside the timing
    shadow_wall = min(_oneshot_wall() for _ in range(3))
    sw = d.shadow.diff(last=0)["window"]
    d.shadow.disarm()
    shadow_overhead_pct = (
        100.0 * (shadow_wall - base_wall) / max(base_wall, 1e-9)
    )
    emit(
        "shadow_eval_overhead_pct",
        round(shadow_overhead_pct, 2),
        "%",
        sample_rate=0.1,
        sampled_flows=sw["sampled"],
        sampled_batches=sw["sampled_batches"],
        changed=sw["changed"],
        allow_to_deny=sw["allow_to_deny"],
        deny_to_allow=sw["deny_to_allow"],
        gate=(
            "< 5% at sample rate 0.1, judged on real hardware; "
            "the deterministic second-gather byte model is "
            "hard-gated in tools/gatherprof.py"
        ),
    )

    # ---- bit-identity gate: streamed == one-shot --------------------
    gate_rec = make(np.random.default_rng(12), batch * 2)
    gate_buf = encode_flow_records(**gate_rec)
    ref = d.process_flows(
        gate_buf, batch_size=batch, collect_verdicts=True
    )
    plane = d.serving_plane(batch_size=batch, slo_ms=50.0)
    step = max(1, (batch * 2) // 16)
    subs = [
        plane.submit(
            rec={
                k: v[i : i + step] for k, v in gate_rec.items()
            },
            tenant="bench",
        )
        for i in range(0, batch * 2, step)
    ]
    for r in subs:
        r.wait(timeout=300)
    for field in ("allowed", "match_kind", "proxy_port"):
        got = np.concatenate([getattr(r, field) for r in subs])
        assert np.array_equal(got, ref.verdicts[field]), (
            f"streamed verdict stream diverged from one-shot "
            f"in {field}"
        )

    # ---- mesh-router chip-fault leg ---------------------------------
    devs = jax.devices()
    if len(devs) >= 2 and len(devs) % 2 == 0:
        tp = 2
        dp = len(devs) // tp
        mesh = jax.sharding.Mesh(
            np.array(devs).reshape(dp, tp), ("batch", "table")
        )
        version, htables, _, host_states = (
            d.endpoint_manager.published_with_states()
        )

        def fold(ep, ident, dport, proto, dirn, frag):
            return lattice_fold_host(
                host_states, ep, ident, dport, proto, dirn,
                is_fragment=frag,
            )

        router = ChipFailoverRouter(
            mesh, htables,
            bank=ChipBreakerBank(
                recovery_timeout=0.05, failure_threshold=1
            ),
            host_fold=fold,
        )
        router.publish(htables)
        router.publish(htables)
        d.attach_mesh_router(router)
        victim = int(router.ordinals[dp - 1, tp - 1])
        faultinject.arm("engine.dispatch", f"raise:chip={victim}")
        try:
            subs = [
                plane.submit(
                    rec={
                        k: v[i : i + step]
                        for k, v in gate_rec.items()
                    },
                    tenant="bench",
                )
                for i in range(0, batch * 2, step)
            ]
            for r in subs:
                r.wait(timeout=300)
        finally:
            faultinject.disarm("engine.dispatch")
        for field in ("allowed", "match_kind", "proxy_port"):
            got = np.concatenate(
                [getattr(r, field) for r in subs]
            )
            assert np.array_equal(got, ref.verdicts[field]), (
                f"mesh-fault streamed stream diverged in {field}"
            )
        emit(
            "serve_mesh_fault_gate", 1, "bool",
            victim_chip=victim,
            replica_hits=router.stats.replica_hits,
            rerouted_batches=router.stats.rerouted_batches,
        )
        d.mesh_router = None
        d.mesh_route_dispatch = False
    else:
        emit(
            "serve_mesh_fault_gate", 0, "bool",
            skipped=f"{len(devs)} device(s): no chip to lose",
        )

    # ---- sustained open-loop serving --------------------------------
    flows_per_submit = max(64, batch // 4)
    qps = max(8.0, 2.0 * oneshot_vps / flows_per_submit)
    perf_overhead0 = d.perf.overhead_s
    out = run_serve_bench(
        d,
        seconds=seconds,
        qps=qps,
        flows_per_submit=flows_per_submit,
        tenants={"bench": 1.0},
        batch_size=batch,
        slo_ms=50.0,
        make_records=make,
        seed=13,
        poisson=False,  # uniform arrivals (the acceptance shape)
    )
    if d.serving is not None:
        d.serving.stop()
        d.serving = None
    ratio = out["sustained_verdicts_per_sec"] / max(
        oneshot_vps, 1e-9
    )
    emit(
        "sustained_verdicts_per_sec",
        round(out["sustained_verdicts_per_sec"]),
        "verdicts/s",
        vs_oneshot_async=round(ratio, 3),
        offered_qps=round(qps, 1),
        flows_per_submit=flows_per_submit,
        avg_batch_fill_pct=round(out["avg_batch_fill_pct"], 1),
        shed_flows=out["shed_flows"],
        batches=out["batches"],
        note=(
            "open-loop uniform arrivals at ~2x the one-shot rate "
            "(saturation); acceptance ratio >= 0.9 judged on real "
            "hardware — the 2-CPU container's ingest threads "
            "starve the XLA device"
        ),
    )
    emit(
        "serving_p99_ms",
        round(out["serving_p99_ms"], 2),
        "ms",
        serving_p50_ms=round(out["serving_p50_ms"], 2),
        early_dispatches=out["early_dispatches"],
        degraded_batches=out["degraded_batches"],
    )
    # --- perf-plane overhead: the always-on live performance plane's
    # OWN accounted bookkeeping seconds (PerfPlane.overhead_s:
    # per-batch window appends + gauge exports measured inside
    # observe_batch) over the serve segment's wall without it — the
    # tracing_overhead_pct discipline, at FULL sampling (the perf
    # plane has no sample rate: every batch is observed) -------------
    perf_overhead_s = d.perf.overhead_s - perf_overhead0
    perf_overhead_pct = (
        perf_overhead_s
        / max(out["wall_s"] - perf_overhead_s, 1e-9)
    ) * 100.0
    assert perf_overhead_pct < 2.0, (
        f"perf-plane overhead {perf_overhead_pct:.3f}% breaches "
        f"the 2% gate at full sampling"
    )
    emit(
        "perfplane_overhead_pct",
        round(perf_overhead_pct, 4),
        "%",
        perfplane_seconds=round(perf_overhead_s, 6),
        serve_wall_seconds=round(out["wall_s"], 3),
        batches_observed=out["batches"],
        note=(
            "live performance plane bookkeeping (phase windows + "
            "SLO ledger + gauge exports) measured inside the "
            "serving loop; gate < 2% at full sampling (every "
            "batch observed — there is no sample rate)"
        ),
    )


# ---------------------------------------------------------------------------
# config 5 combined: fused datapath + inline L7 (the datapath+proxy
# system, envoy/cilium_l7policy.cc:193 / pkg/proxy/kafka.go:116)
# ---------------------------------------------------------------------------

# redirected-flow compaction cap per batch: the L7 matchers run on a
# fixed-size compacted slice (proxy-bound flows are a few percent of
# traffic); overflow is counted in the header and asserted zero
_L7_CAP = 1 << 17


def build_l7_payloads(args, rng, pool, fleet):
    """Per-pool-flow L7 request payloads: HTTP fields for flows aimed
    at HTTP ports, Kafka fields for Kafka ports (the first request of
    each replayed connection).  Returns device-resident padded
    tensors aligned with the pool row index."""
    from cilium_tpu.l7.http import pad_requests, trim_packed
    from cilium_tpu.l7.kafka import KafkaRequest, pad_kafka_requests

    n = len(pool["saddr"])
    dport = pool["dport"]
    reqs = []
    for i in range(n):
        p = int(dport[i])
        if 8000 <= p < 8016:
            k = int(rng.integers(0, 5))
            path = (
                f"/api/v{p % 4}/items",
                f"/api/v{(p + 1) % 4}/items",  # version mismatch mix
                "/api/v9/nope",
                "/health",
                f"/api/v{p % 4}/x{i % 97}",
            )[k]
            method = "GET" if k != 3 else "POST"
            reqs.append((method.encode(), path.encode(), b""))
        else:
            reqs.append((b"", b"", b""))
    m, ml, p_, pl, h, hl, overflow = pad_requests(reqs)
    assert not overflow.any()
    m, p_, h = trim_packed(m, ml), trim_packed(p_, pl), trim_packed(h, hl)

    kreqs = []
    for i in range(n):
        pt = int(dport[i])
        if 9090 <= pt < 9098:
            kreqs.append(
                KafkaRequest(
                    kind=0,
                    version=0,
                    client_id=f"client{i % 4}",
                    topics=(f"topic{int(rng.integers(0, 48))}",),
                    parsed=True,
                )
            )
        else:
            kreqs.append(
                KafkaRequest(kind=0, version=0, client_id="",
                             topics=(), parsed=True)
            )
    kf = pad_kafka_requests(fleet.kafka, kreqs)
    import jax

    http_dev = tuple(
        jax.device_put(x) for x in (m, ml, p_, pl, h, hl)
    )
    kafka_dev = tuple(jax.device_put(np.asarray(x)) for x in kf)
    return reqs, kreqs, http_dev, kafka_dev


def _combined_step_fn(fleet, pool_n):
    """One jitted combined step per direction: device picks → fused
    datapath → compact redirected rows → inline L7 verdicts →
    combined counts.  Returns a function

      (tables, pool_dev, http_pool, kafka_pool, key, acc) →
        (header u32 [4] = allowed/redirected/l7_allowed/overflow, acc)
    """
    import jax
    import jax.numpy as jnp

    from cilium_tpu.engine.datapath import _datapath_core
    from cilium_tpu.l7.fleet import evaluate_fleet_l7
    from cilium_tpu.maps.policymap import INGRESS
    from cilium_tpu.replay import _flows_from_pool

    def step(tables, pool_dev, dir_idx, http_pool, kafka_pool, key,
             acc, static_direction):
        import jax.random as jrandom

        # picks draw from THIS direction's pool subset (dir_idx): the
        # direction-specialized programs mirror how packets arrive at
        # the two hooks, as the headline loop does
        r = jrandom.randint(
            key, (_COMBINED_BATCH,), 0, dir_idx.shape[0],
            dtype=jnp.uint32,
        )
        picks = dir_idx[r]
        flows = _flows_from_pool(pool_dev, picks)
        out, acc = _datapath_core(
            tables, flows, with_counters=True, acc=acc,
            emit_sec_id=False, static_direction=static_direction,
        )
        b = picks.shape[0]
        redirected = (out.proxy_port > 0) & out.allowed.astype(bool)
        row_id = jnp.arange(b, dtype=jnp.int32)
        order = jnp.argsort(
            jnp.where(redirected, row_id, jnp.int32(b))
        )[:_L7_CAP]
        valid = redirected[order]
        rows_pool = picks[order]  # pool row of each compacted flow

        http_fields = tuple(
            jnp.asarray(a)[rows_pool] for a in http_pool
        )
        kafka_fields = tuple(
            jnp.asarray(a)[rows_pool] for a in kafka_pool
        )
        l7_ok = evaluate_fleet_l7(
            fleet,
            flows.ep_index[order],
            flows.direction[order],
            out.l4_slot[order],
            out.sec_id[order].astype(jnp.int32),  # idx-form sec
            jnp.ones(order.shape, bool),
            http_fields=http_fields,
            kafka_fields=kafka_fields,
        ) & valid

        # combined allow: redirected flows need the L7 verdict too
        n_redirected = redirected.sum(dtype=jnp.uint32)
        overflow = n_redirected - valid.sum(dtype=jnp.uint32)
        l7_allowed = l7_ok.sum(dtype=jnp.uint32)
        combined = (
            out.allowed.astype(jnp.uint32).sum(dtype=jnp.uint32)
            - n_redirected
            + l7_allowed
        )
        header = jnp.stack(
            [combined, n_redirected, l7_allowed, overflow]
        )
        return header, acc

    return (
        jax.jit(
            lambda t, pd, di, hp, kp, k, a: step(
                t, pd, di, hp, kp, k, a, INGRESS
            ),
            donate_argnums=(6,),
        ),
        jax.jit(
            lambda t, pd, di, hp, kp, k, a: step(
                t, pd, di, hp, kp, k, a, 1
            ),
            donate_argnums=(6,),
        ),
    )


_COMBINED_BATCH = 1 << 21


def run_config5_combined(args, d, tables, pool, oracle_ctx, states):
    """The end-to-end datapath+proxy number: fused verdicts with the
    compiled fleet L7 matchers applied inline to redirected flows —
    ONE measured pipeline, the analog of kernel datapath + Envoy
    being the serving system."""
    import jax
    import jax.random as jrandom

    from cilium_tpu.engine.verdict import make_counter_buffers
    from cilium_tpu.l7.fleet import compile_fleet_l7
    from cilium_tpu.replay import pack_flow_pool

    rng = np.random.default_rng(23)
    t0 = time.perf_counter()
    fleet = compile_fleet_l7(d)
    fleet_compile_s = time.perf_counter() - t0
    reqs, kreqs, http_dev, kafka_dev = build_l7_payloads(
        args, rng, pool, fleet
    )
    pool_dev = jax.device_put(pack_flow_pool(pool))
    pool_n = len(pool["saddr"])
    dir_in = jax.device_put(
        np.nonzero(pool["direction"] == 0)[0].astype(np.uint32)
    )
    dir_eg = jax.device_put(
        np.nonzero(pool["direction"] == 1)[0].astype(np.uint32)
    )

    step_in, step_eg = _combined_step_fn(fleet, pool_n)

    # --- bit-identity gate: sampled picks through a full-output path ---
    _gate_combined(
        args, d, tables, pool, oracle_ctx, states, fleet, reqs, kreqs,
        http_dev, kafka_dev, rng,
    )

    acc = jax.device_put(make_counter_buffers(tables.policy))
    base = jrandom.PRNGKey(101)
    # warmup both directions
    h0, acc = step_in(tables, pool_dev, dir_in, http_dev, kafka_dev,
                      jrandom.fold_in(base, 0), acc)
    h1, acc = step_eg(tables, pool_dev, dir_eg, http_dev, kafka_dev,
                      jrandom.fold_in(base, 1), acc)
    jax.block_until_ready((h0, h1))
    _ = np.asarray(h0)

    import jax.numpy as jnp

    n_batches = max(args.tuples // (2 * _COMBINED_BATCH), 1)
    tot = jnp.zeros(4, jnp.uint32)
    recent = []
    t0 = time.perf_counter()
    for i in range(n_batches):
        hin, acc = step_in(
            tables, pool_dev, dir_in, http_dev, kafka_dev,
            jrandom.fold_in(base, 2 * i + 2), acc,
        )
        heg, acc = step_eg(
            tables, pool_dev, dir_eg, http_dev, kafka_dev,
            jrandom.fold_in(base, 2 * i + 3), acc,
        )
        tot = tot + hin + heg  # lazy on-device accumulation
        recent.append((hin, heg))
        if len(recent) > 4:
            recent.pop(0)
    totals = np.asarray(tot)  # one final D2H syncs the pipeline
    dt = time.perf_counter() - t0
    total = n_batches * 2 * _COMBINED_BATCH
    assert int(totals[3]) == 0, "L7 compaction cap overflow"
    emit(
        "config5_combined_verdicts_per_sec",
        round(total / dt),
        "verdicts/s",
        vs_baseline=round(total / dt / BASELINE_PER_CHIP, 3),
        tuples=total,
        allowed=int(totals[0]),
        l7_redirected=int(totals[1]),
        l7_allowed=int(totals[2]),
        fleet_l7_compile_s=round(fleet_compile_s, 2),
        note=(
            "fused datapath + inline fleet L7 (HTTP DFA + Kafka "
            "tensors) in one measured pipeline; mixed config-5 policy"
        ),
    )


def _gate_combined(
    args, d, tables, pool, oracle_ctx, states, fleet, reqs, kreqs,
    http_dev, kafka_dev, rng,
):
    """Bit-identity of the combined path vs the composed host oracle
    INCLUDING L7: fused verdict, then host-side HTTP/Kafka matching
    for redirected samples."""
    import jax
    import jax.numpy as jnp

    from cilium_tpu.engine.datapath import datapath_step
    from cilium_tpu.l7.fleet import (
        PARSER_HTTP_ID,
        PARSER_KAFKA_ID,
        evaluate_fleet_l7,
    )
    from cilium_tpu.l7.http import http_rule_matches_host
    from cilium_tpu.l7.kafka import matches_rules_host
    from cilium_tpu.replay import read_flow_batches

    sample = rng.integers(0, len(pool["saddr"]), size=512)
    buf = encode_pool_sample(pool, sample)
    flows = next(read_flow_batches(buf, len(sample)))[0]
    out = datapath_step(tables, flows)

    want_allow, want_proxy, want_sec = composed_oracle(
        oracle_ctx, states, pool, list(sample)
    )
    assert (np.asarray(out.allowed) == want_allow).all()
    assert (np.asarray(out.proxy_port) == want_proxy).all()
    id_index, _ = d.endpoint_manager.identity_index()

    # device combined L7 on exactly the sampled rows
    rows_pool = jnp.asarray(sample.astype(np.uint32))
    http_fields = tuple(jnp.asarray(a)[rows_pool] for a in http_dev)
    kafka_fields = tuple(jnp.asarray(a)[rows_pool] for a in kafka_dev)
    # translate sec ids to idx-form for the L7 ident gating
    sec_idx = np.asarray(
        [id_index.get(int(s), 0) for s in np.asarray(out.sec_id)],
        np.int32,
    )
    got_l7 = np.asarray(
        evaluate_fleet_l7(
            fleet,
            flows.ep_index,
            flows.direction,
            out.l4_slot,
            jnp.asarray(sec_idx),
            jnp.ones(len(sample), bool),
            http_fields=http_fields,
            kafka_fields=kafka_fields,
        )
    )

    # host oracle: per-scope rule sets from the compiled fleet specs
    http_by_scope = {}
    for r, spec in enumerate(fleet.http.device_rules if fleet.http else []):
        http_by_scope.setdefault(spec.scope_key, []).append(spec)
    kafka_by_scope = {}
    for r, spec in enumerate(fleet.kafka.specs if fleet.kafka else []):
        kafka_by_scope.setdefault(spec.scope_key, []).append(spec)

    allowed = np.asarray(out.allowed)
    proxy = np.asarray(out.proxy_port)
    slots = np.asarray(out.l4_slot)
    eps = np.asarray(flows.ep_index)
    dirs = np.asarray(flows.direction)
    mismatches = 0
    for row, i in enumerate(sample):
        if not (allowed[row] and proxy[row] > 0):
            continue
        scope = (int(eps[row]), int(dirs[row]), int(slots[row]))
        kind = fleet.parser_kind[scope]
        sidx = int(sec_idx[row])
        if kind == PARSER_HTTP_ID:
            m, p, h = reqs[int(i)]
            want = any(
                sidx in spec.identity_indices
                and http_rule_matches_host(spec, m, p, h)
                for spec in http_by_scope.get(scope, [])
            )
        elif kind == PARSER_KAFKA_ID:
            scoped = kafka_by_scope.get(scope, [])
            want = matches_rules_host(kreqs[int(i)], scoped, sidx)
        else:
            want = False
        if bool(got_l7[row]) != want:
            mismatches += 1
    assert mismatches == 0, (
        f"combined L7 diverges from host oracle on {mismatches} samples"
    )


# ---------------------------------------------------------------------------
# config 6: the fused IPv6 datapath (ipv6_policy + lb6_local)
# ---------------------------------------------------------------------------


def config6(args) -> None:
    """v6 sibling of the fused replay: prefilter6 → lb6 DNAT with
    service stickiness → CT6 → ipcache6 → shared lattice, timed at a
    1M-flow batch with a composed-oracle subsample."""
    import jax
    import jax.numpy as jnp

    from cilium_tpu.compiler.tables import compile_map_states
    from cilium_tpu.ct.table import (
        CT_EGRESS,
        CT_INGRESS,
        CT_RELATED,
        CT_REPLY,
        CTMap,
        CTTuple,
    )
    from cilium_tpu.engine.datapath6 import (
        Datapath6Tables,
        FlowBatch6,
        build_prefilter6,
        compile_ct6,
        datapath6_step,
    )
    from cilium_tpu.engine.oracle import policy_can_access
    from cilium_tpu.identity import RESERVED_WORLD
    from cilium_tpu.ipcache.lpm6 import (
        build_ipcache6,
        ip6_limbs,
        lookup_host6,
    )
    from cilium_tpu.lb.device6 import (
        compile_lb6,
        lb6_lookup_host,
        slave_for_host,
    )
    from cilium_tpu.lb.service import L3n4Addr, ServiceManager
    from cilium_tpu.maps.policymap import (
        INGRESS,
        PolicyKey,
        PolicyMapStateEntry,
    )

    rng = np.random.default_rng(29)
    n_ident = 4096
    base_id = 4096
    ids = list(range(base_id, base_id + n_ident))
    # /128 per identity under 2001:db8::/32 + some broader nets
    ipcache6 = {}
    addrs = []
    for i, num_id in enumerate(ids):
        a = f"2001:db8:{i >> 8:x}:{i & 0xFF:x}::{(i % 9) + 1:x}"
        ipcache6[f"{a}/128"] = num_id
        addrs.append(a)
    ipcache6["fd00::/8"] = ids[0]

    state = {}
    ports = rng.choice(np.arange(1000, 30000), size=64, replace=False)
    for num_id in ids[::2]:
        p = int(ports[num_id % len(ports)])
        state[PolicyKey(num_id, p, 6, INGRESS)] = PolicyMapStateEntry()
    for num_id in ids[::5]:
        state[PolicyKey(num_id, 0, 0, INGRESS)] = PolicyMapStateEntry()
    for num_id in ids[::3]:
        state[PolicyKey(num_id, 8443, 6, 1)] = PolicyMapStateEntry()
    tables_pol = compile_map_states([state], ids, identity_pad=1024)

    mgr = ServiceManager()
    vip = "fd00:77::1"
    backends = addrs[:4]
    mgr.upsert(
        L3n4Addr(vip, 443, 6),
        [L3n4Addr(b, 8443, 6) for b in backends],
    )
    ct = CTMap()
    world = Datapath6Tables(
        prefilter=build_prefilter6(["2600:1::/32"]),
        ipcache=build_ipcache6(ipcache6),
        ct=compile_ct6(ct),
        policy=tables_pol,
        lb=compile_lb6(mgr),
    )
    world = jax.device_put(world)

    n = 1 << 20
    pick = rng.integers(0, len(addrs), size=n)
    saddr = np.array([ip6_limbs(a) for a in addrs], np.uint32)[pick]
    to_vip = rng.random(n) < 0.1
    dpick = rng.integers(0, len(addrs), size=n)
    daddr = np.array([ip6_limbs(a) for a in addrs], np.uint32)[dpick]
    daddr[to_vip] = ip6_limbs(vip)
    direction = (rng.random(n) < 0.5).astype(np.int64)
    direction[to_vip] = 1
    dport = rng.choice(ports, size=n).astype(np.int64)
    dport[to_vip] = 443
    flows = FlowBatch6.from_numpy(
        ep_index=np.zeros(n, np.int32),
        saddr=saddr,
        daddr=daddr,
        sport=rng.integers(1024, 60000, size=n),
        dport=dport,
        proto=np.full(n, 6),
        direction=direction,
    )
    flows = jax.device_put(flows)
    out = datapath6_step(world, flows)
    jax.block_until_ready(out.allowed)

    # composed oracle subsample (incl. lb6 DNAT)
    allowed = np.asarray(out.allowed)
    slave_arr = np.asarray(out.lb_slave)
    sample = rng.integers(0, n, size=256)
    for i in sample:
        s = addrs[int(pick[i])]
        d = vip if to_vip[i] else addrs[int(dpick[i])]
        dirn = int(direction[i])
        eff_d, eff_p = d, int(dport[i])
        if dirn == 1:
            svc = lb6_lookup_host(mgr, d, eff_p, 6)
            if svc is not None and svc.backends:
                sl = slave_for_host(
                    svc, s, d, int(np.asarray(flows.sport)[i]),
                    eff_p, 6,
                )
                assert int(slave_arr[i]) == sl, i
                eff_d = svc.backends[sl - 1].addr.ip
                eff_p = svc.backends[sl - 1].addr.port
        sec_ip = s if dirn == INGRESS else eff_d
        sec = lookup_host6(ipcache6, sec_ip) or RESERVED_WORLD
        v = policy_can_access(state, sec, eff_p, 6, dirn)
        assert bool(allowed[i]) == v.allowed, i

    t0 = time.perf_counter()
    outs = [datapath6_step(world, flows) for _ in range(8)]
    jax.block_until_ready(outs)
    vps = 8 * n / (time.perf_counter() - t0)
    emit(
        "config6_ipv6_fused_verdicts_per_sec",
        round(vps),
        "verdicts/s",
        tuples=n,
        identities=n_ident,
        bit_identical=True,
        note="fused v6: prefilter6+lb6/DNAT+CT6+ipcache6+lattice",
    )


# ---------------------------------------------------------------------------
# config 1: minimum end-to-end slice
# ---------------------------------------------------------------------------


def config1() -> None:
    import jax
    import jax.numpy as jnp

    import __graft_entry__
    from cilium_tpu.engine.oracle import evaluate_batch_oracle
    from cilium_tpu.engine.verdict import _verdict_kernel

    n = 1024
    tables, batch, state = __graft_entry__._build_example(
        batch=n, return_state=True
    )
    step = jax.jit(_verdict_kernel)
    out = step(tables, batch)  # warmup/compile
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    out = step(tables, batch)
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0

    want_allow, want_proxy, want_kind = evaluate_batch_oracle(
        [state],
        ep_index=np.asarray(batch.ep_index),
        identity=np.asarray(batch.identity),
        dport=np.asarray(batch.dport),
        proto=np.asarray(batch.proto),
        direction=np.asarray(batch.direction),
    )
    assert (np.asarray(out.allowed) == want_allow).all(), (
        "config1 allow divergence vs oracle"
    )
    assert (np.asarray(out.proxy_port) == want_proxy).all()
    assert (np.asarray(out.match_kind) == want_kind).all()
    emit(
        "config1_l3l4_1k_tuples_ms",
        round(dt * 1000, 2),
        "ms",
        tuples=n,
        allows=int(np.asarray(out.allowed).sum()),
        bit_identical=True,
    )


# ---------------------------------------------------------------------------
# config 2: CIDR LPM
# ---------------------------------------------------------------------------


def config2(args) -> None:
    import jax

    from cilium_tpu.engine.verdict import (
        TupleBatch,
        evaluate_batch_from_ips,
    )
    from cilium_tpu.compiler.tables import compile_map_states
    from cilium_tpu.engine.oracle import policy_can_access
    from cilium_tpu.ipcache.lpm import build_lpm
    from cilium_tpu.prefilter import build_prefilter
    from cilium_tpu.maps.policymap import (
        INGRESS,
        PolicyKey,
        PolicyMapStateEntry,
    )

    rng = np.random.default_rng(11)
    base_local = 1 << 24
    # 20k prefixes: /16s, /24s and /32s over 10.0.0.0/8
    mapping = {}
    ids = []
    for i in range(64):
        mapping[f"10.{i}.0.0/16"] = base_local + len(ids)
        ids.append(base_local + len(ids))
    for i in range(4096):
        mapping[f"10.{64 + i // 256}.{i % 256}.0/24"] = base_local + len(ids)
        ids.append(base_local + len(ids))
    for i in range(16384):
        a, b = 128 + i // 8192, (i // 32) % 256
        mapping[f"10.{a}.{b}.{i % 32 * 8}/32"] = base_local + len(ids)
        ids.append(base_local + len(ids))
    lpm = build_lpm(mapping)

    # one endpoint allowing half the CIDR identities on port 443 + L3
    state = {}
    for num_id in ids[::2]:
        state[PolicyKey(num_id, 443, 6, INGRESS)] = PolicyMapStateEntry()
    for num_id in ids[::5]:
        state[PolicyKey(num_id, 0, 0, INGRESS)] = PolicyMapStateEntry()
    tables = compile_map_states([state], ids, identity_pad=1024)

    def make_cidr_batch(count):
        """One tuple distribution for BOTH config2 runs — the spec'd
        100k batch and the amortized 1M batch must measure the same
        workload."""
        addrs = (
            0x0A000000 | rng.integers(0, 1 << 24, size=count)
        ).astype(np.uint32)
        return addrs, TupleBatch.from_numpy(
            ep_index=np.zeros(count, np.int32),
            identity=np.zeros(count, np.uint32),
            dport=rng.choice([443, 80], size=count),
            proto=np.full(count, 6),
            direction=np.zeros(count, np.int64),
        )

    def timed_vps(step_fn, steps, count):
        t0 = time.perf_counter()
        outs = [step_fn() for _ in range(steps)]
        jax.block_until_ready(outs)
        return steps * count / (time.perf_counter() - t0)

    n = args.cidr_tuples
    src, batch = make_cidr_batch(n)
    src_d = jax.device_put(src)
    tables_d = jax.device_put(tables)
    lpm_d = jax.device_put(lpm)
    out = evaluate_batch_from_ips(lpm_d, tables_d, src_d, batch)
    jax.block_until_ready(out)

    # oracle subsample
    host = HostLPM(mapping)
    sample = rng.integers(0, n, size=512)
    allowed = np.asarray(out.allowed)
    dports = np.asarray(batch.dport)
    for i in sample:
        sec = host.lookup(int(src[i]))
        v = policy_can_access(state, sec, int(dports[i]), 6, INGRESS)
        assert bool(allowed[i]) == v.allowed, (
            f"CIDR config divergence at {i}"
        )

    vps = timed_vps(
        lambda: evaluate_batch_from_ips(lpm_d, tables_d, src_d, batch),
        16,
        n,
    )

    # supplementary: the same tables at a 1M-tuple batch — the
    # per-dispatch fixed cost weighs on the spec'd 100k batch, so the
    # large batch shows the dispatch-amortized rate
    n_big = 1 << 20
    src_big, batch_big = make_cidr_batch(n_big)
    src_big_d = jax.device_put(src_big)
    out_big = evaluate_batch_from_ips(
        lpm_d, tables_d, src_big_d, batch_big
    )
    jax.block_until_ready(out_big)
    emit(
        "config2_cidr_verdicts_per_sec_1m_batch",
        round(
            timed_vps(
                lambda: evaluate_batch_from_ips(
                    lpm_d, tables_d, src_big_d, batch_big
                ),
                8,
                n_big,
            )
        ),
        "verdicts/s",
        prefixes=len(mapping),
        tuples=n_big,
        note="same tables, dispatch overhead amortized",
    )
    emit(
        "config2_cidr_verdicts_per_sec",
        round(vps),
        "verdicts/s",
        prefixes=len(mapping),
        tuples=n,
        bit_identical=True,
    )


# ---------------------------------------------------------------------------
# config 3: HTTP L7
# ---------------------------------------------------------------------------


def config3(args) -> None:
    import jax

    from cilium_tpu.l7.http import (
        HTTPRuleSpec,
        compile_http_rules,
        evaluate_http_batch,
        http_rule_matches_host,
        pad_requests,
    )

    rng = np.random.default_rng(13)
    n_ident = 1024
    specs = []
    for i in range(24):
        specs.append(
            HTTPRuleSpec(
                identity_indices=list(
                    rng.integers(0, n_ident, size=64)
                ),
                method="GET|POST" if i % 3 else "GET",
                path=f"/api/v{i % 4}/[a-z]+(/[0-9]+)?",
                host="" if i % 2 else r"svc[0-9]+\.cluster\.local",
            )
        )
    policy = compile_http_rules(specs, n_ident)

    # request templates → padded tensors once, then gather to 1M
    templates = []
    for i in range(256):
        method = rng.choice(["GET", "POST", "PUT", "DELETE"])
        path = rng.choice(
            [
                f"/api/v{i % 4}/users/{i}",
                f"/api/v{i % 4}/items",
                f"/health",
                f"/api/v9/nope",
                f"/api/v{i % 4}/x" + "y" * int(rng.integers(0, 40)),
            ]
        )
        host = rng.choice(
            [f"svc{i % 8}.cluster.local", "evil.example.com", ""]
        )
        templates.append(
            (method.encode(), path.encode(), host.encode())
        )
    tm, tml, tp, tpl, th, thl, _ = pad_requests(templates)
    # trim each field to its occupied pow2 width — the scans cost per
    # processed byte, and real requests rarely fill the field budgets
    from cilium_tpu.l7.http import trim_packed

    tm = trim_packed(tm, tml)
    tp = trim_packed(tp, tpl)
    th = trim_packed(th, thl)
    n = args.l7_requests
    pick = rng.integers(0, len(templates), size=n)
    ident = rng.integers(0, n_ident, size=n).astype(np.int32)
    known = np.ones(n, dtype=bool)

    tbl = policy.tables
    # tables enter as jit constants (HTTPTables is host-side metadata,
    # not a pytree)
    step = jax.jit(lambda *t: evaluate_http_batch(tbl, *t))
    dev = [
        jax.device_put(x)
        for x in (
            tm[pick], tml[pick], tp[pick], tpl[pick], th[pick],
            thl[pick], ident, known,
        )
    ]
    out = step(*dev)
    jax.block_until_ready(out)

    # host oracle subsample
    allowed = np.asarray(out[0])
    sample = rng.integers(0, n, size=256)
    for i in sample:
        m, p, h = templates[int(pick[i])]
        want = any(
            int(ident[i]) in spec.identity_indices
            and http_rule_matches_host(spec, m, p, h)
            for spec in specs
        )
        assert bool(allowed[i]) == want, f"HTTP divergence at {i}"

    steps = 8
    t0 = time.perf_counter()
    outs = [step(*dev) for _ in range(steps)]
    jax.block_until_ready(outs)
    rps = steps * n / (time.perf_counter() - t0)
    emit(
        "config3_http_requests_per_sec",
        round(rps),
        "requests/s",
        rules=len(specs),
        requests=n,
        bit_identical=True,
    )


# ---------------------------------------------------------------------------
# config 4: Kafka L7
# ---------------------------------------------------------------------------


def config4(args) -> None:
    import jax

    from cilium_tpu.l7.kafka import (
        KafkaRequest,
        KafkaRuleSpec,
        compile_kafka_rules,
        evaluate_kafka_batch,
        matches_rules_host,
        pad_kafka_requests,
    )

    rng = np.random.default_rng(17)
    n_ident = 1024
    specs = []
    for i in range(24):
        specs.append(
            KafkaRuleSpec(
                identity_indices=frozenset(
                    int(x) for x in rng.integers(0, n_ident, size=64)
                ),
                api_keys=(0,) if i % 2 else (1, 2, 3),
                topic=f"topic{i % 16}" if i % 3 else "",
            )
        )
    tables = compile_kafka_rules(specs, n_ident)

    templates = []
    for i in range(256):
        kind = int(rng.choice([0, 1, 2, 3, 8, 9]))
        topics = [f"topic{int(t)}" for t in rng.integers(0, 24,
                  size=int(rng.integers(0, 3)))]
        templates.append(
            KafkaRequest(
                kind=kind,
                version=0,
                client_id=f"client{i % 4}",
                topics=tuple(topics),
                parsed=True,
            )
        )
    packed = pad_kafka_requests(tables, templates)
    n = args.l7_requests
    pick = rng.integers(0, len(templates), size=n)
    ident = rng.integers(0, n_ident, size=n).astype(np.int32)
    known = np.ones(n, dtype=bool)
    dev = [jax.device_put(np.asarray(a)[pick]) for a in packed]
    dev += [jax.device_put(ident), jax.device_put(known)]

    # tables enter as jit constants (KafkaTables is host metadata)
    step = jax.jit(lambda *t: evaluate_kafka_batch(tables, *t))
    out = step(*dev)
    jax.block_until_ready(out)

    allowed = np.asarray(out)
    sample = rng.integers(0, n, size=256)
    for i in sample:
        req = templates[int(pick[i])]
        want = matches_rules_host(req, specs, int(ident[i]))
        assert bool(allowed[i]) == want, f"Kafka divergence at {i}"

    steps = 8
    t0 = time.perf_counter()
    outs = [step(*dev) for _ in range(steps)]
    jax.block_until_ready(outs)
    rps = steps * n / (time.perf_counter() - t0)
    emit(
        "config4_kafka_requests_per_sec",
        round(rps),
        "requests/s",
        rules=len(specs),
        requests=n,
        bit_identical=True,
    )


# ---------------------------------------------------------------------------


def smoke() -> None:
    """Small end-to-end from real rules.  It names the platform it
    ran on; the check on the chip is chip_smoke.py."""
    import jax

    import __graft_entry__

    dev = jax.devices()[0]
    fn, args = __graft_entry__.entry()
    out = jax.jit(fn)(*args)
    n = int(np.asarray(out.allowed).sum())
    print(
        f"smoke OK on {dev.platform} ({dev.device_kind}): {n} allows "
        f"on {out.allowed.shape[0]} tuples"
    )


def build_parser() -> argparse.ArgumentParser:
    """The bench's options; chip_smoke.py takes its config-5
    defaults from here."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument(
        "--seed", type=int, default=0,
        help="base seed mixed into every sampled distribution "
        "(Zipf picks included) so any run reproduces from its "
        "logged seed alone; 0 keeps the historical fixed streams",
    )
    ap.add_argument(
        "--configs", default="1,2,3,4,5,6",
        help="comma-separated subset of 1-6",
    )
    ap.add_argument("--rules", type=int, default=50_000)
    ap.add_argument("--endpoints", type=int, default=32)
    ap.add_argument("--identities", type=int, default=65_536)
    ap.add_argument("--tuples", type=int, default=48_000_000)
    ap.add_argument("--pool", type=int, default=50_000)
    ap.add_argument("--batch", type=int, default=1 << 22)
    ap.add_argument("--oracle-sample", type=int, default=2048)
    ap.add_argument(
        "--trace-sample-rate", type=float, default=1.0,
        help="span-plane head-sampling probability for the "
        "tracing_overhead_pct loop (default: trace everything — "
        "the per-batch span count is bounded, like the flow "
        "plane's head-sampled allows)",
    )
    ap.add_argument("--cidr-tuples", type=int, default=100_000)
    ap.add_argument("--l7-requests", type=int, default=1_000_000)
    ap.add_argument(
        "--no-autotune", action="store_true",
        help="skip the batch-size / pack-width search and run the "
        "headline loop at --batch with the compiled pack width",
    )
    ap.add_argument(
        "--autotune-p99-ms", type=float, default=2000.0,
        help="p99 batch-latency bound the autotuner must respect "
        "when maximizing verdicts/s",
    )
    ap.add_argument(
        "--zipf-s", type=float, default=1.1,
        help="skew parameter of the rank-Zipf flow generator behind "
        "the verdict-memoization lines (verdict_cache_hit_rate, "
        "dedup_factor, effective_verdicts_per_sec_per_chip); the "
        "uncached verdicts_per_sec_per_chip headline stays on the "
        "uniform pool replay",
    )
    ap.add_argument(
        "--async-depth", type=int, default=2,
        help="batches in flight beyond the drain point in the "
        "double-buffered headline dispatch loop",
    )
    ap.add_argument(
        "--no-subword", action="store_true",
        help="skip the sub-word hot-plane transform (compact L4 / "
        "CT / ipcache lanes) and run the headline on the 3-word "
        "layouts",
    )
    ap.add_argument(
        "--persist-pairs", type=int, default=4,
        help="pair batches evaluated per launch by the persistent "
        "fused-pair program (lax.scan super-batch); 1 = one launch "
        "per pair, still no per-direction dispatch",
    )
    ap.add_argument(
        "--serve-batch", type=int, default=1 << 12,
        help="coalesced device-batch jit class of the serving-"
        "plane bench (run_serving_bench)",
    )
    ap.add_argument(
        "--serve-seconds", type=float, default=8.0,
        help="open-loop arrival window of the sustained-QPS "
        "serving bench",
    )
    return ap


def main() -> None:
    args = build_parser().parse_args()

    from cilium_tpu.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.smoke:
        smoke()
        return

    # Config 5 (the headline) runs FIRST so a budget kill of the
    # whole bench can never lose it; the driver's tail-parse reads
    # the last line, so the headline JSON line is re-emitted at exit.
    configs = {c.strip() for c in args.configs.split(",")}
    if "5" in configs:
        run_config5(args)
        # the per-chip failover lines ride config 5 (cheap: a small
        # dedicated world, not the 50k-rule fleet)
        run_failover_bench(args)
        # the continuous-serving-plane lines ride config 5 too
        # (their own small daemon world, not the 50k-rule fleet)
        run_serving_bench(args)
    if "1" in configs:
        config1()
    if "2" in configs:
        config2(args)
    if "3" in configs:
        config3(args)
    if "4" in configs:
        config4(args)
    if "6" in configs:
        config6(args)
    if "5" in configs and _HEADLINE:
        print(json.dumps(_HEADLINE), flush=True)  # re-emit for tail-parse


if __name__ == "__main__":
    main()
