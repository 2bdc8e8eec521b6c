"""The config-5 world builder: a Cilium deployment built through the
real control plane from a seed.

A >=50k-rule mixed L3/L4/L7 policy (plain L4, L3-only, CIDR, HTTP and
Kafka rules) goes through policy_add -> regeneration ->
FleetCompiler; the identities fill the ipcache, 16 service VIPs load-
balance onto the endpoints, one CIDR is prefiltered, and a pool of
unique flows (2.5% of them real clients of the L7 rules) samples the
traffic.  `build_config5` returns the daemon, the DatapathTables and
the flow pool; `pack_pool_pairs` and `zipf_picks` stage that pool the
way the fused-pair program takes it.

chip_smoke.py and tools/cacheprof.py build their world here, and
`build_parser` holds the options they read, with their defaults.
This file is not a benchmark: the benchmark is benchmark/run.py
(BENCHMARK.json), whose world is benchmark/world.py.
"""

from __future__ import annotations

import argparse
import ipaddress
import time

import numpy as np


def ip_u32(s: str) -> int:
    return int(ipaddress.ip_address(s))


from cilium_tpu.engine.hostpath import HostLPM  # noqa: E402


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
    uniform.  Shared with tools/cacheprof.py, whose hit-rate curve
    samples this distribution."""
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
    per-direction pool subsets (the host half of the fused-pair
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
    """The one-rule churn unit (tools/cacheprof.py's publish
    boundary): allow `team` → `app` on one TCP port."""
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


def build_parser() -> argparse.ArgumentParser:
    """The world's options; chip_smoke.py takes its config-5 defaults
    from here."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--rules", type=int, default=50_000)
    ap.add_argument("--endpoints", type=int, default=32)
    ap.add_argument("--identities", type=int, default=65_536)
    ap.add_argument("--pool", type=int, default=50_000)
    ap.add_argument("--batch", type=int, default=1 << 22)
    ap.add_argument("--oracle-sample", type=int, default=2048)
    ap.add_argument(
        "--persist-pairs", type=int, default=4,
        help="pair batches evaluated per launch by the persistent "
        "fused-pair program (lax.scan super-batch); 1 = one launch "
        "per pair, still no per-direction dispatch",
    )
    return ap
