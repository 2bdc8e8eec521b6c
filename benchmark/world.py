"""The benchmark's default world: a Cilium deployment built from a seed
through the program's public entry points (Daemon, policy_add,
regenerate_all, ipcache.upsert, ServiceManager, compile_ct,
compile_lb).

The generators are copies of bench.py's (build_rules, make_flow_pool,
zipf_picks, pack_pool_pairs): bench.py may change, this file may not.
benchmark/tests/test_world.py shows that both give the same rules,
pool and packed pairs for the same seed.

Beside the program's objects the world keeps a plain description of
what it asked for (rule specs, addresses, services, prefilter), which
is all that benchmark/reference.py reads.

The world contract.  A configuration without a `world` key is built
here; one with `"world": "<name>"` is built by
benchmark/worlds/<name>.py, which the harness finds by that name
(harness.world_module) as it finds loops and per-layer readers.  A
world module gives

- `build_world(cfg, rng)`: the world of configuration `cfg`, drawn
  from `rng`, which the harness seeds from the configuration's
  `world_seed`;
- `TINY`: the configuration keys that make the world small enough for
  the tests on the CPU (benchmark/tests/test_correct.py).

The world is one object; who reads which attribute:

- the harness: `daemon` (fallback_counters) and `timings` (set-up
  phases on stderr).  It sets `cfg`, the configuration, before the
  loop is built, so that a loop can read its deployment's own keys;
- the `replay` loop: `tables`, `pool`, `ct`, `index` and `timings`;
  it sets `ct_seeded` once it has seeded conntrack;
- benchmark/reference.py: `specs`, `ep_ip`, `id_ips`, `n_teams`,
  `services`, `prefilter_cidrs` and `index`.

A world whose rules reference.py cannot express brings its own
reference, next to its own loop, and that loop imports it, as
`replay` imports benchmark/reference.py.
"""

from __future__ import annotations

import dataclasses
import ipaddress
import time
from types import SimpleNamespace

import numpy as np

# The rule mix: a draw below the first cut makes an L4 rule, below the
# second an L3-only rule, then CIDR, then HTTP; the rest are Kafka.
# configs/n110.json documents the same mix as shares.
RULE_CUTS = (0.84, 0.92, 0.96, 0.99)
# Each pool flow's chance of each kind (make_flow_pool).
POOL_MIX = {
    "l7_bound": 0.025,
    "junk_ports": 0.10,
    "egress_to_vip": 0.10,
    "prefiltered": 0.02,
    "world": 0.03,
    "fragments": 0.02,
}
# Sizes at which this world is built in the tests on the CPU.
TINY = dict(rules=1500, endpoints=8, identities=1024, pool=3000)


def ip_u32(s: str) -> int:
    return int(ipaddress.ip_address(s))


# ---------------------------------------------------------------------------
# copied generators (bench.py build_rules, make_flow_pool, zipf_picks,
# pack_pool_pairs); build_rules also returns the plain rule specs
# ---------------------------------------------------------------------------


def build_rules(rng, n_rules, n_endpoints, n_teams, cuts=RULE_CUTS):
    """A mixed policy: plain L4 (84%), L3-only (8%), CIDR (4%), HTTP L7
    (3%), Kafka L7 (1%) at the default `cuts`; every rule selects one
    app (endpoint) and allows one team (identity group).  Returns
    (rules, all_ports, l7_pairs, specs); specs[i] = (app_idx, kind,
    team_idx, port, proto, block) with kind in l4/l3/cidr/http/kafka."""
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
    specs = []
    l7_pairs = []  # (endpoint_idx, dport, team_idx) of L7 rules
    for i in range(n_rules):
        app_idx = i % n_endpoints
        app = f"app{app_idx}"
        team_idx = int(rng.integers(0, n_teams))
        team = f"t{team_idx}"
        kind = rng.random()
        sel = es("app", app)
        src = es("team", team)
        if kind < cuts[0]:
            port = int(plain_ports[int(rng.integers(0, len(plain_ports)))])
            proto = "TCP" if rng.random() < 0.7 else "UDP"
            specs.append(
                (app_idx, "l4", team_idx, port,
                 6 if proto == "TCP" else 17, -1)
            )
            ingress = IngressRule(
                from_endpoints=[src],
                to_ports=[
                    PortRule(
                        ports=[PortProtocol(port=str(port), protocol=proto)]
                    )
                ],
            )
        elif kind < cuts[1]:
            specs.append((app_idx, "l3", team_idx, 0, 0, -1))
            ingress = IngressRule(from_endpoints=[src])  # L3-only
        elif kind < cuts[2]:
            block = int(rng.integers(0, 256))
            specs.append((app_idx, "cidr", -1, 0, 0, block))
            ingress = IngressRule(
                from_cidr_set=[CIDRRule(cidr=f"198.18.{block}.0/24")]
            )
        elif kind < cuts[3]:
            port = http_ports[int(rng.integers(0, len(http_ports)))]
            l7_pairs.append((app_idx, port, team_idx))
            specs.append((app_idx, "http", team_idx, port, 6, -1))
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
            l7_pairs.append((app_idx, port, team_idx))
            specs.append((app_idx, "kafka", team_idx, port, 6, -1))
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
        [(int(p), 6) for p in plain_ports]
        + [(int(p), 17) for p in plain_ports]
        + [(p, 6) for p in http_ports]
        + [(p, 6) for p in kafka_ports]
    )
    return rules, all_ports, l7_pairs, specs


def make_flow_pool(args, rng, ep_ip, id_ips, vips, all_ports, index,
                   l7_pairs=None, n_teams=1, mix=POOL_MIX):
    """A pool of unique flows.  At the default `mix`, 2.5% are
    proxy-bound L7 traffic (an allowed team member hitting an L7 rule's
    port at its endpoint), 10% junk ports, 10% of egress to service
    VIPs, 2% prefiltered sources, 3% world sources, 2% fragments."""
    n = args.pool
    ep_ids = np.asarray(sorted(ep_ip), np.int64)
    ep_axis = np.asarray([index[int(e)] for e in ep_ids], np.int32)
    ep_addr = np.asarray([ep_ip[int(e)] for e in ep_ids], np.uint32)

    pick_ep = rng.integers(0, len(ep_ids), size=n)
    direction = (rng.random(n) < 0.5).astype(np.uint8)  # 0=in 1=eg
    peer_ip = id_ips[rng.integers(0, len(id_ips), size=n)]
    pre = rng.random(n) < mix["prefiltered"]
    world = rng.random(n) < mix["world"]
    peer_ip = np.where(
        pre,
        ip_u32("203.0.113.0") + rng.integers(0, 256, size=n),
        np.where(
            world,
            ip_u32("8.8.0.0") + rng.integers(0, 1 << 16, size=n),
            peer_ip,
        ),
    ).astype(np.uint32)
    to_vip = (direction == 1) & (rng.random(n) < mix["egress_to_vip"])
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
    junk = rng.random(n) < mix["junk_ports"]
    dport = np.where(junk, rng.integers(30000, 65536, size=n), dport)
    dport = np.where(to_vip, 80, dport).astype(np.uint16)
    proto = np.where(junk, 6, proto)
    proto = np.where(to_vip, 6, proto).astype(np.uint8)
    sport = rng.integers(1024, 65536, size=n).astype(np.uint16)
    frag = (rng.random(n) < mix["fragments"]).astype(np.uint8)

    ep_index = ep_axis[pick_ep].astype(np.uint32)
    if l7_pairs:
        # overlay last so junk/VIP/prefilter mixing can't clobber the
        # L7 flows' defining fields
        l7 = np.nonzero(rng.random(n) < mix["l7_bound"])[0]
        pick_rule = rng.integers(0, len(l7_pairs), size=len(l7))
        for row, r in zip(l7, pick_rule):
            app_i, port, team_idx = l7_pairs[int(r)]
            member = int(rng.integers(0, len(id_ips) // n_teams))
            i_id = member * n_teams + team_idx
            if i_id >= len(id_ips):
                i_id = team_idx
            direction[row] = 0
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
    """Ranked-Zipf sample of pool rows: rank r drawn with probability
    proportional to r^-s, ranks mapped through a random permutation;
    s=0 is uniform."""
    ranks = np.arange(1, n + 1, dtype=np.float64)
    w = ranks ** -float(s)
    w /= w.sum()
    perm = prng.permutation(n)
    return perm[prng.choice(n, size=size, p=w)]


def pack_pool_pairs(pool, prng, half: int, k: int, zipf_s=None):
    """k host-staged [2, 4, half] u32 packed4 pairs from the
    per-direction pool subsets, picked uniformly (or Zipf(zipf_s) over
    each subset).  Returns (pairs, picks), picks[i] = (ingress rows,
    egress rows) of the pool behind pair i."""
    from cilium_tpu.engine.datapath import pack_flow_records4

    subsets = [np.nonzero(pool["direction"] == d)[0] for d in (0, 1)]
    pairs, picks = [], []
    for _ in range(k):
        pair = np.empty((2, 4, half), np.uint32)
        rows = []
        for row, subset in enumerate(subsets):
            if zipf_s is None:
                p = subset[prng.integers(0, len(subset), size=half)]
            else:
                p = subset[zipf_picks(prng, len(subset), half, zipf_s)]
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


# ---------------------------------------------------------------------------
# the world (a copy of bench.build_config5 that also keeps the plain
# description the reference reads)
# ---------------------------------------------------------------------------


def build_world(cfg: dict, rng, rule_cuts=RULE_CUTS,
                pool_mix=POOL_MIX) -> SimpleNamespace:
    """Endpoints, the identity universe, the policy, services and the
    prefilter of configuration `cfg`, through the program's control
    plane, and the flow pool.  A world module of another mix calls it
    with its own `rule_cuts` and `pool_mix`."""
    from cilium_tpu.ct.device import compile_ct
    from cilium_tpu.ct.table import CTMap
    from cilium_tpu.daemon import Daemon
    from cilium_tpu.engine.datapath import DatapathTables
    from cilium_tpu.ipcache.ipcache import IPIdentity
    from cilium_tpu.ipcache.lpm import specialize_ipcache_to_idx
    from cilium_tpu.labels import Label, Labels
    from cilium_tpu.lb.device import compile_lb
    from cilium_tpu.lb.service import L3n4Addr, ServiceManager
    from cilium_tpu.prefilter import build_prefilter

    n_eps = int(cfg["endpoints"])
    n_ids = int(cfg["identities"])
    timings = {}
    d = Daemon(num_workers=8)
    d.policy_trigger.close(wait=True)  # explicit sweeps

    t0 = time.perf_counter()
    ep_ip = {}
    for i in range(n_eps):
        ip = f"10.250.{i // 256}.{i % 256}"
        ep_ip[100 + i] = ip_u32(ip)
        d.create_endpoint(
            100 + i,
            Labels({"app": Label("app", f"app{i}", "k8s")}),
            ipv4=ip,
            name=f"ep{i}",
        )
    n_teams = max(n_ids // int(cfg["team_size"]), 1)
    id_ips = []
    for i in range(n_ids - n_eps):
        labels = Labels(
            {
                "team": Label("team", f"t{i % n_teams}", "k8s"),
                "svc": Label("svc", f"s{i}", "k8s"),
            }
        )
        ident, _ = d.identity_allocator.allocate(labels)
        ip = 0x0A000000 | (i + 1)  # 10.0.0.0/8, dense
        id_ips.append(ip)
        d.ipcache.upsert(
            str(ipaddress.ip_address(ip)),
            IPIdentity(ident.id, "kvstore"),
        )
    timings["identity_setup_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    rules, all_ports, l7_pairs, specs = build_rules(
        rng, int(cfg["rules"]), n_eps, n_teams, rule_cuts
    )
    d.policy_add(rules)
    timings["policy_add_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    d.regenerate_all("benchmark import")
    timings["regenerate_s"] = time.perf_counter() - t0
    _, policy_tables, index = d.endpoint_manager.published()

    t0 = time.perf_counter()
    prefilter_map = {c: 1 for c in cfg["prefilter_cidrs"]}
    mgr = ServiceManager()
    services = []  # (vip u32, port, [(backend ip u32, port), ...])
    for i in range(int(cfg["services"])):
        vip = f"172.16.0.{i + 1}"
        picked = rng.choice(
            n_eps, size=int(cfg["backends_per_service"]), replace=False
        )
        backends = [
            (ep_ip[100 + int(b)], int(all_ports[i][0])) for b in picked
        ]
        mgr.upsert(
            L3n4Addr(vip, 80, 6),
            [
                L3n4Addr(str(ipaddress.ip_address(ip)), port, 6)
                for ip, port in backends
            ],
        )
        services.append((ip_u32(vip), 80, backends))

    ct = CTMap()
    tables = DatapathTables(
        prefilter=build_prefilter(prefilter_map),
        ipcache=specialize_ipcache_to_idx(
            d.lpm_builder.tables(), policy_tables
        ),
        ct=compile_ct(ct),
        lb=compile_lb(mgr),
        policy=policy_tables,
    )
    timings["tables_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    pool = make_flow_pool(
        SimpleNamespace(pool=int(cfg["pool"])), rng, ep_ip,
        np.asarray(id_ips, np.uint32), [s[0] for s in services],
        all_ports, index, l7_pairs=l7_pairs, n_teams=n_teams, mix=pool_mix,
    )
    timings["pool_s"] = time.perf_counter() - t0
    return SimpleNamespace(
        daemon=d, tables=tables, index=dict(index), pool=pool, ct=ct,
        timings=timings,
        # the plain description (all the reference reads)
        specs=specs, ep_ip=ep_ip, id_ips=np.asarray(id_ips, np.uint32),
        n_teams=n_teams, services=services,
        prefilter_cidrs=list(cfg["prefilter_cidrs"]),
    )


def seed_conntrack(world) -> int:
    """Every pool flow once through the program's fused replay with CT
    writeback (replay.replay_pool), then the device CT snapshot is
    recompiled.  Returns the CT entries created."""
    from cilium_tpu.ct.device import compile_ct
    from cilium_tpu.replay import replay_pool

    n = len(world.pool["saddr"])
    batch = 1 << int(np.ceil(np.log2(max(n, 2))))
    t0 = time.perf_counter()
    replay_pool(
        world.tables, world.pool, np.arange(n, dtype=np.uint32),
        batch_size=batch, ct_map=world.ct,
    )
    world.timings["ct_replay_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    world.tables = dataclasses.replace(world.tables, ct=compile_ct(world.ct))
    world.timings["ct_compile_s"] = time.perf_counter() - t0
    return len(world.ct.entries)


def headline_tables(tables):
    """The fused headline's tables: the hot policy plane at the
    compiled pack width and the sub-word layouts (as chip_smoke.py)."""
    from cilium_tpu.compiler.tables import split_hot
    from cilium_tpu.engine.datapath import (
        DatapathTables,
        subword_datapath_tables,
    )

    hot = DatapathTables(
        prefilter=tables.prefilter, ipcache=tables.ipcache, ct=tables.ct,
        lb=tables.lb, policy=split_hot(tables.policy),
    )
    return subword_datapath_tables(hot)
