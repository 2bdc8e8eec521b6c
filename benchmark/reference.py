"""The plain reference: Cilium v1.2 datapath semantics for the worlds
of benchmark/world.py, written from the rules and addresses the
benchmark asked for.  It imports nothing of the program and reads
nothing the program made (no map states, tables, identity numbers or
proxy ports).

Per flow it follows bpf_lxc's order: XDP prefilter (deny by source
CIDR) -> service lookup and DNAT with FNV-1a backend selection
(egress only) -> conntrack lookup (reverse tuple first: REPLY, then
the forward tuple: ESTABLISHED, else NEW) -> identity of the peer
from the ipcache (WORLD on a miss) -> the three-probe policy lattice
of bpf/lib/policy.h (exact L4 key, L3-only key, L4 wildcard key;
fragments skip the L4 probes) -> combine (bpf_lxc.c:962-985).

Policy semantics (pkg/endpoint/policy.go, pkg/policy/l4.go): an
endpoint enforces ingress iff some rule selects it; egress is never
enforced here (no rule has egress sections), so every identity the
agent knows, reserved:world included, may be reached.  Rules on one
(endpoint, port, protocol) merge into one L4 filter, which redirects
to a proxy when any of them carries L7 rules; the teams a label-based
L3-only rule allows join every such filter of the endpoint
(repository.go:128).

Identities, proxy ports, services and L4 slots are numbered by the
program; the reference names them by what they stand for (a peer's
labels, an endpoint's port, a VIP), and the comparison requires the
program's numbers to map one-to-one onto those names.
"""

from __future__ import annotations

import ipaddress

import numpy as np

# lattice outcome codes (bpf/lib/policy.h probe order)
MATCH_NONE, MATCH_L4, MATCH_L3, MATCH_L4_WILD, MATCH_FRAG = 0, 1, 2, 3, 4
# conntrack outcomes (bpf/lib/conntrack.h)
CT_NEW, CT_ESTABLISHED, CT_REPLY, CT_RELATED = 0, 1, 2, 3
INGRESS, EGRESS = 0, 1
# ipv4_ct_tuple flags (bpf/lib/common.h)
TUPLE_F_OUT, TUPLE_F_IN = 0, 1

FNV_OFFSET, FNV_PRIME = 2166136261, 16777619


def fnv1a_words(words: np.ndarray) -> np.ndarray:
    """32-bit FNV-1a over the little-endian bytes of u32 words
    [N, W] -> u32 [N]."""
    h = np.full(words.shape[0], FNV_OFFSET, np.uint64)
    for w in range(words.shape[1]):
        col = words[:, w].astype(np.uint64)
        for shift in (0, 8, 16, 24):
            h = ((h ^ ((col >> np.uint64(shift)) & np.uint64(0xFF)))
                 * np.uint64(FNV_PRIME)) & np.uint64(0xFFFFFFFF)
    return h


class Reference:
    """Verdicts of one world.  `desc` holds the plain description:
    specs, ep_ip, id_ips, n_teams, services, prefilter_cidrs, and
    `index` (endpoint id -> the endpoint axis the flows carry)."""

    def __init__(self, desc) -> None:
        self.n_ids = len(desc.id_ips)
        self.n_teams = int(desc.n_teams)
        self.enforced = set()
        self.l3_teams = {}
        self.cidr_blocks = {}
        self.l4 = {}  # (app, port, proto) -> set of teams
        self.redirect = set()  # (app, port, proto) with L7 rules
        all_blocks = set()
        for app, kind, team, port, proto, block in desc.specs:
            self.enforced.add(app)
            if kind == "l3":
                self.l3_teams.setdefault(app, set()).add(team)
            elif kind == "cidr":
                self.cidr_blocks.setdefault(app, set()).add(block)
                all_blocks.add(block)
            else:
                self.l4.setdefault((app, port, proto), set()).add(team)
                if kind in ("http", "kafka"):
                    self.redirect.add((app, port, proto))
        self.all_blocks = all_blocks
        self.id_of_ip = {int(ip): i for i, ip in enumerate(desc.id_ips)}
        self.app_of_ip = {int(ip): ep - 100 for ep, ip in desc.ep_ip.items()}
        self.app_of_axis = {
            int(axis): ep - 100 for ep, axis in desc.index.items()
        }
        self.prefilter = []
        for cidr in desc.prefilter_cidrs:
            net = ipaddress.ip_network(cidr)
            self.prefilter.append(
                (int(net.network_address), int(net.netmask))
            )
        self.services = {
            (vip, port, 6): backends
            for vip, port, backends in desc.services
        }
        self.service_ids = {
            key: k for k, key in enumerate(sorted(self.services))
        }

    # -- stages ------------------------------------------------------------

    def peer(self, ip: int) -> tuple:
        """The peer's identity by what it is: ('id', i) the i-th
        cluster identity, ('ep', app), ('cidr', block), ('world',)."""
        i = self.id_of_ip.get(ip)
        if i is not None:
            return ("id", i)
        app = self.app_of_ip.get(ip)
        if app is not None:
            return ("ep", app)
        if ip >> 16 == (198 << 8) | 18:  # 198.18.0.0/16
            block = (ip >> 8) & 0xFF
            if block in self.all_blocks:
                return ("cidr", block)
        return ("world",)

    def peer_code(self, p: tuple) -> int:
        kind = p[0]
        if kind == "id":
            return p[1]
        if kind == "ep":
            return self.n_ids + p[1]
        if kind == "cidr":
            return 2 * self.n_ids + p[1]
        return 3 * self.n_ids

    def team_of(self, p: tuple) -> int:
        return p[1] % self.n_teams if p[0] == "id" else -1

    def lattice(self, app, direction, p, dport, proto, frag):
        """(allowed, match kind, redirect key or None)."""
        if direction == EGRESS or app not in self.enforced:
            return True, MATCH_L3, None  # L3 key of every identity
        team = self.team_of(p)
        if not frag:
            key = (app, dport, proto)
            if team in self.l4.get(key, ()):
                return (True, MATCH_L4,
                        key if key in self.redirect else None)
            # repository.go:128 wildcardL3L4Rules: a team allowed at L3
            # joins every L7 filter of the endpoint, so its traffic to
            # that port is redirected
            if key in self.redirect and team in self.l3_teams.get(app, ()):
                return True, MATCH_L4, key
        if team >= 0 and team in self.l3_teams.get(app, ()):
            return True, MATCH_L3, None
        if p[0] == "cidr" and p[1] in self.cidr_blocks.get(app, ()):
            return True, MATCH_L3, None
        # no rule selects all identities, so no L4 wildcard key exists
        return False, (MATCH_FRAG if frag else MATCH_NONE), None

    def prefiltered(self, saddr: int) -> bool:
        return any((saddr & m) == base for base, m in self.prefilter)

    def lb(self, saddr, daddr, sport, dport, proto):
        """(service key, 1-based backend, backend ip, backend port) or
        None when (daddr, dport, proto) is no service frontend."""
        key = (daddr, dport, proto)
        backends = self.services.get(key)
        if not backends:
            return None
        h = fnv1a_words(
            np.array([[saddr, daddr, (sport << 16) | dport, proto]],
                     np.uint64)
        )
        slave = int(h[0]) % len(backends) + 1
        ip, port = backends[slave - 1]
        return key, slave, ip, port

    # -- the whole pipeline over pool rows --------------------------------

    def flows(self, pool, ct_keys=None) -> dict:
        """Per pool row, every column the fused datapath emits, by the
        reference's own names.  `ct_keys` is the conntrack table as a
        set of (daddr, saddr, dport, sport, proto, flags) keys."""
        ct_keys = ct_keys or set()
        n = len(pool["saddr"])
        cols = {
            c: np.zeros(n, np.int64) for c in (
                "allowed", "match_kind", "ct_result", "pre_dropped",
                "final_daddr", "final_dport", "lb_slave", "ct_create",
                "ct_delete", "ipcache_miss", "peer", "redirect_key",
                "service", "pol_allow",
            )
        }
        redirect_keys = {}
        for r in range(n):
            app = self.app_of_axis[int(pool["ep_index"][r])]
            saddr, daddr = int(pool["saddr"][r]), int(pool["daddr"][r])
            sport, dport = int(pool["sport"][r]), int(pool["dport"][r])
            proto = int(pool["proto"][r])
            direction = int(pool["direction"][r])
            frag = bool(pool["is_fragment"][r])

            pre = self.prefiltered(saddr)
            eff_daddr, eff_dport, slave, service = daddr, dport, 0, -1
            if direction == EGRESS:
                hit = self.lb(saddr, daddr, sport, dport, proto)
                if hit is not None:
                    key, slave, eff_daddr, eff_dport = hit
                    service = self.service_ids[key]
            flags = TUPLE_F_OUT if direction == INGRESS else TUPLE_F_IN
            fwd = (eff_daddr, saddr, eff_dport, sport, proto, flags)
            rev = (saddr, eff_daddr, sport, eff_dport, proto, flags ^ 1)
            if rev in ct_keys:
                ct = CT_REPLY
            elif fwd in ct_keys:
                ct = CT_ESTABLISHED
            else:
                ct = CT_NEW
            p = self.peer(saddr if direction == INGRESS else eff_daddr)
            pol, kind, rkey = self.lattice(
                app, direction, p, eff_dport, proto, frag
            )
            pass_ct = ct in (CT_REPLY, CT_RELATED)
            allowed = (not pre) and (pass_ct or pol)
            redirect = (
                rkey is not None and pol and allowed
                and ct in (CT_NEW, CT_ESTABLISHED)
            )
            cols["allowed"][r] = allowed
            cols["pol_allow"][r] = pol
            cols["match_kind"][r] = kind
            cols["ct_result"][r] = ct
            cols["pre_dropped"][r] = pre
            cols["final_daddr"][r] = eff_daddr
            cols["final_dport"][r] = eff_dport
            cols["lb_slave"][r] = slave
            cols["service"][r] = service
            cols["ct_create"][r] = ct == CT_NEW and allowed
            cols["ct_delete"][r] = (
                ct == CT_ESTABLISHED and not pol and not pass_ct
                and not pre
            )
            cols["ipcache_miss"][r] = p == ("world",)
            cols["peer"][r] = self.peer_code(p)
            cols["redirect_key"][r] = (
                redirect_keys.setdefault(rkey, len(redirect_keys))
                if redirect else -1
            )
        return cols

    def conntrack_after_seed(self, pool) -> set:
        """The CT keys one pass of every pool row over an empty table
        leaves: the forward tuple of every flow it allowed
        (ct_create4 for CT_NEW + allowed, bpf_lxc.c:978)."""
        cols = self.flows(pool)
        keys = set()
        for r in np.nonzero(cols["ct_create"])[0]:
            flags = (TUPLE_F_OUT if int(pool["direction"][r]) == INGRESS
                     else TUPLE_F_IN)
            keys.add((
                int(cols["final_daddr"][r]), int(pool["saddr"][r]),
                int(cols["final_dport"][r]), int(pool["sport"][r]),
                int(pool["proto"][r]), flags,
            ))
        return keys


# the datapath's per-direction stage histogram, column by column
# (the [2, 20] telemetry block the fused program accumulates)
def telemetry_masks(c: dict) -> list:
    allowed = c["allowed"].astype(bool)
    pre = c["pre_dropped"].astype(bool)
    kind = c["match_kind"]
    ct = c["ct_result"]
    pol = c["pol_allow"].astype(bool)
    denied = ~allowed
    post = denied & ~pre
    pass_ct = (ct == CT_REPLY) | (ct == CT_RELATED)
    return [
        np.ones(len(allowed), bool),  # total
        allowed,  # forwarded
        denied,
        pre,  # dropped by the prefilter
        post & (kind == MATCH_NONE),  # dropped by policy
        post & (kind == MATCH_FRAG),  # dropped: fragment
        kind == MATCH_L4,
        kind == MATCH_L3,
        kind == MATCH_L4_WILD,
        kind == MATCH_NONE,
        kind == MATCH_FRAG,
        c["lb_slave"] > 0,  # DNAT to a backend
        ct == CT_NEW,
        ct == CT_ESTABLISHED,
        ct == CT_REPLY,
        ct == CT_RELATED,
        pass_ct & ~pol & ~pre,  # allowed by conntrack alone
        c["ct_delete"].astype(bool),
        c["ipcache_miss"].astype(bool),  # identity fell back to world
        (c["redirect_key"] >= 0) & allowed,  # sent to a proxy
    ]


def telemetry_of(c: dict, weights_by_direction) -> np.ndarray:
    """[2, 20] stage counts: weights_by_direction[d][r] is how often
    pool row r appears among direction d's tuples."""
    masks = telemetry_masks(c)
    out = np.zeros((2, len(masks)), np.int64)
    for d, w in enumerate(weights_by_direction):
        w = np.asarray(w, np.int64)
        for j, m in enumerate(masks):
            out[d, j] = int(w[m].sum())
    return out
