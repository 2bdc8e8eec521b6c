"""What decides `correct`: the program's outputs against the plain
reference (benchmark/reference.py), each number beside its limit.

The fused datapath's answer for a tuple is a function of its pool row
alone while the tables stay fixed, as they do through the window.  So
every tuple of a launch is checked by reducing the launch's columns,
on the device, to each pool row's smallest and largest value
(`RowExtremes`): a row whose tuples disagree is an answer altered
where it was produced, and a row's one value is then compared with
the reference's answer for that row.
"""

from __future__ import annotations

from functools import partial

import numpy as np

from benchmark import reference as R

# DatapathVerdicts columns the fused programs fill, as u32 words
WORDS = (
    "allowed", "proxy_port", "match_kind", "ct_result", "pre_dropped",
    "sec_id", "final_daddr", "final_dport", "rev_nat", "lb_slave",
    "ct_create", "ct_delete", "tunnel_endpoint", "l4_slot",
    "ipcache_miss",
)
EXACT = (
    "allowed", "match_kind", "ct_result", "pre_dropped", "final_daddr",
    "final_dport", "lb_slave", "ct_create", "ct_delete", "ipcache_miss",
)


def _extremes_update(n_rows, lo, hi, seen, out, picks):
    import jax
    import jax.numpy as jnp

    words = jnp.stack(
        [getattr(out, c).astype(jnp.uint32) for c in WORDS]
    )  # [W, B]
    seg = picks.astype(jnp.int32)
    mn = jax.vmap(
        lambda w: jax.ops.segment_min(w, seg, num_segments=n_rows)
    )(words)
    mx = jax.vmap(
        lambda w: jax.ops.segment_max(w, seg, num_segments=n_rows)
    )(words)
    cnt = jax.ops.segment_sum(
        jnp.ones(seg.shape, jnp.uint32), seg, num_segments=n_rows
    )
    return jnp.minimum(lo, mn), jnp.maximum(hi, mx), seen + cnt


class RowExtremes:
    """Per pool row and direction, the smallest and largest value of
    every column over all tuples folded in, and how many there were."""

    def __init__(self, n_rows: int) -> None:
        import jax
        import jax.numpy as jnp

        self.n_rows = n_rows
        w = len(WORDS)
        self.state = [
            (
                jnp.full((w, n_rows), 0xFFFFFFFF, jnp.uint32),
                jnp.zeros((w, n_rows), jnp.uint32),
                jnp.zeros((n_rows,), jnp.uint32),
            )
            for _ in range(2)
        ]
        self._update = jax.jit(partial(_extremes_update, n_rows))

    def fold(self, direction: int, out, picks) -> None:
        lo, hi, seen = self.state[direction]
        self.state[direction] = self._update(lo, hi, seen, out, picks)

    def host(self):
        """[(lo, hi, seen)] per direction as numpy, lo/hi as
        {column: [n_rows]}."""
        res = []
        for lo, hi, seen in self.state:
            lo, hi = np.asarray(lo), np.asarray(hi)
            res.append((
                {c: lo[i].astype(np.int64) for i, c in enumerate(WORDS)},
                {c: hi[i].astype(np.int64) for i, c in enumerate(WORDS)},
                np.asarray(seen).astype(np.int64),
            ))
        return res


def one_to_one_violations(keys: np.ndarray, values: np.ndarray) -> int:
    """How far `keys` -> `values` is from a bijection: for each key the
    number of extra values it maps to, plus for each value the number
    of extra keys mapping to it."""
    if len(keys) == 0:
        return 0
    pairs = np.unique(np.stack([keys, values], axis=1), axis=0)
    n_keys = len(np.unique(pairs[:, 0]))
    n_vals = len(np.unique(pairs[:, 1]))
    return (len(pairs) - n_keys) + (len(pairs) - n_vals)


def compare_rows(ref: dict, obs_by_dir, pool) -> dict:
    """Replay checks over every pool row that some tuple carried.
    `ref` is Reference.flows(); obs_by_dir is RowExtremes.host().
    Returns {name: value} (every limit is 0) and, under '_program',
    the program's per-row values that the counter check reads."""
    inconsistent = wrong = 0
    rows_all, prog = [], {c: [] for c in WORDS}
    for d, (lo, hi, seen) in enumerate(obs_by_dir):
        rows = np.nonzero(seen > 0)[0]
        bad = np.zeros(len(rows), bool)
        for c in WORDS:
            bad |= lo[c][rows] != hi[c][rows]
        inconsistent += int(bad.sum())
        miss = np.zeros(len(rows), bool)
        for c in EXACT:
            miss |= lo[c][rows] != ref[c][rows]
        miss |= (lo["proxy_port"][rows] > 0) != (ref["redirect_key"][rows] >= 0)
        miss |= (lo["rev_nat"][rows] > 0) != (ref["service"][rows] >= 0)
        miss |= lo["tunnel_endpoint"][rows] != 0
        wrong += int(miss.sum())
        rows_all.append(rows)
        for c in WORDS:
            prog[c].append(lo[c][rows])
    rows = np.concatenate(rows_all)
    prog = {c: np.concatenate(v) for c, v in prog.items()}
    direction = pool["direction"][rows].astype(np.int64)

    red = ref["redirect_key"][rows] >= 0
    svc = ref["service"][rows] >= 0
    kind = ref["match_kind"][rows]
    l4 = (kind == R.MATCH_L4) | (kind == R.MATCH_L4_WILD)
    # the program numbers its L4 slots per (direction, port, protocol)
    slot_key = (direction * (1 << 16) + ref["final_dport"][rows]) * 256 + (
        pool["proto"][rows].astype(np.int64)
    )
    parts = (
        one_to_one_violations(ref["peer"][rows], prog["sec_id"]),
        one_to_one_violations(
            ref["redirect_key"][rows][red], prog["proxy_port"][red]
        ),
        one_to_one_violations(ref["service"][rows][svc], prog["rev_nat"][svc]),
        one_to_one_violations(slot_key[l4], prog["l4_slot"][l4]),
    )
    return {
        "rows_inconsistent": inconsistent,
        "rows_wrong": wrong,
        "names_not_one_to_one": sum(parts),
        "_program": (rows, direction, prog),
    }


def expected_counters(ref, program, pool, weights, shape, kg: int):
    """The counter block the window should have accumulated: each
    pool row's hits, as often as the row was replayed
    (weights[direction][row]), at the program's own entry for the hit
    (its L4 slot, or kg + its identity index for an L3 hit)."""
    rows, direction, prog = program
    kind = ref["match_kind"][rows]
    l4 = (kind == R.MATCH_L4) | (kind == R.MATCH_L4_WILD)
    hit = l4 | (kind == R.MATCH_L3)
    col = np.where(l4, prog["l4_slot"], kg + prog["sec_id"])
    w = np.where(direction == 0, weights[0][rows], weights[1][rows])
    hit &= col < shape[2]
    acc = np.zeros(shape, np.int64)
    np.add.at(
        acc,
        (pool["ep_index"][rows][hit].astype(np.int64), direction[hit],
         col[hit]),
        w[hit],
    )
    return acc
