"""What decides `correct`, on the CPU at a tiny size: a sound run
passes, the control (the reference with one guarantee of the
configuration broken, put in the program's place) fails, and so does
a run with the timed path broken underneath in each way the cell can
break.  The harness's look for a chip is skipped: run_cell is given
the CPU device.  (On one chip there is no exchange between chips to
leave out.)  The faults below break the replay loop's fused program;
a cell of another loop brings its own."""

import os
import time

import jax
import jax.numpy as jnp
import pytest

from benchmark import harness as H

SPEC = H.load_json(os.path.join(H.ROOT, "BENCHMARK.json"))
CELLS = [w["name"] for w in SPEC["workloads"]]
TINY = dict(rules=1500, endpoints=8, identities=1024, pool=3000)


def run(cell: str, seed: int = 3, control: bool = False) -> dict:
    wl, cfg, traffic = H.find_cell(SPEC, cell)
    cfg.update(TINY)
    traffic.update(pairs_per_launch=2, tuples_per_direction=2048)
    return H.run_cell(SPEC, wl, cfg, traffic, seed, 1.0, False,
                      jax.devices()[:1], time.perf_counter(),
                      control=control)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_passes(cell):
    out = run(cell)
    assert out["correct"], out["checks"]
    assert all(c["value"] == 0 for c in out["checks"].values())
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails(cell):
    """The harness itself reports the control as not correct, while
    the program's own readings in the same run are sound."""
    out = run(cell, control=True)
    assert not out["correct"], out["checks"]
    assert all(v == 0 for v in out["program_checks"].values())
    assert out["checks"]["rows_wrong"]["value"] > 0


def test_unknown_loop_refused():
    wl, cfg, traffic = H.find_cell(SPEC, CELLS[0])
    traffic["loop"] = "no_such_loop"
    with pytest.raises(SystemExit, match="no_such_loop"):
        H.run_cell(SPEC, wl, dict(cfg, **TINY), traffic, 3, 1.0, False,
                   jax.devices()[:1], time.perf_counter(), world=object())


# -- faults in the replay's fused program ----------------------------------


def _wrap_persistent(monkeypatch, fault):
    from cilium_tpu.engine import datapath

    orig = datapath.persistent_pair_program

    def patched(k):
        fn = orig(k)

        def faulty(tables, pairs, acc, telem):
            return fault(fn, tables, pairs, acc, telem)

        return faulty

    monkeypatch.setattr(datapath, "persistent_pair_program", patched)


def _answer_altered(fn, tables, pairs, acc, telem):
    outs_i, outs_e, acc, telem = fn(tables, pairs, acc, telem)
    outs_i.allowed = outs_i.allowed.at[0, 0].set(1 - outs_i.allowed[0, 0])
    return outs_i, outs_e, acc, telem


def _half_left_out(fn, tables, pairs, acc, telem):
    half = pairs.shape[-1] // 2
    outs_i, outs_e, acc, telem = fn(tables, pairs[..., :half], acc, telem)
    pad = jax.tree.map(
        lambda a: jnp.concatenate([a, jnp.zeros_like(a)], axis=-1), (outs_i, outs_e)
    )
    return pad[0], pad[1], acc, telem


def _state_unchanged(fn, tables, pairs, acc, telem):
    keep = (jnp.array(acc, copy=True), jnp.array(telem, copy=True))
    outs_i, outs_e, _, _ = fn(tables, pairs, acc, telem)
    return outs_i, outs_e, keep[0], keep[1]


@pytest.mark.parametrize(
    "fault", [_answer_altered, _half_left_out, _state_unchanged],
    ids=["answer_altered", "half_left_out", "state_unchanged"],
)
def test_replay_fault_fails(monkeypatch, fault):
    _wrap_persistent(monkeypatch, fault)
    out = run(CELLS[0])
    assert not out["correct"], out["checks"]
