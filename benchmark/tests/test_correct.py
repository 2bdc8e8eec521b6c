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


def tiny_cell(cell: str, **keys):
    """The cell with `keys` set in its configuration, at its world's
    TINY sizes and a small launch."""
    wl, cfg, traffic = H.find_cell(SPEC, cell)
    cfg.update(keys)
    cfg.update(H.world_module(cfg).TINY)
    traffic.update(pairs_per_launch=2, tuples_per_direction=2048)
    return wl, cfg, traffic


def run(cell: str, seed: int = 3, control: bool = False) -> dict:
    wl, cfg, traffic = tiny_cell(cell)
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
    wl, cfg, traffic = tiny_cell(CELLS[0])
    traffic["loop"] = "no_such_loop"
    with pytest.raises(SystemExit, match="no_such_loop"):
        H.run_cell(SPEC, wl, cfg, traffic, 3, 1.0, False,
                   jax.devices()[:1], time.perf_counter(), world=object())


def test_missing_world_refused():
    """A configuration that names a world with no module stops the run
    with a message that names the missing file."""
    wl, cfg, traffic = H.find_cell(SPEC, CELLS[0])
    cfg["world"] = "no_such_world"
    with pytest.raises(SystemExit, match="worlds/no_such_world.py"):
        H.run_cell(SPEC, wl, cfg, traffic, 3, 1.0, False,
                   jax.devices()[:1], time.perf_counter())


def test_default_world():
    """n110, a configuration without a `world` key, is built by
    benchmark/world.py."""
    from benchmark import world as W

    _, cfg, _ = H.find_cell(SPEC, "n110.replay")
    assert "world" not in cfg
    assert H.world_module(cfg) is W
    assert W.__file__ == os.path.join(H.HERE, "world.py")


# A world brought by files alone: most rules HTTP or Kafka, most pool
# flows bound for an L7 rule's port, from benchmark/world.py's helpers.
REDIRECT_HEAVY = '''
from benchmark import world as W

TINY = W.TINY


def build_world(cfg, rng):
    return W.build_world(cfg, rng, rule_cuts=(0.15, 0.15, 0.2, 0.7),
                         pool_mix=dict(W.POOL_MIX, l7_bound=0.7))
'''


def test_world_from_files(tmp_path, monkeypatch):
    """A world module written only as a file, under a `worlds/`
    directory the harness is pointed at, runs through run_cell with the
    replay loop: the sound run passes every check and the control
    fails."""
    from benchmark import reference as R

    (tmp_path / "redirect_heavy.py").write_text(REDIRECT_HEAVY)
    monkeypatch.setitem(H.DIRS, "worlds", str(tmp_path))
    wl, cfg, traffic = tiny_cell(CELLS[0], world="redirect_heavy")
    assert H.world_module(cfg).__file__ == str(tmp_path / "redirect_heavy.py")
    world = H.build_world(cfg)
    kinds = [spec[1] for spec in world.specs]
    assert sum(k in ("http", "kafka") for k in kinds) > 0.5 * len(kinds)
    redirected = R.Reference(world).flows(world.pool, set())["redirect_key"]
    assert (redirected >= 0).mean() > 0.5

    args = (SPEC, wl, cfg, traffic, 3, 1.0, False, jax.devices()[:1])
    out = H.run_cell(*args, time.perf_counter(), world=world)
    assert world.cfg is cfg
    assert out["correct"], out["checks"]
    assert all(c["value"] == 0 for c in out["checks"].values())
    out = H.run_cell(*args, time.perf_counter(), control=True, world=world)
    assert not out["correct"], out["checks"]
    assert all(v == 0 for v in out["program_checks"].values())
    assert out["checks"]["rows_wrong"]["value"] > 0


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
