"""2D-mesh (batch × identity-table) sharded evaluator correctness.

`engine.sharded.make_mesh_evaluator` shards the allow-bit word axis of
the PolicyTables across the `table` mesh axis and combines probe hits
with a psum — the TPU analog of the cluster-wide correctness guarantee
in pkg/kvstore/allocator/allocator.go:423 (every node computes the same
verdict from the same distributed state).  These tests run on the
8-virtual-device CPU mesh forced by conftest.py and check:

  * 4x2 and 2x4 meshes agree bit-for-bit with the host oracle and with
    the single-device kernel;
  * the sharded L3/L4 packet counters equal the single-device ones;
  * a multi-word-per-shard identity universe (identity_pad=256 → 8
    words → 4 words per shard at table=2) exercises the shard-offset
    arithmetic (sharded.py:96-99) beyond one word per shard.
"""

import copy

import numpy as np
import pytest

import jax

from cilium_tpu.compiler.tables import compile_map_states
from cilium_tpu.engine.oracle import evaluate_batch_oracle
from cilium_tpu.engine.sharded import make_mesh_evaluator
from cilium_tpu.engine.verdict import (
    TupleBatch,
    _verdict_kernel_with_counters,
    evaluate_batch,
)

from tests.test_verdict_engine import random_map_state, random_tuples

# Spread identities over many bit-words: dense cluster-scope ids plus
# reserved ones, > 64 distinct ids → several 32-bit words.
WIDE_IDS = [1, 2, 3, 4, 5] + [256 + i for i in range(120)] + [65536, 70000]


def _mesh(dp, tp):
    devs = jax.devices()
    assert len(devs) == 8, "conftest must force 8 virtual devices"
    return jax.sharding.Mesh(
        np.array(devs).reshape(dp, tp), ("batch", "table")
    )


def _build(seed, n_eps=3, identity_pad=256, batch=768):
    rng = np.random.default_rng(seed)
    states = [
        random_map_state(rng, WIDE_IDS, n_l4=16, n_l3=24)
        for _ in range(n_eps)
    ]
    tables = compile_map_states(
        states, WIDE_IDS, identity_pad=identity_pad, filter_pad=16
    )
    t = random_tuples(rng, batch, n_eps, WIDE_IDS)
    return states, tables, t


@pytest.mark.parametrize("dp,tp", [(4, 2), (2, 4)])
@pytest.mark.parametrize("seed", [0, 1])
def test_mesh_matches_oracle(dp, tp, seed):
    states, tables, t = _build(seed)
    mesh = _mesh(dp, tp)

    want_allow, want_proxy, want_kind = evaluate_batch_oracle(
        copy.deepcopy(states), **t
    )

    step = make_mesh_evaluator(mesh)
    got, _, _ = step(tables, TupleBatch.from_numpy(**t))

    np.testing.assert_array_equal(np.asarray(got.allowed), want_allow)
    np.testing.assert_array_equal(np.asarray(got.proxy_port), want_proxy)
    np.testing.assert_array_equal(np.asarray(got.match_kind), want_kind)


@pytest.mark.parametrize("dp,tp", [(4, 2), (2, 4)])
def test_mesh_counters_match_single_device(dp, tp):
    _, tables, t = _build(seed=7)
    mesh = _mesh(dp, tp)
    batch = TupleBatch.from_numpy(**t)

    ref_v, ref_l4, ref_l3 = jax.jit(_verdict_kernel_with_counters)(
        tables, batch
    )
    got_v, got_l4, got_l3 = make_mesh_evaluator(mesh)(tables, batch)

    np.testing.assert_array_equal(
        np.asarray(got_v.allowed), np.asarray(ref_v.allowed)
    )
    np.testing.assert_array_equal(np.asarray(got_l4), np.asarray(ref_l4))
    np.testing.assert_array_equal(np.asarray(got_l3), np.asarray(ref_l3))
    # the workload actually produced hits (the test isn't vacuous)
    assert int(np.asarray(got_l4).sum()) + int(np.asarray(got_l3).sum()) > 0


def test_traced_dispatch_per_chip_spans():
    """engine.sharded.traced_dispatch: verdicts pass through
    untouched, jit cache hits/misses are counted per call, and each
    dispatch lands a mesh.dispatch span carrying the mesh's chip count
    and the batch's rows."""
    from cilium_tpu import tracing
    from cilium_tpu.engine.sharded import traced_dispatch
    from cilium_tpu.metrics import registry as metrics

    states, tables, t = _build(seed=3)
    mesh = _mesh(4, 2)
    batch = TupleBatch.from_numpy(**t)
    want, _, _ = make_mesh_evaluator(mesh)(tables, batch)

    tracer = tracing.Tracer(seed=55)
    site = "engine.sharded.test"
    hits0 = metrics.jit_cache_hits.get(site)
    miss0 = metrics.jit_cache_misses.get(site)
    step = traced_dispatch(
        make_mesh_evaluator(mesh), mesh, site=site
    )
    tok = tracing._current.set(None)
    old_tracer, tracing.tracer = tracing.tracer, tracer
    try:
        got, _, _ = step(tables, batch)
        got2, _, _ = step(tables, batch)
    finally:
        tracing.tracer = old_tracer
        tracing._current.reset(tok)
    np.testing.assert_array_equal(
        np.asarray(got.allowed), np.asarray(want.allowed)
    )
    np.testing.assert_array_equal(
        np.asarray(got2.allowed), np.asarray(want.allowed)
    )
    assert metrics.jit_cache_misses.get(site) == miss0 + 1
    assert metrics.jit_cache_hits.get(site) == hits0 + 1

    parents = [
        s for s in tracer.snapshot() if s.name == "mesh.dispatch"
    ]
    assert len(parents) == 2
    for parent in parents:
        assert parent.attrs["chips"] == 8
        assert parent.attrs["rows"] == len(t["identity"])


def test_multiword_per_shard_universe():
    """identity_pad=256 → 8 bit-words; at table=2 each shard owns 4
    words, so word-offset clipping and per-shard L3 counter slices are
    exercised across word boundaries."""
    states, tables, t = _build(seed=3, identity_pad=256)
    assert tables.l3_allow_bits.shape[-1] == 8  # 256/32 words
    mesh = _mesh(4, 2)

    want_allow, _, _ = evaluate_batch_oracle(copy.deepcopy(states), **t)
    batch = TupleBatch.from_numpy(**t)
    got, l4c, l3c = make_mesh_evaluator(mesh)(tables, batch)

    np.testing.assert_array_equal(np.asarray(got.allowed), want_allow)
    # every allowed L3-match lands exactly one counter bump
    single = evaluate_batch(tables, batch)
    np.testing.assert_array_equal(
        np.asarray(got.match_kind), np.asarray(single.match_kind)
    )
    hits = int(np.asarray(l4c).sum() + np.asarray(l3c).sum())
    allows = int(np.asarray(got.allowed).sum())
    assert hits == allows


def test_table_axis_one_degenerates():
    """table=1 (pure batch-parallel 8x1 mesh) must equal the
    single-device kernel too — the psum over a singleton axis is the
    identity."""
    _, tables, t = _build(seed=11)
    mesh = _mesh(8, 1)
    batch = TupleBatch.from_numpy(**t)
    got, _, _ = make_mesh_evaluator(mesh)(tables, batch)
    ref = evaluate_batch(tables, batch)
    np.testing.assert_array_equal(
        np.asarray(got.allowed), np.asarray(ref.allowed)
    )
    np.testing.assert_array_equal(
        np.asarray(got.proxy_port), np.asarray(ref.proxy_port)
    )


@pytest.mark.parametrize("dp,tp", [(4, 2), (8, 1)])
def test_mesh_telemetry_per_chip_bit_identical(dp, tp):
    """collect_telemetry: each batch shard's [2, TELEM_COLS] rows
    equal a host telemetry_masks fold of that shard's slice, the
    chip-sum equals the whole-batch fold, and verdicts stay
    bit-identical to the plain evaluator."""
    from cilium_tpu.engine.verdict import TELEM_COLS, telemetry_masks

    states, tables, t = _build(seed=5)
    mesh = _mesh(dp, tp)
    batch = TupleBatch.from_numpy(**t)
    v, l4c, l3c, per_chip = make_mesh_evaluator(
        mesh, collect_telemetry=True
    )(tables, batch)
    per_chip = np.asarray(per_chip).astype(np.uint64)
    assert per_chip.shape == (dp, 2, TELEM_COLS)

    ref = evaluate_batch(tables, batch)
    np.testing.assert_array_equal(
        np.asarray(v.allowed), np.asarray(ref.allowed)
    )
    allowed = np.asarray(v.allowed)
    kind = np.asarray(v.match_kind)
    proxy = np.asarray(v.proxy_port)
    dirs = np.asarray(t["direction"])
    z = np.zeros(len(allowed), np.int32)
    masks = telemetry_masks(z, z, kind, allowed, z, proxy, z, z, xp=np)
    b = len(allowed)
    shard = b // dp
    for chip in range(dp):
        sl = slice(chip * shard, (chip + 1) * shard)
        for d in (0, 1):
            in_dir = dirs[sl] == d
            for c, m in enumerate(masks):
                assert per_chip[chip, d, c] == int(
                    np.sum(m[sl] & in_dir)
                ), (chip, d, c)
    total = per_chip.sum(axis=0)
    for d in (0, 1):
        in_dir = dirs == d
        for c, m in enumerate(masks):
            assert total[d, c] == int(np.sum(m & in_dir))


def test_mesh_telemetry_one_scrape_covers_mesh():
    """The ROADMAP multi-chip aggregation item, end to end: fold the
    per-chip histogram once, serve the registry, and ONE scrape
    reports mesh-total counters plus per-chip `chip`-labeled rows
    that sum to the total."""
    import urllib.request

    from cilium_tpu.engine.verdict import (
        TELEM_DENIED,
        TELEM_FORWARDED,
    )
    from cilium_tpu.health import start_metrics_server
    from cilium_tpu.metrics import Registry
    from cilium_tpu.telemetry import fold_telemetry_per_chip

    _, tables, t = _build(seed=13)
    mesh = _mesh(4, 2)
    batch = TupleBatch.from_numpy(**t)
    _, _, _, per_chip = make_mesh_evaluator(
        mesh, collect_telemetry=True
    )(tables, batch)
    per_chip = np.asarray(per_chip).astype(np.uint64)

    registry = Registry()
    total = fold_telemetry_per_chip(per_chip, registry=registry)
    np.testing.assert_array_equal(total, per_chip.sum(axis=0))

    server = start_metrics_server(port=0, registry=registry)
    try:
        host, port = server.server_address
        text = (
            urllib.request.urlopen(
                f"http://{host}:{port}/metrics", timeout=10
            )
            .read()
            .decode()
        )
    finally:
        server.shutdown()

    # mesh-total counters in the same scrape
    fwd_total = sum(
        registry.forward_count.get(d) for d in ("INGRESS", "EGRESS")
    )
    assert fwd_total == int(total[:, TELEM_FORWARDED].sum()) > 0
    assert int(total[:, TELEM_DENIED].sum()) > 0
    assert "cilium_forward_count_total" in text
    assert "cilium_datapath_telemetry_per_chip_total" in text
    # the per-chip rows sum to the mesh total, per column
    for column, want in (
        ("forwarded", int(total[:, TELEM_FORWARDED].sum())),
        ("denied", int(total[:, TELEM_DENIED].sum())),
    ):
        got = sum(
            registry.telemetry_per_chip.get(str(chip), column, d)
            for chip in range(per_chip.shape[0])
            for d in ("INGRESS", "EGRESS")
        )
        assert got == want, column
    # every chip exposed its own labeled row
    for chip in range(per_chip.shape[0]):
        assert f'chip="{chip}"' in text


def test_scaled_world_fused_mesh_vs_host_oracle():
    """Config5-SHAPED world (thousands of identities through the real
    control plane, mixed rules, CT/LB/prefilter populated): the FULL
    fused datapath over a batch-sharded mesh must stay bit-identical
    to the composed HOST oracle, and the bare lattice over the 2D
    (batch x table) mesh must match single-device — with the table
    axis holding MANY bit-words per shard (the >HBM sharding shape of
    SURVEY §2.9)."""
    import __graft_entry__ as ge
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from cilium_tpu.engine.datapath import (
        FlowBatch,
        _datapath_kernel_accum,
    )
    from cilium_tpu.engine.hostpath import composed_oracle
    from cilium_tpu.engine.verdict import make_counter_buffers

    tables, pool, oracle_ctx, states = ge._build_scaled_world(
        n_identities=2048, n_rules=256, n_endpoints=4
    )
    stables = tables.policy
    n_ids = stables.id_table.shape[0]
    assert (n_ids // 32) % 2 == 0
    assert n_ids // 32 // 2 >= 16  # many words per shard

    devs = jax.devices("cpu")[:8]
    mesh2d = Mesh(np.array(devs).reshape(4, 2), ("batch", "table"))
    rng = np.random.default_rng(9)
    real_ids = stables.id_table[
        stables.id_table != np.uint32(0xFFFFFFFF)
    ]
    t = dict(
        ep_index=rng.integers(0, stables.l4_meta.shape[0], size=512),
        identity=rng.choice(real_ids, size=512),
        dport=rng.integers(1, 30000, size=512),
        proto=rng.choice([6, 17], size=512),
        direction=rng.integers(0, 2, size=512),
    )
    batch = TupleBatch.from_numpy(**t)
    got, l4c, l3c = make_mesh_evaluator(mesh2d)(stables, batch)
    ref = evaluate_batch(stables, batch)
    np.testing.assert_array_equal(
        np.asarray(got.allowed), np.asarray(ref.allowed)
    )

    # full fused path, batch-sharded, vs the composed host oracle
    mesh1d = Mesh(np.array(devs), ("batch",))
    replicated = NamedSharding(mesh1d, P())
    sharded = NamedSharding(mesh1d, P("batch"))
    b = (len(pool["saddr"]) // 8) * 8
    flows = FlowBatch.from_numpy(
        **{k: pool[k][:b] for k in (
            "ep_index", "saddr", "daddr", "sport", "dport", "proto",
            "direction", "is_fragment",
        )}
    )
    step = jax.jit(
        _datapath_kernel_accum,
        in_shardings=(replicated, sharded, replicated),
        donate_argnums=(2,),
    )
    out, _ = step(
        jax.device_put(tables, replicated),
        jax.device_put(flows, sharded),
        jax.device_put(make_counter_buffers(stables), replicated),
    )
    sample = rng.integers(0, b, size=256)
    want_allow, want_proxy, _ = composed_oracle(
        oracle_ctx, states, pool, list(sample)
    )
    np.testing.assert_array_equal(
        np.asarray(out.allowed)[sample], want_allow
    )
    np.testing.assert_array_equal(
        np.asarray(out.proxy_port)[sample], want_proxy
    )
