"""Compile-only guards for the chip: the main path's programs compiled
for a described (not attached) TPU v5e at real widths.  The TPU
compiler refuses here what the chip would refuse (out of memory,
tiling), at no chip time.  Nothing runs, so nothing is timed.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and every xdist
worker imports this file.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

B = 65_536  # tuples per direction
K = 4  # the benchmark's pairs per persistent launch


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure: cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    # a described-chip compile written to the persistent cache cannot
    # be read back without the chip: keep the cache off around these
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            np.shape(x), np.asarray(x).dtype, sharding=sharding
        ),
        tree,
    )


@pytest.fixture(scope="module")
def headline(one_chip):
    """The scaled world's headline tables (hot policy plane, sub-word
    layouts) and counter/telemetry carries, as shapes on one chip."""
    import __graft_entry__
    from cilium_tpu.compiler.tables import split_hot
    from cilium_tpu.engine.datapath import (
        DatapathTables,
        subword_datapath_tables,
    )
    from cilium_tpu.engine.verdict import (
        make_counter_buffers,
        make_telemetry_buffers,
    )

    tables, _, _, _ = __graft_entry__._scaled_world_cached()
    hot, _ = subword_datapath_tables(
        DatapathTables(
            prefilter=tables.prefilter, ipcache=tables.ipcache,
            ct=tables.ct, lb=tables.lb, policy=split_hot(tables.policy),
        )
    )
    return {
        "tables": _shapes(hot, one_chip),
        "policy": _shapes(tables.policy, one_chip),
        "acc": _shapes(make_counter_buffers(tables.policy), one_chip),
        "telem": _shapes(make_telemetry_buffers(), one_chip),
    }


def _compile(jitted, *args):
    ma = jitted.lower(*args).compile().memory_analysis()
    total = (
        ma.argument_size_in_bytes + ma.output_size_in_bytes
        + ma.temp_size_in_bytes
    )
    assert 0 < total < 16e9, ma


@pytest.mark.parametrize("program", ["pair", "persistent"])
def test_fused_programs_compile_for_v5e(program, headline, one_chip):
    from cilium_tpu.engine.datapath import (
        datapath_step_accum_pair_telem_packed4_stacked,
        persistent_pair_program,
    )

    if program == "pair":
        fn = datapath_step_accum_pair_telem_packed4_stacked
        staged = jax.ShapeDtypeStruct(
            (2, 4, B), jnp.uint32, sharding=one_chip
        )
    else:
        fn = persistent_pair_program(K)
        staged = jax.ShapeDtypeStruct(
            (K, 2, 4, B), jnp.uint32, sharding=one_chip
        )
    _compile(
        fn, headline["tables"], staged, headline["acc"],
        headline["telem"],
    )


def test_l7_program_compiles_for_v5e(one_chip):
    """The L7 program (l7.fleet.fleet_l7_program) over the persistent
    program's outputs at K pairs of B tuples per direction, for a small
    fleet of HTTP (Host and header rules among them) and Kafka rules."""
    from cilium_tpu.engine.datapath import persistent_pair_program
    from cilium_tpu.engine.verdict import (
        make_counter_buffers,
        make_telemetry_buffers,
    )
    from cilium_tpu.l7.fleet import L7_COUNTS, fleet_l7_program
    from tests.test_l7_datapath import build_world, request_table

    _, tables, _, fleet, _ = build_world()
    pairs = jax.ShapeDtypeStruct((K, 2, 4, B), jnp.uint32)
    outs = jax.eval_shape(
        persistent_pair_program(K), tables, pairs,
        make_counter_buffers(tables.policy), make_telemetry_buffers(),
    )[:2]
    on_chip = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        (pairs, outs),
    )
    program, args = fleet_l7_program(fleet)
    _compile(
        program, _shapes(args, one_chip),
        _shapes(request_table(fleet), one_chip), on_chip[0], *on_chip[1],
        _shapes(np.zeros(len(L7_COUNTS), np.uint32), one_chip),
        *[jax.ShapeDtypeStruct((2, B), jnp.uint32, sharding=one_chip)] * K,
    )


def test_lattice_evaluate_batch_compiles_for_v5e(headline, one_chip):
    from cilium_tpu.engine.verdict import TupleBatch, evaluate_batch

    def col(dtype):
        return jax.ShapeDtypeStruct((B,), dtype, sharding=one_chip)

    batch = TupleBatch(
        ep_index=col(jnp.int32), identity=col(jnp.uint32),
        dport=col(jnp.int32), proto=col(jnp.int32),
        direction=col(jnp.int32), is_fragment=col(jnp.bool_),
    )
    _compile(evaluate_batch, headline["policy"], batch)


def test_http_strided_dfa_scan_compiles_for_v5e(one_chip):
    from cilium_tpu.l7.http import (
        HTTPRuleSpec,
        compile_http_rules,
        evaluate_http_batch,
    )

    rules = [
        HTTPRuleSpec(
            identity_indices=[i % 16],
            method=("GET", "POST", "PUT")[i % 3],
            path=f"/api/v{i}/[a-z]+/.*",
        )
        for i in range(64)
    ]
    tables = compile_http_rules(rules, n_identities=16).tables
    assert tables.path_sdfa is not None  # the strided scan, not bytewise

    def scan(m, ml, p, pl, h, hl, idx, known):
        return evaluate_http_batch(tables, m, ml, p, pl, h, hl, idx, known)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    lens = s((B,), jnp.int32)
    _compile(
        jax.jit(scan),
        s((B, 16), jnp.uint8), lens, s((B, 128), jnp.uint8), lens,
        s((B, 64), jnp.uint8), lens, lens, s((B,), jnp.bool_),
    )
