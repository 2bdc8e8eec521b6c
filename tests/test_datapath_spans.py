"""The persistent fused program's own measurement points: the host
spans of PersistentPairDispatcher.submit and the stage scopes of the
fused pipeline (engine/datapath.py), at a tiny world on the CPU."""

import re

import jax
import jax.numpy as jnp
import numpy as np

from cilium_tpu import tracing
from cilium_tpu.engine.datapath import (
    PersistentPairDispatcher,
    persistent_pair_program,
)
from cilium_tpu.engine.verdict import (
    make_counter_buffers,
    make_telemetry_buffers,
)
from tests.test_subword import _fused_subword_world, _mk_pair

STAGES = (
    "unpack", "prefilter", "ct", "lb", "ipcache", "lattice", "verdict",
    "accounting",
)
CHILDREN = [
    "datapath.stack", "datapath.upload", "datapath.enqueue",
    "datapath.outputs",
]


def _carry(sub):
    return (
        jax.device_put(make_counter_buffers(sub.policy)),
        jax.device_put(make_telemetry_buffers()),
    )


def test_persistent_launch_spans():
    """One datapath.launch per launch, with its four children in
    order and inside it; the staging submit records nothing."""
    _, sub, _ = _fused_subword_world(7)
    rng = np.random.default_rng(2)
    half = 64
    pairs = [_mk_pair(rng, half) for _ in range(4)]
    tracer = tracing.Tracer(seed=61)
    tok = tracing._current.set(None)
    old, tracing.tracer = tracing.tracer, tracer
    try:
        disp = PersistentPairDispatcher(sub, 2, *_carry(sub))
        assert disp.submit(pairs[0]) == []
        assert tracer.snapshot() == []
        outs = disp.submit(pairs[1])
        outs += disp.submit(pairs[2]) + disp.submit(pairs[3])
        jax.block_until_ready(outs)
    finally:
        tracing.tracer = old
        tracing._current.reset(tok)
    assert len(outs) == 4 and disp.launches == 2
    # the first launch also records its jit.compile under the enqueue
    spans = [s for s in tracer.snapshot() if s.name.startswith("datapath.")]
    launches = [s for s in spans if s.name == "datapath.launch"]
    assert len(launches) == 2
    assert len(spans) == 2 * (1 + len(CHILDREN))
    for launch in launches:
        assert launch.attrs == {
            "pairs": 2, "tuples": 2 * 2 * half,
            "bytes": 2 * pairs[0].nbytes,
        }
        kids = sorted(
            (s for s in spans if s.parent_id == launch.span_id),
            key=lambda s: s.start,
        )
        assert [s.name for s in kids] == CHILDREN
        assert all(s.trace_id == launch.trace_id for s in kids)
        end = launch.start + launch.duration
        t = launch.start
        for s in kids:
            assert t <= s.start and s.start + s.duration <= end
            t = s.start + s.duration


def test_persistent_program_carries_stage_scopes():
    """Every stage scope of the fused pipeline names some op of the
    compiled persistent program (op_name metadata, where a profiler
    trace's reduction finds it)."""
    _, sub, _ = _fused_subword_world(7)
    acc, telem = _carry(sub)
    pairs = jax.ShapeDtypeStruct((2, 2, 4, 64), jnp.uint32)
    text = (
        persistent_pair_program(2)
        .lower(sub, pairs, acc, telem)
        .compile()
        .as_text()
    )
    scopes = set()
    for op_name in re.findall(r'op_name="([^"]*)"', text):
        scopes.update(op_name.split(";")[0].split("/"))
    assert scopes >= set(STAGES), sorted(set(STAGES) - scopes)
