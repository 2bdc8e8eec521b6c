"""benchmark/program_trace.py and its readers on a small profiler trace
recorded on the CPU (data/program_trace.xplane.pb and the program's
optimized HLO, data/program_trace.hlo.txt, made by
data/make_program_trace.py).  The CPU has no device plane, so the
tests name the executor threads' lines as the device's operations; the
expected numbers are sums of the trace's event durations, by hand."""

import os
from types import SimpleNamespace

import pytest

from benchmark import harness
from benchmark import program_trace as P

DATA = os.path.join(os.path.dirname(__file__), "data")
XPLANE = os.path.join(DATA, "program_trace.xplane.pb")
EXEC_LINE = "tf_XLAPjRtCpuClient"
WINDOW_NS = 171481858
# the program's operations in the window, per launch
SORT_1 = 173099 + 38055185 + 34217 + 20211947 + 209856 + 18778985
LATTICE = 119399 + 234345 + 494792  # add_bitcast_fusion: ct gather fused in
SORT_0 = 159365 + 33692470 + 36804 + 15770846 + 157106 + 15746775


@pytest.fixture(scope="module")
def hlo():
    with open(os.path.join(DATA, "program_trace.hlo.txt")) as fh:
        return fh.read()


@pytest.fixture(scope="module")
def pt():
    return P.load(
        XPLANE, "replay.window", device_prefix="/host:CPU",
        ops_line=EXEC_LINE, modules_line=EXEC_LINE,
    )


def test_stage_of_op_names():
    assert P.stage_of("jit(program)/while/body/closed_call/ct/gather") == "ct"
    # innermost stage wins; a merged name reads its first part
    assert P.stage_of("jit(p)/lattice/jit(f)/accounting/add") == "accounting"
    assert P.stage_of("jit(p)/lb/xor;ct/broadcast_in_dim") == "lb"
    assert P.stage_of("jit(program)/jit(sort)/sort") is None
    assert P.stage_of("jit(p)/ctx/add") is None
    assert P.stage_of(None) is None


def test_hlo_maps_instructions_and_fusions(hlo):
    names = P.hlo_op_names(hlo)
    assert names["add_bitcast_fusion"] == "jit(program)/lattice/add"
    assert names["sort.0"] == "jit(program)/accounting/jit(sort)/sort"
    assert names["sort.1"] == "jit(program)/jit(sort)/sort"
    # the fusion's root is the lattice add; the ct gather is fused in
    assert P.fused_stages(hlo)["add_bitcast_fusion"] == ["ct", "lattice"]
    stages = P.hlo_stages(hlo)
    assert stages["add_bitcast_fusion"] == "lattice"
    assert stages["sort.0"] == "accounting"
    assert stages["sort.1"] is None


def test_fusions_and_inserted_instructions_take_a_stage():
    """The TPU compiler's rewrite of the counter scatter leaves the
    fusion's root without metadata: the fusion goes to the one stage
    fused into it, and stays unscoped where there are several.  An
    instruction with no metadata (a layout copy the compiler inserted)
    goes to its nearest producer's stage; one whose op_name names no
    stage (the scan's output stacking) stays unscoped."""
    hlo = "\n".join([
        "%fused_a (p: u32[8]) -> u32[8] {",
        '  %r.1 = u32[8]{0} reshape(%p), metadata={op_name="p/accounting/c"}',
        "  ROOT %scatter.3 = u32[8]{0} scatter(%r.1)",
        "}",
        "%fused_b (p: u32[8]) -> u32[8] {",
        '  %g.1 = u32[8]{0} gather(%p), metadata={op_name="p/ct/gather"}',
        '  %a.1 = u32[8]{0} add(%g.1), metadata={op_name="p/lattice/add"}',
        "  ROOT %copy.2 = u32[8]{0} copy(%a.1)",
        "}",
        "ENTRY %main (x: u32[8]) -> u32[8] {",
        "  %fusion.1 = u32[8]{0} fusion(%x), kind=kCustom, calls=%fused_a",
        "  %fusion.2 = u32[8]{0} fusion(%fusion.1), calls=%fused_b",
        "  %copy.3 = u32[8]{0} copy(%fusion.2)",
        "  %copy.4 = u32[8]{0} copy(%copy.3)",
        "  ROOT %dus.5 = u32[8]{0} dynamic-update-slice(%copy.4), "
        'metadata={op_name="p/while/body/dynamic_update_slice"}',
        "}",
    ])
    stages = P.hlo_stages(hlo)
    assert stages["fusion.1"] == "accounting"
    assert stages.get("fusion.2") is None
    # through the unscoped fusion to the accounting one
    assert stages["copy.3"] == stages["copy.4"] == "accounting"
    assert stages["dus.5"] is None
    assert dict(P._OPCODE.findall(hlo))["fusion.2"] == "fusion"


def test_program_ops_and_spans(pt):
    assert pt.window_ns == WINDOW_NS
    assert pt.n_devices == 1
    # other modules (the eager slices) and the `end:` markers left out
    assert pt.op_ns() == {
        "sort.1": SORT_1, "add_bitcast_fusion": LATTICE, "sort.0": SORT_0,
    }
    assert pt.span_ms("datapath.stack") == pytest.approx(
        (95800 + 74208 + 115161) / 3 / 1e6
    )
    assert pt.span_ms("datapath.upload") == pytest.approx(
        (912111 + 439800 + 546953) / 3 / 1e6
    )
    assert pt.span_ms("datapath.missing") is None


def test_stage_attribution_by_root(pt, hlo):
    by_stage = pt.stage_ns(P.hlo_stages(hlo))
    assert by_stage == {None: SORT_1, "lattice": LATTICE,
                        "accounting": SORT_0}


def test_idle_under_spans_by_hand(pt):
    # stack: no operation runs.  upload: the H2D dispatch runs in the
    # first two.  enqueue: idle until the program's first sort starts,
    # and in the gap between its two events
    staging = (
        95800 + (912111 - 43484) + (1275177 - 1150345) + (1448887 - 1448276)
        + 74208 + (439800 - 32581) + (96416987 - 96372039)
        + (96451791 - 96451204)
        + 115161 + 546953 + 102206
    )
    assert pt.idle_under(P.STAGING) == pytest.approx(
        100.0 * staging / WINDOW_NS
    )
    # outputs: the first launch's spans the gaps between the program's
    # operations and the idle time after it; the second lies under the
    # program's first sort; nothing runs under the third
    outputs = (2324 + 1014 + 609 + 2790 + (95798627 - 73482206)) + 1454641
    assert pt.idle_under(P.OUTPUTS) == pytest.approx(
        100.0 * outputs / WINDOW_NS
    )
    assert pt.idle_under(("datapath.missing",)) is None
    assert P.overlap([(0, 10), (20, 30)], [(5, 25)]) == 10


def _ctx(module_ns, launches, tuples_per_launch):
    return SimpleNamespace(
        reduced=SimpleNamespace(
            program_ns=lambda m: (module_ns, launches)
        ),
        loop=SimpleNamespace(
            window_span="replay.window",
            tuples_per_launch=tuples_per_launch,
        ),
    )


def test_readers_on_the_fixture(pt, hlo, monkeypatch):
    monkeypatch.setattr(P, "for_run", lambda ctx: pt)
    monkeypatch.setattr(P, "program_hlo", lambda loop: hlo)
    pt.memo.clear()
    module_ns = SORT_1 + LATTICE + SORT_0 + 1000
    tuples = 3 * 65536
    ctx = _ctx(module_ns, 3, 65536)
    got = {
        name: harness.load_module("layer_metrics", name).read(ctx)
        for name in (
            "fused.ct_ns_per_tuple", "fused.lb_ns_per_tuple",
            "fused.ipcache_ns_per_tuple", "fused.lattice_ns_per_tuple",
            "fused.accounting_ns_per_tuple", "fused.other_ns_per_tuple",
            "staging.stack_ms_per_launch", "staging.upload_ms_per_launch",
            "device_idle_pct.staging", "device_idle_pct.outputs",
        )
    }
    assert got["fused.ct_ns_per_tuple"] == 0.0
    assert got["fused.lattice_ns_per_tuple"] == LATTICE / tuples
    assert got["fused.accounting_ns_per_tuple"] == SORT_0 / tuples
    assert got["fused.other_ns_per_tuple"] == (SORT_1 + 1000) / tuples
    six = sum(v for k, v in got.items() if k.startswith("fused."))
    assert six == pytest.approx(module_ns / tuples)
    assert got["staging.stack_ms_per_launch"] == pt.span_ms("datapath.stack")
    assert got["device_idle_pct.outputs"] == pt.idle_under(P.OUTPUTS)
    assert all(v is not None for v in got.values())


def test_readers_silent_without_the_programs_points(pt, monkeypatch):
    """A program with no stage scope and no datapath.* spans (an older
    commit) and an untraced run read as nothing."""
    bare = P.ProgramTrace(
        window=pt.window, n_devices=1, busy=pt.busy, ops=list(pt.ops),
    )
    monkeypatch.setattr(P, "for_run", lambda ctx: bare)
    monkeypatch.setattr(P, "program_hlo", lambda loop: "")
    ctx = _ctx(1.0, 3, 65536)
    for name in ("fused.ct_ns_per_tuple", "fused.other_ns_per_tuple",
                 "staging.upload_ms_per_launch", "device_idle_pct.staging"):
        assert harness.load_module("layer_metrics", name).read(ctx) is None
    monkeypatch.undo()
    untraced = SimpleNamespace(reduced=None, loop=None)
    assert P.for_run(untraced) is None
    assert P.stages_per_tuple(untraced) is None
