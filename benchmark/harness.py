"""One run of one benchmark cell.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name: the cell in BENCHMARK.json, its
configuration file (whose optional `world` key names the module that
builds its world, benchmark/worlds/<world>.py, and benchmark/world.py
without it), its traffic file benchmark/traffic/<traffic>.json
(parameters of the loop that its `loop` key names,
benchmark/loops/<loop>.py) and one reader per per-layer metric,
benchmark/layer_metrics/<metric>.py.  Adding a cell, a traffic mix, a
kind of world, a kind of loop or a metric adds files and entries; no
file here changes.  benchmark/world.py states what a world gives and
who reads it.

Measurement refuses any platform but a TPU: no result line, exit code
3.  The last line of stdout is the result object; each number that
decides `correct` is printed beside its limit on the last lines of
stderr and under "checks", the last key of the result.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import sys
import time
from types import SimpleNamespace

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")
TRACE_DIR = os.path.join(HERE, "_out", "trace")
# where load_module finds each kind of module
DIRS = {kind: os.path.join(HERE, kind)
        for kind in ("loops", "layer_metrics", "worlds")}


class NoAccelerator(RuntimeError):
    pass


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(spec: dict, name: str):
    """(workload entry, configuration dict, traffic dict)."""
    wl = next((w for w in spec["workloads"] if w["name"] == name), None)
    if wl is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    entry = next(c for c in spec["configs"] if c["name"] == wl["config"])
    cfg = load_json(os.path.join(ROOT, entry["file"]))
    traffic = load_json(os.path.join(HERE, "traffic", wl["traffic"] + ".json"))
    return wl, cfg, traffic


def cell_metrics(spec: dict, name: str):
    """The end-to-end and the per-layer metrics this cell reports."""
    e2e = [
        m for m in spec["end_to_end"]
        if name in m.get("workloads", [name])
    ]
    moved = {m["name"] for m in e2e}
    layer = [
        m for m in spec["per_layer"]
        if (name in m["workloads"] if "workloads" in m
            else m["moves"] in moved)
    ]
    return e2e, layer


def load_module(kind: str, name: str):
    """benchmark/<kind>/<name>.py: a traffic file's loop
    (kind "loops"), a per-layer metric's reader ("layer_metrics") or a
    configuration's world ("worlds")."""
    path = os.path.join(DIRS[kind], name + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"no {kind} module {name!r}: {path} is missing")
    mod_spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_" + name.replace(".", "_"), path
    )
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def require_tpu(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(
            f"no TPU: JAX found {devs[0].platform!r} devices; the "
            f"benchmark measures on a TPU only"
        )
    if len(devs) < chips:
        raise NoAccelerator(f"{chips} chip(s) asked for, {len(devs)} found")
    return devs[:chips]


class CompileCounter:
    """Counts JAX traces and backend compiles (jax.monitoring) and the
    program's own jit compile seconds (tracing.track_jit)."""

    EVENTS = (
        "/jax/core/compile/backend_compile_duration",
        "/jax/core/compile/jaxpr_trace_duration",
    )

    def __init__(self) -> None:
        import jax.monitoring

        self.events = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, key, duration, **kw) -> None:
        if key in self.EVENTS:
            self.events += 1

    def reading(self) -> tuple:
        from cilium_tpu.metrics import registry as metrics

        total = sum(metrics.jit_compile_seconds.snapshot().values())
        return self.events, total


def fallback_counters(d) -> dict:
    """The resilience fallbacks (as chip_smoke.check_no_fallback)."""
    from cilium_tpu import faultinject
    from cilium_tpu.metrics import registry as metrics

    return {
        "daemon.degraded_batches": d.degraded_batches,
        "degraded_batches_total": metrics.degraded_batches_total.get(),
        "publish_fallback_total": metrics.publish_fallback_total.get(),
        "dispatch_retries_total": metrics.dispatch_retries_total.get(),
        "device_publish_retry_at": d._device_publish_retry_at,
        "fault_sites_armed": int(faultinject.any_armed()),
    }


def say(msg: str) -> None:
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


def world_module(cfg: dict):
    """The module that builds configuration `cfg`'s world:
    benchmark/worlds/<world>.py where `cfg` has a `world` key, else
    benchmark/world.py."""
    if "world" in cfg:
        return load_module("worlds", cfg["world"])
    from benchmark import world as W

    return W


def build_world(cfg: dict):
    world = world_module(cfg).build_world(
        cfg, np.random.default_rng(int(cfg["world_seed"]))
    )
    sizes = " ".join(f"{k}={v}" for k, v in cfg.items() if type(v) is int)
    say(
        f"world {cfg['name']}: {sizes} phases="
        + json.dumps({k: round(v, 3) for k, v in world.timings.items()})
    )
    return world


def run_cell(spec, wl, cfg, traffic, seed, seconds, trace, devs, t_start,
             control: bool = False, world=None) -> dict:
    """Build, warm, measure, check.  Returns the result object (the
    contract's keys, with "checks" last).  With `control` the checks
    are the control's (the loop's check with the control put in the
    program's place) and the program's own readings come under
    "program_checks"."""
    import jax

    counter = CompileCounter()
    if world is None:
        world = build_world(cfg)
    loop_mod = load_module("loops", traffic["loop"])
    window_s = float(traffic["trace_seconds"]) if trace else float(seconds)
    phases = {"start_to_world_s": time.perf_counter() - t_start}
    t0 = time.perf_counter()
    world.cfg = cfg
    loop = loop_mod.build(world, traffic, seed, say)
    phases["traffic_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    loop.warm()
    phases["warm_s"] = time.perf_counter() - t0
    say("set-up phases: " + json.dumps(
        {k: round(v, 3) for k, v in phases.items()}
    ))

    trace_path = None
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # Python call tracing stalls the host
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    compiles0 = counter.reading()
    fallbacks0 = fallback_counters(world.daemon)
    setup_s = time.perf_counter() - t_start
    stats = loop.run(window_s)
    compiles1 = counter.reading()
    if trace:
        jax.profiler.stop_trace()
        from benchmark import trace as T

        trace_path = T.find_xplane(TRACE_DIR)
    peak = 0
    for dv in devs:
        st = dv.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))

    common = {
        "compiles_in_window": (
            (compiles1[0] - compiles0[0])
            + int(compiles1[1] != compiles0[1]), 0
        ),
        "fallbacks_moved": (
            sum(
                1 for k, v in fallback_counters(world.daemon).items()
                if v != fallbacks0[k] or v
            ), 0
        ),
    }
    res = loop.check(stats, False)
    checks = dict(common, **res.checks)
    out = {}
    if control:
        out["program_checks"] = {k: v for k, (v, _) in checks.items()}
        checks = dict(common, **loop.check(stats, True).checks)

    metrics = {}
    device = {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs), "memory_peak_bytes": peak,
    }
    e2e, layer = cell_metrics(spec, wl["name"])
    out = dict({"correct": all(v <= lim for v, lim in checks.values()),
                "attempted": res.attempted, "failed": res.failed}, **out)
    if not trace:
        values = dict(res.e2e, setup_s=setup_s)
        for m in e2e:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        from benchmark import trace as T

        reduced = None
        if trace_path is not None:
            reduced = T.reduce(trace_path, loop.window_span)
            device["busy_s"] = reduced.busy_ns / 1e9
            device["window_s"] = reduced.window_ns / 1e9
            say("device programs (ns, events): " + json.dumps(
                {k: [v, reduced.module_count[k]]
                 for k, v in reduced.module_ns.items()}
            ))
        ctx = SimpleNamespace(
            workload=wl, traffic=traffic, loop=loop, stats=stats,
            reduced=reduced, res=res,
        )
        for m in layer:
            v = load_module("layer_metrics", m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if reduced is not None:
            out["breakdown"] = T.breakdown(reduced)
    out["metrics"] = metrics
    out["device"] = device
    out["checks"] = {
        k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()
    }
    return out


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def parse(argv):
    ap = argparse.ArgumentParser(description="One run of one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, t_start=None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse(argv)
    spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    wl, cfg, traffic = find_cell(spec, args.workload)
    try:
        devs = require_tpu(int(wl["chips"]))
    except NoAccelerator as exc:
        say(str(exc))
        return 3
    from cilium_tpu.compile_cache import enable_compile_cache

    import jax

    say(f"compile cache: {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    out = run_cell(spec, wl, cfg, traffic, args.seed, args.seconds,
                   bool(args.trace), devs, t_start)
    for name, c in out["checks"].items():
        say(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(out), flush=True)
    return 0
