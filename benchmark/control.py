#!/usr/bin/env python3
"""The control of a cell on the chip, at the cell's own size.  Not part
of the benchmark's own runs.

One process builds the world once; then, for each seed, one run with a
short window whose checks go through the harness twice: with the
program's answers (the lower reading) and with the control put in the
program's place (the loop's check with `control`: the reference with
one guarantee of the configuration broken).  The control has to come
out as not correct.

    python3 benchmark/control.py --workload n110.replay --seeds 5,6,7 --seconds 5
"""

import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    from benchmark import harness as H

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args()
    spec = H.load_json(os.path.join(H.ROOT, "BENCHMARK.json"))
    wl, cfg, traffic = H.find_cell(spec, args.workload)
    try:
        devs = H.require_tpu(int(wl["chips"]))
    except H.NoAccelerator as exc:
        H.say(str(exc))
        return 3
    from cilium_tpu.compile_cache import enable_compile_cache

    enable_compile_cache()
    world = H.build_world(cfg)
    for seed in (int(s) for s in args.seeds.split(",")):
        out = H.run_cell(spec, wl, cfg, traffic, seed, args.seconds,
                         False, devs, time.perf_counter(), control=True,
                         world=world)
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "control_correct": out["correct"],
            "program": out["program_checks"],
            "control": {k: c["value"] for k, c in out["checks"].items()},
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
