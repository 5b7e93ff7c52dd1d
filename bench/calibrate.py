"""Readings a cell's correctness limits are set from, at the cell's size.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 10

One process: for each seed the cell runs as ``run.py`` runs it (a short
window at the cell's own load), and the numbers ``correct`` compares are
read three ways on the same sample: the program's (the lower reading), the
control's (the plain reference in float8 weights in the program's place:
the upper reading), and for training the reference with half of each
microbatch left out (a fault the step can have).  One JSON line per seed
and reading.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--no-control", action="store_true")
    args = ap.parse_args()
    from bench import run as run_mod, spec as spec_lib
    spec = spec_lib.cell(spec_lib.benchmark(), args.workload)
    run_mod.chips_or_exit(spec["chips"])
    run_mod.set_compile_cache()
    import numpy as np
    from bench import check, serve, train
    runner = {"serve": serve, "train": train}[spec["traffic"]["mode"]]
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        run = runner.run(spec, seed, args.seconds, False, t0,
                         str(run_mod.TRACE_DIR))
        reads = {"program": None}
        if not args.no_control:
            reads["control_fp8"] = "fp8"
        if spec["traffic"]["mode"] == "train" and not args.no_control:
            reads["fault_half_batch"] = "half"
        for name, mode in reads.items():
            t = time.perf_counter()
            if spec["traffic"]["mode"] == "serve":
                nums = check.serve(run, spec, seed, quant=mode)
            else:
                nums = check.train(run, spec, seed,
                                   quant=mode if mode == "fp8" else None,
                                   fault=mode if mode == "half" else None)
            stats = {}
            if "gaps" in run:
                g = run.pop("gaps")
                stats = {"mean": float(g.mean()),
                         "p90": float(np.quantile(g, 0.9)),
                         "p95": float(np.quantile(g, 0.95)),
                         "p99": float(np.quantile(g, 0.99)),
                         "share_over_0.05": float((g > 0.05).mean()),
                         "share_over_0.1": float((g > 0.1).mean())}
            print(json.dumps({"seed": seed, "reading": name,
                              "gap_stats": stats,
                              "numbers": {k: v["value"]
                                          for k, v in nums.items()},
                              "readings": run.pop("readings", {}),
                              "seconds": time.perf_counter() - t,
                              "info": run.get("info")}), flush=True)
        del run
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
