"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic and limits come from ``BENCHMARK.json``
and the files it names (``spec.py``).  With ``--trace 0`` the result line
carries the cell's end-to-end metrics; with ``--trace 1`` its per-layer
metrics, the device's busy and window seconds, and a breakdown.  The last
line of standard output is one JSON object; the numbers compared for
``correct`` are the last lines of standard error.  Exits non-zero, printing
no result, where JAX finds no TPU or fewer chips than the cell needs, and
where a program was compiled or loaded inside the measured window (the
warm-up missed a shape).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

TRACE_DIR = ROOT / ".bench_trace"


def set_compile_cache() -> str:
    """JAX's persistent cache in ``JAX_COMPILATION_CACHE_DIR`` or
    ``<checkout>/.jax_cache``, caching every program however quick its
    compile and however small its entry."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def chips_or_exit(chips: int) -> None:
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        print(f"no accelerator: {e}", file=sys.stderr)
        sys.exit(3)
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"need {chips} TPU chip(s); JAX found {len(devs)} "
              f"{devs[0].platform} device(s)", file=sys.stderr)
        sys.exit(3)


def execute(spec, seed: int, seconds: float, traced: bool,
            t_start: float, trace_dir: Path = TRACE_DIR) -> dict:
    """Everything after the look for chips: drive the cell, check it, read
    its metrics.  Returns the result line's object (with ``check``)."""
    from bench import check, common, peaks, spec as spec_lib, trace
    from bench import serve, train
    runner = {"serve": serve, "train": train}[spec["traffic"]["mode"]]
    run = runner.run(spec, seed, seconds, traced, t_start, str(trace_dir))
    dev = common.device_info(spec["chips"])
    run["device"] = dev
    run["chips"] = spec["chips"]
    run["config"] = spec["config"]
    run["peak"] = peaks.peak(dev["kind"]) if dev["platform"] == "tpu" \
        else None
    run["trace"] = None
    if traced:
        run["trace"] = trace.reduce(trace.load(str(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
    numbers = runner.check(run, spec, seed)
    metrics = {}
    for m in spec["per_layer" if traced else "end_to_end"]:
        v = spec_lib.reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = dict(dev, memory_peak_bytes=int(run["memory_peak_bytes"]))
    out = {"correct": check.passed(numbers),
           "attempted": int(run["attempted"]), "failed": int(run["failed"]),
           "metrics": metrics, "device": device}
    if traced and run["trace"] is not None:
        t = run["trace"]
        device["busy_s"], device["window_s"] = t["busy_s"], t["window_s"]
        out["breakdown"] = {"device_ops": t["device_ops"],
                            "idle_gaps": t["idle_gaps"]}
    out["info"] = run.get("info", {})
    out["check"] = numbers
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from bench import spec as spec_lib
    spec = spec_lib.cell(spec_lib.benchmark(), args.workload)
    chips_or_exit(spec["chips"])
    set_compile_cache()
    out = execute(spec, args.seed, args.seconds, bool(args.trace), T_START)
    info = out.pop("info")
    print("info " + json.dumps(info), flush=True)
    if info["compiles_in_window"]:
        print("programs compiled or loaded inside the measured window: "
              + ", ".join(info["compiled_in_window"]), file=sys.stderr)
    for name, n in out["check"].items():
        print(f"{name} {n['value']!r} limit {n['limit']!r}", file=sys.stderr)
    if info["compiles_in_window"]:
        return 4
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
