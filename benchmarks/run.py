# One function per paper table/figure. Prints ``name,us_per_call,derived``
# CSV; ``--json PATH`` additionally writes the rows as a perf-trajectory
# artifact (e.g. BENCH_runtime.json) for CI comparison across PRs.
# Sub-suites: paper_sim (Reshape Ch.3 figures on the Tier-A simulator),
# runtime_bench (Amber Ch.2 + live-MoE on the real JAX runtime),
# maestro_bench (Ch.4 FRT/materialization), gauntlet (scenario-diverse
# SLO-graded load harness + autotune recovery).
#
# Each suite exposes a per-bench registry (``benches(smoke)`` -> list of
# (name, fn)) when its benches can run individually; ``--only`` filters on
# those names and ``--timeout`` arms a per-bench wall-clock guard (SIGALRM,
# main thread, POSIX) so one wedged bench turns into an ERROR row instead
# of hanging the whole run.
import argparse
import contextlib
import json
import signal
import sys
import threading


class BenchTimeout(Exception):
    pass


@contextlib.contextmanager
def _guard(seconds: int, name: str):
    """Per-bench wall-clock guard.  SIGALRM only works on the main thread
    of a POSIX process; anywhere else the guard degrades to a no-op rather
    than failing the run."""
    usable = (seconds > 0 and hasattr(signal, "SIGALRM")
              and threading.current_thread() is threading.main_thread())
    if not usable:
        yield
        return

    def _alarm(signum, frame):
        raise BenchTimeout(f"{name} exceeded {seconds}s wall-clock guard")

    old = signal.signal(signal.SIGALRM, _alarm)
    signal.alarm(seconds)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


def _suite_benches(sname, mod, smoke):
    """A suite's per-bench registry, falling back to one whole-suite entry
    for suites that don't expose ``benches``."""
    if hasattr(mod, "benches"):
        return mod.benches(smoke)
    run = (lambda: mod.run(smoke=True)) if smoke else mod.run
    return [(sname, run)]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--suite", default="all",
                    choices=["all", "sim", "runtime", "maestro",
                             "gauntlet"])
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows as a JSON perf artifact")
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: only the fast A/B comparison benches of "
                         "the runtime suite; miniaturized gauntlet "
                         "scenarios")
    ap.add_argument("--only", default=None, metavar="NAME",
                    help="run only benches whose registry name contains "
                         "this substring (e.g. one gauntlet scenario)")
    ap.add_argument("--timeout", type=int, default=900, metavar="SECONDS",
                    help="per-bench wall-clock guard; 0 disables")
    args = ap.parse_args()

    sys.path.insert(0, "src")
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    suites = []
    if args.suite in ("all", "sim") and not args.smoke:
        from benchmarks import paper_sim
        suites.append(("sim", paper_sim))
    if args.suite in ("all", "runtime"):
        from benchmarks import runtime_bench
        suites.append(("runtime", runtime_bench))
    if args.suite in ("all", "maestro") and not args.smoke:
        from benchmarks import maestro_bench
        suites.append(("maestro", maestro_bench))
    if args.suite in ("all", "gauntlet"):
        from benchmarks import gauntlet
        suites.append(("gauntlet", gauntlet))

    print("name,us_per_call,derived")
    failures = 0
    results = []
    for sname, mod in suites:
        for bname, fn in _suite_benches(sname, mod, args.smoke):
            if args.only and args.only not in bname:
                continue
            try:
                with _guard(args.timeout, f"{sname}/{bname}"):
                    rows = fn()
            except (Exception, BenchTimeout) as e:  # pragma: no cover
                failures += 1
                print(f"{sname}/{bname}/ERROR,0,{type(e).__name__}:{e}",
                      flush=True)
                results.append({"suite": sname,
                                "name": f"{sname}/{bname}/ERROR",
                                "us_per_call": 0.0,
                                "derived": f"{type(e).__name__}:{e}"})
                continue
            for name, us, derived in rows:
                print(f"{name},{us:.1f},{derived}", flush=True)
                results.append({"suite": sname, "name": name,
                                "us_per_call": round(us, 1),
                                "derived": derived})
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"suites": [s for s, _ in suites],
                       "failures": failures, "rows": results}, f, indent=1)
        print(f"# wrote {args.json}", file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == '__main__':
    main()
