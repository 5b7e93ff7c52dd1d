"""Find the highest rate a serving cell's engine sustains (its knee).

    python3 bench/sweep.py --workload <cell> --rates 1,2,3,4 --seconds 30

One process, one engine: shapes are compiled once, then for each rate the
cell's mix (at that rate, the seed's window stream) runs for ``--seconds``
and is drained with no further arrivals.  Prints one JSON line per rate:
time to first token and per output token (p50, p90), output tokens per
second, and the queue left at the window's end.  A rate is sustained while
that queue stays near empty and the first-token tail stays flat.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parent.parent / "src"),
                str(Path(__file__).resolve().parent.parent)]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()
    from bench import run as run_mod, spec as spec_lib
    spec = spec_lib.cell(spec_lib.benchmark(), args.workload)
    run_mod.chips_or_exit(spec["chips"])
    run_mod.set_compile_cache()
    import numpy as np
    from bench import gen, model, serve, stats
    from bench.common import Spans
    from repro.runtime.serve import BatchedServer
    c, tr = spec["config"], dict(spec["traffic"])
    sv = dict(c["serving"])
    sv.update(tr.get("serving", {}))
    cfg = model.arch(c)
    params = model.make_params(c, gen.jax_seed(args.seed, "weights"))
    eng = BatchedServer(cfg, params, max_len=sv["max_len"],
                        slots=sv["slots"], prefill_chunk=sv["prefill_chunk"],
                        decode_chunk=sv["decode_chunk"]).engine(seed=1)
    serve.warm_shapes(eng, sv["prefill_chunk"], sv["decode_chunk"])
    for rate in [float(r) for r in args.rates.split(",")]:
        tr["rate"] = rate
        loop = serve.OpenLoop(eng, Spans(False), sv["slots"])
        loop.sample = True
        t0 = time.perf_counter()
        tok0 = eng.tokens_out
        reqs = gen.serve_requests(tr, c["vocab_size"], args.seed, "window",
                                  args.seconds)
        loop.run(serve._due(reqs, t0),
                 lambda now: now >= t0 + args.seconds)
        t1 = loop.last_end
        queued = len(eng.queue)
        toks = eng.tokens_out - tok0
        loop.run(serve._due([], t0), lambda now: not loop.busy())
        rs = [{"due": r.due, "t_first": r.req.t_first, "t_done": r.req.t_done,
               "n_out": len(r.req.tokens)} for r in loop.records]
        run = {"requests": rs, "t_end": time.perf_counter()}
        ttft, tpot = stats.ttft_s(run), stats.tpot_s(run)
        print(json.dumps({
            "rate": rate, "n": len(rs), "queued_at_end": queued,
            "out_tok_s": toks / (t1 - t0),
            "ttft_p50_ms": 1e3 * stats.percentile(ttft, 50),
            "ttft_p90_ms": 1e3 * stats.percentile(ttft, 90),
            "tpot_p50_ms": 1e3 * stats.percentile(tpot, 50),
            "tpot_p90_ms": 1e3 * stats.percentile(tpot, 90),
            "occupancy": float(np.mean(loop.occupancy or [0]))}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
