"""The serving loop: an open loop of requests into ``ServeEngine``.

One process, one thread.  The loop submits each request when it is due
(``traffic/<mix>.json`` gives the rate and the length laws), runs
``engine.tick()`` whenever a slot or the queue holds work, and sleeps to the
next due time otherwise.  Latencies are taken from each request's *due*
time, so a stalled loop shows as latency, and the loop's own lateness is
reported beside them.

Phases: shapes are compiled first (``warm_shapes``), then the mix runs for
``warmup_s`` so the slots hold steady-state traffic, then the measured
window, then (where the mix reports tails) a drain in which arrivals go on
at the same rate until every request that arrived in the window has
finished.  After that the engine is freed and a sample of finished requests
is compared with the plain reference (``reference.py``).
"""
from __future__ import annotations

import gc
import time
from collections import deque
from contextlib import ExitStack
from typing import Dict, List

import numpy as np

from bench import flops, gen, model, trace as trace_lib
from bench.common import CompileLog, Spans, memory_peak

QUIET_S = 5.0           # warm-up ends after this long without a compile
WARM_EXTRA_S = 30.0     # ... or this long after its set length


class Record:
    """One submitted request: its generated form, due time and the
    engine's ``Request``."""
    __slots__ = ("gen", "due", "submitted", "req")

    def __init__(self, g, due, submitted, req):
        self.gen, self.due, self.submitted, self.req = g, due, submitted, req


class OpenLoop:
    def __init__(self, eng, spans: Spans, slots_total: int):
        self.eng = eng
        self.spans = spans
        self.slots_total = slots_total
        self.records: List[Record] = []
        self.occupancy: List[float] = []
        self.sample = False
        self.last_end = time.perf_counter()

    def busy(self) -> bool:
        return bool(self.eng.queue) or any(
            r is not None for r in self.eng.active)

    def run(self, pending: deque, stop) -> None:
        """Submit and tick until ``stop(now)``; checked before every tick,
        so the last tick may end after the stop time."""
        eng, span = self.eng, self.spans
        while True:
            now = time.perf_counter()
            if stop(now):
                return
            while pending and pending[0][0] <= now:
                due, g = pending.popleft()
                with span("bench.submit"):
                    r = eng.submit(g.prompt, max_new=g.max_new,
                                   temperature=0.0)
                self.records.append(Record(g, due, time.perf_counter(), r))
            if self.busy():
                if self.sample:
                    self.occupancy.append(
                        sum(r is not None for r in eng.active)
                        / self.slots_total)
                with span("bench.tick"):
                    eng.tick()
                self.last_end = time.perf_counter()
            else:
                nxt = pending[0][0] if pending else now + 0.002
                with span("bench.wait"):
                    time.sleep(min(max(nxt - now, 0.0), 0.01))


def _due(reqs, base: float) -> deque:
    return deque((base + g.due, g) for g in reqs)


def warm_shapes(eng, prefill_chunk: int, decode_chunk: int) -> int:
    """Compile every tick program and eager helper the engine can reach,
    before any traffic: full-pool ticks of each length, compacted decode
    ticks of each row count and length, and the admission scatter for each
    number of joiners.  Each call runs with every slot inactive, so the
    pool's state is unchanged, and each slot's PRNG key is put back after.
    Mirrors the dispatch and commit code of
    ``ServeEngine._plan_tick``/``_commit_tick``/``_admit`` for unplaced
    pools (the engine has no public way to compile its shapes); returns
    the number of programs driven."""
    import jax
    import jax.numpy as jnp

    def pow2s(top):
        out, x = [], 1
        while x <= top:
            out.append(x)
            x *= 2
        return out

    n = 0
    for sp in eng.pools:
        S = sp.slots
        keys = sp.keys
        params, _ = eng._params_for(sp)
        fn = eng._tick
        for L in pow2s(max(prefill_chunk, decode_chunk)):
            outs = fn(params, sp.pool, sp.pos,
                      jnp.asarray(np.zeros((S, L), np.int32)),
                      jnp.asarray(np.ones((S,), np.int32)),
                      jnp.asarray(np.zeros((S,), bool)),
                      jnp.asarray(np.zeros((S,), bool)), sp.keys,
                      jnp.asarray(np.zeros((S,), np.float32)))
            sp.pool, sp.pos, sp.keys = outs[0], outs[1], outs[2]
            np.asarray(outs[3]), np.asarray(outs[4])
            n += 1
        top = 1
        while top < int(S * eng.compact_frac):
            top *= 2
        for nc in pow2s(min(top, S)):
            idx = np.arange(nc, dtype=np.int32)
            jidx = jnp.asarray(idx)
            for L in pow2s(decode_chunk):
                pool_c = jax.tree.map(lambda c: c[jidx], sp.pool)
                outs = fn(params, pool_c, sp.pos[jidx],
                          jnp.asarray(np.zeros((nc, L), np.int32)),
                          jnp.asarray(np.ones((nc,), np.int32)),
                          jnp.asarray(np.zeros((nc,), bool)),
                          jnp.asarray(np.zeros((nc,), bool)),
                          sp.keys[jidx],
                          jnp.asarray(np.zeros((nc,), np.float32)))
                pool_n, pos_n, keys_n = outs[0], outs[1], outs[2]
                sp.pool = jax.tree.map(lambda p, q: p.at[jidx].set(q),
                                       sp.pool, pool_n)
                sp.pos = sp.pos.at[jidx].set(pos_n)
                sp.keys = sp.keys.at[jidx].set(keys_n)
                np.asarray(outs[3]), np.asarray(outs[4])
                n += 1
        for j in range(1, S + 1):
            idx = jnp.asarray(list(range(j)), jnp.int32)
            sp.keys = sp.keys.at[idx].set(jnp.stack([sp.keys[0]] * j))
            n += 1
        jax.block_until_ready((sp.pool, sp.pos, sp.keys))
        sp.keys = keys
    jax.random.fold_in(eng._base_key, 0).block_until_ready()
    return n


def counters(eng) -> Dict:
    """The engine's numeric attributes (and its prefix cache's), for
    per-layer readers that count what the engine did in the window."""
    def nums(obj):
        return {k: v for k, v in vars(obj).items()
                if isinstance(v, (int, float)) and not isinstance(v, bool)}
    out = {"engine": nums(eng)}
    if getattr(eng, "prefix", None) is not None:
        out["prefix"] = nums(eng.prefix)
    return out


def progress(r) -> int:
    """Positions a request has fed through the model so far."""
    return int(r.prompt_off) + len(r.tokens)


def run(spec: Dict, seed: int, seconds: float, traced: bool,
        t_start: float, trace_dir: str) -> Dict:
    """Drive one cell; returns the raw context the metric readers read."""
    import jax
    from repro.runtime.serve import BatchedServer

    c, tr, chips = spec["config"], spec["traffic"], spec["chips"]
    cfg = model.arch(c)
    model.check_layout(c, cfg)
    sv = dict(c["serving"])
    sv.update(tr.get("serving", {}))
    need = gen.max_request_len(tr) + max(sv["prefill_chunk"],
                                         sv["decode_chunk"])
    if need > sv["max_len"]:
        raise SystemExit(f"max_len {sv['max_len']} < longest request "
                         f"+ chunk {need}")
    params = model.make_params(c, gen.jax_seed(seed, "weights"))
    jax.block_until_ready(params)
    server = BatchedServer(cfg, params, max_len=sv["max_len"],
                           slots=sv["slots"],
                           prefill_chunk=sv["prefill_chunk"],
                           decode_chunk=sv["decode_chunk"])
    eng = server.engine(seed=gen.jax_seed(seed, "engine"))
    clog = CompileLog()
    n_shapes = warm_shapes(eng, sv["prefill_chunk"], sv["decode_chunk"])
    spans = Spans(annotate=traced)
    loop = OpenLoop(eng, spans, sv["slots"])
    vocab = c["vocab_size"]

    # warm-up: the cell's own mix at its own rate, from the seed's warm
    # stream, until the slots hold steady-state traffic and a stretch of
    # ``QUIET_S`` has compiled nothing
    warm_s = float(tr.get("warmup_s", 10.0))
    t_w = time.perf_counter()
    warm = _due(gen.serve_requests(tr, vocab, seed, "warm",
                                   warm_s + WARM_EXTRA_S), t_w)
    loop.run(warm, lambda now: now >= t_w + warm_s - QUIET_S)
    while True:
        mark, t_q = clog.count(), time.perf_counter()
        loop.run(warm, lambda now: now >= t_q + QUIET_S)
        if clog.count() == mark or \
                t_q + QUIET_S >= t_w + warm_s + WARM_EXTRA_S:
            break
    n_warm = len(loop.records)

    # the measured window; arrivals go on from where warm-up left them
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    drain_max = float(tr.get("drain_max_s", 90.0))
    pending = _due(gen.serve_requests(tr, vocab, seed, "window", seconds), t0)
    pending.extend(_due(gen.serve_requests(tr, vocab, seed, "after",
                                           drain_max), t0 + seconds))
    c0, tok0, cnt0 = clog.count(), eng.tokens_out, counters(eng)
    before = {id(r.req): progress(r.req) for r in loop.records}
    loop.sample = True
    spans.durations.clear()
    with ExitStack() as stack:
        if traced:
            stack.enter_context(trace_lib.capture(trace_dir))
        with spans(trace_lib.WINDOW_SPAN):
            loop.run(pending, lambda now: now >= t0 + seconds)
    t1 = loop.last_end
    loop.sample = False
    window_s = t1 - t0
    compiled = clog.since(c0)
    tokens = eng.tokens_out - tok0
    cnt1 = counters(eng)
    window_recs = [r for r in loop.records[n_warm:] if r.due < t0 + seconds]
    work = 0.0
    for r in loop.records:
        p0 = before.get(id(r.req), 0)
        work += flops.serve_flops(c, p0, progress(r.req))
    tick_spans = list(spans.durations.get("bench.tick", []))

    # drain: arrivals continue on schedule until every request that arrived
    # in the window has finished (cells that report tails only)
    t_drain = time.perf_counter()
    if tr.get("drain", True):
        loop.run(pending, lambda now: now >= t_drain + drain_max or all(
            r.req.t_done is not None for r in window_recs))
    drain_s = time.perf_counter() - t_drain
    mem = memory_peak(chips)

    finished = [(r.gen.prompt, np.asarray(r.req.tokens[:r.gen.max_new],
                                          np.int32))
                for r in loop.records
                if r.req.t_done is not None
                and len(r.req.tokens) >= r.gen.max_new]
    reqs = [{"due": r.due, "submitted": r.submitted,
             "t_first": r.req.t_first, "t_done": r.req.t_done,
             "n_out": len(r.req.tokens), "max_new": r.gen.max_new,
             "prompt": len(r.gen.prompt)} for r in window_recs]
    occupancy = list(loop.occupancy)
    t_end = time.perf_counter()
    del server, eng, loop
    gc.collect()
    late = [r["submitted"] - r["due"] for r in reqs]
    # a request that never finished failed, where the cell waits for all
    # (an overloaded cell leaves its queue standing at the window's end)
    failed = sum(r["n_out"] < r["max_new"] for r in reqs) \
        if tr.get("drain", True) else 0
    info = {"compiles_in_window": len(compiled),
            "compiled_in_window": sorted(set(compiled)),
            "warm_shapes": n_shapes, "warm_programs": c0,
            "window_requests": len(reqs), "drain_s": drain_s,
            "late_p50_ms": 1e3 * float(np.median(late)) if late else None,
            "late_max_ms": 1e3 * max(late) if late else None,
            "ticks_in_window": len(tick_spans)}
    return {"kind": "serve", "setup_s": setup_s, "window_s": window_s,
            "requests": reqs, "t_end": t_end, "tokens": tokens,
            "model_flops": work, "tick_spans": tick_spans,
            "occupancy": occupancy, "memory_peak_bytes": mem,
            "counters": {"start": cnt0, "end": cnt1},
            "finished": finished, "params": params,
            "attempted": len(reqs), "failed": failed, "info": info}


def check(run: Dict, spec: Dict, seed: int) -> Dict:
    from bench import check as check_lib
    return check_lib.serve(run, spec, seed)
