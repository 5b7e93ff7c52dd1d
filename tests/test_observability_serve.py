"""ServeEngine's spans and counters: the admission, prefill and phase
totals that split a tick's host time and a request's time to first token,
the spans a profile shows nested inside ``serve.tick``, and the benchmark
readers that read the counters."""
import glob
import math
import os
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_arch
from repro.engine import ServeEngine
from repro.models import lm

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

PHASES = ("admit_s", "plan_s", "commit_s")
NEW_READERS = ("host_ms.chat", "prefill_share.chat", "queue_wait_ms.chat",
               "prefill_ms.chat")


@pytest.fixture(scope="module")
def moe():
    cfg = get_arch("olmoe-1b-7b-smoke")
    return cfg, lm.init(cfg, jax.random.PRNGKey(0))


def _prompts(cfg, n, rng):
    return [rng.integers(1, cfg.vocab, size=int(rng.integers(3, 12)))
            for _ in range(n)]


def test_counters_split_the_wall_and_marks_are_ordered(moe, rng):
    cfg, params = moe
    eng = ServeEngine(cfg, params, max_len=64, slots=2, prefill_chunk=8,
                      decode_chunk=4, prefix_cache=True)
    prompts = _prompts(cfg, 5, rng)
    t0 = time.perf_counter()
    reqs = [eng.submit(p, max_new=6) for p in prompts]
    eng.run_until_done()
    # an exact greedy repeat is answered from the result cache: it is
    # counted apart and never admitted to a slot
    again = eng.submit(prompts[0], max_new=6)
    eng.run_until_done()
    wall = time.perf_counter() - t0
    c = eng._inspect("counters")["counters"]
    assert again.t_admit is None and again.tokens == reqs[0].tokens
    assert c["cache_answered"] == 1
    assert c["admitted"] == len(reqs) == c["first_tokens"]
    for k in PHASES + ("queue_wait_s", "prefill_s", "prefill_tick_s",
                       "decode_tick_s"):
        assert c[k] >= 0, k
    assert sum(c[k] for k in PHASES) <= wall
    assert c["prefill_tick_s"] + c["decode_tick_s"] <= wall
    assert c["prefill_tick_s"] > 0 and c["decode_tick_s"] > 0
    for r in reqs:
        assert r.t_submit <= r.t_admit <= r.t_first <= r.t_done
    # the counters are the totals of the marks they summarize
    assert c["queue_wait_s"] == pytest.approx(
        sum(r.t_admit - r.t_submit for r in reqs))
    assert c["prefill_s"] == pytest.approx(
        sum(r.t_first - r.t_admit for r in reqs))
    # _inspect returns the engine's own attributes, which the benchmark's
    # counter snapshot reads
    assert all(c[k] == getattr(eng, k) for k in c)


def _events(log_dir):
    from jax.profiler import ProfileData
    f = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                  recursive=True)
    assert f, "no profile written"
    out = []
    for plane in ProfileData.from_file(f[0]).planes:
        for line in plane.lines:
            for e in line.events:
                out.append((plane.name, line.name, e.name,
                            e.start_ns, e.start_ns + e.duration_ns,
                            dict(e.stats)))
    return out


def test_profile_shows_the_tick_phases_nested_with_attributes(moe, rng,
                                                             tmp_path):
    cfg, params = moe
    eng = ServeEngine(cfg, params, max_len=64, slots=2, prefill_chunk=8,
                      decode_chunk=4)
    eng.submit(_prompts(cfg, 1, rng)[0], max_new=3)
    eng.tick()                          # compile outside the profile
    eng.submit(_prompts(cfg, 1, rng)[0], max_new=3)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        eng.run_until_done()
    finally:
        jax.profiler.stop_trace()
    ev = _events(str(tmp_path))
    ticks = [e for e in ev if e[2] == "serve.tick"]
    assert ticks
    for e in ticks:
        assert e[5]["mode"] in ("prefill", "decode")
        assert {"compact", "L", "rows", "part", "group"} <= set(e[5])
    # the first layout choice explores; a tick that only exploits carries
    # no ``explore``
    assert any("serve_compact:bootstrap" in e[5].get("explore", "")
               for e in ticks), [e[5] for e in ticks]

    def inside(e):
        return any(t[:2] == e[:2] and t[3] <= e[3] and e[4] <= t[4]
                   for t in ticks)

    for name in ("serve.control", "serve.admit", "serve.plan",
                 "serve.commit"):
        mine = [e for e in ev if e[2] == name]
        assert mine and all(inside(e) for e in mine), name
    jobs = [e for e in ev if e[2] in ("serve_prefill", "serve_decode")]
    assert jobs and all(inside(e) for e in jobs)


def test_readers_give_finite_values_on_the_tiny_cell(tmp_path):
    from bench import serve, spec
    from bench.tests.harness import tiny_cell
    run = serve.run(tiny_cell("serve"), 2 ** 33 + 7, 2.0, False,
                    time.perf_counter(), str(tmp_path))
    vals = {m: spec.reader(m)(run) for m in NEW_READERS}
    assert all(v is not None and math.isfinite(v) and v >= 0
               for v in vals.values()), vals
    assert 0 <= vals["prefill_share.chat"] <= 100
    # a run whose engine keeps none of these counters reads nothing
    bare = dict(run, counters={k: {"engine": {"tick_no": 1}}
                               for k in ("start", "end")})
    assert all(spec.reader(m)(bare) is None for m in NEW_READERS)
    assert np.isfinite(spec.reader("tick_ms.chat")(run))
