"""ServeEngine: continuous batching under the Amber control plane.

Serving runs as engine jobs over a fixed pool of *slots*, each slot holding
one request's KV/SSM cache at its own sequence position (the per-slot state
is the old ``BatchedServer``'s batch row, promoted to join/evict at tick
boundaries).  A **tick** is one jitted dispatch that advances every
participating slot by ``chunk`` positions:

* a *prefill* slot consumes up to ``chunk`` prompt tokens (chunked batched
  prefill — one dispatch per chunk instead of the old one dispatch per
  token);
* a *decode* slot feeds its pending sampled token and keeps sampling
  in-jit, emitting up to ``chunk`` new tokens per dispatch;
* a slot whose prompt ends mid-tick transitions prefill -> decode inside
  the same dispatch.

Between ticks the engine polls the controller mailbox, so Pause / Inspect /
Update land at tick granularity exactly like the training loop's microbatch
control points, and while paused the engine keeps answering Inspect —
serving gets §2.4.4 semantics for free.  Tick *composition* (decode-only vs
prefill) is a Maestro min-FRT choice over the two candidate region
workflows (``jobs.serve_tick_workflow``): short decode ticks preempt long
prefills until the aging bound forces prefill progress.

**Speculative in-tick decoding** (``spec_decode=True``): a *proposer*
(:class:`Proposer`) drafts up to ``cfg.serve.spec_len`` tokens per decode
tick; the target model verifies the whole draft chain in the same
chunk-scan dispatch: a carried ``valid`` mask commits the longest accepted
prefix and masks every non-positional state update (recurrent caches, pos,
table) past the first mismatch, which keeps *all* cache families correct
(recurrent and conv state cannot be position-rewound the way KV rows can)
and makes greedy outputs bit-identical to plain decode by construction — an
accepted draft IS the token greedy decode would have fed.  Two proposers
share that contract:

* ``ngram`` — a per-slot n-gram suffix-hash table, int32 arrays living in
  the donated slot pool and updated in-jit from every token the slot
  streams (prompt and generated alike), so proposing costs no host
  round-trip.  Strong on repetitive streams, collapses on random text.
* ``draft`` — a second, much smaller parameter set (``engine.draft``:
  either a truncated-layer *self*-draft sliced from the serve model, or an
  independently-specified/distilled small config) that greedily decodes
  ``spec_len - 1`` steps ahead inside the same dispatch.  Its per-slot
  cache rows live in the donated pool (``pool["draft"]``) — reset-masked on
  join, snapshotted/seeded by the prefix cache with the rest of the row —
  and are advanced by every committed token on *every* arm (prefill, plain
  decode, and verify alike), so the draft state is always exactly the
  committed stream.  The propose scan runs on throwaway copies; a wrong,
  stale, or hot-swapped draft (``update(draft_params=...)``) can only
  lower acceptance, never change outputs.

Which arm a decode tick runs — plain, ``spec:ngram``, or ``spec:draft`` —
is an engine decision from measured per-arm acceptance-rate and runtime
EMAs (``Engine._choose_decode_arm``); speculative arms are host-gated to
all-greedy participants because verifying sampled (temperature > 0)
continuations greedily would change their distribution.

**Multi-pool, priority-aware serving**: a ServeEngine owns ``pools`` slot
pools (each a :class:`SlotPool` with its own donated cache pool; the tick
jits are shared across pools via the memoized ``build_slot_tick``), and
requests carry a ``priority`` naming one of ``cfg.serve.classes``.  Each
scheduling round, every pool with work offers its candidate ticks
(``jobs.TickCandidate``) and ``Engine.choose_serve_job`` picks ONE
(pool, composition) under the weighted-FRT objective — candidate FRT costed
with the pool's own measured per-token EMA, divided by the summed class
weight of the requests the tick advances — subject to per-class aging
bounds: an admitted prefill that has sat out ``max_defer`` scheduled ticks
forces its pool's prefill candidate, whatever the weights say.  With one
pool and the default single-class table the engine takes the original
single-pool decision path (``Engine.choose_serve_tick``) unchanged.

**Cross-request prefix cache + result cache** (``prefix_cache=True``): the
engine treats the KV/SSM state of every prefix it has prefilled as a
first-class, reusable artifact (``engine.prefix_cache``).  At prefill tick
boundaries a still-prefilling slot's pool row — every cache leaf plus its
n-gram table, at the frozen position — is snapshotted into a radix tree
keyed by the consumed token prefix; a joining request that shares a cached
prefix *seeds* its slot from the snapshot with one jitted batched row write
(the same no-eager-scatter discipline as the reset-mask join) and prefills
only the unshared suffix, and an exact-repeat greedy request is answered
straight from the result cache without touching a slot.  Reuse is a
measured Maestro decision, not a heuristic: ``Engine.choose_prefix_admission``
prices ``jobs.prefix_seed_workflow`` (copy + suffix) against
``jobs.prefill_workflow`` (recompute) with per-pool CostBook EMAs.  Seeding
and result hits are host-gated to greedy requests, like the speculative
arm: a sampled request's key stream advances once per scan step, so
skipping prefill steps would change which draws produce its tokens.
Seeded state is bit-identical to recomputation by construction — the tick
consumes tokens one ``lm.decode_step`` at a time, so the state after P
tokens does not depend on chunking or on which slot ran them.

**Device-placed pools + elastic scale** (``placements={pool: mesh}``): a
slot pool may own a real device group — its params are committed to the
pool's :func:`repro.runtime.sharding.pool_mesh` (replicated at the default
``serve.pool_tp=1``, tensor-parallel above it) and its donated pool state
lives there under :func:`pool_specs` — so decode ticks for pools on
disjoint devices overlap: the scheduling round still picks ONE arbitration
winner, but with ``serve.parallel_ticks`` the engine co-dispatches plain
decode ticks for the other placed pools in the same round (async dispatch;
each pool's measured time is its elapsed-from-round-start, so the EMAs see
the overlapped reality).  Placement feeds back into the decisions:
candidate ticks carry a device-group *load* term and a pending-migration
*transfer* term (``scheduler.placement_adjusted_frt``), and admission onto
placed pools is an engine decision over occupancy-inflated per-token EMAs
(``Engine.choose_admission_pool``).  Pools are elastic under load:
``add_pool()`` joins a new (optionally placed) pool, ``drain_pool()``
stops admission and live-migrates the in-flight slots — full pool rows,
positions and PRNG keys, moved by a jitted gather → ``device_put`` →
jitted batched scatter path (``_migrate_slots``) — then retires the empty
pool.  A slot's row + position + key fully determine its continuation, so
greedy outputs are bit-identical across any migration, and zero requests
drop.

Scheduling objective: serving minimizes (weighted) **first-response time**
— a user is waiting on the first token — where training minimizes
completion time; see ``core.scheduler`` for both objectives.

Invariants the differential harness (tests/test_serve_differential.py)
enforces on this module:

* **Greedy bit-identicality** — greedy outputs equal the static
  ``BatchedServer.generate_static`` oracle, token for token, under every
  tick ordering, pool count, priority mix, compact gather, and speculative
  arm the scheduler can produce.  Scheduling reorders work; it must never
  change results.
* **Reset-mask join** — a request joins a slot by flagging the row for
  in-jit zeroing (the ``reset`` mask) instead of eager scatters; no stale
  cache, n-gram-table, or position state may leak between consecutive
  occupants of a slot, in any pool.

The per-slot compute is ``jax.vmap`` over the stock ``lm.decode_step`` —
per-slot positions come from batching the *function*, not from touching the
block-level cache layouts — and greedy outputs are bit-identical to the old
token-by-token server (the regression oracle in the tests).
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
import threading
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core import messages as M
from repro.core.breakpoints import GlobalCountBreakpoint, LocalBreakpoint
from repro.engine.engine import Engine
from repro.engine.jobs import (COST_DEFAULTS, Job, TickCandidate,
                               layout_kind, pool_kind, spec_kind)
from repro.engine.prefix_cache import PrefixAnalyzer, PrefixCache, to_host
from repro.models import lm
from repro.models.blocks import POSITIONAL_CACHE_TYPES
from repro.runtime import trace
from repro.runtime.sharding import (axis_size, named, param_specs, pool_mesh,
                                    pool_specs)


def sample_traced(logits, key, temp):
    """In-jit sampler with a *traced* temperature: greedy at temp<=0,
    categorical otherwise (both branches computed; jnp.where selects)."""
    greedy = jnp.argmax(logits, -1).astype(jnp.int32)
    t = jnp.maximum(temp, 1e-6)
    samp = jax.random.categorical(key, logits / t).astype(jnp.int32)
    return jnp.where(temp > 0, samp, greedy)


# xxhash/murmur-style odd multipliers, one per n-gram context position
_NG_MULTS = (0x9E3779B1, 0x85EBCA6B, 0xC2B2AE35, 0x27D4EB2F)


class Proposer:
    """One speculative-proposer arm: the source of the draft chain the
    target verifies.

    The contract every implementation shares (and the differential harness
    enforces): ``build(cfg, draft_cfg, ng_hash, push)`` returns a traced
    ``propose(dparams, draft_caches, ng, ctx, pos, toks) -> [L] tokens``
    whose output chain starts with ``toks[0]`` (the pending committed
    token) followed by ``L-1`` proposals, and which mutates **no persistent
    state** — any state the proposal consumes is carried through the scan
    as throwaway copies.  The verify scan then re-feeds every token through
    the persistent per-slot state under the valid-mask/freeze discipline,
    so a proposer can only affect *acceptance*: correctness is the target
    model's argmax, whatever was proposed."""

    name: str = ""

    @staticmethod
    def build(cfg, draft_cfg, ng_hash, push):
        raise NotImplementedError


class NgramProposer(Proposer):
    """Successor lookups from the slot's in-pool n-gram suffix table."""

    name = "ngram"

    @staticmethod
    def build(cfg, draft_cfg, ng_hash, push):
        def propose(dparams, draft, ng, ctx, pos, toks):
            L = toks.shape[0]

            def step(carry, _):
                win, tok = carry
                win = push(win, tok)
                nxt = ng[ng_hash(win)]
                return (win, nxt), nxt

            _, drafts = jax.lax.scan(step, (ctx, toks[0]), None,
                                     length=L - 1)
            return jnp.concatenate([toks[:1], drafts])

        return propose


class DraftProposer(Proposer):
    """Greedy decode of the small draft model, ``L-1`` steps ahead of the
    committed stream.  The scan starts from the slot's persistent draft
    cache row and position but carries *copies* — the overshoot state a
    partially-rejected chain would leave behind is simply dropped, and the
    verify scan advances the persistent draft row by exactly the committed
    tokens instead."""

    name = "draft"

    @staticmethod
    def build(cfg, draft_cfg, ng_hash, push):
        assert draft_cfg is not None, \
            "the draft proposer needs draft_cfg/draft_params"

        def propose(dparams, draft, ng, ctx, pos, toks):
            L = toks.shape[0]

            def step(carry, _):
                caches, p, tok = carry
                logits, new = lm.decode_step(
                    dparams, {"caches": caches, "pos": p}, tok[None, None],
                    draft_cfg)
                nxt = jnp.argmax(logits[0], -1).astype(jnp.int32)
                return (new["caches"], new["pos"], nxt), nxt

            _, drafts = jax.lax.scan(step, (draft, pos, toks[0]), None,
                                     length=L - 1)
            return jnp.concatenate([toks[:1], drafts])

        return propose


PROPOSERS = {p.name: p for p in (NgramProposer, DraftProposer)}


@functools.lru_cache(maxsize=None)
def build_slot_tick(cfg: ArchConfig, spec_len: int = 0,
                    draft_cfg: Optional[ArchConfig] = None,
                    proposer: str = "ngram"):
    """Jitted tick: vmap of a per-slot chunk scan over ``lm.decode_step``.

    Per slot: a pool row (cache leaves ``[n, 1, S, ...]`` plus the n-gram
    suffix table ``ng [T]`` and its context window ``ctx [n_ctx]``), scalar
    pos, tokens ``[chunk]``, ``n_given`` (how many are prompt/pending tokens
    — the rest are sampled in-jit), active mask, PRNG key, temperature.
    Emits the ``[chunk]`` sampled tokens plus ``n_valid`` (committed count);
    position ``j``'s emission is the model's continuation after consuming
    token ``j``.  Inactive slots run (vmap is rectangular) but their state
    updates are masked out.

    Every tick — plain and speculative — *learns* in-jit: each fed token is
    written into the slot's suffix table under the hash of the ``n_ctx``
    tokens that preceded it, so the table is warm whichever arm the engine
    ran last (collisions only cost acceptance, never correctness).

    ``spec_len > 0`` builds the speculative variant (decode-only, all-greedy
    participants): the named ``proposer`` (:data:`PROPOSERS`) produces a
    ``spec_len``-token draft chain ahead of the scan; the scan verifies it
    with a carried ``valid`` mask that freezes non-positional caches, pos
    and table past the first mismatch, and ``n_valid`` reports the
    committed prefix (the accepted drafts plus the model's own correction
    token).  No sampling and no PRNG-key advance happen on this path — the
    keys pass through untouched.

    ``draft_cfg`` (not None) threads a draft-model parameter set through
    the tick as a second, non-donated argument: the signature grows to
    ``(params, dparams, pool, ...)`` and the pool carries per-slot draft
    cache rows under ``pool["draft"]`` which EVERY arm advances by each
    token it feeds the target (prefill chunks, plain decode, and the
    verify scan alike — under the same valid-mask/frozen-pos discipline),
    so whichever arm ran last, the draft state equals the committed stream.
    The draft shares the slot's position (it consumes exactly the target's
    tokens), and its rejected speculative writes die the same way the
    target's do: the frozen pos makes them land on one dead row.

    Memoized per (cfg, spec_len, draft_cfg, proposer): every ServeEngine
    over the same config shares one jit, so compiled tick specializations
    are reused across engine instances (the differential test harness
    builds hundreds).
    """
    table = cfg.serve.spec_table
    n_ctx = cfg.serve.spec_ctx
    assert table & (table - 1) == 0, "serve.spec_table must be a power of 2"
    assert 1 <= n_ctx <= len(_NG_MULTS), "serve.spec_ctx out of range"

    def ng_hash(ctx):
        h = jnp.uint32(0)
        for i in range(n_ctx):
            h = h ^ (ctx[i].astype(jnp.uint32) * jnp.uint32(_NG_MULTS[i]))
        return (h & jnp.uint32(table - 1)).astype(jnp.int32)

    def push(ctx, tok):
        if n_ctx == 1:
            return tok[None]
        return jnp.concatenate([ctx[1:], tok[None]])

    def feed_draft(dparams, draft, pos, tok, valid=None):
        """Advance the persistent per-slot draft row by one fed token at the
        shared (possibly frozen) ``pos``.  ``valid`` (verify scan only)
        applies the same positional/recurrent masking split the target's
        caches get: positional draft writes under a frozen pos land on one
        dead row the next accepted token overwrites, recurrent draft leaves
        must be frozen explicitly."""
        _, new = lm.decode_step(
            dparams, {"caches": draft, "pos": pos}, tok[None, None],
            draft_cfg)
        if valid is None:
            return new["caches"]
        return {
            t: (new["caches"][t] if t in POSITIONAL_CACHE_TYPES
                else jax.tree.map(lambda o, n: jnp.where(valid, n, o),
                                  draft[t], new["caches"][t]))
            for t in draft}

    propose = PROPOSERS[proposer].build(cfg, draft_cfg, ng_hash, push) \
        if spec_len else None

    def write_back(active, caches, c2, ng, ng2, ctx, ctx2, draft0, d2):
        """The slot's pool row after the scan: the new state where the
        slot was active, the old one where it sat out."""
        with jax.named_scope("kv_pool"):
            pool_f = {"caches": jax.tree.map(
                lambda o, n: jnp.where(active, n, o), caches, c2),
                "ng": jnp.where(active, ng2, ng),
                "ctx": jnp.where(active, ctx2, ctx)}
            if draft_cfg is not None:
                pool_f["draft"] = jax.tree.map(
                    lambda o, n: jnp.where(active, n, o), draft0, d2)
        return pool_f

    def one_slot(params, dparams, pool, pos, toks, n_given, active, reset,
                 key, temp):
        caches, ng, ctx = pool["caches"], pool["ng"], pool["ctx"]
        # a freshly joined slot starts from a zeroed cache row, an empty
        # suffix table, zeroed draft state and pos 0 — folded into the tick
        # so the join costs no eager scatter dispatches
        with jax.named_scope("kv_pool"):
            caches = jax.tree.map(
                lambda c: jnp.where(reset, jnp.zeros_like(c), c), caches)
            ng = jnp.where(reset, 0, ng)
            ctx = jnp.where(reset, 0, ctx)
            pos = jnp.where(reset, 0, pos)
            draft0 = None
            if draft_cfg is not None:
                draft0 = jax.tree.map(
                    lambda c: jnp.where(reset, jnp.zeros_like(c), c),
                    pool["draft"])
        L = toks.shape[0]

        if spec_len:
            # draft chain from the proposer arm this tick compiled for; the
            # propose scan carries throwaway state copies (rolling-window
            # draft caches wrap, so kept overshoot writes could alias valid
            # history — see DraftProposer)
            if L > 1:
                toks = propose(dparams, draft0, ng, ctx, pos, toks)

            def body(carry, j):
                caches, draft, pos, ng, win, valid = carry
                tok = toks[j]
                # learn the stream (valid steps only: rejected drafts are
                # not real stream tokens and would poison the table)
                hidx = ng_hash(win)
                ng = ng.at[hidx].set(jnp.where(valid, tok, ng[hidx]))
                win = jnp.where(valid, push(win, tok), win)
                logits, new = lm.decode_step(
                    params, {"caches": caches, "pos": pos}, tok[None, None],
                    cfg)
                with jax.named_scope("head"):
                    nxt = jnp.argmax(logits[0], -1).astype(jnp.int32)
                # freeze only NON-positional state past the first mismatch:
                # KV rows a rejected step writes land at the frozen pos (or
                # ring onto it), which attention masks out and the next
                # accepted token overwrites before any read — but recurrent
                # leaves (rwkv's mixed states, mamba's conv window and SSM
                # state) cannot be position-rewound, so their rejected
                # writes must be masked out
                caches = {
                    t: (new["caches"][t] if t in POSITIONAL_CACHE_TYPES
                        else jax.tree.map(
                            lambda o, n: jnp.where(valid, n, o),
                            caches[t], new["caches"][t]))
                    for t in caches}
                if draft_cfg is not None:
                    # the persistent draft row consumes the same committed
                    # tokens the target does, under the same freeze
                    draft = feed_draft(dparams, draft, pos, tok, valid)
                pos = jnp.where(valid, new["pos"], pos)
                nxt_ok = jnp.where(j + 1 < L,
                                   toks[jnp.minimum(j + 1, L - 1)] == nxt,
                                   False)
                return (caches, draft, pos, ng, win, valid & nxt_ok), \
                    (nxt, valid)

            (c2, d2, p2, ng2, ctx2, _), (emitted, valids) = jax.lax.scan(
                body, (caches, draft0, pos, ng, ctx, jnp.bool_(True)),
                jnp.arange(L))
            pool_f = write_back(active, caches, c2, ng, ng2, ctx, ctx2,
                                draft0, d2)
            n_valid = jnp.where(active, valids.sum(dtype=jnp.int32), 0)
            return (pool_f, jnp.where(active, p2, pos), key, emitted,
                    n_valid)

        def body(carry, j):
            caches, draft, pos, prev, key, ng, win = carry
            tok = jnp.where(j < n_given, toks[j], prev)
            hidx = ng_hash(win)
            ng = ng.at[hidx].set(tok)
            win = push(win, tok)
            logits, new = lm.decode_step(
                params, {"caches": caches, "pos": pos}, tok[None, None], cfg)
            if draft_cfg is not None:
                # the draft shadows every arm (prefill chunks and plain
                # decode too), so its state always equals the committed
                # stream whichever arm the engine picks next tick
                draft = feed_draft(dparams, draft, pos, tok)
            key, sub = jax.random.split(key)
            with jax.named_scope("head"):
                nxt = sample_traced(logits[0], sub, temp)
            return (new["caches"], draft, new["pos"], nxt, key, ng, win), nxt

        (c2, d2, p2, _, k2, ng2, ctx2), emitted = jax.lax.scan(
            body, (caches, draft0, pos, toks[0], key, ng, ctx),
            jnp.arange(L))
        pool_f = write_back(active, caches, c2, ng, ng2, ctx, ctx2, draft0,
                            d2)
        return (pool_f, jnp.where(active, p2, pos),
                jnp.where(active, k2, key), emitted,
                jnp.where(active, jnp.int32(L), 0))

    vm = jax.vmap(one_slot, in_axes=(None, None, 0, 0, 0, 0, 0, 0, 0, 0))
    if draft_cfg is None:
        # draft-free ticks keep the historical 9-arg signature (dparams is
        # an empty pytree folded out of the jit)
        def tick(params, pool, pos, toks, n_given, active, reset, key,
                 temp):
            return vm(params, None, pool, pos, toks, n_given, active,
                      reset, key, temp)

        return jax.jit(tick, donate_argnums=(1,))
    return jax.jit(vm, donate_argnums=(2,))


@functools.lru_cache(maxsize=None)
def build_row_snapshot(cfg: ArchConfig):
    """Jitted single-row gather: one slot's full pool row (every cache
    leaf, n-gram table, context window) as fresh buffers — the capture side
    of the prefix cache.  ``slot`` is traced, so one compile covers every
    slot; memoized per cfg like ``build_slot_tick``."""
    return jax.jit(lambda pool, slot: jax.tree.map(lambda p: p[slot], pool))


@functools.lru_cache(maxsize=None)
def build_seed_write(cfg: ArchConfig):
    """Jitted batched seed write: scatter ``k`` snapshot rows (and their
    frozen positions) into a donated slot pool in ONE dispatch — the join
    path's no-eager-scatter discipline applied to seeding.  Writing the
    whole row subsumes the reset-mask zeroing: a seeded slot starts from
    the snapshot state exactly as a reset slot starts from zeros, so no
    stale state can leak from the previous occupant."""
    def seed(pool, pos, idx, rows, new_pos):
        pool = jax.tree.map(lambda p, r: p.at[idx].set(r), pool, rows)
        return pool, pos.at[idx].set(new_pos)

    return jax.jit(seed, donate_argnums=(0, 1))


@functools.lru_cache(maxsize=None)
def build_pool_gather(cfg: ArchConfig):
    """Jitted batched row gather — the capture side of slot migration: ``k``
    slots' full pool rows (every cache leaf, n-gram table + context window,
    draft rows) plus their positions and PRNG keys as fresh buffers, ready
    to ``device_put`` at the destination placement.  Memoized per cfg; the
    jit re-specializes per source sharding, so one build covers every
    placed pool."""
    return jax.jit(lambda pool, pos, keys, idx: (
        jax.tree.map(lambda p: p[idx], pool), pos[idx], keys[idx]))


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                   # [plen] int32
    max_new: int
    temperature: float = 0.0
    key: Any = None                      # private PRNG key (sampling)
    priority: str = "default"            # one of cfg.serve.classes
    pin_pool: Optional[int] = None       # admission restricted to this pool
    joined_version: int = 0              # params_version at admission: a
    #                                      request straddling a weight swap
    #                                      (joined old, finished new) is
    #                                      hybrid-state and must store
    #                                      neither results nor snapshots
    tokens: List[int] = dataclasses.field(default_factory=list)
    pool: int = -1                       # slot pool joined (-1: queued)
    slot: int = -1                       # slot within the pool
    prompt_off: int = 0
    pending_tok: int = -1                # emitted but not yet fed back
    seed_node: Any = None                # prefix-cache node this slot seeded
    #                                      from (ref held until eviction)
    # aging bookkeeping: scheduled ticks this prefill has sat out since it
    # last advanced; the peak is kept for the starvation regression tests
    deferred: int = 0
    max_deferred: int = 0
    # wall-clock marks: queued, joined a slot, first token, completion
    t_submit: float = 0.0
    t_admit: Optional[float] = None
    t_first: Optional[float] = None
    t_done: Optional[float] = None
    done: threading.Event = dataclasses.field(
        default_factory=threading.Event, repr=False)

    @property
    def prefilling(self) -> bool:
        return self.prompt_off < len(self.prompt)

    def output(self) -> np.ndarray:
        return np.asarray(self.tokens[:self.max_new], np.int32)


class SlotPool:
    """One donated slot pool: the per-pool device state the tick mutates.

    Every pool owns its cache rows, per-slot n-gram tables, positions, PRNG
    keys and reset mask; the compiled tick functions are NOT per-pool —
    ``build_slot_tick`` memoizes per (cfg, spec_len, draft_cfg, proposer),
    so pools of equal slot count share one jit.  ``pool_id`` is the
    engine-visible identity: tick jobs are recorded under
    ``jobs.pool_kind(kind, pool_id)`` (the per-pool cost EMAs the
    weighted-FRT arbitration scores) and acceptance under
    ``jobs.accept_kind(pool_id, arm)``.

    ``mesh`` (not None) *places* the pool: the donated state is committed
    to the mesh's devices under :func:`repro.runtime.sharding.pool_specs`
    (slot dim over ``data`` when divisible, trailing dims over ``model``
    at pool_tp > 1 — both reduction-free splits, so placement never
    touches bit-identicality), and the engine keeps a params copy on the
    same devices (``ServeEngine._params_for``).  ``lid`` is the pool's
    stable engine-local id: list position changes as pools drain away, the
    lid never does (requests address pools by it)."""

    def __init__(self, cfg: ArchConfig, pool_id: int, slots: int,
                 max_len: int, base_key,
                 draft_cfg: Optional[ArchConfig] = None,
                 mesh: Optional[Mesh] = None, lid: int = 0):
        self.pool_id = pool_id
        self.lid = lid
        self.mesh = mesh
        self.draining = False
        self.slots = slots
        one = lm.init_cache(cfg, 1, max_len)
        self.pool = {
            "caches": jax.tree.map(
                lambda x: jnp.zeros((slots,) + x.shape, x.dtype),
                one["caches"]),
            # per-slot n-gram suffix table + its context window: part of the
            # donated pool so draft proposal never leaves the device
            "ng": jnp.zeros((slots, cfg.serve.spec_table), jnp.int32),
            "ctx": jnp.zeros((slots, cfg.serve.spec_ctx), jnp.int32),
        }
        if draft_cfg is not None:
            # per-slot draft-model cache rows: same donated pool, so they
            # are reset-masked on join, snapshotted and seeded by the prefix
            # cache, and advanced in-jit with everything else
            done = lm.init_cache(draft_cfg, 1, max_len)
            self.pool["draft"] = jax.tree.map(
                lambda x: jnp.zeros((slots,) + x.shape, x.dtype),
                done["caches"])
        self.pos = jnp.zeros((slots,), jnp.int32)
        self.pos_host = np.zeros((slots,), np.int64)   # device-sync-free view
        self.reset = np.zeros((slots,), bool)          # zero these rows in-jit
        self.keys = jax.random.split(base_key, slots)
        if mesh is not None:
            state = {"pool": self.pool, "pos": self.pos, "keys": self.keys}
            placed = jax.device_put(state,
                                    named(mesh, pool_specs(mesh, state)))
            self.pool, self.pos, self.keys = \
                placed["pool"], placed["pos"], placed["keys"]
        self.active: List[Optional[Request]] = [None] * slots

    def free_slots(self) -> int:
        return sum(r is None for r in self.active)

    def devices(self) -> tuple:
        """The device group this pool's state lives on (the default device
        for unplaced pools) — the disjointness key for parallel group ticks
        and the identity of the engine's placed-params cache."""
        if self.mesh is not None:
            return tuple(self.mesh.devices.flat)
        return (jax.devices()[0],)

    def put(self, x):
        """Commit a value (pytree ok) to this pool's placement, replicated.
        Host/uncommitted inputs and rows gathered on ANOTHER pool's mesh
        both land here as local buffers, so the following eager scatter or
        seed-write jit runs entirely on this pool's devices."""
        if self.mesh is not None:
            return jax.device_put(x, NamedSharding(self.mesh, P()))
        return jax.device_put(x, jax.devices()[0])


@dataclasses.dataclass
class _TickPlan:
    """One planned tick, built by ``ServeEngine._plan_tick`` and not yet
    run: the resolved arm/length/participants/layout plus an **async**
    dispatch thunk (launches the jit, does NOT block).  Splitting plan →
    dispatch → commit is what lets one scheduling round co-dispatch ticks
    for several device-placed pools and overlap them before blocking on
    any (the parallel group-tick path)."""
    sp: SlotPool
    mode: str
    spec: bool
    arm: str
    L: int
    part: List[Request]
    part_slots: List[int]
    n_given: np.ndarray
    idx: np.ndarray
    compact: bool
    compact_ok: bool
    job: Job
    extras: tuple
    dispatch: Any


class ServeEngine:
    def __init__(self, cfg: ArchConfig, params, max_len: int = 128,
                 slots: int = 4, prefill_chunk: int = 16,
                 decode_chunk: int = 4, engine: Optional[Engine] = None,
                 seed: int = 0, compact_decode: Optional[bool] = None,
                 spec_decode: bool = False, pool_id: int = 0,
                 pools: int = 1,
                 class_pools: Optional[Dict[str, tuple]] = None,
                 prefix_cache: bool = False, params_version: int = 0,
                 draft: Optional[str] = None,
                 draft_cfg: Optional[ArchConfig] = None,
                 draft_params=None,
                 placements: Optional[Dict[int, Any]] = None,
                 autotune: Any = False):
        self.cfg = cfg
        self.params = params
        self.engine = engine or Engine()
        self.max_len = max_len
        self.slots = slots
        self.prefill_chunk = prefill_chunk
        self.decode_chunk = decode_chunk
        # lane-waste mitigation: when at least half the pool sits out a
        # decode tick (idle slots + prefill slots deferred by the min-FRT
        # rule), gather the participants into a compact batch before the
        # tick vmap so sat-out lanes stop burning decode FLOPs.  Costs one
        # gather + scatter-back of the participating cache rows per tick,
        # so it is gated on the pool being at least half idle — and within
        # that gate, layout is a MEASURED CostBook arm: compact_decode=None
        # (the default) lets ``Engine.choose_compact`` flip per tick from
        # per-pool compact-vs-full per-token EMAs; True/False pins it.
        self.compact_decode = compact_decode
        self.compact_ticks = 0
        # tunable knobs, seeded from config but hot-updatable (update()
        # handlers + the AutoTuner meta-controller): the live speculative
        # draft length, and the compaction-eligibility fraction — a decode
        # tick is compact-eligible when its participants fit in
        # ``int(slots * compact_frac)`` lanes.  0.5 reproduces the
        # historical ``slots // 2`` gate exactly.  Hot spec_len changes are
        # safe mid-stream: _tick_len caps L against every participant's
        # cache headroom and _plan_tick skips slots that would overrun.
        self.spec_len = int(cfg.serve.spec_len)
        self.compact_frac = 0.5
        # speculative in-tick decoding (see module docstring): offers the
        # engine extra tick arms — proposer draft + chunk-scan verify —
        # whose use is decided per tick from measured per-arm
        # acceptance/runtime EMAs.  ``pool_id`` offsets this engine's pool
        # ids (pools get pool_id..pool_id+pools-1) so acceptance and
        # runtime EMAs stay namespaced when several ServeEngines share one
        # Engine.
        self.spec_decode = spec_decode
        self.pool_id = pool_id
        self.spec_ticks = 0
        self.spec_proposed = 0      # draft tokens offered for verification
        self.spec_accepted = 0      # draft tokens committed
        # per-arm speculative counters ({"ngram": {...}, "draft": {...}})
        self.spec_arms: Dict[str, Dict[str, int]] = {}
        # draft-model proposer: draft="self" slices a truncated self-draft
        # out of the serve params (cfg.serve.draft_layers blocks + shared
        # head); an independent/distilled draft arrives as
        # draft_cfg+draft_params.  Either way the draft is acceptance-only:
        # it can never change outputs (engine.draft module docstring).
        from repro.engine.draft import slice_draft_params, truncated_draft_cfg
        self.draft_cfg: Optional[ArchConfig] = None
        self.draft_params = None
        # remembered so a hot params publish can re-slice the self-draft
        # (an independent draft is republished separately via draft_params)
        self._self_draft = draft == "self"
        if draft is not None:
            assert draft == "self", f"unknown draft mode {draft!r}"
            assert draft_cfg is None and draft_params is None, \
                "draft='self' derives the draft from the serve params"
            self.draft_cfg = truncated_draft_cfg(cfg)
            self.draft_params = slice_draft_params(params, cfg,
                                                   self.draft_cfg)
        elif draft_cfg is not None:
            assert draft_params is not None, \
                "an independent draft_cfg needs draft_params"
            self.draft_cfg = draft_cfg
            self.draft_params = draft_params
        # priority classes: name -> PriorityClass; the first table entry is
        # the default for requests submitted without a priority
        self.classes = {c.name: c for c in cfg.serve.classes}
        self._default_class = cfg.serve.classes[0].name
        # optional class -> admissible-pool routing (BatchedServer wires
        # this up); classes not listed may join any pool.  Validated here:
        # a typo'd class name or out-of-range pool id must fail at
        # construction, not mid-serve inside _admit
        self.class_pools = dict(class_pools or {})
        for cls, pids in self.class_pools.items():
            assert cls in self.classes, \
                f"class_pools names unknown class {cls!r}"
            assert pids and all(0 <= p < max(int(pools), 1) for p in pids), \
                f"class_pools[{cls!r}]={pids}: pool ids must be in " \
                f"[0, {max(int(pools), 1)})"
        self._base_key = jax.random.PRNGKey(seed)
        # device placement table: local pool id -> Mesh.  Values accepted
        # as a Mesh, a single jax.Device, or a device sequence (normalized
        # through runtime.sharding.pool_mesh at cfg.serve.pool_tp).  Pools
        # not listed stay on the default device — the legacy layout.
        self.placements: Dict[int, Mesh] = {}
        for i, plc in (placements or {}).items():
            assert 0 <= int(i) < max(int(pools), 1), \
                f"placements[{i}]: no such pool (pools={pools})"
            self.placements[int(i)] = self._as_mesh(plc)
        # per-device-group params copies for placed pools, built lazily on
        # first tick and invalidated by identity when params/draft_params
        # are hot-swapped (ServeEngine._params_for)
        self._pool_params: Dict[tuple, Dict[str, Any]] = {}
        # pool registry: each pool its own donated device state; pool 0
        # derives its slot keys straight from the engine seed (the exact
        # pre-multi-pool layout), later pools fold their index in.  List
        # position is transient (drained pools drop out); ``lid`` is the
        # stable identity requests/routing address pools by.
        self.pools: List[SlotPool] = [
            SlotPool(cfg, pool_id + i, slots, max_len,
                     self._base_key if i == 0
                     else jax.random.fold_in(self._base_key,
                                             0x7F000000 + i),
                     draft_cfg=self.draft_cfg,
                     mesh=self.placements.get(i), lid=i)
            for i in range(max(int(pools), 1))]
        self._next_local = max(int(pools), 1)
        self._last_mig_dst: Optional[int] = None
        self.migrated_slots = 0
        self.parallel_group_ticks = 0
        self._tick = build_slot_tick(cfg, 0, self.draft_cfg)
        self._compiled: set = set()    # (spec, tick_len, rows) already jitted
        # cross-request prefix cache + result cache (module docstring):
        # snapshots committed prompt prefixes at prefill tick boundaries and
        # seeds joining slots from the deepest match when the engine's
        # measured FRT comparison says the seed path answers first.
        # ``params_version`` keys the result cache: a hot weight swap bumps
        # it so stale answers cannot serve.
        sc = cfg.serve
        self.params_version = params_version
        self.prefix: Optional[PrefixCache] = PrefixCache(
            sc.prefix_cache_nodes, sc.prefix_min_len,
            sc.result_cache_entries) if prefix_cache else None
        self._analyzer = PrefixAnalyzer(sc.prefix_min_len,
                                        sc.prefix_pin_count,
                                        sc.prefix_history)
        self._n_submitted = 0
        self.queue: Deque[Request] = deque()
        self.tick_no = 0
        self.tokens_out = 0
        # observability counters (running totals, never reset): host
        # seconds of the tick's phases and of whole ticks by composition,
        # and where a request's time to first token goes — the wait for a
        # slot (submit -> admit) and its prefill (admit -> first token)
        self.admit_s = 0.0
        self.plan_s = 0.0
        self.commit_s = 0.0
        self.prefill_tick_s = 0.0
        self.decode_tick_s = 0.0
        self.queue_wait_s = 0.0
        self.admitted = 0
        self.prefill_s = 0.0
        self.first_tokens = 0
        self.cache_answered = 0      # result-cache hits: never take a slot
        self._rid = itertools.count()
        self.hit_breakpoints: List[str] = []
        # closed-loop knob tuning (engine.autotune): the meta-controller
        # that makes the engine's OWN knobs (spec_len, compact_frac,
        # prefill_chunk, class weights) a result-aware Maestro decision.
        # ``autotune=True`` wires the default knob set; a dict passes
        # AutoTuner kwargs (knobs=, window=, ...); False leaves the knobs
        # config-pinned.  Built last: the tuner reads live engine state.
        self.autotuner = None
        if autotune:
            from repro.engine.autotune import AutoTuner
            kw = dict(autotune) if isinstance(autotune, dict) else {}
            self.autotuner = AutoTuner(self, **kw)

    # ------------------------------------------------ single-pool back-compat
    @property
    def active(self) -> List[Optional[Request]]:
        """Admitted requests across every pool (slot-ordered within pools).
        Read-only flattened view; per-pool state lives on ``self.pools``."""
        return [r for sp in self.pools for r in sp.active]

    @property
    def single_pool(self) -> bool:
        """True when scheduling can take the original single-pool path:
        one pool AND the default single-class table.  This path is kept
        decision-identical (not just output-identical) to the pre-priority
        engine — the differential harness pins it against the static
        oracle."""
        return len(self.pools) == 1 and len(self.classes) == 1

    # ------------------------------------------------------------- placement
    def _as_mesh(self, plc) -> Mesh:
        """Normalize a placement value (Mesh | Device | device sequence)
        to a pool mesh at the configured tensor-parallel degree."""
        if isinstance(plc, Mesh):
            return plc
        if isinstance(plc, (list, tuple)):
            return pool_mesh(plc, self.cfg.serve.pool_tp)
        return pool_mesh([plc], self.cfg.serve.pool_tp)

    def _pool(self, lid: int) -> Optional[SlotPool]:
        """Pool by stable local id (None once drained away)."""
        for sp in self.pools:
            if sp.lid == lid:
                return sp
        return None

    def _params_for(self, sp: SlotPool):
        """(target params, draft params) committed to the pool's placement.
        Unplaced pools share the engine's own references; placed pools get
        a per-device-group copy — replicated at pool_tp=1 (the
        bit-identicality default), tensor-parallel under the
        ``param_specs`` rules when the pool mesh carries a model axis.
        Cached by device group and invalidated by source identity, so a hot
        ``draft_params`` republish reaches placed pools on their next
        tick."""
        if sp.mesh is None:
            return self.params, self.draft_params
        ent = self._pool_params.setdefault(sp.devices(), {})
        if ent.get("src") is not self.params:
            if axis_size(sp.mesh, "model") > 1:
                sh = named(sp.mesh, param_specs(self.cfg, sp.mesh,
                                                fsdp=False))
            else:
                sh = NamedSharding(sp.mesh, P())
            ent["params"] = jax.device_put(self.params, sh)
            ent["src"] = self.params
        if self.draft_cfg is not None and \
                ent.get("dsrc") is not self.draft_params:
            ent["draft"] = jax.device_put(self.draft_params,
                                          NamedSharding(sp.mesh, P()))
            ent["dsrc"] = self.draft_params
        return ent["params"], ent.get("draft")

    def _group_busy(self, sp: SlotPool) -> float:
        """Occupancy fraction of the OTHER pools sharing any of this
        pool's devices — the contention term of placement-aware admission
        and of the arbitration's ``load`` input.  Zero when the pool's
        device group is exclusively its own."""
        devs = set(sp.devices())
        tot = occ = 0
        for o in self.pools:
            if o is sp or not devs & set(o.devices()):
                continue
            tot += o.slots
            occ += o.slots - o.free_slots()
        return occ / tot if tot else 0.0

    def add_pool(self, placement=None, slots: Optional[int] = None) -> int:
        """Elastic scale-out: append a new slot pool under load, optionally
        device-placed (``placement``: Mesh | Device | device sequence).
        Returns the pool's local id — immediately admissible, usable as
        ``submit(pool=...)``.  Slot PRNG keys derive from the engine seed
        and the local id exactly as construction-time pools do, so an
        engine built with N pools and one grown to N pools are
        key-identical."""
        lid = self._next_local
        self._next_local += 1
        mesh = None if placement is None else self._as_mesh(placement)
        sp = SlotPool(self.cfg, self.pool_id + lid,
                      slots or self.slots, self.max_len,
                      jax.random.fold_in(self._base_key, 0x7F000000 + lid),
                      draft_cfg=self.draft_cfg, mesh=mesh, lid=lid)
        self.pools.append(sp)
        if mesh is not None:
            self.placements[lid] = mesh
        return lid

    def drain_pool(self, lid: int) -> None:
        """Elastic scale-in, live: stop admitting to pool ``lid`` and
        migrate its in-flight slots out — up to ``cfg.serve.migrate_batch``
        per tick (bounding the per-tick stall), destination chosen by
        ``Engine.choose_migration_dst`` — then retire the empty pool.  The
        draining pool keeps offering candidate ticks until its last slot
        leaves, so nothing stops streaming; migrated continuations are
        greedy-bit-identical (``_migrate_slots``) and zero requests drop.
        Queued requests pinned to the pool fall back to open routing."""
        sp = self._pool(lid)
        assert sp is not None, f"no pool {lid}"
        assert any(o is not sp and not o.draining for o in self.pools), \
            "drain_pool would leave no admissible pool"
        sp.draining = True
        for req in self.queue:
            if req.pin_pool == lid:
                req.pin_pool = None

    def _drain_step(self) -> None:
        """One migration batch per draining pool per tick; pools empty of
        slots are removed.  A fully-saturated fleet simply defers the
        migration — the draining pool keeps serving its slots until
        capacity opens up."""
        for src in [sp for sp in self.pools if sp.draining]:
            occ = [(s, r) for s, r in enumerate(src.active)
                   if r is not None]
            if occ:
                opts = [{"pool": o.lid, "free": o.free_slots(),
                         "busy": self._group_busy(o),
                         "devices": len(o.devices())}
                        for o in self.pools
                        if o is not src and not o.draining
                        and o.free_slots() > 0]
                if not opts:
                    continue
                dst_lid = self.engine.choose_migration_dst(opts)
                dst = self._pool(dst_lid)
                self._last_mig_dst = dst_lid
                moves = occ[:min(self.cfg.serve.migrate_batch,
                                 dst.free_slots())]
                self._migrate_slots(src, dst, moves)
            if src.free_slots() == src.slots:
                self.pools.remove(src)
                self.placements.pop(src.lid, None)

    def _migrate_slots(self, src: SlotPool, dst: SlotPool,
                       moves: List[tuple]) -> None:
        """Move in-flight slots ``src -> dst``: one jitted batched gather
        of the full pool rows (every cache family, n-gram table + context,
        draft rows) plus positions and PRNG keys on the source placement,
        a ``device_put`` to the destination placement, and one jitted
        batched scatter — the seed-write jit, which writes whole rows and
        so subsumes reset-mask zeroing.  A slot's row + position + key
        fully determine its continuation (the tick consumes tokens one
        ``lm.decode_step`` at a time), so greedy outputs are bit-identical
        across any migration; a never-ticked join travels as its garbage
        row plus its still-pending reset flag, which the next tick zeroes
        in-jit as usual.  Measured as a ``serve_migrate`` job (per-token:
        the consumed positions moved), with the destination's pool-scoped
        EMA feeding ``choose_migration_dst`` and the arbitration's ``xfer``
        term."""
        k = len(moves)
        free = [s for s in range(dst.slots) if dst.active[s] is None]
        assert k and len(free) >= k
        dst_slots = free[:k]
        src_idx = jnp.asarray([s for s, _ in moves], jnp.int32)
        dst_idx = jnp.asarray(dst_slots, jnp.int32)
        gather = build_pool_gather(self.cfg)
        seed_fn = build_seed_write(self.cfg)
        ntok = max(int(sum(src.pos_host[s] for s, _ in moves)), 1)
        ck = ("migrate", src.devices(), dst.devices(), k)
        cold = ck not in self._compiled
        self._compiled.add(ck)
        job = Job("serve_migrate", tokens=ntok, meta={"cold": cold})
        extras = (Job(pool_kind("serve_migrate", dst.pool_id), tokens=ntok,
                      meta={"cold": cold}),)

        def thunk():
            rows, pos, keys = gather(src.pool, src.pos, src.keys, src_idx)
            rows, pos, keys = dst.put((rows, pos, keys))
            pool_n, pos_n = seed_fn(dst.pool, dst.pos, dst_idx, rows, pos)
            keys_n = dst.keys.at[dst_idx].set(keys)
            return jax.block_until_ready((pool_n, pos_n, keys_n))

        dst.pool, dst.pos, dst.keys = self.engine.run_job(
            job, thunk, extra=extras)
        for (s, r), d in zip(moves, dst_slots):
            dst.active[d] = r
            dst.pos_host[d] = src.pos_host[s]
            dst.reset[d] = bool(src.reset[s])
            r.pool, r.slot = dst.lid, d
            src.active[s] = None
            src.reset[s] = False
            src.pos_host[s] = 0
        self.migrated_slots += k

    # ------------------------------------------------------------- requests
    def submit(self, prompt, max_new: int = 16, temperature: float = 0.0,
               key=None, priority: Optional[str] = None,
               pool: Optional[int] = None) -> Request:
        """Queue a request.  ``key`` pins the request's private sampling
        stream (reproducibility); default derives one from the engine seed
        and the request id.  ``priority`` names a ``cfg.serve.classes``
        entry (default: the table's first class); ``pool`` pins admission
        to one slot pool (default: class routing, then least-loaded)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        assert prompt.size >= 1, "empty prompt"
        need = prompt.size + max_new + max(
            self.prefill_chunk, self.decode_chunk,
            self.spec_len if self.spec_decode else 0)
        assert need <= self.max_len, \
            f"prompt+max_new+chunk={need} exceeds max_len={self.max_len}"
        priority = priority or self._default_class
        assert priority in self.classes, \
            f"unknown priority {priority!r}; classes: {list(self.classes)}"
        assert pool is None or self._pool(pool) is not None, \
            f"no pool {pool}; live pools: {[sp.lid for sp in self.pools]}"
        rid = next(self._rid)
        if key is None:
            key = jax.random.fold_in(self._base_key, rid)
        req = Request(rid, prompt, max_new, temperature, key=key,
                      priority=priority, pin_pool=pool,
                      t_submit=time.perf_counter())
        if self.prefix is not None:
            # workload analyzer: count this prompt's grid prefixes and
            # periodically pin the hottest ones against LRU eviction
            self._analyzer.record(prompt)
            self._n_submitted += 1
            if self._n_submitted % 32 == 0:
                for p in self._analyzer.hot_prefixes()[:8]:
                    self.prefix.pin(p)
        self.queue.append(req)
        return req

    def _evict(self, req: Request) -> None:
        sp = self._pool(req.pool)
        # a request that straddled a weight swap (joined under an older
        # params_version) ran partly on old weights: its slot state and its
        # output are hybrid artifacts of neither version — store nothing
        fresh = req.joined_version == self.params_version
        if self.prefix is not None:
            if self.cfg.serve.snapshot_on_evict and fresh:
                # "commit extends the tree": snapshot the slot's full
                # committed path (prompt + generated) so an agent-loop
                # follow-up whose prompt extends this response seeds from
                # here.  Off by default — the per-evict row copy only pays
                # off on such workloads.
                path = np.concatenate(
                    [req.prompt, np.asarray(req.tokens, np.int32)]
                )[:int(sp.pos_host[req.slot])]
                if len(path) >= self.prefix.min_len and not (
                        (n := self.prefix.lookup(path)) is not None
                        and n.snapshot is not None
                        and n.version == self.params_version):
                    self._snapshot_slot(sp, req.slot, path)
            if req.seed_node is not None:
                self.prefix.release(req.seed_node)
                req.seed_node = None
            # finished greedy outputs become exact-hit answers for repeats
            # (version-gated: a hybrid-state output keyed under the current
            # version would serve an answer neither weight set produces)
            if fresh:
                self.prefix.result_store(req.prompt, req.max_new,
                                         req.temperature,
                                         self.params_version, req.output())
        sp.active[req.slot] = None
        req.pool = req.slot = -1
        req.t_done = time.perf_counter()
        req.done.set()

    def _finish_from_cache(self, req: Request, tokens: List[int]) -> None:
        """Answer a request straight from the result cache: no slot, no
        prefill, no decode — the first and last token land together."""
        req.tokens = list(tokens)
        now = time.perf_counter()
        req.t_first = req.t_first or now
        req.t_done = now
        self.tokens_out += len(req.tokens)
        self.cache_answered += 1
        req.done.set()

    def _snapshot_slot(self, sp: SlotPool, slot: int, path) -> None:
        """Capture one slot's pool row (jitted gather, measured as a
        ``serve_snapshot`` job) and commit it into the radix tree under
        ``path`` — the token prefix the slot has consumed so far.  The row
        is normalized to host numpy (``prefix_cache.to_host``) before it
        enters the tree: snapshots are placement-portable — captured on any
        pool's mesh, seeding any other pool (the seed-write jit re-commits
        host rows wherever the destination lives) — and hold no device
        buffers alive while cached."""
        cold = ("snapshot", sp.devices()) not in self._compiled
        self._compiled.add(("snapshot", sp.devices()))
        snap_fn = build_row_snapshot(self.cfg)
        job = Job("serve_snapshot", tokens=len(path), meta={"cold": cold})
        pjob = Job(pool_kind("serve_snapshot", sp.pool_id),
                   tokens=len(path), meta={"cold": cold})
        row = self.engine.run_job(
            job, lambda: jax.block_until_ready(snap_fn(sp.pool, slot)),
            extra=(pjob,))
        self.prefix.insert(path, snapshot=to_host(row),
                           version=self.params_version)

    def _allowed_pools(self, req: Request) -> List[int]:
        if req.pin_pool is not None:
            return [req.pin_pool]
        allowed = self.class_pools.get(req.priority)
        if allowed is not None:
            live = [p for p in allowed
                    if (sp := self._pool(p)) is not None
                    and not sp.draining]
            if live:
                return live
            # every routed pool drained away: fall back to open routing
            # rather than stranding the class
        return [sp.lid for sp in self.pools if not sp.draining]

    def _admit(self) -> None:
        """Join queued requests into free slots.  The cache-row zeroing and
        position reset are deferred into the next tick's jit (the ``reset``
        mask) — stale recurrent/rolling state must not leak between
        requests, but eager per-join scatters cost more than the tick's
        compute at smoke scale.  Only the tiny per-slot PRNG key is written
        eagerly (one batched scatter per pool for all its joiners).

        Routing: a pinned request only joins its pool; otherwise the
        class-routing table restricts the admissible pools, and among those
        the emptiest pool wins (ties: lowest pool id).  Requests whose
        admissible pools are all full stay queued — in order, without
        blocking later requests bound for a free pool — via one linear
        pass that rebuilds the queue.

        Prefix cache (when enabled): an exact result-cache hit answers the
        request here — it never takes a slot.  Otherwise a greedy request
        looks up its longest snapshotted prompt prefix, and if the engine's
        measured FRT comparison picks the seed path, the slot starts from
        the snapshot: ``prompt_off``/``pos`` begin at the cached depth and
        ``reset`` stays False (the seed write replaces the whole row, so no
        stale state survives).  Sampled requests never seed: the plain arm
        splits the slot's PRNG key once per scan step *including prefill
        steps*, so skipping prefill would shift a sampled request's key
        stream — greedy outputs ignore the key, which is exactly why the
        bit-identicality claim holds.  All seed rows land in ONE jitted
        batched write per pool (the join path's no-eager-scatter rule)."""
        joined: Dict[int, list] = {}
        seeds: Dict[int, list] = {}
        remaining: Deque[Request] = deque()
        now = time.perf_counter()
        for req in self.queue:
            if (self.prefix is not None and req.temperature <= 0
                    and (out := self.prefix.result_lookup(
                        req.prompt, req.max_new, req.temperature,
                        self.params_version)) is not None):
                self._finish_from_cache(req, out)
                continue
            cands = [p for p in self._allowed_pools(req)
                     if (c := self._pool(p)) is not None
                     and not c.draining and c.free_slots() > 0]
            if not cands:
                remaining.append(req)
                continue
            if self.placements and len(cands) > 1:
                # placement-aware admission: an engine decision over
                # occupancy-inflated per-pool per-token EMAs — a fast idle
                # device group beats a fast contended one
                pid = self.engine.choose_admission_pool(
                    [{"pool": p, "free": self._pool(p).free_slots(),
                      "busy": self._group_busy(self._pool(p)),
                      "devices": len(self._pool(p).devices())}
                     for p in cands])
            else:
                pid = max(cands,
                          key=lambda p: (self._pool(p).free_slots(), -p))
            sp = self._pool(pid)
            slot = next(s for s in range(sp.slots) if sp.active[s] is None)
            req.pool, req.slot = pid, slot
            req.joined_version = self.params_version
            req.t_admit = now
            self.admitted += 1
            self.queue_wait_s += now - req.t_submit
            sp.active[slot] = req
            node = None
            if self.prefix is not None and req.temperature <= 0:
                # >= 1 prompt token must remain to produce the first logits;
                # only snapshots captured under the CURRENT params version
                # may seed — old-version KV state under new weights would
                # replay stale state (the hot-swap staleness bug)
                node = self.prefix.longest_match(
                    req.prompt, limit=len(req.prompt) - 1,
                    version=self.params_version)
            if node is not None and self.engine.choose_prefix_admission(
                    node.depth, len(req.prompt) - node.depth,
                    pool_id=sp.pool_id) == "seed":
                self.prefix.acquire(node)
                req.seed_node = node
                req.prompt_off = node.depth
                sp.reset[slot] = False
                sp.pos_host[slot] = node.depth
                seeds.setdefault(pid, []).append((slot, node))
                self.prefix.seeded += 1
                self.prefix.tokens_avoided += node.depth
            else:
                if node is not None:
                    self.prefix.seed_declined += 1
                sp.reset[slot] = True
                sp.pos_host[slot] = 0
            joined.setdefault(pid, []).append((slot, req))
        self.queue = remaining
        for pid, js in joined.items():
            sp = self._pool(pid)
            idx = jnp.asarray([s for s, _ in js], jnp.int32)
            ks = jnp.stack([req.key for _, req in js])
            if sp.mesh is not None:
                ks = sp.put(ks)      # keep the scatter on the pool devices
            sp.keys = sp.keys.at[idx].set(ks)
        for pid, ss in seeds.items():
            sp = self._pool(pid)
            idx = jnp.asarray([s for s, _ in ss], jnp.int32)
            # snapshots are host numpy (placement-portable): stacked rows
            # arrive uncommitted, so the seed jit commits them wherever
            # this pool's donated state lives
            rows = jax.tree.map(lambda *rs: jnp.stack(rs),
                                *[n.snapshot for _, n in ss])
            new_pos = jnp.asarray([n.pos for _, n in ss], jnp.int32)
            cold = ("seed", sp.devices(), len(ss)) not in self._compiled
            self._compiled.add(("seed", sp.devices(), len(ss)))
            seed_fn = build_seed_write(self.cfg)
            depth = sum(n.depth for _, n in ss)
            job = Job("serve_seed", tokens=depth, meta={"cold": cold})
            pjob = Job(pool_kind("serve_seed", sp.pool_id), tokens=depth,
                       meta={"cold": cold})
            sp.pool, sp.pos = self.engine.run_job(
                job, lambda: jax.block_until_ready(seed_fn(
                    sp.pool, sp.pos, idx, rows, new_pos)),
                extra=(pjob,))

    # -------------------------------------------------------------- control
    def _inspect(self, what: str) -> Dict[str, Any]:
        info = {"tick": self.tick_no, "queue_depth": len(self.queue),
                "tokens_out": self.tokens_out,
                "paused": self.engine.controller.paused,
                # where the host time and the time to first token went
                # (running totals; engine README, "Observability")
                "counters": {k: getattr(self, k) for k in (
                    "admit_s", "plan_s", "commit_s", "prefill_tick_s",
                    "decode_tick_s", "queue_wait_s", "admitted",
                    "prefill_s", "first_tokens", "cache_answered")},
                "spec": {"enabled": self.spec_decode,
                         "ticks": self.spec_ticks,
                         "proposed": self.spec_proposed,
                         "accepted": self.spec_accepted,
                         "draft": None if self.draft_cfg is None
                         else self.draft_cfg.name,
                         "arms": {a: dict(c)
                                  for a, c in self.spec_arms.items()}},
                # decision telemetry ring buffer: every choose_* call the
                # engine made, with the per-arm scores and CostBook inputs
                # it saw — the explainability substrate (ROADMAP item 5)
                "decisions": list(self.engine.decisions),
                "prefix_cache": (self.prefix.stats()
                                 if self.prefix is not None
                                 else {"enabled": False}),
                "slots": [None if r is None else
                          {"rid": r.rid, "prompt_off": r.prompt_off,
                           "plen": len(r.prompt), "out": len(r.tokens),
                           "max_new": r.max_new, "priority": r.priority,
                           "deferred": r.deferred}
                          for r in self.active],
                "pools": [{"id": sp.pool_id, "lid": sp.lid,
                           "slots": sp.slots, "free": sp.free_slots(),
                           "draining": sp.draining,
                           "devices": ([str(d) for d in sp.devices()]
                                       if sp.mesh is not None else None)}
                          for sp in self.pools],
                "placement": {"placed_pools": len(self.placements),
                              "migrated_slots": self.migrated_slots,
                              "parallel_group_ticks":
                                  self.parallel_group_ticks},
                "classes": {n: {"weight": c.weight,
                                "max_defer": c.max_defer}
                            for n, c in self.classes.items()},
                # live tunable-knob values + the meta-controller's state:
                # the telemetry schema the gauntlet/autotune stack reads
                "knobs": {"spec_len": self.spec_len,
                          "compact_frac": self.compact_frac,
                          "prefill_chunk": self.prefill_chunk,
                          "decode_chunk": self.decode_chunk,
                          "class_weights": {n: c.weight
                                            for n, c in
                                            self.classes.items()}},
                "autotune": (self.autotuner.snapshot()
                             if self.autotuner is not None
                             else {"enabled": False}),
                "engine": self.engine.inspect()}
        return info

    def _apply_updates(self, updates: Dict[str, Any]) -> None:
        if "max_prefill_defer" in updates:
            self.engine.max_prefill_defer = int(updates["max_prefill_defer"])
        if "decode_chunk" in updates:
            self.decode_chunk = int(updates["decode_chunk"])
        if "prefill_chunk" in updates:
            self.prefill_chunk = int(updates["prefill_chunk"])
        if "spec_decode" in updates:
            self.spec_decode = bool(updates["spec_decode"])
        if "spec_len" in updates:
            # hot draft-length change: mid-stream safety comes from the
            # existing guards (_tick_len headroom cap, _plan_tick overrun
            # skip); a value the cache can't host simply shrinks the tick
            self.spec_len = max(int(updates["spec_len"]), 0)
        if "compact_frac" in updates:
            self.compact_frac = min(max(
                float(updates["compact_frac"]), 0.0), 1.0)
        if "class_weights" in updates:
            # per-class weight retune ({name: weight}): arbitration-only
            # state, so a frozen-dataclass replace at the tick boundary is
            # the whole swap — aging bounds (max_defer) are NOT tunable,
            # they are the starvation guarantee
            for name, w in dict(updates["class_weights"]).items():
                assert name in self.classes, \
                    f"class_weights names unknown class {name!r}"
                self.classes[name] = dataclasses.replace(
                    self.classes[name], weight=float(w))
        if "autotune" in updates:
            on = updates["autotune"]
            if on and self.autotuner is None:
                from repro.engine.autotune import AutoTuner
                kw = dict(on) if isinstance(on, dict) else {}
                self.autotuner = AutoTuner(self, **kw)
            elif not on:
                self.autotuner = None
        if "compact_decode" in updates:
            v = updates["compact_decode"]
            self.compact_decode = None if v is None else bool(v)
        if "draft_params" in updates:
            # hot draft republish: a draft is acceptance-only state, so the
            # swap needs no drain, no re-seed and no cache relayout — the
            # next draft-arm tick simply proposes from the new weights.
            # Ignored when no draft was configured at construction: hot
            # ENABLING a draft would need a pool relayout (draft rows).
            if self.draft_cfg is not None:
                self.draft_params = updates["draft_params"]
        if "prefix_cache" in updates:
            on = bool(updates["prefix_cache"])
            if on and self.prefix is None:
                sc = self.cfg.serve
                self.prefix = PrefixCache(sc.prefix_cache_nodes,
                                          sc.prefix_min_len,
                                          sc.result_cache_entries)
            elif not on and self.prefix is not None:
                # in-flight seeded requests keep their (host) refs on the
                # dropped tree; nothing reads it again, so just detach
                self.prefix = None
        if "params" in updates:
            # hot weight swap (the train->serve publish path): commit the
            # incoming host trees once and rebind — the fresh object
            # identity is what invalidates _params_for's per-device-group
            # cache, and any tick already planned this round closed over
            # the OLD reference at plan time, so it commits consistently
            # (its requests are version-gated out of storing results).
            self.params = jax.tree.map(jnp.asarray, updates["params"])
            if self._self_draft:
                from repro.engine.draft import slice_draft_params
                self.draft_params = slice_draft_params(
                    self.params, self.cfg, self.draft_cfg)
            # an explicit params_version in the same update wins; a bare
            # params swap auto-bumps so stale results can never serve
            self._bump_version(int(updates.get(
                "params_version", self.params_version + 1)))
        elif "params_version" in updates:
            # hot weight swap signaled out-of-band: new version keys the
            # result cache so stale answers cannot serve (old entries age
            # out of the LRU) and flushes stale prefix snapshots
            self._bump_version(int(updates["params_version"]))

    def _bump_version(self, version: int) -> None:
        """Move to a new params version: snapshots captured under any other
        version are flushed from the radix tree (they can never match again
        — ``longest_match`` filters by version — so keeping them is pure
        waste; ``serve.flush_prefix_on_publish=False`` keeps them for
        workloads that flip between versions).  The result cache needs no
        flush: its keys carry the version, old entries age out of the LRU."""
        if version == self.params_version:
            return
        self.params_version = int(version)
        if self.prefix is not None and self.cfg.serve.flush_prefix_on_publish:
            self.prefix.flush_versions(self.params_version)

    def update(self, **updates) -> None:
        """Queue a hot update through the controller mailbox — applied at
        the next tick boundary, like every control client's updates.
        ``update(params=..., params_version=...)`` is the weight-publish
        entry point (TrainLoop's ``publish_every`` hook calls it): in-flight
        planned ticks finish on the old reference, requests admitted after
        the boundary see the new weights, and zero requests drop."""
        self.engine.controller.send(M.update(**updates))

    def _poll(self) -> bool:
        r = self.engine.poll(self.tick_no, 0, self._inspect)
        self._apply_updates(r["updates"])
        return r["stopped"]

    def _check_breakpoints(self, emitted: int) -> None:
        m = {"emitted": float(emitted), "queue": float(len(self.queue)),
             "active": float(sum(r is not None for r in self.active)),
             "tokens_out": float(self.tokens_out)}
        for bp in self.engine.local_bps:
            if bp.check(m):
                self.hit_breakpoints.append(bp.name)
                self.engine.controller.paused = True
        for bp in list(self.engine.global_bps):
            if bp.update([emitted]):
                self.hit_breakpoints.append(bp.name)
                self.engine.controller.paused = True
                self.engine.global_bps.remove(bp)

    # ----------------------------------------------------------------- tick
    def _tick_len(self, sp: SlotPool, act: List[Request], mode: str,
                  chunk: int) -> int:
        """Adaptive tick length: no slot needs more than its remaining
        horizon, so trim the chunk to the longest one (rounded up to a
        power of two — the tick jit specializes on L, and an arbitrary L
        would compile once per distinct tail length).  ``cap`` keeps the
        tick inside the tightest participant's cache headroom: submit()
        reserves a chunk of slack, but a hot chunk-size update could
        otherwise leave a near-full slot unable to ever run again."""
        need, cap = 1, chunk
        for r in act:
            if mode != "prefill" and r.prefilling:
                continue
            h = (len(r.prompt) - r.prompt_off) if r.prefilling \
                else (r.max_new - len(r.tokens))
            need = max(need, min(h, chunk))
            cap = min(cap, self.max_len - int(sp.pos_host[r.slot]))
        L = 1
        while L < need:
            L *= 2
        L = min(L, chunk)
        while L > max(cap, 1):
            L //= 2
        return L

    def _pool_spec_ok(self, act: List[Request]) -> bool:
        """The speculative arms are only offered when every decode
        participant is greedy: verifying sampled continuations greedily
        would change their distribution (module docstring)."""
        dec = [r for r in act if not r.prefilling]
        return (self.spec_decode and self.spec_len > 1
                and bool(dec) and all(r.temperature <= 0 for r in dec))

    def _pool_spec_arms(self, act: List[Request]) -> tuple:
        """The proposer arms this pool's decode tick may run, by name.
        With a draft model loaded the engine arbitrates {plain, spec:ngram,
        spec:draft}; without, the historical {plain, spec:ngram} pair."""
        if not self._pool_spec_ok(act):
            return ()
        return ("ngram", "draft") if self.draft_cfg is not None \
            else ("ngram",)

    def _candidates(self) -> List[TickCandidate]:
        """One TickCandidate per (pool, composition) with work: the menu
        ``Engine.choose_serve_job`` arbitrates under weighted FRT.  A
        prefill candidate is ``aged`` as soon as any of its requests has
        sat out its class's ``max_defer`` scheduled ticks."""
        cands = []
        draining = any(sp.draining for sp in self.pools)
        for sp in self.pools:
            act = [r for r in sp.active if r is not None]
            if not act:
                continue
            pre = [r for r in act if r.prefilling]
            dec = [r for r in act if not r.prefilling]
            weight = lambda rs: sum(self.classes[r.priority].weight
                                    for r in rs)
            # placement terms (zero on the legacy unplaced layout, so the
            # arbitration scores reduce exactly to weighted FRT there):
            # ``load`` is the pool's device-group contention, ``xfer`` the
            # migration traffic about to land on it (pending draining
            # slots x the measured per-move cost, charged to the pool the
            # drain is currently routing into)
            load = self._group_busy(sp) if self.placements else 0.0
            xfer = 0.0
            if draining and self._last_mig_dst == sp.lid:
                pend = sum(o.slots - o.free_slots()
                           for o in self.pools if o.draining)
                t_mig = self.engine.costs.estimate_first(
                    [pool_kind("serve_migrate", sp.pool_id),
                     "serve_migrate"], COST_DEFAULTS["serve_migrate"])
                batches = -(-pend // max(self.cfg.serve.migrate_batch, 1))
                xfer = batches * t_mig
            if dec:
                arms = self._pool_spec_arms(act)
                cands.append(TickCandidate(
                    sp.pool_id, "decode", n_dec=len(dec), n_pre=len(pre),
                    chunk=self.decode_chunk, weight=weight(dec),
                    spec_len=self.spec_len if arms else 0,
                    arms=arms, load=load, xfer=xfer))
            if pre:
                overdue = max(r.deferred - self.classes[r.priority].max_defer
                              for r in pre)
                cands.append(TickCandidate(
                    sp.pool_id, "prefill", n_dec=len(dec), n_pre=len(pre),
                    pre_toks=sum(len(r.prompt) - r.prompt_off for r in pre),
                    chunk=self.prefill_chunk, weight=weight(pre),
                    aged=overdue >= 0, overdue=max(overdue, 0),
                    load=load, xfer=xfer))
        return cands

    def _age_prefills(self, part: List[Request]) -> None:
        """Post-tick aging bookkeeping: every ADMITTED prefill that did not
        advance this tick — sat out a decode tick on its own pool, or lives
        on a pool that lost the arbitration — ages one tick; participants
        reset.  The counters drive the per-class aging bound (weighted
        path) and the starvation regression tests."""
        ran = set(id(r) for r in part)
        for pool in self.pools:
            for r in pool.active:
                if r is None or not r.prefilling:
                    continue
                if id(r) in ran:
                    r.deferred = 0
                else:
                    r.deferred += 1
                    r.max_deferred = max(r.max_deferred, r.deferred)

    def _plan_tick(self, sp: SlotPool, act: List[Request],
                   mode: str) -> Optional[_TickPlan]:
        """Build one pool's tick without running it: resolve the
        speculative arm, tick length, participants, layout (compact vs
        full) and job records, and close over an **async** dispatch thunk
        — launching the jit without blocking, so a scheduling round can
        co-dispatch plans for several device-placed pools (the parallel
        group-tick path) before waiting on any of them."""
        spec_len = self.spec_len
        if mode == "spec":
            # bare-"spec" back-compat (old monkeypatched deciders): map to
            # the strongest proposer this engine carries
            mode = "spec:draft" if self.draft_cfg is not None \
                else "spec:ngram"
        spec = mode.startswith("spec:")
        arm = mode.split(":", 1)[1] if spec else ""
        if spec:
            L = self._tick_len(sp, act, mode, spec_len)
            if L < 2:
                mode, spec, arm = "decode", False, ""
                # a 1-token tick has nothing to draft
        if not spec:
            chunk = (self.prefill_chunk if mode == "prefill"
                     else self.decode_chunk)
            L = self._tick_len(sp, act, mode, chunk)
        toks = np.zeros((sp.slots, L), np.int32)
        n_given = np.ones((sp.slots,), np.int32)
        active = np.zeros((sp.slots,), bool)
        temps = np.zeros((sp.slots,), np.float32)
        part: List[Request] = []
        for r in act:
            if mode != "prefill" and r.prefilling:
                continue                      # prefill slots sit this one out
            if int(sp.pos_host[r.slot]) + L > self.max_len:
                continue                      # defensive: never overrun cache
            s = r.slot
            if r.prefilling:
                g = min(len(r.prompt) - r.prompt_off, L)
                toks[s, :g] = r.prompt[r.prompt_off:r.prompt_off + g]
                n_given[s] = g
            else:
                toks[s, 0] = r.pending_tok
            active[s] = True
            temps[s] = r.temperature
            part.append(r)
        if not part:
            return None
        # lane-waste mitigation: with >= half the pool sitting out this
        # decode tick, gather participants into a compact batch (padded to
        # a power of two with idle rows so the jit specializes on few batch
        # sizes).  Pad rows run inactive — their state round-trips
        # unchanged — and the scatter-back touches only gathered rows, so
        # sat-out slots keep their pending reset flags and cache state.
        part_slots = [r.slot for r in part]
        # layout arm: inside the half-idle eligibility gate, compact-vs-full
        # is either pinned by the config override or chosen per tick by the
        # engine from measured per-pool layout EMAs (Engine.choose_compact)
        compact_ok = mode != "prefill" \
            and len(part) <= int(sp.slots * self.compact_frac)
        compact = compact_ok and (
            self.compact_decode if self.compact_decode is not None
            else self.engine.choose_compact(sp.pool_id))
        if compact:
            nc = 1
            while nc < len(part):
                nc *= 2
            pads = [s for s in range(sp.slots) if s not in set(part_slots)]
            idx = np.asarray(part_slots + pads[:nc - len(part)], np.int32)
        else:
            idx = np.arange(sp.slots, dtype=np.int32)
        rows = len(idx)
        # fresh specialization tracking keeps compiles out of the EMAs; the
        # device group is part of the key because the shared jit
        # re-specializes (and re-compiles) per input sharding, so a placed
        # pool's first tick of a shape is compile-carrying even when an
        # unplaced pool already ran that shape
        ckey = (sp.devices(), arm if spec else False, L, rows)
        cold = ckey not in self._compiled
        self._compiled.add(ckey)
        kind = ("serve_prefill" if mode == "prefill"
                else spec_kind(arm) if spec else "serve_decode")
        ntok = L * len(part)
        job = Job(kind, tokens=ntok, meta={"cold": cold})
        # the same measurement lands under the pool-scoped kind too: the
        # per-pool EMA is the parallelism term of the multi-pool arbitration
        extras = [Job(pool_kind(kind, sp.pool_id), tokens=ntok,
                      meta={"cold": cold})]
        if spec:
            # arm-agnostic aggregate: the bootstrap fallback of the
            # per-pool t_tok chain (Engine._pool_t_tok)
            extras.append(Job("serve_spec_decode", tokens=ntok,
                              meta={"cold": cold}))
        if compact_ok:
            # layout EMAs only accumulate on layout-ELIGIBLE ticks, so the
            # compact-vs-full comparison is apples-to-apples (same
            # occupancy regime, not compact-halfidle vs full-busy)
            extras.append(Job(layout_kind(compact, sp.pool_id),
                              tokens=ntok, meta={"cold": cold}))
        # build_slot_tick memoizes per (cfg, spec_len, draft_cfg, proposer),
        # so this lookup is a cache hit after the first tick of each arm
        fn = build_slot_tick(self.cfg, spec_len, self.draft_cfg, arm) \
            if spec else self._tick
        params, dparams = self._params_for(sp)
        dargs = (dparams,) if self.draft_cfg is not None else ()
        if compact:
            jidx = jnp.asarray(idx)

            def dispatch():
                pool_c = jax.tree.map(lambda c: c[jidx], sp.pool)
                return fn(params, *dargs, pool_c, sp.pos[jidx],
                          jnp.asarray(toks[idx]), jnp.asarray(n_given[idx]),
                          jnp.asarray(active[idx]),
                          jnp.asarray(sp.reset[idx]), sp.keys[jidx],
                          jnp.asarray(temps[idx]))
        else:
            def dispatch():
                return fn(params, *dargs, sp.pool, sp.pos,
                          jnp.asarray(toks), jnp.asarray(n_given),
                          jnp.asarray(active), jnp.asarray(sp.reset),
                          sp.keys, jnp.asarray(temps))
        return _TickPlan(sp=sp, mode=mode, spec=spec, arm=arm, L=L,
                         part=part, part_slots=part_slots, n_given=n_given,
                         idx=idx, compact=compact, compact_ok=compact_ok,
                         job=job, extras=tuple(extras), dispatch=dispatch)

    def _commit_tick(self, plan: _TickPlan, outs) -> int:
        """Write one dispatched tick's results back: device state
        (pool/pos/keys), the host position view, token commits, evictions,
        prefill snapshots and speculative counters.  Returns the number of
        new tokens emitted; the caller aggregates aging, breakpoint and
        tick-count bookkeeping once per scheduling round."""
        sp, L, spec, part = plan.sp, plan.L, plan.spec, plan.part
        n_given, idx = plan.n_given, plan.idx
        if plan.compact:
            pool_n, pos_n, keys_n, emitted, nvalid = outs
            jidx = jnp.asarray(idx)
            sp.pool = jax.tree.map(lambda p, n: p.at[jidx].set(n),
                                   sp.pool, pool_n)
            sp.pos = sp.pos.at[jidx].set(pos_n)
            sp.keys = sp.keys.at[jidx].set(keys_n)
            sp.reset[idx] = False
            em_rows = np.asarray(emitted)
            em = np.zeros((sp.slots, L), em_rows.dtype)
            em[idx] = em_rows
            nv = np.zeros((sp.slots,), np.int64)
            nv[idx] = np.asarray(nvalid)
            self.compact_ticks += 1
        else:
            sp.pool, sp.pos, sp.keys, emitted, nvalid = outs
            sp.reset[:] = False           # zeroing landed inside the jit
            em = np.asarray(emitted)
            nv = np.asarray(nvalid).astype(np.int64)
        # the tick reports how far each slot really advanced: L for every
        # active slot on the plain arms, the committed prefix under spec
        sp.pos_host += nv
        n_new = 0
        now = time.perf_counter()
        for r in part:
            s, g = r.slot, int(n_given[r.slot])
            if r.prefilling:
                r.prompt_off += g
                if r.prefilling:
                    continue                  # prompt continues next tick
            need = r.max_new - len(r.tokens)
            last = int(nv[s]) if spec else L
            outs_r = em[s, g - 1:last][:need]
            if outs_r.size and r.t_first is None:
                r.t_first = now               # first-token latency mark
                self.first_tokens += 1
                self.prefill_s += now - r.t_admit
            r.tokens.extend(int(t) for t in outs_r)
            n_new += len(outs_r)
            if len(r.tokens) >= r.max_new:
                self._evict(r)
            else:
                r.pending_tok = int(em[s, last - 1])
        if self.prefix is not None and plan.mode == "prefill":
            # snapshot capture: a prefill tick boundary where the slot has
            # consumed exactly a prompt prefix (no decode output fed back
            # yet) is a reusable state — commit it into the radix tree
            # unless that path already owns a snapshot.  The guard on
            # pos_host == prompt_off excludes slots that transitioned to
            # decode mid-tick: their rows hold generated tokens too.
            for r in part:
                if (r.pool < 0 or r.prompt_off < self.prefix.min_len
                        or int(sp.pos_host[r.slot]) != r.prompt_off
                        or r.joined_version != self.params_version):
                    # the version gate: a slot that joined before a weight
                    # swap holds state computed under the OLD weights —
                    # capturing it under the current version would poison
                    # the tree for every later seed
                    continue
                path = r.prompt[:r.prompt_off]
                n = self.prefix.lookup(path)
                if n is not None and n.snapshot is not None \
                        and n.version == self.params_version:
                    continue          # stale-version snapshots re-capture
                self._snapshot_slot(sp, r.slot, path)
        if spec:
            proposed = (L - 1) * len(part)
            accepted = int(sum(int(nv[s]) - 1 for s in plan.part_slots))
            self.spec_ticks += 1
            self.spec_proposed += proposed
            self.spec_accepted += accepted
            st = self.spec_arms.setdefault(
                plan.arm, {"ticks": 0, "proposed": 0, "accepted": 0})
            st["ticks"] += 1
            st["proposed"] += proposed
            st["accepted"] += accepted
            if proposed:
                self.engine.observe_accept(sp.pool_id,
                                           accepted / proposed,
                                           arm=plan.arm)
        return n_new

    def _group_plans(self, winner: _TickPlan) -> List[_TickPlan]:
        """Opportunistic co-ticks for the parallel group-tick path: plain
        (non-speculative) plans for OTHER placed pools whose device groups
        are disjoint from the winner's (and each other's) — prefill when
        the pool still consumes prompt, decode otherwise (a prefill tick
        carries the pool's decoding slots along, so either way every slot
        with work advances).  The arbitration winner is unchanged —
        co-ticks only add work that would otherwise idle those devices;
        they run no speculative arm and record no extra decisions.  Empty
        without placements or when ``cfg.serve.parallel_ticks`` is off."""
        if not self.cfg.serve.parallel_ticks or winner.sp.mesh is None:
            return []
        used = set(winner.sp.devices())
        out = []
        for sp in self.pools:
            if sp is winner.sp or sp.mesh is None:
                continue
            devs = set(sp.devices())
            if devs & used:
                continue
            act = [r for r in sp.active if r is not None]
            if not act:
                continue
            mode = "prefill" if any(r.prefilling for r in act) else "decode"
            p = self._plan_tick(sp, act, mode)
            if p is None:
                continue
            used |= devs
            out.append(p)
        return out

    def tick(self) -> bool:
        """One engine iteration.  Returns False when stopped, True otherwise
        (including idle ticks).  Control messages land here — between ticks
        — and Inspect keeps answering while paused (the controller blocks
        inside poll until Resume).

        Scheduling: on the single-pool/single-class path the composition is
        the original ``Engine.choose_serve_tick`` min-FRT decision; with
        multiple pools or priority classes each pool's candidate ticks go
        through ``Engine.choose_serve_job`` (weighted FRT, placement-
        adjusted, + per-class aging bounds) and one pool wins the round —
        then, with device-placed pools, plain decode ticks for the other
        placed pools co-dispatch alongside the winner (``_group_plans``)
        so disjoint device groups decode concurrently.

        Observability: the tick runs inside a ``serve.tick`` span whose
        phases are spans too (``serve.control``, ``serve.admit``,
        ``serve.plan``, the dispatch span ``Engine.run_job`` names by job
        kind, ``serve.commit``); the phase and composition totals land in
        the engine's counters (``_inspect()["counters"]``)."""
        with trace.span("serve.tick") as tick_span:
            alive, plan = self._run_tick(tick_span)
        if plan is not None:
            if plan.mode == "prefill":
                self.prefill_tick_s += tick_span.seconds
            else:                         # plain and speculative decode
                self.decode_tick_s += tick_span.seconds
        return alive

    def _run_tick(self, tick_span) -> tuple:
        """The body of ``tick``: returns (not stopped, the winning plan or
        None for an idle round)."""
        with trace.span("serve.control"):
            if self._poll():
                return False, None
            self._drain_step()
        with trace.span("serve.admit", into=(self, "admit_s")):
            self._admit()
        with trace.span("serve.plan", into=(self, "plan_s")):
            decisions = self.engine.decisions
            mark = decisions[-1] if decisions else None
            plan = self._choose_plan()
            group = self._group_plans(plan) if plan is not None else []
        if plan is None:
            return True, None
        tick_span.set(mode=plan.mode, compact=plan.compact, L=plan.L,
                      rows=len(plan.idx), part=len(plan.part),
                      group=len(group), explore=self._explored(mark))
        if not group:
            outs = self.engine.run_job(
                plan.job, lambda: jax.block_until_ready(plan.dispatch()),
                extra=plan.extras)
            with trace.span("serve.commit", into=(self, "commit_s")):
                part = list(plan.part)
                n_new = self._commit_tick(plan, outs)
                self._end_round(part, n_new)
            return True, plan
        # parallel group tick: launch every plan's jit before blocking on
        # any (async PJRT dispatch overlaps them on the disjoint device
        # groups), block in dispatch order, then commit.  Each pool's
        # measured time is its elapsed-from-round-start — the overlapped
        # reality its EMAs should price — with cold flags respected exactly
        # as run_job would.
        plans = [plan] + group
        done = []
        with trace.span(plan.job.kind, group=len(plans)):
            t0 = time.perf_counter()
            live = [(p, p.dispatch()) for p in plans]
            for p, outs in live:
                jax.block_until_ready(outs)
                done.append((p, outs, time.perf_counter() - t0))
        with trace.span("serve.commit", into=(self, "commit_s")):
            part, n_new = [], 0
            for p, outs, dt in done:
                self.engine.observe(p.job, dt)
                for j in p.extras:
                    self.engine.observe(j, dt)
                n_new += self._commit_tick(p, outs)
                part.extend(p.part)
            self.parallel_group_ticks += len(group)
            self._end_round(part, n_new)
        return True, plan

    def _choose_plan(self) -> Optional[_TickPlan]:
        """The round's composition decision and its plan (None when no pool
        has work)."""
        spec_len = self.spec_len
        if self.single_pool:
            sp = self.pools[0]
            act = [r for r in sp.active if r is not None]
            if not act:
                return None
            n_pre = sum(r.prefilling for r in act)
            n_dec = len(act) - n_pre
            pre_toks = sum(len(r.prompt) - r.prompt_off
                           for r in act if r.prefilling)
            arms = self._pool_spec_arms(act)
            mode = self.engine.choose_serve_tick(
                n_dec, n_pre, pre_toks, self.decode_chunk,
                self.prefill_chunk,
                spec_len=spec_len if arms else 0,
                pool_id=sp.pool_id, arms=arms)
        else:
            cands = self._candidates()
            if not cands:
                return None
            gid, mode = self.engine.choose_serve_job(cands)
            sp = self._pool(gid - self.pool_id)
            act = [r for r in sp.active if r is not None]
        return self._plan_tick(sp, act, mode)

    def _explored(self, mark) -> str:
        """The decisions made since ``mark`` (the newest decision before
        this round) that explored rather than exploited, as
        ``decision:why`` — why a tick ran an arm its scores did not pick."""
        out = []
        for d in reversed(self.engine.decisions):
            if d is mark:
                break
            if d.get("why") in ("bootstrap", "explore", "re-explore"):
                out.append(f"{d['decision']}:{d['why']}")
        return ",".join(reversed(out))

    def _end_round(self, part: List[Request], n_new: int) -> None:
        """Once per scheduling round, after its commits: aging, token and
        tick counts, breakpoints and the autotuner hook."""
        self._age_prefills(part)
        self.tokens_out += n_new
        self._check_breakpoints(n_new)
        self.tick_no += 1
        if self.autotuner is not None:
            # meta-control at the tick boundary, work ticks only: idle
            # ticks return before planning, so windows never accumulate
            # empty time
            self.autotuner.on_tick()

    # ----------------------------------------------------------- convenience
    def run_until_done(self, max_ticks: int = 10_000) -> None:
        for _ in range(max_ticks):
            if not self.tick():
                return
            if not self.queue and all(r is None for r in self.active):
                return
        raise RuntimeError("serve engine did not drain within max_ticks")

    def generate(self, prompts: np.ndarray, max_new: int = 16,
                 temperature: float = 0.0, seed=None,
                 priorities=None) -> np.ndarray:
        """Batch convenience with the old ``BatchedServer.generate``
        contract: rectangular prompts in, ``[B, max_new]`` tokens out.
        ``seed`` pins per-request sampling keys, so repeated calls with the
        same seed reproduce (per request, not per lockstep batch — the
        old static path shared one key across the batch).  ``priorities``
        optionally names a traffic class per prompt."""
        base = None if seed is None else jax.random.PRNGKey(seed)
        reqs = [self.submit(p, max_new, temperature,
                            key=None if base is None
                            else jax.random.fold_in(base, i),
                            priority=None if priorities is None
                            else priorities[i])
                for i, p in enumerate(prompts)]
        self.run_until_done()
        return np.stack([r.output() for r in reqs])
