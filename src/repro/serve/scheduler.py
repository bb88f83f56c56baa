"""Continuous-batching scheduler: admission, chunked prefill, eviction,
prefix-cache adoption, and speculative-decode planning.

One scheduler tick produces one :class:`TickPlan` — the padded arrays a
single jitted ``models/lm.py:decode_paged`` call consumes.  Every batch
row is in exactly one phase per tick:

* **prefill** — the row feeds the next ``prefill_chunk`` tokens of its
  pending context (prompt, or prompt + generated after an eviction);
* **decode** — the row feeds its one last sampled token;
* **idle** — no request mapped (or deferred this tick): ``n_valid = 0``,
  K/V writes go to the null block, logits ignored.

Requests admit from a FIFO queue the moment a row and enough pool blocks
free up — mid-batch, not when the tick drains.  With prefix caching on,
admission first ADOPTS the longest cached block chain matching the
request's context (``kv_cache.PagedKVCache.adopt_prefix``): adopted
tokens skip prefill entirely, and only the remainder feeds through
chunks.  When the pool cannot cover a row's next chunk, the most recently
admitted *other* row is evicted (LIFO victim, vLLM's recompute policy):
its block REFERENCES drop (blocks another sequence shares stay put —
release is refcount-aware), and it re-queues at the FRONT of the waiting
queue with ``pending = prompt + generated`` so it re-prefills (or
re-adopts) its full context on re-admission.

Every feed passes the copy-on-write barrier
(``PagedKVCache.make_writable``) before its tokens are consumed: writes
never land in a block that is shared or hash-registered; the barrier's
``(src, dst)`` page copies ride the plan for the engine to apply first.

RNG contract: two modes.

* ``rng_mode="request"`` (default, PR-4 behavior): each request's key is
  folded ONCE at submission (``fold_in(base_key, rid)`` unless the
  request carries its own seed), and every stochastic draw downstream —
  SC bits per token (see ``decode_paged``) and the sampling draw per
  generated token — derives from (that key, absolute position).  Tokens
  are a function of the request alone: identical served solo, batched,
  admitted mid-stream, or evicted and resumed.
* ``rng_mode="content"`` (forced by ``prefix_cache=True``): the SC key
  of CONTEXT token t is a chain over token content —
  ``C_t = fold_in(C_{t-1}, token_t)`` seeded from
  ``fold_in(base_key, _CONTENT_SALT)`` — so two requests sharing a
  prompt prefix draw bitwise-identical SC bits there, which is exactly
  what makes a cached KV block reusable across requests on stochastic
  backends.  SAMPLING keys stay per-request (``sample_key``), so
  temperature>0 requests still draw independently.  Tokens remain a
  function of (content, request key) alone — still invariant to batch
  composition, chunking, and eviction/resume.

Speculative decoding: on a pure-decode tick, greedy rows with pool head-
room are marked ``spec_rows`` — the engine drafts ``spec_k`` tokens with
the paired cheap backend (``sc.draft_backend``) and verifies them in ONE
width-(k+1) ``decode_paged`` call; ``on_tokens`` commits the accepted
run.  The scheduler only PLANS speculation (block reservation + write
barrier over the drafted span); the draft/verify loop lives in
``engine.PagedServingEngine``.
"""

from __future__ import annotations

import dataclasses
from collections import deque

import jax
import jax.numpy as jnp

from repro.serve.kv_cache import PagedKVCache

_SAMPLE_SALT = 0x5EED       # separates sampling folds from SC-bit folds
_CONTENT_SALT = 0xC047      # seeds the content-chain keys (rng_mode=content)


@dataclasses.dataclass
class Sequence:
    """One admitted request's scheduling state."""

    req: object                     # serve.engine.Request
    key: object                     # raw (2,) uint32 per-request key
    fed: int = 0                    # context tokens already in the cache
    pending: list = dataclasses.field(default_factory=list)
    # True while the row is feeding context (prompt, or prompt+generated
    # after an eviction); flips False once the context is consumed and
    # the row switches to one-token decode feeds.  Pure observability
    # state: it distinguishes prefill-chunk trace events from decode
    # feeds and never influences scheduling.
    prefilling: bool = True
    # Content-chain SC keys, one per context position (rng_mode=content
    # only; extended lazily).  ckeys[t] is a function of tokens[0..t] and
    # the engine seed alone, so it survives eviction/resume unchanged.
    ckeys: list = dataclasses.field(default_factory=list)
    # High-water mark: one past the highest position ever fed.  Eviction
    # leaves it as it is, so a feed below it is a position fed again
    # (counted as re-fed); positions a resumed row re-adopts from the
    # prefix cache are never fed, so they never count.
    high: int = 0

    @property
    def context_len(self) -> int:
        return len(self.req.prompt) + len(self.req.generated)

    def context_tokens(self) -> list:
        return list(self.req.prompt) + list(self.req.generated)

    def reset_for_recompute(self) -> None:
        """Eviction: drop cache state, keep tokens; re-prefill everything.
        ``ckeys`` survives — content keys depend on tokens, not on cache
        state, and the tokens are unchanged."""
        self.fed = 0
        self.pending = self.context_tokens()
        self.prefilling = True


@dataclasses.dataclass
class TickPlan:
    """Arrays for one ``decode_paged`` call, plus host bookkeeping."""

    sc: int                         # chunk width of this tick (1 = decode)
    tokens: list                    # (b, sc) int
    lengths: list                   # (b,) pre-feed fill
    n_valid: list                   # (b,) real tokens per row
    tables: list                    # (b, nb) block-table rows
    keys: list                      # (b,) raw per-request keys, or per-row
                                    # (sc, 2) content keys (rng_mode=content)
    sample_rows: list               # [(slot, Sequence)] rows to sample after
    # copy-on-write page copies [(src, dst)] the engine applies BEFORE
    # the step (a write this tick lands in a block that was shared)
    copies: list = dataclasses.field(default_factory=list)
    # [(slot, Sequence)] rows the engine should draft+verify this tick
    # (their pool span through fed + spec_k is reserved and writable)
    spec_rows: list = dataclasses.field(default_factory=list)
    # positions fed for requests (sum of n_valid), positions the step
    # computes (slots x sc), and fed positions below their row's
    # high-water mark (fed again after an eviction)
    fed: int = 0
    computed: int = 0
    refed: int = 0


class Scheduler:
    """Owns the waiting queue, the row grid, and the block allocator.

    ``metrics`` (a ``repro.obs`` registry) and ``tracer`` are the
    observability hooks: the scheduler owns the request-lifecycle
    counters (submitted/admitted/finished/evicted) and emits the
    lifecycle trace events — ``request.submit`` / ``request.admit`` /
    ``request.evict`` / ``request.finish`` plus one ``prefill.chunk``
    event per context chunk fed.  Both default to always-off stand-ins,
    so an uninstrumented scheduler pays one attribute check per site.
    """

    def __init__(self, scfg, kv: PagedKVCache, base_key, on_finish=None,
                 metrics=None, tracer=None):
        from repro import obs
        self.scfg = scfg
        self.kv = kv
        self.base_key = base_key
        self.on_finish = on_finish
        self.waiting: deque = deque()
        self.rows: list = [None] * scfg.slots        # slot -> Sequence | None
        self.admit_stack: list = []                  # admission order (LIFO)
        self.finished: list = []
        self.evictions = 0
        self._dummy_key = jax.random.PRNGKey(0)
        # content-chain mode: forced by prefix caching (shared KV blocks
        # need content-derived SC bits), or opted into standalone
        self.content_mode = bool(
            getattr(scfg, "prefix_cache", False)
            or getattr(scfg, "rng_mode", "request") == "content")
        self._content_base = jax.random.fold_in(base_key, _CONTENT_SALT)
        self.speculative = bool(getattr(scfg, "speculative", False))
        self.spec_k = int(getattr(scfg, "spec_k", 4))
        m = metrics if metrics is not None else obs.MetricsRegistry(
            enabled=False)
        self.tracer = tracer if tracer is not None else obs.NULL_TRACER
        self._m_submitted = m.counter(
            "serve_requests_submitted_total", "requests entering the queue")
        self._m_admitted = m.counter(
            "serve_requests_admitted_total",
            "admissions onto a batch row (re-admissions after eviction "
            "count again)")
        self._m_finished = m.counter(
            "serve_requests_finished_total", "requests completed")
        self._m_evicted = m.counter(
            "serve_evictions_total", "LIFO recompute evictions")
        self._m_prefill_tok = m.counter(
            "serve_prefill_tokens_total",
            "context tokens fed through prefill chunks (resumes re-count; "
            "prefix-cache hits never reach here)")
        self._m_generated = m.counter(
            "serve_tokens_generated_total", "tokens sampled across requests")
        self._m_fed = m.counter(
            "serve_fed_positions_total",
            "positions fed for requests (prompt, re-fed context and "
            "fed-back tokens; chunk padding and idle rows excluded)")
        self._m_step_pos = m.counter(
            "serve_step_positions_total",
            "positions the step computes: slots x tick width, per tick")
        self._m_refed = m.counter(
            "serve_refed_positions_total",
            "fed positions below their row's high-water mark: context fed "
            "again after an eviction")
        self._g_queue = m.gauge("serve_queue_depth", "requests waiting")
        self._g_active = m.gauge(
            "serve_active_requests", "requests holding a batch row")

    def _update_gauges(self) -> None:
        self._g_queue.set(len(self.waiting))
        self._g_active.set(self.active_count)

    # ------------------------------------------------------------------
    def submit(self, req) -> None:
        key = getattr(req, "key", None)
        if key is None:
            key = jax.random.fold_in(self.base_key, req.rid)
            req.key = key
        seq = Sequence(req=req, key=key,
                       pending=list(req.prompt) + list(req.generated))
        self.waiting.append(seq)
        self._m_submitted.inc()
        self._update_gauges()
        self.tracer.event("request.submit", rid=req.rid,
                          prompt_tokens=len(req.prompt))

    def adopt(self, seq: Sequence) -> None:
        """Queue a PRE-BUILT sequence at the admission head (warm
        drain/resume): its first ``seq.fed`` positions already hold valid
        KV under its rid's block table, so on admission it resumes
        feeding ``pending`` from there instead of re-prefilling.
        ``_admit``'s bookkeeping handles it unchanged — ``adopt_prefix``
        no-ops on an existing table and ``ensure``/``has_room`` extend
        it."""
        seq.high = max(seq.high, seq.fed)
        self.waiting.appendleft(seq)
        self._m_submitted.inc()
        self._update_gauges()
        self.tracer.event("request.adopt", rid=seq.req.rid, fed=seq.fed)

    def has_work(self) -> bool:
        return bool(self.waiting) or any(r is not None for r in self.rows)

    @property
    def active_count(self) -> int:
        return sum(r is not None for r in self.rows)

    # ------------------------------------------------------------------
    def _evict_victim(self, keep: Sequence) -> int | None:
        """Free the most recently admitted row other than ``keep``.

        Returns the evicted slot (so an in-flight tick plan can cancel the
        victim's feed), or None when ``keep`` is the only admitted row.
        ``kv.release`` only DEREFERENCES the victim's blocks: blocks a
        prefix-sharing neighbour still maps survive untouched, and
        registered blocks park on the prefix-cache LRU — a resumed victim
        often re-adopts its own blocks instead of re-prefilling."""
        for victim in reversed(self.admit_stack):
            if victim is keep:
                continue
            slot = self.rows.index(victim)
            fed = victim.fed
            self.kv.release(victim.req.rid)
            self.rows[slot] = None
            self.admit_stack.remove(victim)
            victim.reset_for_recompute()
            self.waiting.appendleft(victim)
            self.evictions += 1
            self._m_evicted.inc()
            self._update_gauges()
            self.tracer.event("request.evict", rid=victim.req.rid,
                              generated=len(victim.req.generated), fed=fed)
            return slot
        return None

    def _admit(self) -> None:
        for slot in range(self.scfg.slots):
            if self.rows[slot] is not None or not self.waiting:
                continue
            seq = self.waiting[0]
            cached = self.kv.adopt_prefix(seq.req.rid, seq.context_tokens())
            if cached:
                seq.fed = cached
                seq.pending = seq.context_tokens()[cached:]
            first = min(len(seq.pending), self.scfg.prefill_chunk)
            if not self.kv.has_room(seq.req.rid, seq.fed + first):
                if cached:                   # roll the adoption back:
                    self.kv.release(seq.req.rid)   # hits return to the LRU
                    seq.reset_for_recompute()
                break                        # FIFO: don't starve the head
            self.waiting.popleft()
            self.kv.ensure(seq.req.rid, seq.fed + first)
            self.rows[slot] = seq
            self.admit_stack.append(seq)
            self._m_admitted.inc()
            self._update_gauges()
            self.tracer.event("request.admit", rid=seq.req.rid, slot=slot,
                              resumed=bool(seq.req.generated),
                              cached_tokens=cached)

    # ------------------------------------------------------------------
    def _extend_ckeys(self, seq: Sequence, upto: int) -> None:
        """Grow ``seq.ckeys`` to cover positions [0, upto): the content
        chain ``C_t = fold_in(C_{t-1}, token_t)`` over prompt+generated."""
        ctx = seq.context_tokens()
        while len(seq.ckeys) < upto:
            t = len(seq.ckeys)
            prev = seq.ckeys[t - 1] if t else self._content_base
            seq.ckeys.append(jax.random.fold_in(prev, int(ctx[t])))

    def _row_keys(self, seq, n: int, sc: int):
        """One TickPlan.keys row: the raw request key (request mode) or
        the (sc, 2) stack of content keys for the fed span (content
        mode), dummy-padded — dummies key null-block writes only."""
        if not self.content_mode:
            return self._dummy_key if seq is None else seq.key
        if seq is None or n == 0:
            return jnp.stack([self._dummy_key] * sc)
        self._extend_ckeys(seq, seq.fed + n)
        ks = seq.ckeys[seq.fed:seq.fed + n]
        return jnp.stack(ks + [self._dummy_key] * (sc - n))

    # ------------------------------------------------------------------
    def plan(self) -> TickPlan | None:
        """Build the next tick, mutating row state optimistically (the
        engine always executes the returned plan).  None = nothing to do.

        Two passes.  Pass A reserves pool blocks AND copy-on-write copies
        for every row's intended feed, evicting LIFO victims on OOM — and
        CANCELLING a victim's already-granted feed if it was planned
        earlier in this same tick (its block references just dropped, so
        letting it run would alias freshly re-allocated blocks).  After
        pass A, pure-decode ticks nominate speculative rows (greedy,
        post-prefill, pool headroom through ``fed + 1 + spec_k``) —
        opportunistically: a row that cannot reserve its drafted span
        falls back to plain decode, never evicts for it.  Pass B builds
        the padded arrays only for feeds that survived pass A.

        A row always feeds ``min(len(pending), prefill_chunk)`` tokens —
        a request-local quantity — so a request's chunk boundaries never
        depend on its batch neighbours (decode_paged's per-position rng
        makes numerics chunking-invariant anyway; this keeps schedules
        reproducible too).  The tick width ``sc`` is the widest surviving
        feed: pure-decode ticks collapse to ``sc = 1`` so steady-state
        decoding compiles once and pays no chunk-width padding.
        """
        self._admit()
        if not any(r is not None for r in self.rows):
            return None
        planned: dict = {}                    # slot -> granted feed length
        copies: list = []
        for slot in range(self.scfg.slots):
            seq = self.rows[slot]
            if seq is None:                   # may have been evicted above
                continue
            want = min(len(seq.pending), self.scfg.prefill_chunk)
            while want:
                if self.kv.ensure(seq.req.rid, seq.fed + want):
                    # copy-on-write barrier over the write span — shared
                    # or registered blocks copy out before any scatter
                    cw = self.kv.make_writable(seq.req.rid, seq.fed,
                                               seq.fed + want)
                    if cw is not None:
                        copies.extend(cw)
                        break
                victim_slot = self._evict_victim(keep=seq)
                if victim_slot is None:
                    want = 0                  # defer: sole row, pool full
                    break
                planned.pop(victim_slot, None)
            planned[slot] = want
        # Tick width: EXACTLY two shapes ever reach the jitted step —
        # prefill ticks run at the full chunk width (tail chunks pad, the
        # padding is n_valid-masked into the null block) and pure-decode
        # ticks at width 1 — so serving never recompiles mid-traffic
        # however prompt lengths mix.  (Speculation adds two more fixed
        # shapes: the width-1 draft and the width-(k+1) verify.)
        sc = (self.scfg.prefill_chunk
              if any(n > 1 for n in planned.values()) else 1)
        spec_slots: set = set()
        if self.speculative and sc == 1 and self.spec_k > 0:
            for slot in range(self.scfg.slots):
                seq = self.rows[slot]
                if (seq is None or planned.get(slot, 0) != 1
                        or seq.prefilling or seq.req.temperature > 0.0):
                    continue
                # verify writes positions fed .. fed+spec_k
                if seq.fed + 1 + self.spec_k > self.scfg.max_len:
                    continue
                if not self.kv.ensure(seq.req.rid,
                                      seq.fed + 1 + self.spec_k):
                    continue
                cw = self.kv.make_writable(seq.req.rid, seq.fed + 1,
                                           seq.fed + 1 + self.spec_k)
                if cw is None:
                    continue
                copies.extend(cw)
                spec_slots.add(slot)
        tokens, lengths, n_valid, tables, keys = [], [], [], [], []
        sample_rows, spec_rows = [], []
        refed = 0
        for slot in range(self.scfg.slots):
            seq = self.rows[slot]
            n = planned.get(slot, 0)
            if seq is None:
                tokens.append([0] * sc)
                lengths.append(0)
                n_valid.append(0)
                tables.append(self.kv.null_row())
                keys.append(self._row_keys(None, 0, sc))
                continue
            feed = seq.pending[:n]
            seq.pending = seq.pending[n:]
            tokens.append(list(feed) + [0] * (sc - n))
            lengths.append(seq.fed)
            n_valid.append(n)
            keys.append(self._row_keys(seq, n, sc))
            refed += max(0, min(seq.fed + n, seq.high) - seq.fed)
            seq.fed += n
            seq.high = max(seq.high, seq.fed)
            tables.append(self.kv.table_row(seq.req.rid))
            if n and seq.prefilling:
                self._m_prefill_tok.inc(n)
                self.tracer.event("prefill.chunk", rid=seq.req.rid,
                                  tokens=n, fed=seq.fed)
                if not seq.pending:
                    seq.prefilling = False
            if n:
                self.kv.note_filled(seq.req.rid, seq.context_tokens(),
                                    seq.fed)
            if n and not seq.pending:
                if slot in spec_slots:
                    spec_rows.append((slot, seq))
                else:
                    sample_rows.append((slot, seq))
        fed, computed = sum(n_valid), self.scfg.slots * sc
        self._m_fed.inc(fed)
        self._m_step_pos.inc(computed)
        self._m_refed.inc(refed)
        return TickPlan(sc=sc, tokens=tokens, lengths=lengths,
                        n_valid=n_valid, tables=tables, keys=keys,
                        sample_rows=sample_rows, copies=copies,
                        spec_rows=spec_rows, fed=fed, computed=computed,
                        refed=refed)

    # ------------------------------------------------------------------
    def sample_key(self, seq: Sequence):
        """Key for the sampling draw at ``seq``'s current position — a
        function of (request key, position) only, so re-sampling after an
        eviction resume reproduces the same draw."""
        return jax.random.fold_in(
            jax.random.fold_in(seq.key, _SAMPLE_SALT), seq.fed)

    def on_token(self, slot: int, seq: Sequence, token: int) -> None:
        """Record a sampled token and finish or continue the row."""
        self.on_tokens(slot, seq, [token])

    def on_tokens(self, slot: int, seq: Sequence, toks: list) -> int:
        """Commit a run of tokens for one row (len 1 = plain decode;
        longer = a speculative accept run whose first len-1 tokens
        already have verifier-grade KV in the cache).  Finish conditions
        are checked PER TOKEN — an EOS mid-run truncates the commit.
        Returns how many tokens were committed."""
        for i, token in enumerate(toks):
            if i > 0:
                # the PREVIOUS committed token's KV was written by the
                # verify pass at position fed — advance past it
                seq.fed += 1
                seq.high = max(seq.high, seq.fed)
            seq.req.generated.append(token)
            self._m_generated.inc()
            hit_eos = token == self.scfg.eos_id
            hit_max = len(seq.req.generated) >= seq.req.max_new_tokens
            hit_cap = seq.fed >= self.scfg.max_len - 1
            if hit_eos or hit_max or hit_cap:
                self._finish(slot, seq)
                return i + 1
        seq.pending = [toks[-1]]
        return len(toks)

    def _finish(self, slot: int, seq: Sequence) -> None:
        seq.req.done = True
        self.kv.release(seq.req.rid)
        self.rows[slot] = None
        if seq in self.admit_stack:
            self.admit_stack.remove(seq)
        self.finished.append(seq.req)
        self._m_finished.inc()
        self._update_gauges()
        self.tracer.event("request.finish", rid=seq.req.rid,
                          generated=len(seq.req.generated))
        if self.on_finish is not None:
            self.on_finish(seq.req)
