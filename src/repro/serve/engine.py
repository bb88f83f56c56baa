"""Batched serving engines.

Two engines share this module (and the arch-trace lifecycle):

* :class:`ServingEngine` — the original fixed-slot engine: contiguous
  full-``max_len`` KV rows, batch=1 admission prefill, lock-step decode.
  Still the only engine for mesh-sharded serving, and the baseline the
  serve benchmark measures against.
* :class:`PagedServingEngine` — continuous batching over a block-pool
  paged KV cache with chunked prefill, eviction-on-OOM, and per-request
  rng, serving EVERY model family through a per-family cache plan
  (``kv_cache.CachePlan``: paged KV for attention layers, fixed-size
  SSM state slots for recurrent layers, both for hybrid — see its
  docstring and ``docs/serving.md``).

Fixed-slot engine
-----------------
The engine owns a KV/SSM cache with ``slots`` batch rows. Each slot holds
one in-flight request; when a request finishes (EOS or max tokens), the slot
is immediately refilled from the queue — decode never stalls on stragglers
in the batch (continuous batching). Admission runs prefill for the incoming
prompt with batch=1 and splices the resulting cache into the slot's batch
row; decode steps run for all slots at once (the serve_step the dry-run
lowers). Sampling is per-slot: each request decodes with its OWN
temperature (greedy slots stay deterministic), and the engine rng folds
once per tick. When ``cfg.sc_backend != "exact"`` every prefill/decode
matmul routes through the SC substrate (repro.sc) with a per-call key.

With ``collect_arch_trace=True`` and ``cfg.sc_backend == "array"``, the
engine keeps an arch trace collector installed: every prefill/decode
COMPILATION records its pulse-schedule cost (one record per compiled
shape — jit caching means steady-state ticks add no new records), and
``arch_report()`` returns the aggregate cycles/energy/utilization of
everything compiled so far. Call ``close()`` to detach the collector.

Cross-device batching: pass ``mesh=`` and the continuous-batching slot
grid maps onto the mesh — the decode batch dimension (slots) shards over
the mesh's data axes and every SC contraction splits over the model axis
(``sc.use_mesh`` is entered around prefill/decode tracing, so
``layers.dense`` routes through ``sc_dot_sharded`` automatically).
Per-slot sampling semantics are unchanged: each request keeps its OWN
temperature and greedy slots stay deterministic whatever their batch
neighbours do.  ``slots`` must be a multiple of the mesh's
data-parallel span so every mesh slice owns a whole number of slots.
With arch tracing on, sharded dispatches record per-shard traces stamped
with their shard multiplicity, and ``arch_report()`` merges them as
concurrent banks (makespan = slowest shard).
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import time
import warnings
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.kernels import paged_attention
from repro.models import attention, lm

# Depth of serve.api.build_engine construction scopes on this thread.
# Direct ``ServingEngine(...)`` / ``PagedServingEngine(...)`` calls see
# depth 0 and emit a DeprecationWarning; build_engine enters the scope so
# the sanctioned path constructs silently.
_API_DEPTH = 0


@contextlib.contextmanager
def _api_construction():
    global _API_DEPTH
    _API_DEPTH += 1
    try:
        yield
    finally:
        _API_DEPTH -= 1


def _warn_direct(name: str) -> None:
    if _API_DEPTH == 0:
        warnings.warn(
            f"constructing {name} directly is deprecated; use "
            "repro.serve.build_engine(params, cfg, ServeOptions(...)) — "
            "it picks the engine, applies fused attention / device fault "
            "profiles, and validates option combinations",
            DeprecationWarning, stacklevel=3)


@dataclasses.dataclass
class Request:
    rid: int
    prompt: list            # token ids
    max_new_tokens: int = 16
    temperature: float = 0.0
    generated: list = dataclasses.field(default_factory=list)
    done: bool = False
    # Per-request rng key (raw (2,) uint32).  The paged engine folds it
    # from the engine seed + rid at submission unless the caller set one;
    # every stochastic draw for this request (SC bits, sampling) derives
    # from it, making results independent of batch composition.
    key: object = None


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    slots: int = 4
    max_len: int = 256
    eos_id: int = 2
    seed: int = 0


@dataclasses.dataclass(frozen=True)
class PagedServeConfig:
    """Knobs of the paged continuous-batching engine.

    ``num_blocks = 0`` sizes the pool to the fixed-slot engine's
    reservation (every slot at full ``max_len``, plus the null block);
    smaller pools trade memory for eviction pressure.  ``prefill_chunk``
    caps how many prompt tokens one tick feeds per row (chunked prefill:
    long prompts admit over several ticks instead of stalling the batch).

    ``prefix_cache=True`` turns on block-level prefix caching
    (``serve/kv_cache.py``): requests sharing a prompt prefix adopt each
    other's full KV blocks instead of re-prefilling them.  It forces
    ``rng_mode="content"`` — SC keys for context tokens derive from token
    CONTENT, not request identity, so shared blocks hold bitwise-valid
    KV for every adopter even on stochastic backends.  ``rng_mode`` can
    also be set to ``"content"`` standalone (e.g. to compare cache
    on/off outputs bit-for-bit).

    ``speculative=True`` drafts ``spec_k`` tokens per greedy decode row
    with the cheap paired backend (``draft_backend``, default the
    registry pairing ``sc.draft_backend(cfg.sc_backend)`` — ``moment``
    for stochastic backends) and verifies them in ONE width-(k+1)
    ``decode_paged`` call on the real backend.  Every emitted token is
    the VERIFIER's greedy token, so outputs are token-identical to
    non-speculative decoding; acceptance only moves throughput.
    """

    slots: int = 4
    max_len: int = 256
    eos_id: int = 2
    seed: int = 0
    block_size: int = 16
    num_blocks: int = 0
    prefill_chunk: int = 8
    prefix_cache: bool = False
    rng_mode: str = "request"       # "request" | "content"
    speculative: bool = False
    spec_k: int = 4
    draft_backend: str = ""         # "" = registry pairing for cfg.sc_backend


class _ArchTracedEngine:
    """Arch-trace collector lifecycle shared by both engines.

    ``close()`` is IDEMPOTENT: the first call detaches the collector from
    the global listener list; every later call (or ``__del__`` after an
    explicit close, or a close racing engine teardown) is a no-op, so the
    listener list can never be corrupted by double-uninstall.  Records
    stay readable after close.  ``step()`` implementations wrap their
    tick in ``_detach_on_error`` so a raise mid-tick also detaches —
    a dead engine must not keep recording every later compilation in the
    process.
    """

    # Non-ideal device realized by the SC substrate while this engine
    # ticks (set by serve.api.build_engine from options.fault_profile;
    # None = ideal).  Entered as an ambient sc.use_device_profile scope
    # around step tracing so layers thread it into every ScConfig.
    device_profile = None

    def _init_arch(self, collect_arch_trace: bool, cfg) -> None:
        self._arch_closed = False
        self.arch_collector = None
        if collect_arch_trace and cfg.sc_backend == "array":
            from repro import arch
            self.arch_collector = arch.TraceCollector().install()

    def _device_scope(self):
        if self.device_profile is None:
            return contextlib.nullcontext()
        from repro import sc
        return sc.use_device_profile(self.device_profile)

    def _init_obs(self, metrics, tracer) -> None:
        """Engine-local telemetry (``repro.obs``): each engine owns its
        own always-on metrics registry (``self.metrics``) unless the
        caller supplies one, so concurrent engines never mix series; the
        tracer defaults to the always-off ``NULL_TRACER``."""
        self.metrics = metrics if metrics is not None \
            else obs.MetricsRegistry()
        self.tracer = tracer if tracer is not None else obs.NULL_TRACER
        self._m_ticks = self.metrics.counter(
            "serve_ticks_total", "engine ticks, labeled kind=prefill|decode")
        self._m_errors = self.metrics.counter(
            "serve_errors_total", "engine ticks that raised")

    def _set_sc_ladder_gauge(self, cfg, fused_sc_attention: bool) -> None:
        """``serve_sc_ladder_slices``: Horner slices the fused SC pop-count
        core (``kernels/sc_fused.py:word_counts``) draws per word, set
        once at build when a dense site runs ``pallas_fused`` or attention
        runs ``fused_sc``; engines that run neither leave it unset."""
        from repro import sc
        from repro.kernels import sc_fused
        if not (fused_sc_attention or sc.fast_backend(
                cfg.sc_backend, cfg.sc_nbit) == "pallas_fused"):
            return
        grid = sc.ScConfig()        # the operand grid both call sites use
        self.metrics.gauge(
            "serve_sc_ladder_slices",
            "Horner slices drawn per SC word (of 16)").set(
                sc_fused.NSLICES - sc_fused.ladder_start(
                    1 << grid.operand_bits, grid.quantize))

    def arch_report(self):
        """Aggregate arch cost of everything compiled so far (None when
        trace collection is off or nothing was recorded). NOTE: the
        collector hears every array-backend dispatch in the process while
        installed (same semantics as ``arch.collect()``), not only this
        engine's — run one traced engine at a time for a clean bill."""
        collector = getattr(self, "arch_collector", None)
        if collector is None or not collector.records:
            return None
        return collector.aggregate()

    def arch_request_costs(self):
        """Per-request cost attribution under mixed traffic (None when no
        trace or no finished requests were stamped): the aggregate trace
        cost prorated by each request's token count — see
        ``TraceCollector.cost_per_request``."""
        collector = getattr(self, "arch_collector", None)
        if collector is None or not collector.request_tokens:
            return None
        return collector.cost_per_request()

    def close(self):
        """Detach the arch trace collector (records stay readable).
        Safe to call any number of times, from ``__del__``, or after a
        mid-tick failure — only the first call touches the listener
        list."""
        if getattr(self, "_arch_closed", True):
            return
        self._arch_closed = True
        collector = getattr(self, "arch_collector", None)
        if collector is not None:
            collector.uninstall()

    def __del__(self):
        # A dropped engine must not leave its collector in the global
        # listener list (would leak records and keep tracing active).
        self.close()

    @contextlib.contextmanager
    def _detach_on_error(self):
        try:
            yield
        except Exception:
            self._m_errors.inc()
            self.close()
            raise

    def health_snapshot(self) -> dict:
        """Queue-depth / error-rate view of ``self.metrics`` — the gauges
        ``ft.supervisor.HealthMonitor`` consumes (ROADMAP item 5)."""
        from repro.ft import supervisor
        return dataclasses.asdict(supervisor.engine_health(self.metrics))


class ServingEngine(_ArchTracedEngine):
    def __init__(self, params, cfg, scfg: ServeConfig,
                 collect_arch_trace: bool = False, mesh=None,
                 shard_rules=None, metrics=None, tracer=None):
        _warn_direct("ServingEngine")
        self.params = params
        self.cfg = cfg
        self.scfg = scfg
        self.mesh = mesh
        self.shard_rules = shard_rules
        self._stochastic_substrate = cfg.sc_backend != "exact"
        if mesh is not None and self._stochastic_substrate:
            from repro import sc
            batch_axes = (shard_rules or sc.DEFAULT_RULES).batch
            sizes = dict(mesh.shape)
            dp = math.prod(sizes.get(a, 1) for a in batch_axes)
            if dp > 1 and scfg.slots % dp != 0:
                raise ValueError(
                    f"slots={scfg.slots} must be a multiple of the rules' "
                    f"batch span {dp} on this mesh so slots map onto "
                    f"mesh shards")
        self.cache = lm.init_cache(cfg, scfg.slots, scfg.max_len)
        self.lengths = jnp.zeros((scfg.slots,), jnp.int32)
        self.last_token = jnp.zeros((scfg.slots,), jnp.int32)
        self.active = [None] * scfg.slots       # slot -> Request | None
        self.queue: list[Request] = []
        self.finished: list[Request] = []
        self._rng = jax.random.PRNGKey(scfg.seed)
        self._decode = jax.jit(partial(lm.decode_step, cfg=cfg))
        self._prefill = jax.jit(
            partial(lm.prefill, cfg=cfg, max_len=scfg.max_len))
        self._init_arch(collect_arch_trace, cfg)
        self._init_obs(metrics, tracer)
        self._set_sc_ladder_gauge(cfg, fused_sc_attention=False)
        self._m_submitted = self.metrics.counter(
            "serve_requests_submitted_total", "requests entering the queue")
        self._m_finished = self.metrics.counter(
            "serve_requests_finished_total", "requests completed")
        self._m_generated = self.metrics.counter(
            "serve_tokens_generated_total", "tokens sampled across requests")
        self._g_queue = self.metrics.gauge(
            "serve_queue_depth", "requests waiting")
        self._g_active = self.metrics.gauge(
            "serve_active_requests", "requests holding a slot")

    def _substrate_scope(self):
        """Mesh scope entered around prefill/decode so their TRACING (the
        first call per shape) routes dense() through sc_dot_sharded."""
        if self.mesh is not None and self._stochastic_substrate:
            from repro import sc
            return sc.use_mesh(self.mesh, self.shard_rules)
        return contextlib.nullcontext()

    def _next_key(self):
        self._rng, k = jax.random.split(self._rng)
        return k

    # ------------------------------------------------------------------
    def submit(self, req: Request):
        self.queue.append(req)
        self._m_submitted.inc()
        self._g_queue.set(len(self.queue))
        self.tracer.event("request.submit", rid=req.rid,
                          prompt_tokens=len(req.prompt))

    def _splice_slot(self, slot: int, cache1, length, last_tok):
        """Write a batch=1 prefill cache into batch row ``slot``."""
        def put(full, one):
            # full: (layers, slots, ...); one: (layers, 1, ...)
            return jax.lax.dynamic_update_slice_in_dim(full, one.astype(
                full.dtype), slot, axis=1)
        self.cache = jax.tree.map(put, self.cache, cache1)
        self.lengths = self.lengths.at[slot].set(length)
        self.last_token = self.last_token.at[slot].set(last_tok)

    def _admit(self):
        for slot in range(self.scfg.slots):
            if self.active[slot] is None and self.queue:
                req = self.queue.pop(0)
                prompt = jnp.asarray([req.prompt], jnp.int32)
                with self._substrate_scope():
                    if self._stochastic_substrate:
                        logits, cache1, lens = self._prefill(
                            self.params, prompt, rng=self._next_key())
                    else:
                        logits, cache1, lens = self._prefill(
                            self.params, prompt)
                tok = self._sample(logits, req.temperature)
                req.generated.append(int(tok[0]))
                self.active[slot] = req
                self._splice_slot(slot, cache1, int(lens[0]), int(tok[0]))
                self._m_generated.inc()
                self._g_queue.set(len(self.queue))
                self._g_active.set(sum(r is not None for r in self.active))
                self.tracer.event("request.admit", rid=req.rid, slot=slot,
                                  resumed=False)

    def _sample(self, logits, temperature: float):
        """Sample one admission's tokens (batch=1 prefill logits)."""
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            self._next_key(), logits / temperature, axis=-1).astype(jnp.int32)

    def _sample_slots(self, logits, temperatures):
        """Per-slot sampling: each slot uses its request's own temperature.

        Greedy slots (t <= 0) take the argmax regardless of the rng, so a
        greedy request decodes identically whatever its batch neighbours
        sample.
        """
        temps = jnp.asarray(temperatures, jnp.float32)
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if not any(t > 0.0 for t in temperatures):
            return greedy
        safe = jnp.where(temps > 0.0, temps, 1.0)
        sampled = jax.random.categorical(
            self._next_key(), logits / safe[:, None], axis=-1).astype(jnp.int32)
        return jnp.where(temps > 0.0, sampled, greedy)

    # ------------------------------------------------------------------
    def step(self):
        """One engine tick: admit, batched decode, per-slot sample, harvest.
        A raise mid-tick detaches the arch collector before propagating."""
        with self._detach_on_error(), self._device_scope():
            return self._step()

    def _step(self):
        self._admit()
        if not any(r is not None for r in self.active):
            return False
        self._m_ticks.inc(kind="decode")
        with self._substrate_scope():
            if self._stochastic_substrate:
                logits, self.cache = self._decode(
                    self.params, self.cache, self.last_token, self.lengths,
                    rng=self._next_key())
            else:
                logits, self.cache = self._decode(
                    self.params, self.cache, self.last_token, self.lengths)
        self.lengths = self.lengths + jnp.asarray(
            [1 if r is not None else 0 for r in self.active], jnp.int32)
        toks = self._sample_slots(
            logits, [r.temperature if r is not None else 0.0
                     for r in self.active])
        self.last_token = toks
        for slot, req in enumerate(self.active):
            if req is None:
                continue
            tok = int(toks[slot])
            req.generated.append(tok)
            self._m_generated.inc()
            hit_eos = tok == self.scfg.eos_id
            hit_max = len(req.generated) >= req.max_new_tokens
            hit_cap = int(self.lengths[slot]) >= self.scfg.max_len - 1
            if hit_eos or hit_max or hit_cap:
                req.done = True
                if self.arch_collector is not None:
                    self.arch_collector.note_request(
                        req.rid, len(req.prompt) + len(req.generated))
                self.finished.append(req)
                self.active[slot] = None
                self.lengths = self.lengths.at[slot].set(0)
                self._m_finished.inc()
                self._g_active.set(sum(r is not None for r in self.active))
                self.tracer.event("request.finish", rid=req.rid,
                                  generated=len(req.generated))
        return True

    def run_until_drained(self, max_ticks: int = 10_000):
        ticks = 0
        while (self.queue or any(self.active)) and ticks < max_ticks:
            self.step()
            ticks += 1
        return self.finished


# ---------------------------------------------------------------------------
# Paged continuous-batching engine
# ---------------------------------------------------------------------------


def _sample_rows(keys, logits, temperatures):
    """All rows' sampling draws in ONE call: greedy at t <= 0, categorical
    otherwise.  Per-REQUEST keys (``scheduler.Scheduler.sample_key``) —
    vmapped so row i's draw is a function of its own key alone, never of a
    shared engine rng or its neighbours.  Rows not being sampled this tick
    carry dummy keys; the engine discards their slots."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    safe_t = jnp.maximum(temperatures, 1e-6)
    sampled = jax.vmap(jax.random.categorical)(
        keys, logits / safe_t[:, None]).astype(jnp.int32)
    return jnp.where(temperatures > 0.0, sampled, greedy)


def _jit_step(cfg, **static):
    """``lm.decode_paged`` bound to ``cfg`` and jitted under its own name,
    so the compiled program and the profiler's trace call it
    ``jit_decode_paged`` (a bare ``partial`` has no name)."""
    def decode_paged(*args, **kwargs):
        return lm.decode_paged(*args, cfg=cfg, **static, **kwargs)
    return jax.jit(decode_paged)


class PagedServingEngine(_ArchTracedEngine):
    """Continuous batching over a paged KV cache.

    Differences from the fixed-slot :class:`ServingEngine`:

    * KV memory is a block pool (``serve/kv_cache.py``): sequences own
      just the blocks their fill needs, a finished request's blocks
      recycle into waiting requests mid-batch, and an over-committed pool
      evicts (recompute-style) instead of refusing admission.
    * Prefill is CHUNKED and rides the same jitted step as decode
      (``lm.decode_paged``): one executable at chunk width + one at
      width 1 serve every prompt length — no per-length recompiles and no
      batch=1 admission stalls.
    * RNG is per-request, folded at admission and per absolute token
      position inside the step, so a request's tokens are independent of
      batch composition, chunking, and eviction/resume (the property the
      batch-invariance tests pin).

    ``step()`` is a thin loop over ``scheduler.Scheduler``: plan → one
    jitted call → sample the rows whose pending context emptied.

    Every model family serves here through a per-family cache plan
    (``kv_cache.CachePlan``): attention families page their K/V; SSM
    configs carry fixed-size state rows per batch slot beside the block
    table (the allocator still meters the token budget, so admission /
    chunked prefill / eviction-resume are family-agnostic — the
    recurrent ``ssm_stream`` feed keeps tokens bit-invariant to batch
    composition and chunking); hybrid configs carry both.  Two features
    require RECONSTRUCTIBLE context and are therefore attention-only:
    prefix caching (recurrent state cannot be spliced from adopted
    blocks) and speculative decoding (the verify pass advances state
    past rejected draft positions irreversibly) — both raise at
    construction for state-carrying families.
    """

    def __init__(self, params, cfg, scfg: PagedServeConfig,
                 collect_arch_trace: bool = False, metrics=None,
                 tracer=None):
        _warn_direct("PagedServingEngine")
        from repro.serve import kv_cache as kvc
        from repro.serve import scheduler as sched
        self.cache_plan = kvc.CachePlan.for_config(cfg)
        if self.cache_plan.has_state:
            if scfg.prefix_cache:
                raise ValueError(
                    f"prefix_cache=True needs reconstructible context, but "
                    f"family={cfg.family!r} carries recurrent SSM state — "
                    "adopted KV blocks cannot rebuild a row's state")
            if scfg.speculative:
                raise ValueError(
                    f"speculative=True cannot rewind recurrent state, but "
                    f"family={cfg.family!r} carries SSM state — the verify "
                    "pass would advance it past rejected draft tokens")
        if cfg.mla and getattr(cfg, "paged_attn", "unfused") == "fused_sc":
            raise ValueError(
                "paged_attn='fused_sc' has no latent-attention (MLA) form; "
                "serve an MLA model with 'fused' or 'unfused'")
        self.params = params
        self.cfg = cfg
        self.scfg = scfg
        if scfg.rng_mode not in ("request", "content"):
            raise ValueError(
                f"rng_mode must be 'request' or 'content', got "
                f"{scfg.rng_mode!r}")
        self._init_obs(metrics, tracer)
        num_blocks = scfg.num_blocks or kvc.default_num_blocks(
            scfg.slots, scfg.max_len, scfg.block_size)
        pcfg = kvc.PagedCacheConfig(num_blocks=num_blocks,
                                    block_size=scfg.block_size,
                                    max_len=scfg.max_len)
        if num_blocks < 1 + pcfg.blocks_per_seq:
            raise ValueError(
                f"num_blocks={num_blocks} cannot hold even one max_len="
                f"{scfg.max_len} sequence (+1 null block) at block_size="
                f"{scfg.block_size}; need >= {1 + pcfg.blocks_per_seq}")
        self.kv = kvc.PagedKVCache(pcfg, metrics=self.metrics,
                                   enable_prefix_cache=scfg.prefix_cache)
        self.pages = lm.init_paged_cache(cfg, num_blocks, scfg.block_size,
                                         slots=scfg.slots)
        self.scheduler = sched.Scheduler(
            scfg, self.kv, base_key=jax.random.PRNGKey(scfg.seed),
            on_finish=self._on_finish, metrics=self.metrics,
            tracer=self.tracer)
        # fused_sc attention draws per-token stochastic logits even when
        # the dense substrate is exact, so it needs per-request keys too
        fused_sc_attention = (
            getattr(cfg, "paged_attn", "unfused") == "fused_sc")
        self._stochastic_substrate = (
            cfg.sc_backend != "exact" or fused_sc_attention)
        self._set_sc_ladder_gauge(cfg, fused_sc_attention)
        self._set_cache_gauge()
        # (token, expert) rows routed per fed position: top_k in each MoE
        # layer (counted on the host from the plan, no device sync)
        self._routed_per_position = (
            cfg.top_k * lm.n_backbone_layers(cfg)
            if cfg.family == "moe" else 0)
        self._m_routed = self.metrics.counter(
            "serve_moe_routed_rows_total",
            "(token, expert) rows routed: fed positions x top_k x MoE "
            "layers") if self._routed_per_position else None
        # latent pages the fused MLA walk reads and skips, every MLA layer
        # (counted on the host from the plan, no device sync)
        self._mla_layers = (cfg.n_layers if cfg.mla and getattr(
            cfg, "paged_attn", "unfused") == "fused" else 0)
        if self._mla_layers:
            self._m_mla_walked = self.metrics.counter(
                "serve_mla_pages_walked_total",
                "latent pages the fused MLA kernel reads: per row through "
                "its last live page, x MLA layers")
            self._m_mla_skipped = self.metrics.counter(
                "serve_mla_pages_skipped_total",
                "latent table pages past each row's fill the fused MLA "
                "kernel skips, x MLA layers")
        self._step_fn = _jit_step(cfg)
        self._sample_fn = jax.jit(_sample_rows)
        self._copy_fn = jax.jit(attention.paged_copy_blocks)
        if scfg.speculative:
            from repro import sc
            if scfg.spec_k < 1:
                raise ValueError(
                    f"speculative=True needs spec_k >= 1, got {scfg.spec_k}")
            dname = scfg.draft_backend or sc.draft_backend(cfg.sc_backend)
            sc.get_backend(dname)           # fail fast on unknown names
            # The draft runs the SAME weights on the cheap backend with
            # plain unfused attention: its K/V writes are placeholders the
            # verify pass overwrites, its logits only GUESS tokens.
            dcfg = cfg.replace(sc_backend=dname, paged_attn="unfused")
            self._draft_fn = _jit_step(dcfg)
            # The verifier is the real model at width spec_k+1 returning
            # logits at EVERY fed position (all_logits) — one pass scores
            # the whole drafted run under the exact same per-position key
            # grid as non-speculative decoding, which is what makes its
            # greedy tokens bitwise the non-speculative tokens.
            self._verify_fn = _jit_step(cfg, all_logits=True)
            self._spec_hist = self.metrics.histogram(
                "spec_accepted_tokens",
                "draft tokens accepted per speculative row-tick (0..k)",
                buckets=tuple(float(i) for i in range(scfg.spec_k + 1)))
            self._m_spec_drafted = self.metrics.counter(
                "serve_spec_drafted_tokens_total",
                "tokens drafted by the cheap backend")
            self._m_spec_accepted = self.metrics.counter(
                "serve_spec_accepted_tokens_total",
                "drafted tokens the verifier accepted")
            # host-side replay log: one entry per speculative row-tick —
            # the counter-arithmetic tests re-derive the counters from it
            self.spec_log: list[dict] = []
        self.ticks = 0
        self._seen_decode_tick = False
        # Per-tick decode wall times (ms per live token, width-1 ticks
        # only — the decode hot path the fused kernel targets) land in a
        # fixed-bucket histogram; ``decode_latency_ms()`` is a view over
        # it.  The first decode tick pays jit compilation and is counted
        # separately instead of polluting the latency series.
        self._decode_hist = self.metrics.histogram(
            "serve_decode_ms_per_token",
            "decode wall ms per live token (width-1 ticks, jit tick "
            "dropped)")
        self._m_jit_ticks = self.metrics.counter(
            "serve_decode_jit_ticks_total",
            "decode ticks excluded from the latency series (compile wall)")
        self._init_arch(collect_arch_trace, cfg)

    def _set_cache_gauge(self) -> None:
        """``serve_kv_bytes_per_position``: bytes the paged pool holds per
        cached position, summed over every layer's pools (K and V, or a
        latent row); engines with no paged layers leave it unset."""
        if not self.cache_plan.has_paged:
            return
        # leaves are (layers, blocks, heads, block_size, width)
        per = sum(n * heads * width * v.dtype.itemsize
                  for v in jax.tree.leaves(attention.paged_leaves(self.pages))
                  for n, _, heads, _, width in [v.shape])
        self.metrics.gauge(
            "serve_kv_bytes_per_position",
            "paged cache bytes per cached position, all layers").set(per)

    # -- queue/active views mirroring the fixed-slot engine's attributes --
    @property
    def queue(self):
        return list(self.scheduler.waiting)

    @property
    def active(self):
        return list(self.scheduler.rows)

    @property
    def finished(self):
        return self.scheduler.finished

    @property
    def evictions(self) -> int:
        return self.scheduler.evictions

    def submit(self, req: Request):
        self.scheduler.submit(req)

    def _on_finish(self, req: Request):
        if self.arch_collector is not None:
            self.arch_collector.note_request(
                req.rid, len(req.prompt) + len(req.generated))

    # ------------------------------------------------------------------
    def step(self):
        """One tick: scheduler plan → one jitted chunked step → sample the
        rows that consumed their pending context.  Returns False when
        idle.  A raise mid-tick detaches the arch collector.

        With an enabled tracer the tick is one ``engine.tick`` span with
        a child per host phase, in order: ``engine.plan``,
        ``engine.copy`` (copy-on-write ticks only), ``engine.inputs``,
        ``engine.step``, ``engine.sample`` (ticks that sample) and
        ``engine.commit`` — or ``engine.plan``, ``engine.spec``,
        ``engine.commit`` on a speculative tick."""
        if not self.scheduler.has_work():
            return False
        tr = self.tracer
        with self._detach_on_error(), self._device_scope(), \
                tr.span("engine.tick", tick=self.ticks) as tick:
            with tr.span("engine.plan"):
                plan = self.scheduler.plan()
            if plan is None:
                return False
            if not any(plan.n_valid):
                raise RuntimeError(
                    "scheduler produced a no-progress tick (every row "
                    "deferred) — the block pool is mis-sized")
            spec = bool(plan.spec_rows)
            kind = ("spec" if spec
                    else "decode" if plan.sc == 1 else "prefill")
            live = sum(1 for nv in plan.n_valid if nv)
            tick.annotate(kind=kind, width=plan.sc, live=live, fed=plan.fed,
                          computed=plan.computed, refed=plan.refed)
            self._m_ticks.inc(kind=kind)
            if self._m_routed is not None:
                self._m_routed.inc(plan.fed * self._routed_per_position)
            if self._mla_layers:
                self._count_mla_pages(plan, spec)
            if plan.copies:
                # copy-on-write: a write this tick lands in a block that
                # was shared/registered — carry its K/V to the fresh block
                # before any scatter touches it
                with tr.span("engine.copy"):
                    src = [s for s, _ in plan.copies]
                    dst = [d for _, d in plan.copies]
                    self.pages = self._copy_fn(self.pages, src, dst)
            if spec:
                self._run_spec_plan(plan)
            else:
                self._run_plan(plan, live)
            self.ticks += 1
            return True

    def _count_mla_pages(self, plan, spec: bool) -> None:
        # a speculative tick's fused call is the width-(k+1) verify pass
        width = self.scheduler.spec_k + 1 if spec else plan.sc
        nb = self.kv.cfg.blocks_per_seq
        walked = paged_attention.pages_walked(
            plan.lengths, width, self.scfg.block_size, nb)
        self._m_mla_walked.inc(walked * self._mla_layers)
        self._m_mla_skipped.inc(
            (len(plan.lengths) * nb - walked) * self._mla_layers)

    def _run_plan(self, plan, live: int):
        tr = self.tracer
        with tr.span("engine.inputs"):
            tokens = jnp.asarray(plan.tokens, jnp.int32)
            lengths = jnp.asarray(plan.lengths, jnp.int32)
            n_valid = jnp.asarray(plan.n_valid, jnp.int32)
            tables = jnp.asarray(plan.tables, jnp.int32)
            rng = (jnp.stack(plan.keys) if self._stochastic_substrate
                   else None)
        with tr.span("engine.step"):
            t0 = time.perf_counter()
            logits, self.pages = self._step_fn(
                self.params, self.pages, tables, tokens, lengths, n_valid,
                rng=rng)
            if plan.sc == 1:
                # decode tick: force completion so the wall time covers
                # the device step, then normalize per live row.  The first
                # decode tick is the jit compile — count it, don't time it.
                logits.block_until_ready()
                ms = (time.perf_counter() - t0) * 1e3 / max(live, 1)
                if self._seen_decode_tick:
                    self._decode_hist.observe(ms)
                else:
                    self._seen_decode_tick = True
                    self._m_jit_ticks.inc()
        toks = None
        if plan.sample_rows:
            # One batched sampling call + one host sync per tick: the
            # (slots, vocab) shapes are tick-invariant, so this stays
            # a single compiled executable.  Non-sampling slots get
            # dummy keys and their outputs are discarded.
            with tr.span("engine.sample"):
                keys = [self._dummy_sample_key()] * len(plan.tokens)
                temps = [0.0] * len(plan.tokens)
                for slot, seq in plan.sample_rows:
                    keys[slot] = self.scheduler.sample_key(seq)
                    temps[slot] = seq.req.temperature
                toks = np.asarray(self._sample_fn(
                    jnp.stack(keys), logits,
                    jnp.asarray(temps, jnp.float32))).tolist()   # one sync
        with tr.span("engine.commit"):
            for slot, seq in plan.sample_rows:
                self.scheduler.on_token(slot, seq, toks[slot])

    def _run_spec_plan(self, plan):
        """One speculative tick: ``spec_k`` cheap draft steps, then ONE
        real verify pass, then commit the accepted run per row.

        The draft loop runs the SAME weights through the paired cheap
        backend on a scratch copy of the page pool (``dpages``): each
        width-1 step feeds the previous token at the next position, takes
        the greedy argmax as the draft, and accumulates its own K/V so
        later draft steps can attend to earlier draft tokens.  The
        scratch pool is DROPPED afterwards — ``self.pages`` never holds
        draft-grade K/V.

        The verify pass is the real model at width ``spec_k + 1`` feeding
        ``[t_F, d_1 .. d_k]`` against the pristine pool: ``paged_scatter``
        writes each position's verify-grade K/V before attention reads
        it, so one call both scores every drafted position
        (``all_logits``) and leaves the cache exactly as ``a + 1``
        non-speculative decode ticks would have (positions beyond the
        accepted run hold stale K/V that is length-masked and overwritten
        on the next feed — same contract as chunk padding).  Its rng is
        the SAME per-position key grid as non-speculative decoding, so
        the verifier's greedy tokens are bitwise the non-speculative
        tokens: acceptance moves throughput, never outputs.

        Rows not speculating this tick (temperature > 0, still
        prefilling, or no pool headroom) ride the verify pass with their
        single token (``n_valid`` from the plan) and sample from its
        position-0 logits — a mixed batch costs no extra dispatch.
        """
        tr = self.tracer
        with tr.span("engine.spec"):
            drafts, greedy, toks = self._draft_and_verify(plan)
        k = self.scheduler.spec_k
        with tr.span("engine.commit"):
            for slot, seq in plan.spec_rows:
                vrow = [int(t) for t in greedy[slot]]
                a = 0
                while a < k and drafts[slot][a] == vrow[a]:
                    a += 1
                committed = self.scheduler.on_tokens(slot, seq,
                                                     vrow[:a + 1])
                self._spec_hist.observe(float(a))
                self._m_spec_drafted.inc(k)
                self._m_spec_accepted.inc(a)
                self.spec_log.append(dict(
                    tick=self.ticks, rid=seq.req.rid, k=k,
                    drafted=list(drafts[slot]), verified=vrow,
                    accepted=a, committed=committed))
            for slot, seq in plan.sample_rows:
                self.scheduler.on_token(slot, seq, toks[slot])

    def _draft_and_verify(self, plan):
        """The device half of a speculative tick: ``spec_k`` draft steps,
        the verify pass and the sampler for rows not speculating.
        Returns (drafts per row, the verifier's greedy tokens (b, k+1),
        sampled tokens or None)."""
        k = self.scheduler.spec_k
        b = len(plan.tokens)
        lengths = jnp.asarray(plan.lengths, jnp.int32)
        tables = jnp.asarray(plan.tables, jnp.int32)
        spec_slots = {slot for slot, _ in plan.spec_rows}
        content = self.scheduler.content_mode
        stoch = self._stochastic_substrate
        dummy = self.scheduler._dummy_key
        base_rng = None
        if stoch and not content:
            base_rng = jnp.stack(plan.keys)            # (b, 2) request keys
        chain = None
        vkeys = None
        if stoch and content:
            chain = [plan.keys[r][0] for r in range(b)]  # (2,) per row
            vkeys = [[chain[r]] for r in range(b)]
        draft_nv = jnp.asarray(
            [1 if r in spec_slots else 0 for r in range(b)], jnp.int32)
        cur = [int(plan.tokens[r][0]) for r in range(b)]
        drafts: list[list[int]] = [[] for _ in range(b)]
        dpages = self.pages
        for i in range(k):
            toks = jnp.asarray([[c] for c in cur], jnp.int32)
            if not stoch:
                rng = None
            elif content:
                rng = jnp.stack(chain)[:, None, :]     # (b, 1, 2)
            else:
                rng = base_rng
            dlogits, dpages = self._draft_fn(
                self.params, dpages, tables, toks, lengths + i, draft_nv,
                rng=rng)
            nxt = np.asarray(jnp.argmax(dlogits, axis=-1)).tolist()  # sync
            for r in range(b):
                if r in spec_slots:
                    drafts[r].append(int(nxt[r]))
                    cur[r] = int(nxt[r])
                    if chain is not None:
                        chain[r] = jax.random.fold_in(chain[r], int(nxt[r]))
                if vkeys is not None:
                    vkeys[r].append(chain[r] if r in spec_slots else dummy)
        vtok, vnv = [], []
        for r in range(b):
            if r in spec_slots:
                vtok.append([int(plan.tokens[r][0])] + drafts[r])
                vnv.append(k + 1)
            else:
                vtok.append([int(plan.tokens[r][0])] + [0] * k)
                vnv.append(plan.n_valid[r])
        if not stoch:
            rng = None
        elif content:
            rng = jnp.stack([jnp.stack(vkeys[r]) for r in range(b)])
        else:
            rng = base_rng
        vlogits, self.pages = self._verify_fn(
            self.params, self.pages, tables, jnp.asarray(vtok, jnp.int32),
            lengths, jnp.asarray(vnv, jnp.int32), rng=rng)
        greedy = np.asarray(jnp.argmax(vlogits, axis=-1))   # (b, k+1), sync
        toks = None
        if plan.sample_rows:
            keys = [self._dummy_sample_key()] * b
            temps = [0.0] * b
            for slot, seq in plan.sample_rows:
                keys[slot] = self.scheduler.sample_key(seq)
                temps[slot] = seq.req.temperature
            toks = np.asarray(self._sample_fn(
                jnp.stack(keys), vlogits[:, 0],
                jnp.asarray(temps, jnp.float32))).tolist()
        return drafts, greedy, toks

    # ------------------------------------------------------------------
    # Drain / resume (ft.FleetSupervisor's shard-failover contract)
    # ------------------------------------------------------------------
    def drain(self) -> list:
        """Checkpoint and release EVERY request this engine holds
        (admitted rows and the waiting queue), returning one checkpoint
        dict per request, admission order first.

        Each checkpoint carries the request identity (rid, prompt,
        generated-so-far, per-request key, sampling knobs) plus — for
        attention-only families — the scheduler position (``fed``,
        ``pending``) and the request's filled KV block payload gathered
        from the page pool, so a healthy shard can resume WARM via
        :meth:`restore` without re-prefilling.  State-carrying families
        (ssm/hybrid) checkpoint cold: their recurrent state is not
        reconstructible from pages, so resume recomputes from tokens —
        which the per-(request key, position) rng contract makes
        token-identical anyway (same property eviction-resume relies
        on).  After ``drain()`` the engine is empty and trivially
        drainable."""
        sched = self.scheduler
        ckpts = []
        for slot in range(self.scfg.slots):
            seq = sched.rows[slot]
            if seq is None:
                continue
            ckpts.append(self._checkpoint_seq(seq, warm=True))
            self.kv.release(seq.req.rid)
            sched.rows[slot] = None
            if seq in sched.admit_stack:
                sched.admit_stack.remove(seq)
            self.tracer.event("request.drain", rid=seq.req.rid,
                              fed=seq.fed, warm=ckpts[-1]["kv"] is not None)
        for seq in list(sched.waiting):
            if seq.fed:                      # pre-seeded resume never admitted
                self.kv.release(seq.req.rid)
                seq.reset_for_recompute()
            ckpts.append(self._checkpoint_seq(seq, warm=False))
            self.tracer.event("request.drain", rid=seq.req.rid,
                              fed=0, warm=False)
        sched.waiting.clear()
        sched._update_gauges()
        return ckpts

    def _checkpoint_seq(self, seq, warm: bool) -> dict:
        req = seq.req
        ckpt = dict(
            rid=req.rid, prompt=list(req.prompt),
            generated=list(req.generated),
            max_new_tokens=req.max_new_tokens,
            temperature=req.temperature,
            key=None if req.key is None else np.asarray(req.key),
            fed=seq.fed, pending=list(seq.pending),
            prefilling=seq.prefilling,
            block_size=self.scfg.block_size, kv=None)
        if warm and seq.fed and not self.cache_plan.has_state:
            from repro.serve.kv_cache import blocks_for
            nblk = blocks_for(seq.fed, self.scfg.block_size)
            ids = jnp.asarray(self.kv.tables[req.rid][:nblk], jnp.int32)
            ckpt["kv"] = jax.tree.map(lambda v: np.asarray(v[:, ids]),
                                      attention.paged_leaves(self.pages))
        return ckpt

    def restore(self, ckpt: dict) -> bool:
        """Resume one drained request on THIS engine.  With a KV payload
        (and matching block geometry + headroom) the resume is WARM:
        fresh blocks are allocated, the payload scatters into the page
        pool, and the request re-enters the admission queue at its
        drained position.  Otherwise it falls back to a cold recompute
        resume — a plain re-submit carrying generated-so-far, exactly the
        eviction path.  Returns True for a warm resume."""
        req = Request(rid=ckpt["rid"], prompt=list(ckpt["prompt"]),
                      max_new_tokens=ckpt["max_new_tokens"],
                      temperature=ckpt["temperature"])
        req.generated = list(ckpt["generated"])
        if ckpt["key"] is not None:
            req.key = jnp.asarray(ckpt["key"])
        if ckpt["kv"] is not None and self._restore_warm(req, ckpt):
            self.tracer.event("request.resume", rid=req.rid,
                              fed=ckpt["fed"], warm=True)
            return True
        self.submit(req)
        self.tracer.event("request.resume", rid=req.rid, fed=0, warm=False)
        return False

    def _restore_warm(self, req, ckpt: dict) -> bool:
        from repro.serve import scheduler as sched_mod
        from repro.serve.kv_cache import blocks_for
        fed = ckpt["fed"]
        if (ckpt["block_size"] != self.scfg.block_size
                or self.cache_plan.has_state
                or fed == 0 or fed > self.scfg.max_len
                or self.kv.tables.get(req.rid)
                or not self.kv.has_room(req.rid, fed)
                or not self.kv.ensure(req.rid, fed)):
            return False
        nblk = blocks_for(fed, self.scfg.block_size)
        ids = jnp.asarray(self.kv.tables[req.rid][:nblk], jnp.int32)
        self.pages = {**self.pages, **jax.tree.map(
            lambda pool, v: pool.at[:, ids].set(jnp.asarray(v, pool.dtype)),
            attention.paged_leaves(self.pages), ckpt["kv"])}
        seq = sched_mod.Sequence(req=req, key=req.key, fed=fed,
                                 pending=list(ckpt["pending"]),
                                 prefilling=ckpt["prefilling"])
        self.scheduler.adopt(seq)
        return True

    def decode_latency_ms(self):
        """p50/p95 decode wall ms per token — a view over the
        ``serve_decode_ms_per_token`` histogram in ``self.metrics``.

        The first decode tick pays jit compilation and is never
        recorded; with fewer than TWO recorded ticks after that drop the
        result is None (percentiles over zero samples are undefined, and
        over one sample they gate nothing but scheduling noise)."""
        h = self._decode_hist
        if h.count() < 2:
            return None
        return {"decode_p50_ms": round(h.percentile(50), 3),
                "decode_p95_ms": round(h.percentile(95), 3)}

    def _dummy_sample_key(self):
        return self.scheduler._dummy_key

    def run_until_drained(self, max_ticks: int = 10_000):
        ticks = 0
        while self.scheduler.has_work() and ticks < max_ticks:
            self.step()
            ticks += 1
        return self.scheduler.finished
