#!/usr/bin/env python3
"""Smoke test on the chip: qwen2-0.5b at its published widths (24 layers,
d_model 896, 14 query / 2 KV heads, d_ff 4864, vocab 151936), driven
through the serving entry points a user calls
(``serve.build_engine`` -> ``PagedServingEngine`` -> ``lm.decode_paged``).

    python chip_smoke.py                 # one TPU chip
    python chip_smoke.py --four-chips    # four TPU chips, training only

One chip runs three phases in this one process:

* ``device``      — the platform must be ``tpu``; there is no CPU branch.
* ``serve_exact`` — the exact substrate with the fused paged-attention
  kernel: 4 requests (prompts of 16-64 tokens, 8 greedy new tokens).
  The engine's logits at every generated position must match a full
  ``lm.forward`` over prompt + generated to ``EXACT_TOL``, and its tokens
  the reference's argmax except where the reference's top-2 gap is
  within ``EXACT_TOL``.
* ``serve_sc``    — the same model on the SC substrate
  (``sc_backend="pallas_fused"``, ``paged_attn="fused_sc"``) at
  ``SC_NBIT`` bits: (a) the compiled ``pallas_fused`` kernel equals the
  pure-jnp packed reference bit for bit on a real-width dense site;
  (b) request 0 yields the same tokens served alone and in a batch;
  (c) the engine's logits keep a cosine of at least ``SC_COS_FLOOR``
  against the exact model's logits over the same context.

``--four-chips`` runs only the training comparison: 3 AdamW steps at
published widths on a (data 2, model 2) mesh against the same 3 steps on
a one-device mesh, losses within ``LOSS_RTOL``, with the parameters, the
batch and the step's collectives checked to really span all four chips.

Weights are random, drawn from ``--seed``: no checkpoint ships with the
repository.  Every phase prints one JSON line (compile seconds, wall
seconds, tokens, peak device memory, check results); a failed check
raises, so the script exits non-zero and prints no result.  The last
line of a passing run is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Compiled programs are cached where ``JAX_COMPILATION_CACHE_DIR`` says,
else in ``<checkout>/.jax_cache`` (``repro.launch.compile_cache``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "qwen2-0.5b"
MAX_LEN = 128
# serve_exact: engine vs reference logits, both float32 under
# jax.default_matmul_precision("highest"): they differ only in summation
# order (paged chunked prefill + online softmax vs one blockwise pass).
EXACT_TOL = 2e-3
# serve_sc: stochastic bits per product, and the cosine the SC engine's
# logits must keep against the exact model's on the same context.  At 64
# bits, 24 random-weight layers leave SC logits about as close to the
# exact ones (cosine 0.15-0.35, moment backend at d_model 512, 24 layers)
# as the exact logits of an unrelated context are (-0.12 to 0.24), so the
# check could not tell a working path from one attending to the wrong
# pages.  256 bits keep 0.67-0.74 there; the floor sits between.
SC_NBIT = 256
SC_COS_FLOOR = 0.4
SC_PROMPTS, SC_NEW = (4, 3), 3  # one prefill tick of width 4, then decode
# --four-chips: relative loss agreement of the sharded and the
# one-device run over 3 steps.
LOSS_RTOL = 1e-3
TRAIN_STEPS, TRAIN_BATCH, TRAIN_SEQ = 3, 8, 64

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (or reading a
    compiled program back from the persistent cache), plus the cache's
    hit and miss counts, from JAX's own monitoring events."""

    def __init__(self):
        self.secs = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.secs += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def since(self, mark):
        secs, hits, misses = mark
        return {"compile_s": round(self.secs - secs, 3),
                "cache_hits": self.hits - hits,
                "cache_misses": self.misses - misses}

    def mark(self):
        return self.secs, self.hits, self.misses


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def peak_bytes(devices) -> list:
    return [d.memory_stats()["peak_bytes_in_use"] for d in devices]


def cosine(a, b) -> float:
    return float(a @ b / (np.linalg.norm(a) * np.linalg.norm(b)))


def report(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------


def phase_device(count: int):
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise SystemExit(f"chip_smoke needs a TPU; JAX found {platform!r}")
    if len(devices) < count:
        raise SystemExit(f"chip_smoke needs {count} TPU chips; JAX found "
                         f"{len(devices)}")
    desc = {"platform": platform, "kind": devices[0].device_kind,
            "count": len(devices)}
    report("device", **desc)
    return desc


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


def record_logits(engine) -> dict:
    """Capture the logits each request's tokens were sampled from.

    Wraps the engine's batched sampler (which sees every tick's logits)
    and the scheduler's token commit (which names the row), so the
    record is the engine's own output, position by position.
    """
    out: dict = {}
    latest: dict = {}
    sample_fn, on_token = engine._sample_fn, engine.scheduler.on_token

    def sample(keys, logits, temps):
        latest["logits"] = logits
        return sample_fn(keys, logits, temps)

    def token(slot, seq, tok):
        out.setdefault(seq.req.rid, []).append(
            np.asarray(latest["logits"][slot]))
        on_token(slot, seq, tok)

    engine._sample_fn = sample
    engine.scheduler.on_token = token
    return out


def serve_requests(engine, requests):
    """Submit, drain, and time one batch; returns ({rid: req}, wall s)."""
    for r in requests:
        engine.submit(r)
    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        done = engine.run_until_drained()
    jax.block_until_ready(engine.pages)
    return {r.rid: r for r in done}, time.perf_counter() - t0


def prompts(seed: int, lengths, vocab: int) -> list:
    rng = np.random.default_rng(seed)
    return [rng.integers(3, vocab, size=n).tolist() for n in lengths]


class Reference:
    """``lm.forward`` over prompt + generated, float32 at "highest".

    One compiled shape (4 rows x MAX_LEN): rows pad with token 0 at the
    end, which a causal model never lets an earlier position see.
    """

    ROWS = 4

    def __init__(self, params, cfg):
        from repro.models import lm
        self.params = params
        self.fn = jax.jit(lambda p, t: lm.forward(p, t, cfg))

    def logits(self, reqs) -> list:
        """Per request: (len(generated), vocab) logits — row p is the
        distribution that generated token p."""
        assert len(reqs) <= self.ROWS
        toks = np.zeros((self.ROWS, MAX_LEN), np.int32)
        for i, r in enumerate(reqs):
            seq = r.prompt + r.generated[:-1]
            toks[i, :len(seq)] = seq
        with jax.default_matmul_precision("highest"):
            lg = np.asarray(self.fn(self.params, jnp.asarray(toks)))
        return [lg[i, len(r.prompt) - 1:len(r.prompt) - 1 + len(r.generated)]
                for i, r in enumerate(reqs)]


def phase_serve_exact(params, cfg, ref, clock, seed):
    from repro import serve
    from repro.serve import Request

    mark = clock.mark()
    engine = serve.build_engine(params, cfg, serve.ServeOptions(
        paged=True, fused_attention=True, slots=4, max_len=MAX_LEN,
        seed=seed, eos_id=-1))
    logits = record_logits(engine)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=8, temperature=0.0)
            for i, p in enumerate(prompts(seed, (16, 29, 43, 64), cfg.vocab))]
    done, wall = serve_requests(engine, reqs)
    timing = clock.since(mark)
    ordered = [done[i] for i in range(len(reqs))]
    refs = ref.logits(ordered)
    max_diff, near_ties, mismatched = 0.0, 0, []
    for req, ref_lg in zip(ordered, refs):
        eng_lg = np.stack(logits[req.rid])
        check(eng_lg.shape == ref_lg.shape and len(req.generated) == 8,
              f"request {req.rid}: {eng_lg.shape} logits vs {ref_lg.shape}")
        check(bool(np.isfinite(eng_lg).all()), "non-finite engine logits")
        max_diff = max(max_diff, float(np.abs(eng_lg - ref_lg).max()))
        for p, tok in enumerate(req.generated):
            top2 = np.sort(ref_lg[p])[-2:]
            if tok != int(np.argmax(ref_lg[p])):
                if top2[1] - top2[0] <= EXACT_TOL:
                    near_ties += 1
                else:
                    mismatched.append((req.rid, p))
    tokens = sum(len(r.generated) for r in ordered)
    report("serve_exact", requests=len(ordered), tokens=tokens,
           ticks=engine.ticks, wall_s=round(wall, 3), **timing,
           peak_bytes=peak_bytes(jax.devices()[:1]),
           max_abs_logit_diff=max_diff, tol=EXACT_TOL,
           token_mismatches=len(mismatched), near_tie_mismatches=near_ties)
    check(max_diff <= EXACT_TOL,
          f"engine logits differ from lm.forward by {max_diff} > {EXACT_TOL}")
    check(not mismatched, f"greedy tokens differ at {mismatched}")


def packed_reference(keys, x, w, nbit: int):
    """Pure-jnp ``pallas_fused`` rows mode: per row, the pinned operand
    streams (``ctr_rng.operand_stream``), the packed pop-count oracle
    (``kernels/ref.py``) and the integer K-reduction of
    ``sc/backends.py:pallas_bitexact``."""
    from repro import sc
    from repro.kernels import ref as kref
    from repro.sc import ctr_rng, encoding

    cfg = sc.ScConfig(backend="pallas_fused", nbit=nbit)
    k, n = w.shape
    nwords = nbit // 32
    sw, pw, scw = encoding.encode(w, cfg)
    pwf = encoding.to_fx16(jnp.broadcast_to(pw, (k, n)).reshape(-1))

    def one(key, xr):
        sx, px, scx = encoding.encode(xr, cfg)
        pxf = encoding.to_fx16(jnp.broadcast_to(px[:, None], (k, n))
                               .reshape(-1))
        kx, ky = jax.random.split(key)
        rx = ctr_rng.operand_stream(ctr_rng.raw_key(kx), k * n, nwords)
        ry = ctr_rng.operand_stream(ctr_rng.raw_key(ky), k * n, nwords)
        counts = kref.sc_mul_popcount_ref(pxf, pwf, rx, ry).reshape(k, n)
        sign = sx.astype(jnp.int32)[:, None] * sw.astype(jnp.int32)
        total = jnp.sum(sign * counts, axis=0)
        return total.astype(jnp.float32) / nbit * (scx * scw)

    return jax.lax.map(lambda a: one(*a), (keys, x))


def phase_serve_sc(params, cfg, ref, clock, seed):
    from repro import sc, serve
    from repro.serve import Request

    mark = clock.mark()
    # (a) one real-width dense site: layer 0's q projection, 8 rows x 896
    # x the first 256 output columns, compiled kernel vs packed reference
    w = params["blocks"]["attn"]["wq"][0][:, :256]
    kx, kk = jax.random.split(jax.random.PRNGKey(seed + 1))
    x = jax.random.normal(kx, (8, cfg.d_model), jnp.float32)
    keys = jax.random.split(kk, 8)
    kernel = jax.jit(lambda k_, x_, w_: sc.sc_dot_rows(
        k_, x_, w_, sc.ScConfig(backend="pallas_fused", nbit=SC_NBIT)))
    got = np.asarray(kernel(keys, x, w))
    want = np.asarray(jax.jit(packed_reference, static_argnums=3)(
        keys, x, w, SC_NBIT))
    kernel_equal = bool(np.array_equal(got, want))
    kernel_diffs = int((got != want).sum())

    cfg_sc = cfg.replace(sc_backend="pallas_fused", sc_nbit=SC_NBIT,
                         paged_attn="fused_sc")
    engine = serve.build_engine(params, cfg_sc, serve.ServeOptions(
        paged=True, slots=2, max_len=MAX_LEN, seed=seed, eos_id=-1,
        prefill_chunk=max(SC_PROMPTS)))
    logits = record_logits(engine)
    p0, p1 = prompts(seed + 2, SC_PROMPTS, cfg.vocab)
    batch, wall_batch = serve_requests(engine, [
        Request(rid=0, prompt=p0, max_new_tokens=SC_NEW),
        Request(rid=1, prompt=p1, max_new_tokens=SC_NEW)])
    batch_logits = {rid: np.stack(v) for rid, v in logits.items()}
    logits.clear()
    # (b) request 0 again, alone: same rid => same per-request key
    solo, wall_solo = serve_requests(
        engine, [Request(rid=0, prompt=p0, max_new_tokens=SC_NEW)])
    timing = clock.since(mark)
    # (c) against the exact model over the SC engine's own context; the
    # exact logits of the two requests against each other (unrelated
    # contexts) are reported beside it as the level of chance agreement
    reqs = [batch[0], batch[1]]
    refs = ref.logits(reqs)
    cosines = []
    for req, ref_lg in zip(reqs, refs):
        eng_lg = batch_logits[req.rid]
        check(bool(np.isfinite(eng_lg).all()), "non-finite SC logits")
        cosines += [cosine(a, b) for a, b in zip(eng_lg, ref_lg)]
    chance = [cosine(a, b) for a, b in zip(*refs)]
    tokens = sum(len(r.generated) for r in reqs) + len(solo[0].generated)
    report("serve_sc", nbit=SC_NBIT, tokens=tokens, ticks=engine.ticks,
           wall_batch_s=round(wall_batch, 3), wall_solo_s=round(wall_solo, 3),
           **timing, peak_bytes=peak_bytes(jax.devices()[:1]),
           kernel_bit_equal=kernel_equal, kernel_diffs=kernel_diffs,
           batch_tokens=batch[0].generated, solo_tokens=solo[0].generated,
           cos_min=min(cosines), cos_mean=float(np.mean(cosines)),
           cos_floor=SC_COS_FLOOR, cos_unrelated_max=max(chance))
    check(kernel_equal, f"pallas_fused differs from the packed reference "
                        f"in {kernel_diffs} outputs")
    check(batch[0].generated == solo[0].generated,
          "request 0 served alone and in a batch gave different tokens")
    check(min(cosines) >= SC_COS_FLOOR,
          f"SC logits cosine {min(cosines)} below {SC_COS_FLOOR}")


# ---------------------------------------------------------------------------
# four chips: sharded training against one device
# ---------------------------------------------------------------------------


def _train(cfg, mesh, batches, seed):
    from repro.launch import specs
    from repro.optim import AdamWConfig
    from repro.train import TrainConfig, make_train_step, train_state_init

    tcfg = TrainConfig(optimizer=AdamWConfig(
        lr=1e-3, total_steps=TRAIN_STEPS, warmup_steps=1), seed=seed)
    psh = specs.param_shardings(cfg, mesh)
    ssh = {"params": psh, "opt": specs.opt_shardings(cfg, mesh, psh)}
    bsh = specs.batch_shardings(cfg, mesh, TRAIN_BATCH)
    state = jax.device_put(
        train_state_init(jax.random.PRNGKey(seed), cfg, tcfg), ssh)
    batches = [jax.device_put(b, bsh) for b in batches]
    with jax.default_matmul_precision("highest"):
        step = jax.jit(make_train_step(cfg, tcfg, mesh),
                       in_shardings=(ssh, bsh), out_shardings=(ssh, None),
                       donate_argnums=(0,)).lower(state, batches[0]).compile()
    losses = []
    for b in batches:
        state, metrics = step(state, b)
        losses.append(float(metrics["loss"]))
    return losses, state, batches, step.as_text()


def phase_four_chips(clock, seed):
    from repro.configs import get_config
    from repro.data import SyntheticLMData, make_batch
    from repro.launch.mesh import make_local_mesh

    devices = jax.devices()[:4]
    cfg = get_config(ARCH).replace(param_dtype=jnp.float32,
                                   act_dtype=jnp.float32)
    data = SyntheticLMData(vocab=cfg.vocab, seq_len=TRAIN_SEQ,
                           global_batch=TRAIN_BATCH, seed=seed)
    batches = [make_batch(data, s) for s in range(TRAIN_STEPS)]
    mark = clock.mark()
    one, state1, _, _ = _train(cfg, make_local_mesh(devices=devices[:1]),
                               batches, seed)
    del state1
    mesh = make_local_mesh(model_parallel=2, devices=devices)
    four, state, placed, hlo = _train(cfg, mesh, batches, seed)
    timing = clock.since(mark)

    leaves = jax.tree.leaves(state["params"])
    total = sum(leaf.nbytes for leaf in leaves)
    held = {d.id: 0 for d in devices}
    split = 0
    for leaf in leaves:
        split += leaf.addressable_shards[0].data.shape != leaf.shape
        for shard in leaf.addressable_shards:
            held[shard.device.id] += shard.data.nbytes
    share = {d: round(b / total, 4) for d, b in held.items()}
    batch_rows = sorted(s.data.shape[0]
                        for s in placed[0]["inputs"].addressable_shards)
    collectives = [c for c in ("all-reduce", "reduce-scatter", "all-gather",
                               "all-to-all", "collective-permute")
                   if c in hlo]
    rel = [abs(a - b) / abs(b) for a, b in zip(four, one)]
    report("four_chips", mesh=dict(mesh.shape), steps=TRAIN_STEPS,
           batch=TRAIN_BATCH, seq=TRAIN_SEQ, **timing,
           losses_1dev=one, losses_4dev=four, loss_rel_diff=rel,
           loss_rtol=LOSS_RTOL, param_bytes=total, param_share=share,
           split_param_leaves=f"{split}/{len(leaves)}",
           batch_rows_per_device=batch_rows, collectives=collectives,
           peak_bytes=peak_bytes(devices))
    check(all(np.isfinite(one + four)), "non-finite loss")
    check(max(rel) <= LOSS_RTOL, f"losses disagree: {one} vs {four}")
    check(min(share.values()) >= 0.125 and max(share.values()) <= 0.75,
          f"parameters do not span the four chips: {share}")
    check(batch_rows == [TRAIN_BATCH // 2] * 4,
          f"batch is not split over the data axis: {batch_rows}")
    check(bool(collectives), "the sharded step has no collectives")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip training comparison")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights, prompts and batches")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    desc = phase_device(4 if args.four_chips else 1)
    cache_dir = enable_compile_cache()
    report("compile_cache", dir=cache_dir)
    clock = CompileClock()
    if args.four_chips:
        phase_four_chips(clock, args.seed)
    else:
        from repro.configs import get_config
        from repro.models import lm, params as params_lib

        # as launch/serve.py: published widths, float32, seeded weights
        cfg = get_config(ARCH).replace(param_dtype=jnp.float32,
                                       act_dtype=jnp.float32)
        params = params_lib.init_params(
            jax.random.PRNGKey(args.seed), lm.lm_param_specs(cfg),
            cfg.param_dtype)
        ref = Reference(params, cfg)
        phase_serve_exact(params, cfg, ref, clock, args.seed)
        phase_serve_sc(params, cfg, ref, clock, args.seed)
    print(json.dumps({"ok": True, "device": desc}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
