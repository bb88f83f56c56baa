"""Every model family under the paged engine (the serve half of the zoo
refactor): MoE / SSM / hybrid decode through ``PagedServingEngine`` via
the per-family cache plan, match the fixed-slot engine token for token,
and keep the per-request rng invariants (batch composition, chunking,
eviction/resume) on stochastic substrates."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import lm, params as P
from repro.serve import (PagedServeConfig, PagedServingEngine, Request,
                         ServeConfig, ServingEngine)
from repro.serve.kv_cache import CachePlan

F32 = dict(param_dtype=jnp.float32, act_dtype=jnp.float32)
FAMILY_ARCHS = ["moonlight-16b-a3b", "mamba2-370m", "zamba2-7b"]


def _cfg(arch, **kw):
    return get_smoke_config(arch).replace(**F32, **kw)


def _params(key, cfg):
    return P.init_params(key, lm.lm_param_specs(cfg), cfg.param_dtype)


def _run_paged(params, cfg, reqs, *, slots=2, seed=7, num_blocks=0,
               submit_after=None, **kw):
    defaults = dict(slots=slots, max_len=48, block_size=4, prefill_chunk=3,
                    seed=seed, num_blocks=num_blocks)
    defaults.update(kw)
    eng = PagedServingEngine(params, cfg, PagedServeConfig(**defaults))
    late = dict(submit_after or {})
    for r in reqs:
        eng.submit(r)
    ticks = 0
    while eng.scheduler.has_work() or late:
        for t in [t for t in sorted(late) if ticks >= t]:
            eng.submit(late.pop(t))
        eng.step()
        ticks += 1
        assert ticks < 500
    return eng, {r.rid: r.generated for r in eng.finished}


# ---------------------------------------------------------------------------
# Cache plan
# ---------------------------------------------------------------------------


def test_cache_plan_per_family():
    plans = {a: CachePlan.for_config(_cfg(a)) for a in
             ["qwen2-0.5b"] + FAMILY_ARCHS}
    assert plans["qwen2-0.5b"].has_paged
    assert not plans["qwen2-0.5b"].has_state
    assert plans["moonlight-16b-a3b"].has_paged         # MoE pages like dense
    assert plans["mamba2-370m"].has_state
    assert not plans["mamba2-370m"].has_paged
    hz = plans["zamba2-7b"]
    assert hz.has_paged and hz.has_state                # both cache kinds
    cfg = _cfg("zamba2-7b")
    assert hz.state_layers == lm.n_backbone_layers(cfg)
    assert hz.paged_layers == lm.n_shared_invocations(cfg)
    pages = lm.init_paged_cache(cfg, 8, 4, slots=2)
    assert set(pages) == {"ssm", "k", "v"}
    assert pages["k"].shape[0] == hz.paged_layers
    assert pages["ssm"]["state"].shape[:2] == (hz.state_layers, 2)


# ---------------------------------------------------------------------------
# Paged == fixed-slot, per family (exact backend, greedy)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_paged_matches_fixed_slot_greedy(arch, key):
    cfg = _cfg(arch)
    params = _params(key, cfg)
    prompts = {0: [5, 9, 17, 3], 1: [40, 2, 8, 30, 7]}
    fe = ServingEngine(params, cfg, ServeConfig(slots=2, max_len=48))
    for rid, p in prompts.items():
        fe.submit(Request(rid=rid, prompt=list(p), max_new_tokens=5))
    got_f = {r.rid: r.generated for r in fe.run_until_drained()}
    _, got_p = _run_paged(
        params, cfg,
        [Request(rid=r, prompt=list(p), max_new_tokens=5)
         for r, p in prompts.items()])
    assert got_p == got_f


# ---------------------------------------------------------------------------
# RNG invariants on a stochastic substrate, per family
# ---------------------------------------------------------------------------

REQ0 = dict(rid=0, prompt=[5, 9, 17, 3], max_new_tokens=6, temperature=0.8)
REQ1 = dict(rid=1, prompt=[40, 2, 8, 30, 7, 11, 2, 4], max_new_tokens=6,
            temperature=0.3)


@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_batch_composition_invariance_stochastic(arch, key):
    """Tokens are a function of (request key, position) alone — solo,
    batched, and mid-stream admission all agree bit for bit, for every
    cache-plan family."""
    cfg = _cfg(arch, sc_backend="moment", sc_nbit=256)
    params = _params(key, cfg)
    _, solo = _run_paged(params, cfg, [Request(**REQ0)], slots=1)
    _, full = _run_paged(params, cfg,
                         [Request(**REQ0), Request(**REQ1)], slots=2)
    _, mid = _run_paged(params, cfg, [Request(**REQ1)], slots=2,
                        submit_after={3: Request(**REQ0)})
    assert solo[0] == full[0] == mid[0]


@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_eviction_resume_reproduces_tokens(arch, key):
    """A tight pool forces an eviction mid-decode; the resumed request
    re-feeds its context (rebuilding KV blocks AND/OR recurrent state
    from position 0) and must emit the same tokens as a roomy pool."""
    cfg = _cfg(arch, sc_backend="moment", sc_nbit=256)
    params = _params(key, cfg)
    mk = lambda: [
        Request(rid=0, prompt=[5, 9, 17, 3, 8, 2, 30, 11, 7, 6],
                max_new_tokens=16, temperature=0.6),
        Request(rid=1, prompt=[40, 2, 8, 30, 7, 11, 2, 4, 9, 9],
                max_new_tokens=16, temperature=0.6)]
    roomy_e, roomy = _run_paged(params, cfg, mk(), prefill_chunk=4)
    tight_e, tight = _run_paged(params, cfg, mk(), prefill_chunk=4,
                                num_blocks=13)
    assert tight_e.evictions > 0, "pool was meant to force an eviction"
    assert roomy_e.evictions == 0
    assert roomy == tight


def test_ssm_chunk_width_invariance(key):
    """The recurrent paged feed makes an SSM row's tokens independent of
    the prefill chunking — different prefill_chunk, same bits."""
    cfg = _cfg("mamba2-370m", sc_backend="moment", sc_nbit=256)
    params = _params(key, cfg)
    req = dict(rid=0, prompt=[5, 9, 17, 3, 8, 2, 30, 11], max_new_tokens=6,
               temperature=0.7)
    outs = [_run_paged(params, cfg, [Request(**req)], slots=1,
                       prefill_chunk=c)[1] for c in (2, 3, 8)]
    assert outs[0] == outs[1] == outs[2]


def test_ssm_state_resets_on_slot_reuse(key):
    """A request admitted into a slot a previous request used must not
    inherit its predecessor's recurrent state: serving B after A in one
    engine equals serving B alone."""
    cfg = _cfg("mamba2-370m", sc_backend="moment", sc_nbit=256)
    params = _params(key, cfg)
    a = dict(rid=0, prompt=[5, 9, 17], max_new_tokens=3)
    b = dict(rid=1, prompt=[40, 2, 8, 30], max_new_tokens=5,
             temperature=0.5)
    _, alone = _run_paged(params, cfg, [Request(**b)], slots=1)
    _, after = _run_paged(params, cfg, [Request(**a)], slots=1,
                          submit_after={1: Request(**b)})
    assert after[1] == alone[1]
