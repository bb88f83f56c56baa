"""Model zoo on the SC substrate: every assigned arch runs forward AND
decode on a stochastic backend (no silent exact fallbacks — satellite of
the site-abstraction refactor), MoE capacity semantics match a dense
one-hot reference, and ragged expert shapes survive the per-expert
dispatch."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import sc
from repro.configs import ARCH_IDS, get_smoke_config
from repro.models import layers, lm, moe, params as P

B, S = 1, 8
F32 = dict(param_dtype=jnp.float32, act_dtype=jnp.float32)


def _cfg(arch, **kw):
    return get_smoke_config(arch).replace(**F32, **kw)


def _moe_cfg(**kw):
    """Softmax routing with capacity dispatch: llama4's block at top-2
    with no shared expert (override with ``kw``)."""
    return _cfg("llama4-maverick-400b-a17b",
                **{"top_k": 2, "n_shared_experts": 0, **kw})


def _inputs(key, cfg, s=S):
    if cfg.frontend == "embeddings":
        return jax.random.normal(key, (B, s, cfg.d_model), cfg.act_dtype)
    return jax.random.randint(key, (B, s), 0, cfg.vocab)


# ---------------------------------------------------------------------------
# Every family end-to-end on a stochastic backend
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_every_arch_forward_and_decode_on_moment(arch, key):
    """The acceptance bar of the zoo refactor: each config's forward pass
    AND its prefill+decode loop run with sc_backend='moment' — every
    matmul site (router, expert FFNs, SSM projections, frontend
    projection, unembed) must accept the threaded key."""
    cfg = _cfg(arch, sc_backend="moment", sc_nbit=64)
    params = P.init_params(key, lm.lm_param_specs(cfg), cfg.param_dtype)
    inputs = _inputs(jax.random.fold_in(key, 1), cfg)
    rng = jax.random.fold_in(key, 2)
    logits = lm.forward(params, inputs, cfg, rng=rng)
    assert logits.shape == (B, S, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(logits)))
    logits0, cache, lengths = lm.prefill(params, inputs, cfg, max_len=S + 4,
                                         rng=rng)
    assert bool(jnp.all(jnp.isfinite(logits0)))
    tok = jnp.argmax(logits0, -1).astype(jnp.int32)
    logits1, _ = lm.decode_step(params, cache, tok, lengths, cfg,
                                rng=jax.random.fold_in(rng, 1))
    assert logits1.shape == (B, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(logits1)))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_stochastic_backend_without_rng_raises(arch, key):
    """satellite (a): a stochastic substrate with no key is an ERROR
    naming the site, never a silent exact fallback."""
    cfg = _cfg(arch, sc_backend="moment", sc_nbit=64)
    params = P.init_params(key, lm.lm_param_specs(cfg), cfg.param_dtype)
    inputs = _inputs(jax.random.fold_in(key, 1), cfg)
    with pytest.raises(ValueError, match="site"):
        lm.forward(params, inputs, cfg)


def test_dense_and_expert_dense_key_errors_name_site():
    cfg = _cfg("qwen2-0.5b", sc_backend="moment", sc_nbit=64)
    x = jnp.ones((2, 4), jnp.float32)
    w = jnp.ones((4, 3), jnp.float32)
    with pytest.raises(ValueError, match="'mlp_wi'"):
        layers.dense(x, w, cfg, site="mlp_wi")
    xe = jnp.ones((1, 2, 4, 4), jnp.float32)
    we = jnp.ones((2, 4, 3), jnp.float32)
    with pytest.raises(ValueError, match="'moe_wi'"):
        layers.expert_dense(xe, we, cfg, site="moe_wi")
    # exact stays keyless
    assert layers.dense(x, w, cfg.replace(sc_backend="exact")).shape == (2, 3)


# ---------------------------------------------------------------------------
# MoE capacity semantics vs a dense one-hot reference (satellite c)
# ---------------------------------------------------------------------------


def _moe_onehot_reference(x, p, cfg, cap):
    """GShard-style dense reference: renormalized top-k gates, tokens
    beyond an expert's capacity (in stable flat arrival order) DROP —
    their gate weight contributes nothing and is NOT re-renormalized."""
    b, s, d = x.shape
    k = cfg.top_k
    logits = np.asarray(x, np.float64) @ np.asarray(p["router"], np.float64)
    probs = jax.nn.softmax(jnp.asarray(logits), axis=-1)
    gates, eidx = jax.lax.top_k(probs, k)
    gates = np.asarray(gates / jnp.maximum(gates.sum(-1, keepdims=True),
                                           1e-9))
    eidx = np.asarray(eidx)
    wi, wo = np.asarray(p["wi"]), np.asarray(p["wo"])
    out = np.zeros((b, s, d), np.float64)
    dropped = 0
    for r in range(b):
        seen = {}
        for flat in range(s * k):
            t, j = divmod(flat, k)
            e = int(eidx[r, t, j])
            rank = seen.get(e, 0)
            seen[e] = rank + 1
            if rank >= cap:
                dropped += 1
                continue
            h = np.asarray(x[r, t], np.float64) @ wi[e]
            gate_h, up = np.split(h, 2)
            act = np.asarray(jax.nn.silu(jnp.asarray(gate_h))) * up
            out[r, t] += gates[r, t, j] * (act @ wo[e])
    return out, dropped


def test_moe_capacity_overflow_matches_onehot_reference(key):
    """Overflowing experts drop exactly the late arrivals the one-hot
    formulation drops, with renormalized gates — and drops DO occur."""
    cfg = _moe_cfg(n_experts=2, top_k=1, capacity_factor=0.25)
    s = 32
    cap = moe.capacity(cfg, s)
    assert s * cfg.top_k > cap * cfg.n_experts / 2  # overflow is possible
    p = P.init_params(key, moe.moe_specs(cfg), jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, s, cfg.d_model),
                          jnp.float32)
    got = moe.moe_ffn(x, p, cfg)
    ref, dropped = _moe_onehot_reference(x, p, cfg, cap)
    assert dropped > 0, "test inputs never bound capacity — not a test"
    np.testing.assert_allclose(np.asarray(got), ref, rtol=2e-4, atol=2e-4)


def test_moe_no_overflow_matches_dense_mixture(key):
    """With capacity slack the MoE output equals the unconstrained
    mixture (every token reaches every chosen expert)."""
    cfg = _moe_cfg()
    s = 4                                     # s*k=8 <= cap=8 per expert
    p = P.init_params(key, moe.moe_specs(cfg), jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, s, cfg.d_model),
                          jnp.float32)
    got = moe.moe_ffn(x, p, cfg)
    ref, dropped = _moe_onehot_reference(x, p, cfg, moe.capacity(cfg, s))
    assert dropped == 0
    np.testing.assert_allclose(np.asarray(got), ref, rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# Ragged expert shapes through the per-expert dispatch (satellite c)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["moment", "pallas_fused"])
def test_expert_dense_ragged_shapes_match_per_expert_rows(backend, key):
    """expert_dense's scan must hand each (cap, d)x(d, f) expert problem
    to the registry exactly as a per-expert sc_dot_rows call would —
    including RAGGED shapes (non-power-of-two, non-multiple-of-8 f) that
    stress the kernel autotuner's shape handling."""
    b, e, cap, d, f = 1, 3, 4, 24, 40
    cfg = _cfg("qwen2-0.5b", sc_backend=backend, sc_nbit=64)
    x = jax.random.normal(key, (b, e, cap, d), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (e, d, f), jnp.float32)
    keys = jax.random.split(jax.random.fold_in(key, 2),
                            b * e * cap).reshape(b, e, cap, 2)
    got = layers.expert_dense(x, w, cfg, keys, site="moe_wi")
    assert got.shape == (b, e, cap, f)
    sc_cfg = sc.ScConfig(backend=sc.fast_backend(backend, cfg.sc_nbit),
                         nbit=cfg.sc_nbit)
    eidx = jnp.broadcast_to(jnp.arange(e)[None, :, None], (b, e, cap))
    folded = layers.site_key(keys, "moe_wi", eidx)
    for ei in range(e):
        ref = sc.sc_dot_rows(folded[0, ei], x[0, ei], w[ei], sc_cfg)
        # same keys => same draws; tolerance only covers XLA fusion-order
        # float drift between the scanned and direct dispatch
        np.testing.assert_allclose(np.asarray(got[0, ei]), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)


def test_moe_per_token_keys_follow_dispatch(key):
    """A (b, s, 2) per-token key buffer rides the token->slot scatter:
    the same token's expert matmuls draw identical bits whatever its
    batch neighbours are (the paged engine's invariance contract)."""
    _check_per_token_keys(_moe_cfg(sc_backend="moment", sc_nbit=64), key)


def test_moe_per_token_keys_follow_dropless_dispatch(key):
    """The same contract for a dropless config (sigmoid router with its
    bias, shared experts) on the SC substrate, whose grouped path is the
    capacity dispatch sized so that nothing drops."""
    _check_per_token_keys(
        _cfg("moonlight-16b-a3b", sc_backend="moment", sc_nbit=64), key)


def _check_per_token_keys(cfg, key):
    s = 3
    p = P.init_params(key, moe.moe_specs(cfg), jnp.float32)
    xa = jax.random.normal(jax.random.fold_in(key, 1), (1, s, cfg.d_model),
                           jnp.float32)
    xb = jax.random.normal(jax.random.fold_in(key, 2), (1, s, cfg.d_model),
                           jnp.float32)
    ka = jax.random.split(jax.random.fold_in(key, 3), s)[None]  # (1, s, 2)
    kb = jax.random.split(jax.random.fold_in(key, 4), s)[None]
    solo = moe.moe_ffn(xa, p, cfg, ka)
    both = moe.moe_ffn(jnp.concatenate([xa, xb]), p, cfg,
                       jnp.concatenate([ka, kb]))
    np.testing.assert_array_equal(np.asarray(solo[0]), np.asarray(both[0]))
