"""Model-component correctness: attention equivalences, SSD oracle,
MoE dispatch, RoPE, decode-vs-forward consistency."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import attention, layers, lm, moe, ssm
from repro.models import params as P

F32 = dict(param_dtype=jnp.float32, act_dtype=jnp.float32)
# the generic testbed of softmax routing and capacity dispatch: llama4's
# block at top-2 with no shared expert
MOE_SMOKE = get_smoke_config("llama4-maverick-400b-a17b").replace(
    top_k=2, n_shared_experts=0)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def _qkv(key, b=2, s=64, h=8, kv=2, hd=16):
    kq, kk, kvk = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, h, hd), jnp.float32)
    k = jax.random.normal(kk, (b, s, kv, hd), jnp.float32)
    v = jax.random.normal(kvk, (b, s, kv, hd), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("chunk", [16, 24, 64, 128])
def test_blockwise_equals_full_attention(key, chunk):
    """Flash-style online softmax is exact for any chunking, including
    chunk sizes that do not divide the sequence."""
    q, k, v = _qkv(key)
    full = attention.full_attention(q, k, v)
    block = attention.blockwise_attention(q, k, v, chunk=chunk)
    np.testing.assert_allclose(np.asarray(block), np.asarray(full),
                               rtol=2e-4, atol=2e-4)


def test_blockwise_noncausal(key):
    q, k, v = _qkv(key, s=32)
    full = attention.full_attention(q, k, v, causal=False)
    block = attention.blockwise_attention(q, k, v, causal=False, chunk=8)
    np.testing.assert_allclose(np.asarray(block), np.asarray(full),
                               rtol=2e-4, atol=2e-4)


def test_decode_attention_matches_full(key):
    """One-token decode over a cache == last row of full causal attention."""
    b, s, h, kv, hd = 2, 16, 8, 2, 16
    q, k, v = _qkv(key, b, s, h, kv, hd)
    full = attention.full_attention(q, k, v)
    lengths = jnp.full((b,), s, jnp.int32)
    dec = attention.decode_attention(q[:, -1:], k, v, lengths)
    np.testing.assert_allclose(np.asarray(dec[:, 0]),
                               np.asarray(full[:, -1]), rtol=2e-4, atol=2e-4)


def test_gqa_grouping_no_replication(key):
    """GQA: kv head j serves q heads [j*g, (j+1)*g) — verify against an
    explicit head-replicated reference."""
    b, s, h, kv, hd = 1, 8, 4, 2, 8
    q, k, v = _qkv(key, b, s, h, kv, hd)
    out = attention.full_attention(q, k, v)
    k_rep = jnp.repeat(k, h // kv, axis=2)
    v_rep = jnp.repeat(v, h // kv, axis=2)
    ref = attention.full_attention(q, k_rep, v_rep)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_rope_preserves_norm_and_relativity(key):
    x = jax.random.normal(key, (1, 16, 2, 32))
    pos = jnp.arange(16)[None]
    y = layers.apply_rope(x, pos, 10000.0)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(y), axis=-1),
        np.linalg.norm(np.asarray(x), axis=-1), rtol=1e-5)
    # relative property: <R(p)q, R(p+d)k> depends only on d
    q = jax.random.normal(key, (1, 1, 1, 32))
    kk = jax.random.normal(jax.random.fold_in(key, 1), (1, 1, 1, 32))
    def dot_at(p, d):
        rq = layers.apply_rope(q, jnp.array([[p]]), 10000.0)
        rk = layers.apply_rope(kk, jnp.array([[p + d]]), 10000.0)
        return float(jnp.sum(rq * rk))
    np.testing.assert_allclose(dot_at(0, 3), dot_at(7, 3), rtol=1e-4)


# ---------------------------------------------------------------------------
# SSD (Mamba2) against a naive recurrence oracle
# ---------------------------------------------------------------------------


def _ssd_naive(x, dt, A, B, C):
    """Direct recurrence: H_t = exp(dt_t A) H_{t-1} + dt_t B_t (x) x_t;
    y_t = C_t . H_t."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    H = np.zeros((b, h, n, p), np.float64)
    ys = np.zeros((b, s, h, p), np.float64)
    xn, dtn = np.asarray(x, np.float64), np.asarray(dt, np.float64)
    An, Bn, Cn = np.asarray(A, np.float64), np.asarray(B, np.float64), \
        np.asarray(C, np.float64)
    for t in range(s):
        decay = np.exp(dtn[:, t] * An)                      # (b,h)
        H = decay[:, :, None, None] * H + np.einsum(
            "bn,bh,bhp->bhnp", Bn[:, t], dtn[:, t], xn[:, t])
        ys[:, t] = np.einsum("bn,bhnp->bhp", Cn[:, t], H)
    return ys


@pytest.mark.parametrize("s,chunk", [(32, 8), (30, 8), (16, 16), (7, 4)])
def test_ssd_chunked_matches_naive_recurrence(key, s, chunk):
    b, h, p, n = 2, 3, 4, 5
    kx, kd, kb, kc = jax.random.split(key, 4)
    x = jax.random.normal(kx, (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(kd, (b, s, h)))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 9), (h,)) * 0.3)
    B = jax.random.normal(kb, (b, s, n), jnp.float32)
    C = jax.random.normal(kc, (b, s, n), jnp.float32)
    y, Hf = ssm.ssd_chunked(x, dt, A, B, C, chunk)
    y_ref = _ssd_naive(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(y), y_ref, rtol=2e-3, atol=2e-3)


def test_ssm_prefill_then_decode_matches_full_pass(key):
    """Running s tokens chunked (prefill) then one more token recurrently
    equals running s+1 tokens in one pass — the SSD duality contract."""
    cfg = get_smoke_config("mamba2-370m").replace(**F32)
    params = P.init_params(key, lm.lm_param_specs(cfg), cfg.param_dtype)
    toks = jax.random.randint(key, (1, 9), 2, cfg.vocab)
    # full pass over all 9 tokens
    logits_all = lm.forward(params, toks, cfg)
    # prefill on 8, decode token 9
    logits_p, cache, lengths = lm.prefill(params, toks[:, :8], cfg,
                                          max_len=16)
    logits_d, _ = lm.decode_step(params, cache, toks[:, 8], lengths, cfg)
    np.testing.assert_allclose(np.asarray(logits_d),
                               np.asarray(logits_all[:, -1]),
                               rtol=2e-3, atol=2e-3)


def test_attention_prefill_then_decode_matches_full_pass(key):
    cfg = get_smoke_config("yi-6b").replace(**F32)
    params = P.init_params(key, lm.lm_param_specs(cfg), cfg.param_dtype)
    toks = jax.random.randint(key, (2, 9), 2, cfg.vocab)
    logits_all = lm.forward(params, toks, cfg)
    logits_p, cache, lengths = lm.prefill(params, toks[:, :8], cfg,
                                          max_len=16)
    np.testing.assert_allclose(np.asarray(logits_p),
                               np.asarray(logits_all[:, 7]),
                               rtol=2e-3, atol=2e-3)
    logits_d, _ = lm.decode_step(params, cache, toks[:, 8], lengths, cfg)
    np.testing.assert_allclose(np.asarray(logits_d),
                               np.asarray(logits_all[:, -1]),
                               rtol=2e-3, atol=2e-3)


def test_hybrid_prefill_then_decode_matches_full_pass(key):
    cfg = get_smoke_config("zamba2-7b").replace(**F32)
    params = P.init_params(key, lm.lm_param_specs(cfg), cfg.param_dtype)
    toks = jax.random.randint(key, (1, 9), 2, cfg.vocab)
    logits_all = lm.forward(params, toks, cfg)
    _, cache, lengths = lm.prefill(params, toks[:, :8], cfg, max_len=16)
    logits_d, _ = lm.decode_step(params, cache, toks[:, 8], lengths, cfg)
    np.testing.assert_allclose(np.asarray(logits_d),
                               np.asarray(logits_all[:, -1]),
                               rtol=5e-3, atol=5e-3)


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------


def test_moe_matches_dense_reference(key):
    """Capacity-buffer dispatch == direct per-token expert evaluation when
    capacity is not exceeded."""
    cfg = MOE_SMOKE.replace(capacity_factor=8.0, **F32)  # no drops
    p = P.init_params(key, moe.moe_specs(cfg), jnp.float32)
    x = jax.random.normal(key, (2, 16, cfg.d_model), jnp.float32)
    out = moe.moe_ffn(x, p, cfg)

    # reference: evaluate every expert densely, weight by renormalized gates
    logits = x.astype(jnp.float32) @ p["router"]
    probs = jax.nn.softmax(logits, -1)
    gates, eidx = jax.lax.top_k(probs, cfg.top_k)
    gates = gates / gates.sum(-1, keepdims=True)
    h = jnp.einsum("bsd,edf->besf", x, p["wi"])
    g_, u_ = jnp.split(h, 2, axis=-1)
    act = jax.nn.silu(g_) * u_
    y_all = jnp.einsum("besf,efd->besd", act, p["wo"])       # (b,e,s,d)
    onehot = jax.nn.one_hot(eidx, cfg.n_experts)             # (b,s,k,e)
    w = (gates[..., None] * onehot).sum(2)                   # (b,s,e)
    ref_out = jnp.einsum("bse,besd->bsd", w, y_all)
    if cfg.n_shared_experts:
        ref_out = ref_out + layers.mlp(x, p["shared"], cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               rtol=2e-3, atol=2e-3)


def test_moe_capacity_drops_tokens(key):
    """With capacity_factor << 1 overflow tokens are dropped (output 0
    contribution) instead of corrupting other slots."""
    cfg = MOE_SMOKE.replace(capacity_factor=0.01, **F32)
    p = P.init_params(key, moe.moe_specs(cfg), jnp.float32)
    x = jax.random.normal(key, (1, 32, cfg.d_model), jnp.float32)
    out = moe.moe_ffn(x, p, cfg)
    assert bool(jnp.all(jnp.isfinite(out)))


def test_moe_load_balancing_loss(key):
    probs = jax.nn.softmax(jax.random.normal(key, (2, 8, 4)), -1)
    _, eidx = jax.lax.top_k(probs, 2)
    lbl = float(moe.load_balancing_loss(probs, eidx, 4))
    assert lbl >= 1.0 - 1e-6     # minimum at perfect balance is 1.0


# ---------------------------------------------------------------------------
# Misc model plumbing
# ---------------------------------------------------------------------------


def test_tied_vs_untied_unembed(key):
    cfg_tied = get_smoke_config("qwen2-0.5b").replace(**F32)
    cfg_untied = cfg_tied.replace(tie_embeddings=False)
    pt = P.init_params(key, lm.lm_param_specs(cfg_tied), jnp.float32)
    pu = P.init_params(key, lm.lm_param_specs(cfg_untied), jnp.float32)
    assert "unembed" not in pt and "unembed" in pu


def test_lm_loss_chunking_matches_direct(key):
    """Sequence-chunked loss == direct full-logits cross-entropy."""
    cfg = get_smoke_config("yi-6b").replace(**F32)
    params = P.init_params(key, lm.lm_param_specs(cfg), jnp.float32)
    toks = jax.random.randint(key, (2, 64), 2, cfg.vocab)
    batch = {"inputs": toks, "labels": jnp.roll(toks, -1, 1)}
    loss = float(lm.lm_loss(params, batch, cfg))
    logits = lm.forward(params, toks, cfg)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    nll = -jnp.take_along_axis(logp, batch["labels"][..., None], -1)
    np.testing.assert_allclose(loss, float(nll.mean()), rtol=1e-5)


def test_sc_mode_flows_through_model(key):
    """paper-sc config routes matmuls through the SC engine: stochastic
    forward (different rng -> different logits), exact mode deterministic."""
    cfg = get_smoke_config("paper-sc").replace(**F32)
    params = P.init_params(key, lm.lm_param_specs(cfg), jnp.float32)
    toks = jax.random.randint(key, (1, 16), 2, cfg.vocab)
    l1 = lm.forward(params, toks, cfg, rng=jax.random.PRNGKey(1))
    l2 = lm.forward(params, toks, cfg, rng=jax.random.PRNGKey(2))
    assert float(jnp.abs(l1 - l2).max()) > 0       # stochastic substrate
    exact = cfg.replace(sc_mode="exact")
    e1 = lm.forward(params, toks, exact, rng=jax.random.PRNGKey(1))
    e2 = lm.forward(params, toks, exact, rng=jax.random.PRNGKey(2))
    np.testing.assert_array_equal(np.asarray(e1), np.asarray(e2))
    # SC logits stay close to exact logits (moment-matched noise)
    assert float(jnp.abs(l1 - e1).mean()) < 1.0
