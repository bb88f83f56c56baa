"""Latent attention (MLA) and dropless sigmoid-routed experts —
Moonlight-16B-A3B's block at smoke widths, float32, seeded random
weights — against the plain reference ``models/reference_mla_moe.py``.

Tolerances: program and reference both compute in float32 (the
reference at HIGHEST), so they differ by summation order alone: the
absorbed form reassociates q·(W_UK·c) as (q·W_UK)·c, the paged lookups
use an online softmax, and the grouped expert matmul sums in another
order.  That is ~1e-6 of the largest logit; the bound is 1e-4 of it,
and each test shows a bfloat16 reference departs by more than that.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.kernels import paged_attention as pa
from repro.models import attention, layers, lm, moe
from repro.models import params as P
from repro.models import reference_mla_moe as ref
from repro.serve import Request, ServeOptions, build_engine

F32 = dict(param_dtype=jnp.float32, act_dtype=jnp.float32)
REL = 1e-4          # of the largest magnitude: float32 summation order


def _cfg(**kw):
    return get_smoke_config("moonlight-16b-a3b").replace(**F32, **kw)


def _params(cfg, seed=0):
    return P.init_params(jax.random.PRNGKey(seed), lm.lm_param_specs(cfg),
                         jnp.float32)


def _noisy(cfg, params, seed=1):
    """Gains and the router bias drawn away from 1 and 0, so that a path
    that drops one shows."""
    key = jax.random.PRNGKey(seed)
    specs = jax.tree.leaves(lm.lm_param_specs(cfg),
                            is_leaf=lambda s: isinstance(s, P.ParamSpec))
    leaves, tree = jax.tree.flatten(params)
    return jax.tree.unflatten(tree, [
        v + 0.05 * jax.random.normal(jax.random.fold_in(key, i), v.shape)
        if s.init in ("ones", "zeros") else v
        for i, (s, v) in enumerate(zip(specs, leaves))])


def _close(got, want, what):
    err = float(np.abs(np.asarray(got) - np.asarray(want)).max())
    scale = float(np.abs(np.asarray(want)).max())
    assert err < REL * scale, f"{what}: {err} vs {REL} x {scale}"
    return scale


def _departs(low, want, scale):
    err = float(np.abs(np.asarray(low) - np.asarray(want)).max())
    assert err > REL * scale, "a bfloat16 reference would pass the bound"


@pytest.mark.parametrize("mode", ["fused", "unfused"])
def test_absorbed_paged_attention_equals_naive_reference(mode):
    """The absorbed form over the latent pool (prefill in chunks of 3,
    then single-position decodes) equals the reference's naive MLA, whose
    K and V are decompressed per head."""
    cfg = _cfg(paged_attn=mode)
    a = _noisy(cfg, _params(cfg))["blocks"]["attn"]
    a = jax.tree.map(lambda v: v[0], a)
    c = ref.config_dict(cfg)
    b, t, bs = 2, 10, 4
    x = jax.random.normal(jax.random.PRNGKey(2), (b, t, cfg.d_model))
    want = jax.vmap(lambda h: ref._attention(h, a, c, None))(x)
    pool = lm.init_paged_cache(cfg.replace(n_layers=1, first_k_dense=0),
                               8, bs)["kv"][0]
    assert pool.shape[1:] == (1, bs, attention.latent_width(cfg))
    table = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    outs, pos = [], 0
    for width in (3, 3, 1, 1, 1, 1):
        xs = x[:, pos:pos + width]
        positions = pos + jnp.broadcast_to(jnp.arange(width), (b, width))
        out, pages = attention.paged_attention_block(
            xs, a, cfg, positions, None, {"kv": pool}, table,
            jnp.full((b,), pos, jnp.int32), jnp.full((b,), width, jnp.int32))
        pool = pages["kv"]
        outs.append(out)
        pos += width
    got = jnp.concatenate(outs, axis=1)
    scale = _close(got, want, mode)
    low = jax.vmap(lambda h: ref._attention(h, a, c, "bf16"))(x)
    _departs(low, want, scale)


@pytest.mark.parametrize("mode", ["fused", "unfused"])
def test_engine_chunked_prefill_then_decode_matches_reference(mode):
    """Through build_engine and PagedServingEngine: prompts prefilled in
    chunks of 3 beside a second request, then decoded; the logits every
    token was sampled from equal the reference's full forward at that
    position.  The pool holds one latent row per position per layer."""
    cfg = _cfg(paged_attn=mode)
    params = _noisy(cfg, _params(cfg))
    eng = build_engine(params, cfg, ServeOptions(
        paged=True, slots=2, max_len=32, block_size=4, prefill_chunk=3,
        eos_id=-1))
    width = cfg.kv_lora_rank + cfg.qk_rope_head_dim
    assert jax.tree.map(lambda v: v.shape, eng.pages) == {
        "kv": (2, eng.kv.cfg.num_blocks, 1, 4, width),
        "dense": {"kv": (1, eng.kv.cfg.num_blocks, 1, 4, width)}}
    seen = {}
    sample, on_token = eng._sample_fn, eng.scheduler.on_token

    def recorder(keys, logits, temps):
        seen["logits"] = logits
        return sample(keys, logits, temps)

    def token(slot, seq, tok):
        seen.setdefault(seq.req.rid, []).append(
            np.asarray(seen["logits"][slot]))
        on_token(slot, seq, tok)

    eng._sample_fn, eng.scheduler.on_token = recorder, token
    prompts = {0: [5, 9, 17, 3, 8, 2, 30], 1: [40, 2, 8, 30, 7]}
    for rid, p in prompts.items():
        eng.submit(Request(rid=rid, prompt=list(p), max_new_tokens=4))
    done = {r.rid: r for r in eng.run_until_drained()}
    c = ref.config_dict(cfg)
    fed = sum(len(p) + 4 - 1 for p in prompts.values())
    assert eng.metrics.value("serve_moe_routed_rows_total") == (
        fed * cfg.top_k * lm.n_backbone_layers(cfg))
    assert eng.metrics.value("serve_kv_bytes_per_position") == (
        cfg.n_layers * width * 4)
    for rid, p in prompts.items():
        toks = jnp.asarray(p + done[rid].generated, jnp.int32)
        at = jnp.arange(len(p) - 1, len(toks) - 1)
        want = ref.logits(params, ref.hidden(params, toks, c)[at], c)
        got = np.stack(seen[rid])
        scale = _close(got, want, f"{mode} request {rid}")
        low = ref.logits(params, ref.hidden(params, toks, c, "bf16")[at], c,
                         "bf16")
        _departs(low, want, scale)


BS, NB = 16, 12     # 8 pages a span: 12 is not a multiple of it
FILLS = {"zero": lambda sc, nb: 0, "inside": lambda sc, nb: BS + 5,
         "boundary": lambda sc, nb: 2 * BS, "full": lambda sc, nb: nb * BS - sc}


@pytest.mark.parametrize("nb", [8, NB])
@pytest.mark.parametrize("sc", [1, 16])
@pytest.mark.parametrize("fill", sorted(FILLS))
def test_mla_walk_never_reads_past_fill(fill, sc, nb):
    """Table entries past each row's last live page name NaN pages, the
    last span's spare slots among them: the kernel still equals gather +
    chunk_decode_attention over the clean pool, so it read none of them
    (a masked NaN value would still enter p·v)."""
    rng = np.random.default_rng(sc * nb)
    b, h, r, rope = 2, 2, 16, 8
    dk = r + rope
    pool = jnp.asarray(rng.normal(size=(1 + b * nb, 1, BS, dk)), jnp.float32)
    bt = jnp.asarray(1 + rng.permutation(b * nb).reshape(b, nb), jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, sc, h, dk)), jnp.float32)
    lengths = jnp.asarray([FILLS[fill](sc, nb), 37], jnp.int32)
    assert pa.span_pages(BS, dk, 4, 8 * (-(-h * sc // 8)), nb) == 8
    dead = np.asarray(bt)[np.arange(nb)[None, :] > np.minimum(
        (np.asarray(lengths) + sc - 1) // BS, nb - 1)[:, None]]
    assert dead.size                    # row 1 leaves pages past its fill
    poisoned = pool.at[dead].set(jnp.nan)
    kc = attention.paged_gather(pool, bt)
    want = attention.chunk_decode_attention(q, kc, kc[..., :r], lengths,
                                            0.3)
    got = pa.mla_paged_attention(q, poisoned, bt, lengths, dv=r, scale=0.3)
    assert np.isfinite(np.asarray(got)).all()
    _close(got, want, f"fill {fill}")


@pytest.mark.parametrize("block_size,nb,width,itemsize,want", [
    (16, 128, 576, 2, 8),       # Moonlight: 128 positions a step
    (8, 256, 576, 2, 16),
    (32, 64, 576, 2, 4),
    (128, 16, 576, 2, 1),
    (256, 8, 576, 2, 1),        # a page wider than the tile: one a step
    (16, 3, 576, 2, 3),         # no more than the table holds
    (16, 128, 8192, 4, 2),      # halved to the VMEM budget
])
def test_span_pages_rule(block_size, nb, width, itemsize, want):
    assert pa.span_pages(block_size, width, itemsize, 256, nb) == want


def test_pages_walked_counts_through_last_live_page():
    # block 4, table 8: fills 0, 5 and 30 at width 3 end at positions 2,
    # 7 and 32 — pages 1, 2 and (past the table) all 8
    assert pa.pages_walked([0, 5, 30], 3, 4, 8) == 1 + 2 + 8
    assert pa.pages_walked([4, 3], 1, 4, 8) == 2 + 1


def test_engine_counts_mla_pages_walked_and_skipped():
    """A prompt of 5 in chunks of 3 beside an empty slot, then one
    decode; block 4, table 8, 3 latent layers.  Ticks (lengths, width):
    ([0, 0], 3) reads 1 + 1 pages, ([3, 0], 3) 2 + 1, ([5, 0], 1) 2 + 1."""
    cfg = _cfg(paged_attn="fused")
    eng = build_engine(_params(cfg), cfg, ServeOptions(
        paged=True, slots=2, max_len=32, block_size=4, prefill_chunk=3,
        eos_id=-1))
    eng.submit(Request(rid=0, prompt=[5, 9, 17, 3, 8], max_new_tokens=2))
    eng.run_until_drained()
    assert eng.ticks == 3 and cfg.n_layers == 3
    assert eng.metrics.value("serve_mla_pages_walked_total") == 8 * 3
    assert eng.metrics.value("serve_mla_pages_skipped_total") == (
        3 * 2 * 8 - 8) * 3
    unfused = _cfg(paged_attn="unfused")
    eng = build_engine(_params(unfused), unfused, ServeOptions(
        paged=True, slots=1, max_len=16, block_size=4, prefill_chunk=4))
    assert eng.metrics.value("serve_mla_pages_walked_total") is None


def test_forward_equals_reference():
    """lm.forward (naive MLA, grouped dropless experts) == the reference."""
    cfg = _cfg()
    params = _noisy(cfg, _params(cfg))
    toks = jax.random.randint(jax.random.PRNGKey(3), (12,), 3, cfg.vocab)
    got = lm.forward(params, toks[None], cfg)[0]
    c = ref.config_dict(cfg)
    want = ref.logits(params, ref.hidden(params, toks, c), c)
    _close(got, want, "forward")


def test_dropless_equals_dense_mixture_when_all_tokens_pick_one_expert():
    """The router is forced to send every token to expert 3 alone: the
    grouped dispatch still computes them all (the dense mixture), where
    capacity dispatch at its factor drops most of them."""
    cfg = _cfg(top_k=1)
    p = jax.tree.map(lambda v: v[0], _params(cfg)["blocks"]["ffn"])
    p = dict(p, router=jnp.zeros_like(p["router"]),
             router_bias=jnp.zeros_like(p["router_bias"]).at[3].set(1.0))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 16, cfg.d_model))
    got = moe.moe_ffn(x, p, cfg)
    c = ref.config_dict(cfg)
    want = jax.vmap(lambda h: ref._experts(h, p, c, None))(x)
    _close(got, want, "dropless")
    capped = moe.moe_ffn(x, p, cfg.replace(moe_dropless=False))
    assert float(np.abs(np.asarray(capped - want)).max()) > 0.1


def test_router_bias_moves_selection_not_gates():
    cfg = _cfg()
    p = jax.tree.map(lambda v: v[0], _params(cfg)["blocks"]["ffn"])
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 8, cfg.d_model))
    g0, e0 = moe.route(x, dict(p, router_bias=jnp.zeros(cfg.n_experts)), cfg)
    # a bias on the experts chosen least steers every token to them
    least = jnp.argsort(jnp.bincount(e0.reshape(-1),
                                     length=cfg.n_experts))[:cfg.top_k]
    bias = jnp.zeros(cfg.n_experts).at[least].set(10.0)
    g1, e1 = moe.route(x, dict(p, router_bias=bias), cfg)
    assert set(np.asarray(e1).reshape(-1)) == set(np.asarray(least))
    scores = jax.nn.sigmoid(x @ p["router"])
    s1 = jnp.take_along_axis(scores, e1, -1)
    want = s1 / s1.sum(-1, keepdims=True) * cfg.routed_scaling_factor
    np.testing.assert_allclose(np.asarray(g1), np.asarray(want), rtol=1e-6)
    assert float(jnp.abs(g1 - g0).max()) > 1e-3


def test_router_gates_are_normalized_then_scaled():
    cfg = _cfg()
    p = jax.tree.map(lambda v: v[0], _params(cfg)["blocks"]["ffn"])
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 5, cfg.d_model))
    gates, chosen = moe.route(x, p, cfg)
    np.testing.assert_allclose(np.asarray(gates.sum(-1)),
                               cfg.routed_scaling_factor, rtol=1e-6)
    raw = jnp.take_along_axis(jax.nn.sigmoid(x @ p["router"]), chosen, -1)
    assert float(jnp.abs(raw.sum(-1) - 1.0).min()) > 1e-3
    np.testing.assert_allclose(
        np.asarray(gates),
        np.asarray(raw / raw.sum(-1, keepdims=True)
                   * cfg.routed_scaling_factor), rtol=1e-6)


def test_shared_experts_are_added_once():
    cfg = _cfg()
    p = jax.tree.map(lambda v: v[0], _params(cfg)["blocks"]["ffn"])
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 6, cfg.d_model))
    with_shared = moe.moe_ffn(x, p, cfg)
    routed = moe.moe_ffn(x, {k: v for k, v in p.items() if k != "shared"},
                         cfg.replace(n_shared_experts=0))
    assert p["shared"]["wo"].shape[0] == cfg.n_shared_experts * cfg.moe_d_ff
    np.testing.assert_allclose(
        np.asarray(with_shared - routed),
        np.asarray(layers.mlp(x, p["shared"], cfg)), rtol=1e-5, atol=1e-6)


def test_fused_sc_attention_has_no_mla_form():
    cfg = _cfg(paged_attn="fused_sc", sc_backend="moment", sc_nbit=64)
    with pytest.raises(ValueError, match="MLA"):
        build_engine(_params(cfg), cfg, ServeOptions(
            paged=True, slots=1, max_len=16, block_size=4, prefill_chunk=4))
