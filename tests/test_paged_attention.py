"""Fused paged-attention kernel: fused == unfused to float tolerance
(greedy-identical end to end), SC-sampled QK^T pinned to (request,
position) across batch/chunk/block-size/eviction permutations, the
`attn` autotune kind, and the chunk_decode_attention edge cases the
masking predicate must honour."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.kernels import paged_attention as pa
from repro.models import attention, lm, params as P
from repro.sc import autotune, ctr_rng
from repro.serve import PagedServeConfig, PagedServingEngine, Request

F32 = dict(param_dtype=jnp.float32, act_dtype=jnp.float32)


def _cfg(**kw):
    return get_smoke_config("qwen2-0.5b").replace(**F32, **kw)


def _rand_paged(rng, *, b, sc, h, kvh, hd, bs, nb):
    """Random pool + shuffled block tables (page ids deliberately not
    contiguous, so in-kernel gather is actually exercised)."""
    P_ = b * nb + 2
    k_pages = jnp.asarray(rng.normal(size=(P_, kvh, bs, hd)), jnp.float32)
    v_pages = jnp.asarray(rng.normal(size=(P_, kvh, bs, hd)), jnp.float32)
    bt = jnp.asarray(rng.permutation(P_)[:b * nb].reshape(b, nb), jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, sc, h, hd)), jnp.float32)
    return q, k_pages, v_pages, bt


def _unfused(q, k_pages, v_pages, bt, lengths):
    return attention.chunk_decode_attention(
        q, attention.paged_gather(k_pages, bt),
        attention.paged_gather(v_pages, bt), lengths)


def _token_keys(key, b, sc):
    """(b, sc) independent raw token keys, like decode_paged derives."""
    rk = jax.vmap(jax.random.split, in_axes=(0, None))(
        jax.random.split(key, b), sc)
    return jax.vmap(jax.vmap(ctr_rng.raw_key))(rk)


# ---------------------------------------------------------------------------
# Deterministic fused kernel == unfused reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("block_size,sc", [(4, 1), (4, 5), (8, 1), (8, 3)])
def test_fused_matches_unfused(block_size, sc):
    """Across >= 2 block sizes, width-1 decode AND chunked prefill: the
    fused kernel reproduces gather + chunk_decode_attention, including
    length-0 rows and fills landing exactly on a block boundary."""
    rng = np.random.default_rng(0)
    b, h, kvh, hd, nb = 4, 4, 2, 8, 4
    q, kp, vp, bt = _rand_paged(rng, b=b, sc=sc, h=h, kvh=kvh, hd=hd,
                                bs=block_size, nb=nb)
    maxlen = block_size * nb - sc
    lengths = jnp.asarray(
        [0, block_size, min(2 * block_size, maxlen), maxlen], jnp.int32)
    ref = _unfused(q, kp, vp, bt, lengths)
    got = pa.paged_attention_fused(q, kp, vp, bt, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_fused_row_padding_is_inert():
    """block_q larger than the row count pads query rows; padding must
    not leak into real rows."""
    rng = np.random.default_rng(1)
    q, kp, vp, bt = _rand_paged(rng, b=2, sc=1, h=2, kvh=1, hd=8,
                                bs=4, nb=3)
    lengths = jnp.asarray([3, 7], jnp.int32)
    ref = pa.paged_attention_fused(q, kp, vp, bt, lengths, block_q=2)
    got = pa.paged_attention_fused(q, kp, vp, bt, lengths, block_q=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


def test_fused_grouped_heads_match_per_head_reference():
    """GQA row layout: each query head must read ITS kv head's pages."""
    rng = np.random.default_rng(2)
    q, kp, vp, bt = _rand_paged(rng, b=1, sc=2, h=6, kvh=3, hd=8,
                                bs=4, nb=3)
    lengths = jnp.asarray([5], jnp.int32)
    ref = _unfused(q, kp, vp, bt, lengths)
    got = pa.paged_attention_fused(q, kp, vp, bt, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# chunk_decode_attention edge behaviour (the PR-4 masking off-by-one class)
# ---------------------------------------------------------------------------


def test_chunk_decode_length_zero_is_causal_prefill():
    """lengths == 0 with the whole sequence as one chunk IS causal
    attention: predicate t <= 0 + i."""
    rng = np.random.default_rng(3)
    b, t, h, kvh, hd = 2, 6, 4, 2, 8
    q = jnp.asarray(rng.normal(size=(b, t, h, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, t, kvh, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, t, kvh, hd)), jnp.float32)
    got = attention.chunk_decode_attention(
        q, k, v, jnp.zeros((b,), jnp.int32))
    ref = attention.full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_chunk_decode_single_token_cache():
    """A one-slot cache at length 0: the only key is the query's own
    position, so the output is exactly that V row (softmax over one)."""
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.normal(size=(1, 1, 2, 4)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 1, 1, 4)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 1, 1, 4)), jnp.float32)
    out = attention.chunk_decode_attention(
        q, k, v, jnp.zeros((1,), jnp.int32))
    ref = jnp.broadcast_to(v[:, :, 0][:, :, None], q.shape)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


def test_chunk_decode_mask_boundary_is_inclusive():
    """Row r token i attends positions up to AND INCLUDING lengths[r]+i,
    and nothing past it — checked against a brute-force softmax at fills
    sitting exactly on block boundaries (the off-by-one class)."""
    rng = np.random.default_rng(5)
    b, sc, L, h, hd = 3, 2, 16, 2, 4
    q = jnp.asarray(rng.normal(size=(b, sc, h, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, L, h, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, L, h, hd)), jnp.float32)
    lengths = jnp.asarray([0, 4, 8], jnp.int32)   # block-size-4 boundaries
    got = np.asarray(attention.chunk_decode_attention(q, k, v, lengths))
    qn, kn, vn = (np.asarray(x, np.float64) for x in (q, k, v))
    for r in range(b):
        for i in range(sc):
            last = int(lengths[r]) + i            # inclusive
            for hh in range(h):
                lg = kn[r, : last + 1, hh] @ qn[r, i, hh] / np.sqrt(hd)
                w = np.exp(lg - lg.max())
                w /= w.sum()
                ref = w @ vn[r, : last + 1, hh]
                np.testing.assert_allclose(got[r, i, hh], ref,
                                           rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# SC-sampled QK^T: pinned-counter reproducibility
# ---------------------------------------------------------------------------


def test_sc_kernel_matches_host_twin_bitwise():
    """The kernel's pre-mask SC logits for one (row, head) equal the
    host-side twin bit-for-bit — the anchor for every invariance."""
    rng = np.random.default_rng(6)
    b, sc, h, kvh, hd, bs, nb = 2, 3, 4, 2, 8, 4, 4
    q, kp, vp, bt = _rand_paged(rng, b=b, sc=sc, h=h, kvh=kvh, hd=hd,
                                bs=bs, nb=nb)
    keys = _token_keys(jax.random.PRNGKey(7), b, sc)
    keys4 = pa.split_keys4(keys)
    r, i0, head = 1, 2, 3
    kh = head // (h // kvh)
    gathered = attention.paged_gather(kp, bt)
    host = pa.sc_qk_logits_host(
        keys[r, i0], q[r, i0, head], gathered[r, :, kh],
        np.arange(nb * bs), head, h, nbit=128)
    parts = []
    for j in range(nb):
        page = int(bt[r, j])
        t_abs = j * bs + jnp.arange(bs, dtype=jnp.int32)[None]
        parts.append(np.asarray(pa._sc_logits(
            q[r, i0, head][None], kp[page, kh], keys4[r, i0][None],
            t_abs, jnp.full((1, 1), head, jnp.int32), n_heads=h,
            nbit=128, levels=1 << 10, quantize=True, lane=4)[0]))
    assert np.array_equal(np.concatenate(parts), np.asarray(host))


@pytest.mark.parametrize("operand_bits", [4, 10])
def test_sc_logits_live_ladder_equals_full_ladder(monkeypatch,
                                                  operand_bits):
    """``_sc_logits`` draws only the slices its operand grid leaves live
    (``sc_fused.ladder_start``) and still equals the full 16-slice
    ladder bit for bit — the host twin shares its body, so it cannot be
    this witness."""
    rng = np.random.default_rng(9)
    hd, bs, h = 8, 4, 4
    q = jnp.asarray(rng.normal(size=(2, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(bs, hd)), jnp.float32)
    keys4 = pa.split_keys4(_token_keys(jax.random.PRNGKey(10), 1, 2)[0])
    args = (q, k, keys4, jnp.arange(bs, dtype=jnp.int32)[None],
            jnp.asarray([[1], [3]], jnp.int32))
    kw = dict(n_heads=h, nbit=128, levels=1 << operand_bits,
              quantize=True, lane=1)
    live = np.asarray(pa._sc_logits.__wrapped__(*args, **kw))
    monkeypatch.setattr(pa, "ladder_start", lambda levels, quantize: 0)
    full = np.asarray(pa._sc_logits.__wrapped__(*args, **kw))
    assert np.array_equal(live, full)


def test_sc_tiling_never_changes_bits():
    rng = np.random.default_rng(7)
    b, sc = 2, 3
    q, kp, vp, bt = _rand_paged(rng, b=b, sc=sc, h=4, kvh=2, hd=8,
                                bs=4, nb=4)
    keys = _token_keys(jax.random.PRNGKey(8), b, sc)
    L = jnp.asarray([5, 9], jnp.int32)
    a = pa.paged_attention_fused_sc(keys, q, kp, vp, bt, L, nbit=128)
    c = pa.paged_attention_fused_sc(keys, q, kp, vp, bt, L, nbit=128,
                                    block_q=4, lane_words=1)
    assert np.array_equal(np.asarray(a), np.asarray(c))


def test_sc_batch_permutation_invariance():
    """Reordering the batch permutes the outputs bitwise — no token's
    draw depends on its neighbours."""
    rng = np.random.default_rng(8)
    b, sc = 3, 2
    q, kp, vp, bt = _rand_paged(rng, b=b, sc=sc, h=4, kvh=2, hd=8,
                                bs=4, nb=4)
    keys = _token_keys(jax.random.PRNGKey(9), b, sc)
    L = jnp.asarray([0, 5, 9], jnp.int32)
    out = pa.paged_attention_fused_sc(keys, q, kp, vp, bt, L, nbit=128)
    perm = jnp.asarray([2, 0, 1])
    out_p = pa.paged_attention_fused_sc(
        keys[perm], q[perm], kp, vp, bt[perm], L[perm], nbit=128)
    assert np.array_equal(np.asarray(out_p), np.asarray(out)[perm])


def test_sc_chunk_width_invariance():
    """A token's SC attention output is identical whether it decodes in
    a width-2 chunk or as two width-1 ticks (keys ride the token, the
    counter rides the kv position)."""
    rng = np.random.default_rng(9)
    b, sc, h, kvh, hd, bs, nb = 1, 2, 4, 2, 8, 4, 4
    q, kp, vp, bt = _rand_paged(rng, b=b, sc=sc, h=h, kvh=kvh, hd=hd,
                                bs=bs, nb=nb)
    keys = _token_keys(jax.random.PRNGKey(10), b, sc)
    L = jnp.asarray([6], jnp.int32)
    chunk = pa.paged_attention_fused_sc(keys, q, kp, vp, bt, L, nbit=128)
    solo0 = pa.paged_attention_fused_sc(
        keys[:, :1], q[:, :1], kp, vp, bt, L, nbit=128)
    solo1 = pa.paged_attention_fused_sc(
        keys[:, 1:], q[:, 1:], kp, vp, bt, L + 1, nbit=128)
    assert np.array_equal(np.asarray(chunk[:, 0]), np.asarray(solo0[:, 0]))
    assert np.array_equal(np.asarray(chunk[:, 1]), np.asarray(solo1[:, 0]))


def test_sc_block_size_invariance():
    """The same logical cache stored under block sizes 4 and 8 yields
    the same attention (logits are bitwise-pinned; the online-softmax
    accumulation order differs, so outputs compare to float tolerance
    and the argmax — the token the engine would emit — must agree)."""
    rng = np.random.default_rng(10)
    b, sc, h, kvh, hd = 1, 1, 4, 2, 8
    T = 16
    kc = jnp.asarray(rng.normal(size=(b, T, kvh, hd)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(b, T, kvh, hd)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(b, sc, h, hd)), jnp.float32)
    keys = _token_keys(jax.random.PRNGKey(11), b, sc)
    L = jnp.asarray([11], jnp.int32)
    outs = []
    for bs in (4, 8):
        nb = T // bs
        kp = kc.reshape(nb, bs, kvh, hd).transpose(0, 2, 1, 3)
        vp = vc.reshape(nb, bs, kvh, hd).transpose(0, 2, 1, 3)
        # identity table but through a shuffled pool
        perm = np.asarray([2, 0, 3, 1][:nb])
        inv = np.argsort(perm)
        bt = jnp.asarray(inv[None], jnp.int32)
        outs.append(np.asarray(pa.paged_attention_fused_sc(
            keys, q, kp[perm], vp[perm], bt, L, nbit=128)))
    np.testing.assert_allclose(outs[0], outs[1], rtol=1e-5, atol=1e-5)
    assert np.argmax(outs[0]) == np.argmax(outs[1])


# ---------------------------------------------------------------------------
# Autotune `attn` kernel kind
# ---------------------------------------------------------------------------


def test_attn_cache_key_is_disjoint_from_matmul_keys():
    ak = autotune.attn_cache_key(8, 16, 64, 1024)
    assert ak.startswith("attn|")
    assert ak != autotune.cache_key(8, 16, 64, 1024)
    assert autotune.attn_cache_key(8, 16, 64, 0) != ak


def test_attn_tile_cache_hit_miss_and_malformed():
    stored = autotune.AttnTile(block_q=4, lane_words=8)
    entry = dict(stored.kwargs(), wall_us=1.0)
    cache = {autotune.attn_cache_key(8, 16, 64, 1024): entry}
    assert autotune.get_attn_tile(8, 16, 64, 1024, cache=cache) == stored
    # miss -> heuristic
    assert autotune.get_attn_tile(8, 16, 64, 512, cache=cache) == \
        autotune.heuristic_attn_tile(8, 16, 64, 512)
    # malformed / non-positive entries -> heuristic, not a crash
    bad = {autotune.attn_cache_key(8, 16, 64, 1024): {"block_q": "huge"}}
    assert autotune.get_attn_tile(8, 16, 64, 1024, cache=bad) == \
        autotune.heuristic_attn_tile(8, 16, 64, 1024)
    zero = {autotune.attn_cache_key(8, 16, 64, 1024):
            {"block_q": 0, "lane_words": 16}}
    assert autotune.get_attn_tile(8, 16, 64, 1024, cache=zero) == \
        autotune.heuristic_attn_tile(8, 16, 64, 1024)


def test_attn_heuristic_respects_vmem_cap_and_det_mode():
    det = autotune.heuristic_attn_tile(8, 16, 64, 0)
    assert det.lane_words == 1          # deterministic: no rng words
    big = autotune.heuristic_attn_tile(64, 64, 128, 4096)
    assert (big.block_q * 64 * 128 * big.lane_words
            <= autotune._MAX_TILE_WORDS)
    assert big.block_q >= 1 and big.lane_words >= 1
    for t in autotune.candidate_attn_tiles(8, 16, 64, 1024):
        assert t.block_q * 16 * 64 * t.lane_words <= \
            autotune._MAX_TILE_WORDS


def test_attn_cache_roundtrips_through_disk(tmp_path):
    path = str(tmp_path / "cache.json")
    stored = autotune.AttnTile(block_q=16, lane_words=4)
    autotune.save_cache(
        {autotune.attn_cache_key(6, 4, 16, 128): stored.kwargs()}, path)
    old = os.environ.get("REPRO_SC_AUTOTUNE_CACHE")
    os.environ["REPRO_SC_AUTOTUNE_CACHE"] = path
    autotune.reset_cache()
    try:
        assert autotune.get_attn_tile(6, 4, 16, 128) == stored
    finally:
        if old is None:
            os.environ.pop("REPRO_SC_AUTOTUNE_CACHE")
        else:
            os.environ["REPRO_SC_AUTOTUNE_CACHE"] = old
        autotune.reset_cache()


# ---------------------------------------------------------------------------
# Model / engine integration
# ---------------------------------------------------------------------------


def _decode_paged_once(cfg, key):
    params = P.init_params(key, lm.lm_param_specs(cfg), cfg.param_dtype)
    prompt = jnp.asarray([[5, 9, 17, 3, 8]], jnp.int32)
    _, cache, lengths = lm.prefill(params, prompt, cfg, max_len=32)
    tok = jnp.asarray([[7]], jnp.int32)
    bs = 4
    nb = 32 // bs
    pages = lm.init_paged_cache(cfg, nb + 2, bs)
    bt = jnp.asarray([[1 + i for i in range(nb)]], jnp.int32)

    def put(pool, full):
        def one(pg, fl):
            return attention.paged_scatter(
                pg, bt, fl[:, :5], jnp.zeros((1,), jnp.int32),
                jnp.asarray([5], jnp.int32))
        return jax.vmap(one)(pool, full)

    pages = {"k": put(pages["k"], cache["k"]),
             "v": put(pages["v"], cache["v"])}
    logits, _ = lm.decode_paged(params, pages, bt, tok, lengths,
                                jnp.ones((1,), jnp.int32), cfg)
    return logits


def test_decode_paged_fused_matches_unfused(key):
    ref = _decode_paged_once(_cfg(), key)
    got = _decode_paged_once(_cfg(paged_attn="fused"), key)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    assert np.argmax(got) == np.argmax(ref)


def test_decode_paged_rejects_unknown_mode_and_keyless_fused_sc(key):
    cfg = _cfg(paged_attn="fused_sc", sc_nbit=64)
    params = P.init_params(key, lm.lm_param_specs(cfg), cfg.param_dtype)
    pages = lm.init_paged_cache(cfg, 4, 4)
    bt = jnp.asarray([[1, 2]], jnp.int32)
    args = (params, pages, bt, jnp.asarray([[3]], jnp.int32),
            jnp.zeros((1,), jnp.int32), jnp.ones((1,), jnp.int32))
    with pytest.raises(ValueError, match="fused_sc"):
        lm.decode_paged(*args, _cfg(paged_attn="fused_sc", sc_nbit=64))
    with pytest.raises(ValueError, match="paged_attn"):
        lm.decode_paged(*args, _cfg(paged_attn="bogus"),
                        rng=jnp.zeros((1, 2), jnp.uint32))


def _run_paged(params, cfg, reqs, *, slots, seed=7, num_blocks=0,
               submit_after=None, **kw):
    defaults = dict(slots=slots, max_len=32, block_size=4,
                    prefill_chunk=3, seed=seed, num_blocks=num_blocks)
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


REQ0 = dict(rid=0, prompt=[5, 9, 17, 3], max_new_tokens=5, temperature=0.8)
REQ1 = dict(rid=1, prompt=[40, 2, 8, 30, 7, 11], max_new_tokens=5,
            temperature=0.0)


def test_engine_fused_greedy_matches_unfused(key):
    """The serve engine with paged_attn='fused' emits the same greedy
    tokens as the reference path — the end-to-end equivalence."""
    cfg = _cfg()
    params = P.init_params(key, lm.lm_param_specs(cfg), cfg.param_dtype)
    reqs = lambda: [Request(**REQ1),
                    Request(rid=2, prompt=[12, 33, 7], max_new_tokens=4,
                            temperature=0.0)]
    _, ref = _run_paged(params, cfg, reqs(), slots=2)
    _, got = _run_paged(params, cfg.replace(paged_attn="fused"), reqs(),
                        slots=2)
    assert got == ref


def test_engine_records_decode_latency(key):
    cfg = _cfg(paged_attn="fused")
    params = P.init_params(key, lm.lm_param_specs(cfg), cfg.param_dtype)
    eng, _ = _run_paged(params, cfg, [Request(**REQ1)], slots=1)
    # max_new=5 -> 4 decode ticks; the jit tick drops, 3 land in the
    # histogram the latency view reads from
    assert eng.metrics.histogram("serve_decode_ms_per_token").count() >= 2, \
        "decode ticks must be timed"
    lat = eng.decode_latency_ms()
    assert set(lat) == {"decode_p50_ms", "decode_p95_ms"}
    assert 0 < lat["decode_p50_ms"] <= lat["decode_p95_ms"] * (1 + 1e-9)
    fresh = PagedServingEngine(params, cfg, PagedServeConfig(
        slots=1, max_len=32, block_size=4, prefill_chunk=3))
    assert fresh.decode_latency_ms() is None


@pytest.mark.parametrize("overrides,slices", [
    (dict(), None),
    (dict(paged_attn="fused"), None),
    (dict(sc_backend="moment", sc_nbit=64), None),
    (dict(paged_attn="fused_sc", sc_nbit=64), 10),
    (dict(sc_backend="pallas_fused", sc_nbit=64), 10),
    (dict(sc_backend="pallas_bitexact", sc_nbit=64,
          paged_attn="fused_sc"), 10),
])
def test_engine_sets_sc_ladder_gauge(key, overrides, slices):
    """An engine whose model runs the fused SC ladder reports the slices
    it draws per word (10 of 16 on the 10-bit grid); others leave the
    gauge unset."""
    cfg = _cfg(**overrides)
    params = P.init_params(key, lm.lm_param_specs(cfg), cfg.param_dtype)
    eng = PagedServingEngine(params, cfg, PagedServeConfig(
        slots=1, max_len=32, block_size=4, prefill_chunk=3))
    assert eng.metrics.value("serve_sc_ladder_slices") == slices


def test_engine_fused_sc_batch_composition_invariance(key):
    """paged_attn='fused_sc' rides the paged==contiguous rng contract:
    same request + same key => same tokens served alone, batched, or
    admitted mid-stream — even though attention logits are stochastic."""
    cfg = _cfg(paged_attn="fused_sc", sc_nbit=64)
    params = P.init_params(key, lm.lm_param_specs(cfg), cfg.param_dtype)
    _, solo = _run_paged(params, cfg, [Request(**REQ0)], slots=1)
    _, full = _run_paged(params, cfg,
                         [Request(**REQ0), Request(**REQ1)], slots=2)
    _, mid = _run_paged(params, cfg, [Request(**REQ1)], slots=2,
                        submit_after={2: Request(**REQ0)})
    assert solo[0] == full[0] == mid[0]


def test_engine_fused_sc_eviction_resume_reproduces_tokens(key):
    """A forced eviction + re-prefill reproduces the roomy-pool tokens
    under fused_sc: the attention draw is pinned to (request, position),
    so recomputed K/V land on identical stochastic logits."""
    cfg = _cfg(paged_attn="fused_sc", sc_nbit=64)
    params = P.init_params(key, lm.lm_param_specs(cfg), cfg.param_dtype)
    # 8 + 12 = 20 tokens/seq = 5 blocks each; the 8-usable-block pool
    # cannot hold both, so one sequence must evict and resume.
    mk = lambda: [
        Request(rid=0, prompt=[5, 9, 17, 3, 8, 2, 30, 11],
                max_new_tokens=12, temperature=0.6),
        Request(rid=1, prompt=[40, 2, 8, 30, 7, 11, 2, 4],
                max_new_tokens=12, temperature=0.6)]
    roomy_e, roomy = _run_paged(params, cfg, mk(), slots=2, max_len=32,
                                prefill_chunk=4)
    tight_e, tight = _run_paged(params, cfg, mk(), slots=2, max_len=32,
                                prefill_chunk=4, num_blocks=9)
    assert tight_e.evictions > 0, "pool was meant to force an eviction"
    assert roomy_e.evictions == 0
    assert roomy == tight
