"""GQA attention: full and blockwise (flash-style online-softmax) variants,
plus single-token decode over a KV cache.

Blockwise attention scans over KV chunks with a running (max, denominator,
accumulator) triple, so peak memory is O(S·chunk) instead of O(S²) —
this is what lets prefill_32k lower within HBM and is remat-friendly inside the
layer scan. GQA is computed grouped: q heads are reshaped to
(kv_heads, group) so no KV head replication is materialized.

The paged decode path (:func:`paged_attention_block`) additionally routes
through ``cfg.paged_attn``: ``"unfused"`` runs the reference
gather -> :func:`chunk_decode_attention` sequence, ``"fused"`` /
``"fused_sc"`` dispatch to the single-``pallas_call`` kernels in
``kernels/paged_attention.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import paged_attention
from repro.models import layers
from repro.models.params import ParamSpec

NEG_INF = -1e30


def attn_specs(cfg):
    if cfg.mla:
        return mla_specs(cfg)
    d, h, kv, hd = (
        cfg.d_model,
        cfg.n_heads,
        cfg.n_kv_heads,
        cfg.resolved_head_dim,
    )
    sp = {
        "wq": ParamSpec((d, h * hd), ("embed", "heads"), "scaled"),
        "wk": ParamSpec((d, kv * hd), ("embed", "kv_embed"), "scaled"),
        "wv": ParamSpec((d, kv * hd), ("embed", "kv_embed"), "scaled"),
        "wo": ParamSpec((h * hd, d), ("heads", "embed"), "scaled"),
    }
    if cfg.qkv_bias:
        sp.update(
            {
                "bq": ParamSpec((h * hd,), ("heads",), "zeros"),
                "bk": ParamSpec((kv * hd,), ("kv_embed",), "zeros"),
                "bv": ParamSpec((kv * hd,), ("kv_embed",), "zeros"),
            }
        )
    if cfg.qk_norm:
        sp.update(
            {
                "q_norm": ParamSpec((hd,), (None,), "ones"),
                "k_norm": ParamSpec((hd,), (None,), "ones"),
            }
        )
    return sp


def _project_qkv(x, p, cfg, positions, key=None):
    with jax.named_scope("qkv"):
        b, s, _ = x.shape
        h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
        if key is None:
            keys = [None] * 3
        elif key.ndim > 1:
            # Per-token key arrays (paged/chunked decode): one fold per
            # projection instead of a split, so each token's draw stays a
            # function of its own (request, position) key alone.
            keys = [layers.fold_keys(key, 23 + j) for j in range(3)]
        else:
            keys = list(jax.random.split(key, 3))
        q = layers.dense(x, p["wq"], cfg, keys[0], p.get("bq")).reshape(
            b, s, h, hd
        )
        k = layers.dense(x, p["wk"], cfg, keys[1], p.get("bk")).reshape(
            b, s, kv, hd
        )
        v = layers.dense(x, p["wv"], cfg, keys[2], p.get("bv")).reshape(
            b, s, kv, hd
        )
        if cfg.qk_norm:
            q = layers.rms_norm(q, p["q_norm"], cfg.rms_norm_eps)
            k = layers.rms_norm(k, p["k_norm"], cfg.rms_norm_eps)
        q = layers.apply_rope(q, positions, cfg.rope_theta)
        k = layers.apply_rope(k, positions, cfg.rope_theta)
        return q, k, v


def _grouped(q, kv_heads):
    b, s, h, hd = q.shape
    return q.reshape(b, s, kv_heads, h // kv_heads, hd)


def full_attention(q, k, v, *, causal: bool = True):
    """Reference O(S²) attention. q: (b,s,h,d), k/v: (b,t,kv,d)."""
    b, s, h, hd = q.shape
    kv = k.shape[2]
    qg = _grouped(q, kv)  # (b,s,kv,g,d)
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    logits = (
        jnp.einsum(
            "bskgd,btkd->bkgst",
            qg.astype(jnp.float32),
            k.astype(jnp.float32),
        )
        * scale
    )
    if causal:
        t = k.shape[1]
        mask = jnp.tril(jnp.ones((s, t), bool), k=t - s)
        logits = jnp.where(mask[None, None, None], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", w, v.astype(jnp.float32))
    return out.reshape(b, s, h, hd).astype(q.dtype)


def blockwise_attention(
    q,
    k,
    v,
    *,
    causal: bool = True,
    chunk: int = 1024,
    q_chunk: int | None = None,
):
    """Flash-style attention: q-chunk outer scan x kv-chunk inner scan with
    online softmax. Exact -- matches full_attention to float tolerance.

    Peak intermediate is one (b, kv, g, cq, ckv) logits tile per step. The
    kv step is jax.checkpoint'd so the backward pass (even nested inside the
    per-layer remat scan) recomputes tiles instead of saving every chunk's
    probabilities -- this is what keeps the 32k-prefill cells inside HBM.

    ``q_chunk`` overrides the query-side chunk. Context-parallel attention
    passes q_chunk = s (ONE q block): the q sequence is already sharded over
    the TP axis, and an outer q scan would split the sharded axis across
    sequential scan steps — serializing the devices (EXPERIMENTS §Perf
    cell-2 iteration 2). KV still streams in ``chunk``-sized blocks.
    """
    b, s, h, hd = q.shape
    kv = k.shape[2]
    t_unpadded = k.shape[1]
    t = t_unpadded
    ckv = min(chunk, t)
    if t % ckv != 0:  # pad KV to a chunk multiple with masked slots
        pad = ckv - t % ckv
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        t = t + pad
    cq = min(q_chunk or chunk, s)
    qpad = (-s) % cq
    g = h // kv
    qg = _grouped(q, kv).astype(jnp.float32)  # (b,s,kv,g,d)
    if qpad:
        qg = jnp.pad(qg, ((0, 0), (0, qpad), (0, 0), (0, 0), (0, 0)))
    nq = (s + qpad) // cq
    nkv = t // ckv
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    kc = k.reshape(b, nkv, ckv, kv, hd).astype(jnp.float32)
    vc = v.reshape(b, nkv, ckv, kv, hd).astype(jnp.float32)
    qc = qg.reshape(b, nq, cq, kv, g, hd)
    # Queries are the LAST s positions of the (unpadded) kv timeline
    # (prefill: s == original t; one-token decode uses decode_attention).
    q_off = t_unpadded - s

    @jax.checkpoint
    def kv_step(carry, inputs):
        m, denom, acc, qi, qbase = carry
        kc_i, vc_i, base = inputs
        logits = jnp.einsum("bkgsd,btkd->bkgst", qi, kc_i) * scale
        kv_idx = base + jnp.arange(ckv)  # (ckv,)
        q_idx = qbase + jnp.arange(cq) + q_off  # (cq,)
        mask = (
            kv_idx[None, :] <= q_idx[:, None]
            if causal
            else jnp.ones((cq, ckv), bool)
        )
        valid = (kv_idx < t_unpadded)[None, :]
        logits = jnp.where(
            (mask & valid)[None, None, None], logits, NEG_INF
        )
        m_new = jnp.maximum(m, logits.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new[..., None])
        denom = denom * alpha + p.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bkgst,btkd->bkgsd", p, vc_i
        )
        return (m_new, denom, acc, qi, qbase), None

    bases = jnp.arange(nkv) * ckv
    kcm = jnp.moveaxis(kc, 1, 0)
    vcm = jnp.moveaxis(vc, 1, 0)

    def q_step(_, inputs):
        q_i, qbase = inputs  # (b,cq,kv,g,d)
        qi = jnp.einsum("bskgd->bkgsd", q_i)
        m0 = jnp.full((b, kv, g, cq), NEG_INF, jnp.float32)
        d0 = jnp.zeros((b, kv, g, cq), jnp.float32)
        a0 = jnp.zeros((b, kv, g, cq, hd), jnp.float32)
        (m, denom, acc, _, _), _ = jax.lax.scan(
            kv_step, (m0, d0, a0, qi, qbase), (kcm, vcm, bases)
        )
        out = acc / jnp.maximum(denom, 1e-30)[..., None]  # (b,kv,g,cq,d)
        return None, out

    qbases = jnp.arange(nq) * cq
    _, outs = jax.lax.scan(q_step, None, (jnp.moveaxis(qc, 1, 0), qbases))
    # outs: (nq, b, kv, g, cq, d) -> (b, s, h, d)
    out = jnp.moveaxis(outs, 0, 3)  # (b,kv,g,nq,cq,d)
    out = out.reshape(b, kv, g, nq * cq, hd)
    out = jnp.moveaxis(out, 3, 1)[:, :s].reshape(b, s, h, hd)
    return out.astype(q.dtype)


def decode_attention(q, k_cache, v_cache, length, scale=None):
    """One-token decode: q (b,1,h,d) against cache (b,L,kv,d); mask > length.
    The single-token special case of :func:`chunk_decode_attention` (the
    query sits at position ``length - 1``, i.e. a chunk of one at fill
    ``length - 1``)."""
    return chunk_decode_attention(q, k_cache, v_cache, length - 1, scale)


def chunk_decode_attention(q, k_cache, v_cache, lengths, scale=None):
    """Multi-token decode: a chunk of queries against a per-sequence cache.

    q: (b, sc, h, d) — chunk token i of row r sits at ABSOLUTE position
    ``lengths[r] + i`` (its K/V must already be written into the cache);
    k/v_cache: (b, L, kv, d).  Causal within the chunk, masked beyond each
    row's fill.  ``sc = 1`` reproduces :func:`decode_attention` with
    ``length = lengths + 1`` — the single-token decode is the special case.
    This is the lookup the paged serve path drives after a
    ``paged_gather``; it is also what chunked prefill uses, which is why
    one function serves both phases.  ``scale`` defaults to 1/sqrt(d);
    the values may be narrower than the keys (absorbed MLA).
    """
    b, sc, h, hd = q.shape
    kv = k_cache.shape[2]
    qg = _grouped(q, kv).astype(jnp.float32)  # (b,sc,kv,g,d)
    if scale is None:
        scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    logits = (
        jnp.einsum("bskgd,btkd->bkgst", qg, k_cache.astype(jnp.float32))
        * scale
    )
    t_idx = jnp.arange(k_cache.shape[1])  # (L,)
    q_pos = lengths[:, None] + jnp.arange(sc)[None, :]  # (b, sc)
    mask = t_idx[None, None, :] <= q_pos[:, :, None]  # (b, sc, L)
    logits = jnp.where(mask[:, None, None, :, :], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", w, v_cache.astype(jnp.float32))
    return out.reshape(b, sc, h, v_cache.shape[-1]).astype(q.dtype)


# ---------------------------------------------------------------------------
# Paged KV lookup: fixed-size token blocks + per-sequence block tables
# ---------------------------------------------------------------------------


def paged_gather(pages, block_table):
    """Materialize each sequence's cache view from the block pool.

    pages: (P, kv, bs, d) — the pool (P blocks of bs tokens, one layer;
    each block holds one contiguous (bs, d) slab per kv head, the slice
    the fused kernel DMAs per grid step); block_table: (b, nb) int32 —
    block ids per sequence, position t of row r lives in
    ``pages[block_table[r, t // bs], :, t % bs]``.  Returns the gathered
    (b, nb·bs, kv, d) view — the contiguous-cache layout, which is what
    proves paged == contiguous attention (same downstream math).
    """
    g = jnp.take(pages, block_table, axis=0)  # (b, nb, kv, bs, d)
    b, nb, kv, bs, d = g.shape
    return g.transpose(0, 1, 3, 2, 4).reshape(b, nb * bs, kv, d)


def paged_scatter(pages, block_table, new, lengths, n_valid):
    """Write a chunk's K or V rows into the pool through the block tables.

    new: (b, sc, kv, d) — token i of row r goes to absolute position
    ``lengths[r] + i`` when ``i < n_valid[r]``; tokens beyond a row's
    valid count (chunk padding, idle rows) land in the reserved null
    block 0, which no live sequence ever maps.  Rows' block tables point
    at disjoint pool blocks over the written span (the allocator hands
    each row its own blocks, and prefix-shared blocks are copied out by
    the scheduler's copy-on-write barrier before any write reaches them
    — ``kv_cache.PagedKVCache.make_writable``), so scatters never collide
    except harmlessly inside the null block.
    """
    bs = pages.shape[2]
    b, sc = new.shape[:2]
    nb = block_table.shape[1]
    i = jnp.arange(sc)[None, :]
    t = jnp.clip(lengths[:, None] + i, 0, nb * bs - 1)  # (b, sc)
    valid = i < n_valid[:, None]
    page = jnp.take_along_axis(block_table, t // bs, axis=1)
    page = jnp.where(valid, page, 0)
    off = jnp.where(valid, t % bs, 0)
    flat = new.reshape(b * sc, *new.shape[2:]).astype(pages.dtype)
    return pages.at[page.reshape(-1), :, off.reshape(-1)].set(flat)


def paged_copy_blocks(pages, src, dst):
    """Copy whole pool blocks ``src[i] -> dst[i]`` on every layer.

    The device half of copy-on-write: when the scheduler's write barrier
    (``kv_cache.PagedKVCache.make_writable``) replaces a shared or
    hash-registered block in a sequence's table, the new block must carry
    the old block's K/V before the next scatter overwrites its tail.
    pages: the pool dict (``models/lm.py:init_paged_cache``) whose paged
    leaves are (layers, P, ...) arrays — ``"k"``/``"v"``, or MLA's
    latent ``"kv"``, and a leading dense stack's own under ``"dense"``
    (a hybrid plan's ``"ssm"`` state rows, which are slot- not
    block-indexed, pass through untouched); src/dst: equal-length
    block-id vectors.  Pure indexed-copy — one executable per distinct
    copy count (COW is rare and counts are tiny).
    """
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)

    def cp(pool):
        return pool.at[:, dst].set(pool[:, src])

    return {**pages, **jax.tree.map(cp, paged_leaves(pages))}


def paged_leaves(pages) -> dict:
    """The block-indexed part of a paged cache dict (everything but the
    slot-indexed SSM state rows)."""
    return {k: v for k, v in pages.items() if k != "ssm"}


def paged_attention_block(
    x,
    p,
    cfg,
    positions,
    key,
    pages,
    block_table,
    lengths,
    n_valid,
):
    """Self-attention over the paged KV cache (chunked decode/prefill).

    x: (b, sc, d) chunk activations; the chunk's K/V scatter into the
    pool first, then attention runs over each row's gathered view —
    write-then-gather keeps the math identical to the contiguous path.
    ``cfg.paged_attn`` selects the lookup: ``"unfused"`` (reference
    gather + :func:`chunk_decode_attention`), ``"fused"`` (one Pallas
    kernel, same math to float tolerance), or ``"fused_sc"`` (fused with
    the SC-sampled QK^T; needs per-token keys and draws them under salt
    29, disjoint from the dense-layer salts).  ``pages`` is this layer's
    ``{"k", "v"}`` pools (MLA: ``{"kv"}``, :func:`mla_paged_block`).
    Returns (out, new_pages).
    """
    if cfg.mla:
        return mla_paged_block(x, p, cfg, positions, key, pages,
                               block_table, lengths, n_valid)
    q, k, v = _project_qkv(x, p, cfg, positions, key)
    with jax.named_scope("attn"):
        out, k_pages, v_pages = _paged_lookup(
            q, k, v, cfg, key, pages["k"], pages["v"], block_table, lengths,
            n_valid)
    b, s, _, _ = out.shape
    okey = layers.fold_keys(key, 7)
    with jax.named_scope("attn_out"):
        out = layers.dense(out.reshape(b, s, -1), p["wo"], cfg, okey)
    return out, {"k": k_pages, "v": v_pages}


def _paged_lookup(q, k, v, cfg, key, k_pages, v_pages, block_table, lengths,
                  n_valid):
    """Scatter the chunk's K/V into the pool, then attend over it."""
    k_pages = paged_scatter(k_pages, block_table, k, lengths, n_valid)
    v_pages = paged_scatter(v_pages, block_table, v, lengths, n_valid)
    mode = getattr(cfg, "paged_attn", "unfused")
    if mode == "fused":
        out = paged_attention.paged_attention_fused(
            q, k_pages, v_pages, block_table, lengths
        )
    elif mode == "fused_sc":
        if key is None or key.ndim <= 1:
            raise ValueError(
                "paged_attn='fused_sc' needs per-token rng keys (pass "
                "rng to decode_paged) so attention draws stay pinned to "
                "(request, position)"
            )
        out = paged_attention.paged_attention_fused_sc(
            layers.fold_keys(key, 29),
            q,
            k_pages,
            v_pages,
            block_table,
            lengths,
            nbit=cfg.sc_nbit,
        )
    elif mode == "unfused":
        kc = paged_gather(k_pages, block_table)
        vc = paged_gather(v_pages, block_table)
        out = chunk_decode_attention(q, kc, vc, lengths)
    else:
        raise ValueError(
            f"unknown cfg.paged_attn={mode!r} "
            "(expected 'unfused', 'fused', or 'fused_sc')"
        )
    return out, k_pages, v_pages


def attention_block(
    x,
    p,
    cfg,
    positions,
    key=None,
    *,
    cache=None,
    cache_length=None,
    constrain=None,
):
    """Self-attention sub-block. Returns (out, new_cache).

    Training/prefill: cache is None -> causal attention over the sequence
    (returns the full K/V so prefill can build the cache).
    Decode: cache = {"k", "v"} ring buffers (MLA: {"kv"}, the latent);
    x is (b, 1, d).  The new cache comes back in the same form.
    """
    if cfg.mla:
        return mla_attention_block(x, p, cfg, positions, key, cache=cache,
                                   cache_length=cache_length)
    cst = constrain or (lambda v_, *a: v_)
    q, k, v = _project_qkv(x, p, cfg, positions, key)
    # Layout choice per arch x mesh: TP over heads when the head count
    # divides the model axis; otherwise CONTEXT PARALLELISM — the query
    # sequence shards over `model` (k/v replicate via one cheap gather per
    # layer) so attention FLOPs and the flash tiles stay distributed instead
    # of running head-replicated on every device (16x waste on 40-head
    # configs over a 16-way TP axis — EXPERIMENTS §Perf iteration 3).
    model_size = getattr(cst, "axis_sizes", {}).get("model", 1)
    heads_tp = model_size <= 1 or cfg.n_heads % model_size == 0
    if heads_tp or cache is not None:
        q = cst(q, "batch", "seq", "heads", None)
        k = cst(k, "batch", "seq", "kv_heads", None)
        v = cst(v, "batch", "seq", "kv_heads", None)
    else:
        q = cst(q, "batch", "resid_seq", None, None)
        k = cst(k, "batch", "seq", None, None)
        v = cst(v, "batch", "seq", None, None)
    with jax.named_scope("attn"):
        out, new_cache = _contiguous_lookup(q, k, v, cfg, cache,
                                            cache_length, positions,
                                            heads_tp)
    if heads_tp or cache is not None:
        out = cst(out, "batch", "seq", "heads", None)
    else:
        out = cst(out, "batch", "resid_seq", None, None)
    b, s, _, _ = out.shape
    okey = None if key is None else jax.random.fold_in(key, 7)
    with jax.named_scope("attn_out"):
        out = layers.dense(out.reshape(b, s, -1), p["wo"], cfg, okey)
    return out, new_cache


def _contiguous_lookup(q, k, v, cfg, cache, cache_length, positions,
                       heads_tp):
    """Attention over the ring-buffer cache (decode) or the sequence
    itself (training/prefill).  Returns (out, new_cache)."""
    if cache is not None:
        kc, vc = cache["k"], cache["v"]
        pos = positions[:, 0]  # (b,) write index
        kc = jax.vmap(
            lambda c, upd, i: jax.lax.dynamic_update_slice(
                c, upd, (i, 0, 0)
            )
        )(kc, k, pos)
        vc = jax.vmap(
            lambda c, upd, i: jax.lax.dynamic_update_slice(
                c, upd, (i, 0, 0)
            )
        )(vc, v, pos)
        out = decode_attention(q, kc, vc, cache_length)
        new_cache = {"k": kc, "v": vc}
    else:
        if cfg.attn_impl == "full":
            out = full_attention(q, k, v, causal=True)
        else:
            out = blockwise_attention(
                q,
                k,
                v,
                causal=True,
                chunk=cfg.attn_chunk,
                # CP: q already sharded over `model` -> single q block
                q_chunk=None if heads_tp else q.shape[1],
            )
        new_cache = {"k": k, "v": v}
    return out, new_cache


# ---------------------------------------------------------------------------
# Latent attention (MLA, DeepSeek-V2/V3)
# ---------------------------------------------------------------------------
#
#   q = Wq·x: h heads of (nope + rope);  [c, k_pe] = Wkv_a·x, c normed
#   rope on q_pe and k_pe only (k_pe: one head, shared by all)
#   [k_nope, v] = Wkv_b·c per head;  scores [q_nope, q_pe]·[k_nope, k_pe]
#   scaled by 1/sqrt(nope + rope);  o = Wo·(softmax·v)
#
# The naive form (training, forward, prefill) decompresses K and V per
# head.  The absorbed form (every decode and paged step) never does:
# q_lat = q_nope·W_UK per head, scores q_lat·c + q_pe·k_pe, o_lat =
# Σ p·c, v = o_lat·W_UV.  So the cache holds only [c, k_pe] — one
# (kv_lora_rank + rope)-wide row per position, shared by every head.


def mla_specs(cfg):
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return {
        "wq": ParamSpec((d, h * (dn + dr)), ("embed", "heads"), "scaled"),
        "wkv_a": ParamSpec((d, r + dr), ("embed", None), "scaled"),
        "kv_norm": ParamSpec((r,), (None,), "ones"),
        # per head: [k_nope | v] columns (the published kv_b_proj layout)
        "wkv_b": ParamSpec((r, h * (dn + dv)), (None, "heads"), "scaled"),
        "wo": ParamSpec((h * dv, d), ("heads", "embed"), "scaled"),
    }


def latent_width(cfg) -> int:
    """Values per cached position per layer: [c, k_pe]."""
    return cfg.kv_lora_rank + cfg.qk_rope_head_dim


def mla_scale(cfg) -> float:
    return 1.0 / float(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** 0.5


def _mla_project(x, p, cfg, positions, key):
    """(q_nope, rope(q_pe), latent [c, rope(k_pe)] (b, s, 1, r + rope))."""
    b, s, _ = x.shape
    h, dn = cfg.n_heads, cfg.qk_nope_head_dim
    with jax.named_scope("mla_q"):
        q = layers.dense(x, p["wq"], cfg, layers.site_key(key, "mla_q"),
                         site="mla_q").reshape(b, s, h, -1)
        q_pe = layers.apply_rope(q[..., dn:], positions, cfg.rope_theta)
    with jax.named_scope("mla_kv_a"):
        ckv = layers.dense(x, p["wkv_a"], cfg,
                           layers.site_key(key, "mla_kv_a"), site="mla_kv_a")
        c = layers.rms_norm(ckv[..., :cfg.kv_lora_rank], p["kv_norm"],
                            cfg.rms_norm_eps)
        k_pe = layers.apply_rope(ckv[..., None, cfg.kv_lora_rank:],
                                 positions, cfg.rope_theta)
        latent = jnp.concatenate([c[:, :, None, :], k_pe], axis=-1)
    return q[..., :dn], q_pe, latent


def _kv_b(p, cfg):
    """W_kv_b as (r, h, nope + v): [W_UK | W_UV] per head."""
    return p["wkv_b"].reshape(cfg.kv_lora_rank, cfg.n_heads, -1)


def _head_dense(x, w, cfg, key, site):
    """Per-head weight matmul: x (..., h, K) @ w (K, h, N) -> (..., h, N),
    through the substrate with each head's key folded from ``site``."""
    if cfg.sc_backend == "exact":
        return jnp.einsum("...hk,khn->...hn", x, w.astype(x.dtype))
    return jnp.stack(
        [layers.dense(x[..., i, :], w[:, i], cfg,
                      layers.site_key(key, site, i), site=site)
         for i in range(w.shape[1])], axis=-2)


def _absorbed_q(q_nope, q_pe, p, cfg, key):
    """[q_nope·W_UK, q_pe] (b, s, h, r + rope): queries against latents."""
    with jax.named_scope("mla_q"):
        w_uk = _kv_b(p, cfg)[..., :cfg.qk_nope_head_dim]
        q_lat = _head_dense(q_nope, jnp.transpose(w_uk, (2, 1, 0)), cfg,
                            key, "mla_uk")
        return jnp.concatenate([q_lat, q_pe.astype(q_lat.dtype)], axis=-1)


def _mla_out(o_lat, p, cfg, key):
    """Latent outputs (b, s, h, r) -> W_UV per head -> Wo."""
    b, s = o_lat.shape[:2]
    with jax.named_scope("mla_v_up"):
        w_uv = _kv_b(p, cfg)[..., cfg.qk_nope_head_dim:]
        v = _head_dense(o_lat, w_uv, cfg, key, "mla_uv")
    with jax.named_scope("attn_out"):
        return layers.dense(v.reshape(b, s, -1), p["wo"], cfg,
                            layers.fold_keys(key, 7))


def mla_attention_block(x, p, cfg, positions, key=None, *, cache=None,
                        cache_length=None):
    """MLA sub-block over the sequence (naive form; cache None) or over a
    contiguous latent cache {"kv": (b, L, 1, r + rope)} (absorbed form,
    one new position per row at ``positions[:, 0]``).  Returns (out,
    {"kv": ...}): the sequence's own latents, or the updated cache."""
    q_nope, q_pe, latent = _mla_project(x, p, cfg, positions, key)
    if cache is None:
        b, s, h = *x.shape[:2], cfg.n_heads
        dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
        with jax.named_scope("mla_kv_b"):
            kv = layers.dense(latent[:, :, 0, :cfg.kv_lora_rank], p["wkv_b"],
                              cfg, layers.site_key(key, "mla_kv_b"),
                              site="mla_kv_b").reshape(b, s, h, dn + dv)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(latent[..., cfg.kv_lora_rank:],
                                            (b, s, h, cfg.qk_rope_head_dim)
                                            ).astype(kv.dtype)], axis=-1)
        q = jnp.concatenate([q_nope, q_pe], axis=-1)
        # values zero-padded to the query width (exact: the padded lanes
        # carry nothing), then cut back
        v = jnp.pad(kv[..., dn:], ((0, 0),) * 3 + ((0, q.shape[-1] - dv),))
        with jax.named_scope("attn"):
            if cfg.attn_impl == "full":
                o = full_attention(q, k, v, causal=True)
            else:
                o = blockwise_attention(q, k, v, causal=True,
                                        chunk=cfg.attn_chunk)
        with jax.named_scope("attn_out"):
            out = layers.dense(o[..., :dv].reshape(b, s, h * dv), p["wo"],
                               cfg, layers.fold_keys(key, 7))
        return out, {"kv": latent}
    kc = jax.vmap(lambda c, upd, i: jax.lax.dynamic_update_slice(
        c, upd.astype(c.dtype), (i, 0, 0)))(cache["kv"], latent,
                                            positions[:, 0])
    q = _absorbed_q(q_nope, q_pe, p, cfg, key)
    with jax.named_scope("attn"):
        o_lat = decode_attention(q, kc, kc[..., :cfg.kv_lora_rank],
                                 cache_length, mla_scale(cfg))
    return _mla_out(o_lat, p, cfg, key), {"kv": kc}


def mla_paged_block(x, p, cfg, positions, key, pages, block_table, lengths,
                    n_valid):
    """MLA over the paged latent pool {"kv": (P, 1, bs, r + rope)}: the
    chunk's latents scatter in once, then the absorbed lookup —
    ``mla_paged_attention`` (``paged_attn="fused"``) or gather +
    :func:`chunk_decode_attention` (``"unfused"``, the reference)."""
    q_nope, q_pe, latent = _mla_project(x, p, cfg, positions, key)
    q = _absorbed_q(q_nope, q_pe, p, cfg, key)
    r = cfg.kv_lora_rank
    mode = getattr(cfg, "paged_attn", "unfused")
    with jax.named_scope("attn"):
        pool = paged_scatter(pages["kv"], block_table, latent, lengths,
                             n_valid)
        if mode == "fused":
            o_lat = paged_attention.mla_paged_attention(
                q, pool, block_table, lengths, dv=r, scale=mla_scale(cfg))
        elif mode == "unfused":
            kc = paged_gather(pool, block_table)
            o_lat = chunk_decode_attention(q, kc, kc[..., :r], lengths,
                                           mla_scale(cfg))
        else:
            raise ValueError(
                f"paged_attn={mode!r} has no latent-attention (MLA) form; "
                "use 'fused' or 'unfused'")
    return _mla_out(o_lat, p, cfg, key), {"kv": pool}


def cache_leaves(cfg) -> dict:
    """Per-position, per-layer shape of each cache leaf: GQA K and V
    ``(kv_heads, head_dim)`` each; MLA one latent ``(1, r + rope)``."""
    if cfg.mla:
        return {"kv": (1, latent_width(cfg))}
    kvh, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    return {"k": (kvh, hd), "v": (kvh, hd)}
