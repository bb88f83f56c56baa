"""LM assembly: block definitions, scan-over-layers, prefill and decode.

One code path serves all ten assigned architectures:

  dense / audio / vlm : N × (RMSNorm → GQA attn → RMSNorm → SwiGLU MLP)
  moe                 : K × dense block, then (N - K) × (RMSNorm → attn
                        → RMSNorm → MoE FFN); ``first_k_dense`` = K leading
                        dense layers (width ``d_ff``) hold their own
                        parameter subtree (``dense_blocks``) and cache
                        (``"dense"``) and run before the scanned MoE stack
                        (DeepSeek-V3 / Moonlight: K = 1).  Attention is GQA,
                        or latent attention (MLA) when ``kv_lora_rank`` > 0,
                        whose cache holds one latent row per position
  ssm                 : N × (RMSNorm → Mamba2/SSD block)
  hybrid (zamba2)     : groups of ``attn_every`` Mamba2 layers followed by
                        ONE weight-shared (attn + MLP) block; the scan runs
                        over groups so each shared invocation has a static
                        slot for its own KV cache (zamba2: 81 "layers" =
                        54 ssm + 27 shared invocations, attn_every=2).

Layers are stacked and scanned (``jax.lax.scan`` + per-layer remat), so
compile time and HLO size are O(1) in depth — a 48-layer 400B config lowers
as fast as a 2-layer smoke config. The per-layer PRNG for the SC engine is
folded from the absolute layer index (leading dense layers first).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention, frontend, layers, moe, ssm
from repro.models.params import ParamSpec, tree_map_specs


# --------------------------------------------------------------------------
# Param specs
# --------------------------------------------------------------------------


def _norm_spec(cfg):
    return ParamSpec((cfg.d_model,), ("embed",), "ones")


def block_specs(cfg):
    if cfg.family in ("ssm", "hybrid"):
        return {"ln1": _norm_spec(cfg), "ssm": ssm.ssm_specs(cfg)}
    ffn = moe.moe_specs(cfg) if cfg.family == "moe" else layers.mlp_specs(cfg)
    return {"ln1": _norm_spec(cfg), "attn": attention.attn_specs(cfg),
            "ln2": _norm_spec(cfg), "ffn": ffn}


def dense_block_specs(cfg):
    """A leading dense layer: the backbone's attention, a SwiGLU of d_ff."""
    return {"ln1": _norm_spec(cfg), "attn": attention.attn_specs(cfg),
            "ln2": _norm_spec(cfg), "ffn": layers.mlp_specs(cfg)}


def shared_block_specs(cfg):
    """zamba2's weight-shared transformer block (MHA + MLP)."""
    return {"ln1": _norm_spec(cfg), "attn": attention.attn_specs(cfg),
            "ln2": _norm_spec(cfg), "mlp": layers.mlp_specs(cfg)}


def stack_specs(specs, n: int):
    return tree_map_specs(
        lambda s: ParamSpec((n,) + s.shape, ("layers",) + s.axes, s.init,
                            s.dtype), specs)


def n_backbone_layers(cfg) -> int:
    """Scanned backbone depth (hybrid: ssm layers only; `n_layers` counts
    ssm layers + shared invocations; moe: the layers after the
    ``first_k_dense`` leading dense ones)."""
    if cfg.family == "hybrid":
        return cfg.n_layers * cfg.attn_every // (cfg.attn_every + 1)
    return cfg.n_layers - cfg.first_k_dense


def n_shared_invocations(cfg) -> int:
    if cfg.family != "hybrid":
        return 0
    return n_backbone_layers(cfg) // cfg.attn_every


def lm_param_specs(cfg):
    sp = {
        "embed": layers.embed_specs(cfg),
        "blocks": stack_specs(block_specs(cfg), n_backbone_layers(cfg)),
        "final_norm": _norm_spec(cfg),
    }
    if cfg.first_k_dense:
        sp["dense_blocks"] = stack_specs(dense_block_specs(cfg),
                                         cfg.first_k_dense)
    if not cfg.tie_embeddings:
        sp["unembed"] = ParamSpec((cfg.d_model, cfg.vocab),
                                  ("embed", "vocab"), "scaled")
    if cfg.family == "hybrid":
        sp["shared"] = shared_block_specs(cfg)
    if cfg.frontend == "embeddings":
        sp["frontend"] = frontend.frontend_specs(cfg)
    return sp


def _logits(x, params, cfg, key=None):
    """Output projection (site ``unembed``): ``key`` is the caller's rng
    root — raw (2,) or per-row (..., 2) matching ``x``'s leading dims —
    folded here with the unembed site salt."""
    key = layers.site_key(key, "unembed")
    with jax.named_scope("unembed"):
        if cfg.tie_embeddings:
            return layers.unembed(x, params["embed"], cfg, key).astype(
                jnp.float32)
        return layers.dense(x, params["unembed"], cfg, key,
                            site="unembed").astype(jnp.float32)


def _group(tree, ninv: int, per: int):
    """Reshape stacked-layer leaves (n, ...) -> (ninv, per, ...)."""
    return jax.tree.map(lambda v: v.reshape((ninv, per) + v.shape[1:]), tree)


# --------------------------------------------------------------------------
# Block application
# --------------------------------------------------------------------------


def _norm(x, w, cfg):
    return layers.rms_norm(x, w, cfg.rms_norm_eps)


def _ffn(x, p, cfg, key, cst=None):
    """The block's FFN: MoE where the block holds a router, else MLP."""
    if "router" in p:
        return moe.moe_ffn(x, p, cfg, key, constrain=cst)
    return layers.mlp(x, p, cfg, key, constrain=cst)


def _run_layers(body, x, params, cfg, cache=None):
    """The attention families' layer loop: the ``first_k_dense`` leading
    dense layers one by one, then the scanned backbone.  ``body((x,
    idx), xs) -> ((x, idx + 1), y)`` sees each layer's params (and, with
    ``cache``, that layer's cache leaves as ``(params, cache)``) at its
    absolute index.  Returns (x, the backbone's stacked y with the dense
    layers' under ``"dense"``)."""
    dense = []
    for i in range(cfg.first_k_dense):
        xs = params["dense_blocks"]
        if cache is not None:
            xs = (xs, cache["dense"])
        (x, _), y = body((x, i), jax.tree.map(lambda v: v[i], xs))
        dense.append(y)
    xs = params["blocks"]
    if cache is not None:
        xs = (xs, {k: v for k, v in cache.items() if k != "dense"})
    (x, _), ys = jax.lax.scan(body, (x, cfg.first_k_dense), xs)
    if dense and ys is not None:
        ys["dense"] = jax.tree.map(lambda *vs: jnp.stack(vs), *dense)
    return x, ys


def _apply_block(x, p, cfg, positions, key, cache=None, cache_length=None,
                 cst=None):
    """One backbone block (pre-norm residual). Returns (x, new_cache)."""
    if cfg.family in ("ssm", "hybrid"):
        h, new_cache = ssm.ssm_block(_norm(x, p["ln1"], cfg), p["ssm"],
                                     cfg, key, cache=cache, constrain=cst)
        return x + h, new_cache
    akey = None if key is None else jax.random.fold_in(key, 11)
    h, new_cache = attention.attention_block(
        _norm(x, p["ln1"], cfg), p["attn"], cfg, positions, akey,
        cache=cache, cache_length=cache_length, constrain=cst)
    x = x + h
    fkey = None if key is None else jax.random.fold_in(key, 13)
    return x + _ffn(_norm(x, p["ln2"], cfg), p["ffn"], cfg, fkey, cst), \
        new_cache


def _apply_shared(x, p, cfg, positions, key, cache=None, cache_length=None,
                  cst=None):
    akey = None if key is None else jax.random.fold_in(key, 17)
    h, new_cache = attention.attention_block(
        _norm(x, p["ln1"], cfg), p["attn"], cfg, positions, akey,
        cache=cache, cache_length=cache_length, constrain=cst)
    x = x + h
    mkey = None if key is None else jax.random.fold_in(key, 19)
    x = x + layers.mlp(_norm(x, p["ln2"], cfg), p["mlp"], cfg, mkey,
                       constrain=cst)
    return x, new_cache


def _maybe_remat(fn, cfg):
    return jax.checkpoint(fn) if cfg.remat == "full" else fn


# --------------------------------------------------------------------------
# Forward (train / eval over a full sequence)
# --------------------------------------------------------------------------


def _embed_inputs(params, inputs, cfg, rng=None):
    if cfg.frontend == "embeddings" and inputs.ndim == 3:
        x = inputs.astype(cfg.act_dtype)
        if "frontend" in params:
            x = frontend.project_embeddings(x, params["frontend"], cfg, rng)
        return x
    return layers.embed(inputs, params["embed"]).astype(cfg.act_dtype)


def encode(params, inputs, cfg, *, rng=None, constrain=None,
           constrain_params=None):
    """Backbone pass: inputs (tokens or stub embeddings) -> final hidden
    states (b, s, d) after the last norm."""
    cst = constrain or (lambda v, *a: v)
    cstp = constrain_params or (lambda t: t)
    x = _embed_inputs(params, inputs, cfg, rng)
    b, s = x.shape[:2]
    x = cst(x, "batch", "resid_seq", None)
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))

    if cfg.family == "hybrid":
        ninv, per = n_shared_invocations(cfg), cfg.attn_every
        grouped = _group(params["blocks"], ninv, per)

        def gbody(carry, group_params):
            xc, idx = carry
            for j in range(per):
                lp = cstp(jax.tree.map(lambda v: v[j], group_params))
                key = None if rng is None else jax.random.fold_in(rng, idx * per + j)
                xc, _ = _apply_block(xc, lp, cfg, positions, key, cst=cst)
            k2 = None if rng is None else jax.random.fold_in(rng, 10_000 + idx)
            xc, _ = _apply_shared(xc, params["shared"], cfg, positions, k2,
                                  cst=cst)
            xc = cst(xc, "batch", "resid_seq", None)
            return (xc, idx + 1), None

        (x, _), _ = jax.lax.scan(_maybe_remat(gbody, cfg), (x, 0), grouped)
    else:
        def body(carry, layer_params):
            xc, idx = carry
            key = None if rng is None else jax.random.fold_in(rng, idx)
            xc, _ = _apply_block(xc, cstp(layer_params), cfg, positions, key,
                                 cst=cst)
            xc = cst(xc, "batch", "resid_seq", None)
            return (xc, idx + 1), None

        x, _ = _run_layers(_maybe_remat(body, cfg), x, params, cfg)

    return _norm(x, params["final_norm"], cfg)


def forward(params, inputs, cfg, *, rng=None, constrain=None,
            constrain_params=None):
    """Full logits (b, s, vocab). Prefer lm_loss for training: it never
    materializes the whole logits tensor."""
    cst = constrain or (lambda v, *a: v)
    x = encode(params, inputs, cfg, rng=rng, constrain=constrain,
               constrain_params=constrain_params)
    logits = _logits(x, params, cfg, rng)
    return cst(logits, "batch", "seq", "vocab")


LOSS_SEQ_CHUNK = 1024


def lm_loss(params, batch, cfg, *, rng=None, constrain=None,
            constrain_params=None):
    """Causal next-token cross-entropy, sequence-chunked.

    The (tokens, vocab) logits tensor is the largest activation in any LM
    step, and it only feeds a reduction — so the unembed + log-softmax +
    gather runs per sequence chunk inside a remat'd scan: peak memory drops
    from O(s·vocab) to O(chunk·vocab), and the backward recomputes each
    chunk's logits instead of keeping them alive.
    """
    x = encode(params, batch["inputs"], cfg, rng=rng, constrain=constrain,
               constrain_params=constrain_params)
    labels = batch["labels"]
    b, s, d = x.shape
    c = min(LOSS_SEQ_CHUNK, s)
    if s % c:
        c = s                      # irregular lengths: single chunk
    nc = s // c
    xc = jnp.moveaxis(x.reshape(b, nc, c, d), 1, 0)
    lc = jnp.moveaxis(labels.reshape(b, nc, c), 1, 0)

    @jax.checkpoint
    def chunk_nll(carry, inp):
        tot, i = carry
        xi, li = inp                               # (b,c,d), (b,c)
        key = None if rng is None else jax.random.fold_in(rng, i)
        logits = _logits(xi, params, cfg, key)     # (b,c,vocab) f32
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, li[..., None], axis=-1)[..., 0]
        return (tot + nll.sum(), i + 1), None

    (total, _), _ = jax.lax.scan(
        chunk_nll, (jnp.zeros((), jnp.float32), 0), (xc, lc))
    return total / (b * s)


# --------------------------------------------------------------------------
# KV / SSM cache
# --------------------------------------------------------------------------


def init_cache(cfg, batch: int, max_len: int, dtype=None):
    """Stacked per-layer decode cache (leading axis = backbone layer or
    shared invocation)."""
    dtype = dtype or cfg.act_dtype
    n = n_backbone_layers(cfg)
    kvh, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    if cfg.family in ("ssm", "hybrid"):
        one = ssm.init_ssm_cache(cfg, batch, dtype)
        cache = {"ssm": jax.tree.map(
            lambda v: jnp.broadcast_to(v[None], (n,) + v.shape).copy(), one)}
        if cfg.family == "hybrid":
            ninv = n_shared_invocations(cfg)
            cache["shared_k"] = jnp.zeros((ninv, batch, max_len, kvh, hd),
                                          dtype)
            cache["shared_v"] = jnp.zeros((ninv, batch, max_len, kvh, hd),
                                          dtype)
        return cache

    def leaves(layers_):
        return {name: jnp.zeros((layers_, batch, max_len) + shape, dtype)
                for name, shape in attention.cache_leaves(cfg).items()}

    cache = leaves(n)
    if cfg.first_k_dense:
        cache["dense"] = leaves(cfg.first_k_dense)
    return cache


def init_paged_cache(cfg, num_blocks: int, block_size: int, dtype=None,
                     slots: int | None = None):
    """Per-family paged decode cache (the device half of the cache plan —
    ``serve/kv_cache.py:CachePlan``).

    Attention families: one pool of ``num_blocks`` fixed-size token
    blocks per layer, laid out (blocks, kv heads, block_size, head_dim)
    and addressed through per-sequence block tables
    (``serve/kv_cache.py`` owns the allocator; block 0 is the reserved
    null block padding writes land in).  GQA pages K and V (``"k"``,
    ``"v"``); latent attention pages one ``"kv"`` pool of (blocks, 1,
    block_size, kv_lora_rank + rope): each position's [c, k_pe] once,
    read as keys and values alike.  Leading dense layers keep their own
    pools under ``"dense"``.

    SSM: state is O(1) per sequence — nothing to page.  The cache is one
    fixed-size state + conv-tail row PER BATCH ROW (``slots``), carried
    beside the block table (the block allocator still meters admission/
    eviction token budget; the tables themselves go unused by the model).

    Hybrid: both — SSM state rows for the backbone layers plus paged K/V
    pools for the weight-shared attention invocations.
    """
    dtype = dtype or cfg.act_dtype
    n = n_backbone_layers(cfg)
    kvh, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    if cfg.family in ("ssm", "hybrid"):
        if slots is None:
            raise ValueError(
                f"family={cfg.family!r} carries fixed-size SSM state per "
                "batch row — pass slots= to init_paged_cache")
        one = ssm.init_ssm_cache(cfg, slots, dtype)
        pages = {"ssm": jax.tree.map(
            lambda v: jnp.broadcast_to(v[None], (n,) + v.shape).copy(), one)}
        if cfg.family == "hybrid":
            ninv = n_shared_invocations(cfg)
            pages["k"] = jnp.zeros((ninv, num_blocks, kvh, block_size, hd),
                                   dtype)
            pages["v"] = jnp.zeros((ninv, num_blocks, kvh, block_size, hd),
                                   dtype)
        return pages

    def pools(layers_):
        return {name: jnp.zeros((layers_, num_blocks, heads, block_size,
                                 width), dtype)
                for name, (heads, width)
                in attention.cache_leaves(cfg).items()}

    pages = pools(n)
    if cfg.first_k_dense:
        pages["dense"] = pools(cfg.first_k_dense)
    return pages


def _reset_fresh_state(cache, lengths):
    """Zero the SSM state/conv rows of sequences starting from position 0
    this step (fresh admission or eviction resume) — the recurrent
    analogue of a fresh block table.  cache leaves: (n, b, ...);
    idle rows (lengths == 0, nothing fed) zero harmlessly."""
    fresh = lengths == 0                                  # (b,)

    def z(v):
        m = fresh.reshape((1, -1) + (1,) * (v.ndim - 2))
        return jnp.where(m, jnp.zeros_like(v), v)

    return jax.tree.map(z, cache)


def decode_paged(params, pages, block_table, tokens, lengths, n_valid, cfg,
                 *, rng=None, all_logits: bool = False):
    """One chunked step over the paged KV cache — decode AND prefill.

    tokens: (b, sc) — row r feeds its next ``n_valid[r]`` context tokens
    (decode ticks feed 1; chunked prefill feeds up to sc); positions are
    absolute: token i of row r sits at ``lengths[r] + i``.  Slots beyond
    a row's valid count (chunk padding, idle rows) write their K/V to the
    null block and are masked out of every live query.  Returns
    ``(logits, new_pages)`` with logits (b, vocab) taken at each row's
    LAST VALID position — the next-token distribution once the row's
    pending context is consumed.  With ``all_logits=True`` (a static
    flag — bake it into the jitted partial) logits are (b, sc, vocab),
    one next-token distribution per fed position: the speculative
    verifier reads every drafted position from ONE call.

    RNG contract (what makes continuous batching testable): ``rng`` is a
    (b, 2) array of per-request raw keys.  Inside, every token folds its
    row's key with its ABSOLUTE position, and all layer/call-site folds
    derive from that — so the stochastic bits a token draws depend only on
    (request key, position, layer, call site), never on batch neighbours,
    chunk boundaries, or admission order.  The same request with the same
    key therefore produces identical values served alone, in a full
    batch, or re-prefilled after an eviction.  ``paged_attn="fused_sc"``
    rides the same contract (attention QK^T draws under salt 29), which
    is why it REQUIRES ``rng``.

    Alternatively ``rng`` may be (b, sc, 2) PER-TOKEN keys, already
    resolved by the caller — the scheduler's content-chain mode
    (``rng_mode="content"``, forced by prefix caching) derives token t's
    key from the token CONTENT up to t instead of the request identity,
    so two requests sharing a prompt prefix draw bitwise-identical SC
    bits there and cached KV blocks are safe to share.  Layer/call-site
    folds are identical in both forms.

    SSM / hybrid families ride the same signature with the per-family
    cache plan's pages (``init_paged_cache``): SSM layers feed their
    chunk through :func:`ssm.ssm_stream` — token-recurrent, so a row's
    state is BIT-identical whatever the chunking or batch composition —
    and rows at ``lengths == 0`` (fresh admission or eviction resume)
    zero their state first.  Hybrid adds the weight-shared attention
    block over its own paged K/V pools per invocation.
    """
    if rng is None and getattr(cfg, "paged_attn", "unfused") == "fused_sc":
        raise ValueError("paged_attn='fused_sc' draws stochastic attention "
                         "logits from per-request keys; pass rng=(b, 2) "
                         "raw keys to decode_paged")
    b, sc = tokens.shape
    x = layers.embed(tokens, params["embed"]).astype(cfg.act_dtype)
    positions = lengths[:, None] + jnp.arange(sc)[None, :]      # (b, sc)
    keys = None
    if rng is not None:
        if rng.ndim == 3:
            keys = rng                  # (b, sc, 2) caller-resolved keys
        else:
            per_tok = jnp.broadcast_to(rng[:, None, :],
                                       (b, sc, rng.shape[-1]))
            keys = layers.fold_keys(per_tok, positions)         # (b, sc, 2)
    valid = jnp.arange(sc)[None, :] < n_valid[:, None]          # (b, sc)

    if cfg.family == "ssm":
        ssm_cache = _reset_fresh_state(pages["ssm"], lengths)

        def sbody(carry, scanned):
            xc, idx = carry
            lp, lc = scanned
            lkeys = layers.fold_keys(keys, idx)
            h, nc = ssm.ssm_stream(_norm(xc, lp["ln1"], cfg),
                                   lp["ssm"], cfg, lkeys, lc, valid)
            return (xc + h, idx + 1), nc

        (x, _), new_ssm = jax.lax.scan(
            sbody, (x, 0), (params["blocks"], ssm_cache))
        new_pages = {"ssm": new_ssm}
    elif cfg.family == "hybrid":
        ssm_cache = _reset_fresh_state(pages["ssm"], lengths)
        ninv, per = n_shared_invocations(cfg), cfg.attn_every
        grouped = _group(params["blocks"], ninv, per)
        gcache = _group(ssm_cache, ninv, per)

        def gbody(carry, scanned):
            xc, idx = carry
            gp, gc, kp, vp = scanned
            new_ssm = []
            for j in range(per):
                lp = jax.tree.map(lambda v: v[j], gp)
                lc = jax.tree.map(lambda v: v[j], gc)
                lkeys = layers.fold_keys(keys, idx * per + j)
                h, nc = ssm.ssm_stream(_norm(xc, lp["ln1"], cfg),
                                       lp["ssm"], cfg, lkeys, lc, valid)
                xc = xc + h
                new_ssm.append(nc)
            new_ssm = jax.tree.map(lambda *vs: jnp.stack(vs), *new_ssm)
            k2 = layers.fold_keys(keys, 10_000 + idx)
            h, kv = attention.paged_attention_block(
                _norm(xc, params["shared"]["ln1"], cfg),
                params["shared"]["attn"], cfg, positions,
                layers.fold_keys(k2, 17), {"k": kp, "v": vp}, block_table,
                lengths, n_valid)
            kp, vp = kv["k"], kv["v"]
            xc = xc + h
            xc = xc + layers.mlp(
                _norm(xc, params["shared"]["ln2"], cfg),
                params["shared"]["mlp"], cfg, layers.fold_keys(k2, 19))
            return (xc, idx + 1), (new_ssm, kp, vp)

        (x, _), (ssm_g, k_new, v_new) = jax.lax.scan(
            gbody, (x, 0), (grouped, gcache, pages["k"], pages["v"]))
        n = n_backbone_layers(cfg)
        new_pages = {"ssm": jax.tree.map(
            lambda v: v.reshape((n,) + v.shape[2:]), ssm_g),
            "k": k_new, "v": v_new}
    else:
        def body(carry, scanned):
            xc, idx = carry
            lp, lc = scanned
            lkeys = layers.fold_keys(keys, idx)
            h, lc = attention.paged_attention_block(
                _norm(xc, lp["ln1"], cfg), lp["attn"], cfg, positions,
                layers.fold_keys(lkeys, 11), lc, block_table, lengths,
                n_valid)
            xc = xc + h
            h = _ffn(_norm(xc, lp["ln2"], cfg), lp["ffn"], cfg,
                     layers.fold_keys(lkeys, 13))
            return (xc + h, idx + 1), lc

        x, new_pages = _run_layers(body, x, params, cfg, pages)

    x = _norm(x, params["final_norm"], cfg)
    if all_logits:
        return _logits(x, params, cfg, keys), new_pages
    last = jnp.maximum(n_valid - 1, 0)
    xl = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
    lkey = None
    if keys is not None:
        lkey = jnp.take_along_axis(
            keys, last[:, None, None], axis=1)[:, 0]            # (b, 2)
    logits = _logits(xl, params, cfg, lkey)
    return logits, new_pages


# --------------------------------------------------------------------------
# Decode (one token per sequence) — what `serve_step` lowers
# --------------------------------------------------------------------------


def decode_step(params, cache, tokens, lengths, cfg, *, rng=None,
                constrain=None, constrain_params=None):
    """tokens: (b,) next input ids; lengths: (b,) current cache fill (the new
    token writes at that index). Returns (logits (b, vocab), new_cache)."""
    cst = constrain or (lambda v, *a: v)
    cstp = constrain_params or (lambda t: t)
    x = layers.embed(tokens, params["embed"]).astype(cfg.act_dtype)[:, None]
    positions = lengths[:, None]
    new_lengths = lengths + 1

    if cfg.family in ("ssm", "hybrid"):
        if cfg.family == "hybrid":
            ninv, per = n_shared_invocations(cfg), cfg.attn_every
            grouped = _group(params["blocks"], ninv, per)
            gcache = _group(cache["ssm"], ninv, per)

            def gbody(carry, scanned):
                xc, idx = carry
                gp, gc, kc, vc = scanned
                new_ssm = []
                for j in range(per):
                    lp = cstp(jax.tree.map(lambda v: v[j], gp))
                    lc = jax.tree.map(lambda v: v[j], gc)
                    key = (None if rng is None
                           else jax.random.fold_in(rng, idx * per + j))
                    xc, nc = _apply_block(xc, lp, cfg, positions, key,
                                          cache=lc, cst=cst)
                    new_ssm.append(nc)
                new_ssm = jax.tree.map(lambda *vs: jnp.stack(vs), *new_ssm)
                k2 = (None if rng is None
                      else jax.random.fold_in(rng, 10_000 + idx))
                xc, kv = _apply_shared(
                    xc, params["shared"], cfg, positions, k2,
                    cache={"k": kc, "v": vc}, cache_length=new_lengths,
                    cst=cst)
                return (xc, idx + 1), (new_ssm, kv["k"], kv["v"])

            (x, _), (new_ssm_g, k_new, v_new) = jax.lax.scan(
                gbody, (x, 0),
                (grouped, gcache, cache["shared_k"], cache["shared_v"]))
            n = n_backbone_layers(cfg)
            new_cache = {
                "ssm": jax.tree.map(
                    lambda v: v.reshape((n,) + v.shape[2:]), new_ssm_g),
                "shared_k": k_new, "shared_v": v_new,
            }
        else:
            def body(carry, scanned):
                xc, idx = carry
                lp, lc = scanned
                key = None if rng is None else jax.random.fold_in(rng, idx)
                xc, nc = _apply_block(xc, cstp(lp), cfg, positions, key,
                                      cache=lc, cst=cst)
                return (xc, idx + 1), nc

            (x, _), new_ssm = jax.lax.scan(body, (x, 0),
                                           (params["blocks"], cache["ssm"]))
            new_cache = {"ssm": new_ssm}
    else:
        def body(carry, scanned):
            xc, idx = carry
            lp, lc = scanned
            key = None if rng is None else jax.random.fold_in(rng, idx)
            xc, lc = _apply_block(xc, cstp(lp), cfg, positions, key,
                                  cache=lc, cache_length=new_lengths,
                                  cst=cst)
            return (xc, idx + 1), lc

        x, new_cache = _run_layers(body, x, params, cfg, cache)

    x = _norm(x, params["final_norm"], cfg)
    logits = _logits(x[:, 0], params, cfg, rng)
    return cst(logits, "batch", "vocab"), new_cache


# --------------------------------------------------------------------------
# Prefill — builds the cache from a prompt; what the prefill shapes lower
# --------------------------------------------------------------------------


def prefill(params, inputs, cfg, max_len: int, *, rng=None, constrain=None,
            constrain_params=None):
    """Run the prompt through the model, returning (last-token logits, cache,
    lengths). inputs: (b, s) tokens or (b, s, d) embeddings; s <= max_len."""
    cst = constrain or (lambda v, *a: v)
    cstp = constrain_params or (lambda t: t)
    x = _embed_inputs(params, inputs, cfg, rng)
    b, s = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))

    def pad_kv(kv):
        """A layer's cache leaves (b, s, ...) -> (b, max_len, ...)."""
        return jax.tree.map(
            lambda v: jnp.pad(v, ((0, 0), (0, max_len - s))
                              + ((0, 0),) * (v.ndim - 2)), kv)

    if cfg.family in ("ssm", "hybrid"):
        if cfg.family == "hybrid":
            ninv, per = n_shared_invocations(cfg), cfg.attn_every
            grouped = _group(params["blocks"], ninv, per)

            def gbody(carry, gp):
                xc, idx = carry
                caches = []
                for j in range(per):
                    lp = cstp(jax.tree.map(lambda v: v[j], gp))
                    key = (None if rng is None
                           else jax.random.fold_in(rng, idx * per + j))
                    xc, nc = _apply_block(xc, lp, cfg, positions, key,
                                          cache="prefill", cst=cst)
                    caches.append(nc)
                ssm_c = jax.tree.map(lambda *vs: jnp.stack(vs), *caches)
                k2 = (None if rng is None
                      else jax.random.fold_in(rng, 10_000 + idx))
                xc, kv = _apply_shared(xc, params["shared"], cfg, positions,
                                       k2, cst=cst)
                kv = pad_kv(kv)
                return (xc, idx + 1), (ssm_c, kv["k"], kv["v"])

            (x, _), (ssm_g, kp, vp) = jax.lax.scan(
                _maybe_remat(gbody, cfg), (x, 0), grouped)
            n = n_backbone_layers(cfg)
            cache = {"ssm": jax.tree.map(
                lambda v: v.reshape((n,) + v.shape[2:]), ssm_g),
                "shared_k": kp, "shared_v": vp}
        else:
            def body(carry, lp):
                xc, idx = carry
                key = None if rng is None else jax.random.fold_in(rng, idx)
                xc, nc = _apply_block(xc, cstp(lp), cfg, positions, key,
                                      cache="prefill", cst=cst)
                return (xc, idx + 1), nc

            (x, _), ssm_c = jax.lax.scan(_maybe_remat(body, cfg), (x, 0),
                                         params["blocks"])
            cache = {"ssm": ssm_c}
    else:
        def body(carry, lp):
            xc, idx = carry
            key = None if rng is None else jax.random.fold_in(rng, idx)
            xc, kv = _apply_block(xc, cstp(lp), cfg, positions, key, cst=cst)
            return (xc, idx + 1), pad_kv(kv)

        x, cache = _run_layers(_maybe_remat(body, cfg), x, params, cfg)

    x = _norm(x, params["final_norm"], cfg)
    logits = _logits(x[:, -1], params, cfg, rng)
    lengths = jnp.full((b,), s, jnp.int32)
    return cst(logits, "batch", "vocab"), cache, lengths
