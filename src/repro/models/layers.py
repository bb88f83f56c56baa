"""Primitive layers: RMSNorm, Linear (SC-routable), SwiGLU MLP, RoPE, embed.

Every matmul in the stack goes through :func:`dense`, which routes to the
SC substrate registry when ``cfg.sc_backend != "exact"`` — any registered
backend (jnp moment/bitexact or the Pallas kernels) is selectable per
model config, and all of them are trainable through the straight-through
custom_vjp at the ``sc_dot`` dispatch boundary.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import sc
from repro.models.params import ParamSpec


def rms_norm(x, weight, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(dt) * weight


# ----------------------------- Matmul sites ---------------------------------
#
# Every weight matmul in the stack dispatches through :func:`dense` (or
# :func:`expert_dense`) under a NAMED SITE.  A site's salt is folded into
# the caller's key before the stochastic draw, so two sites fed the same
# (request, position) key still draw independent SC bits.  The salts are
# part of the bit-reproducibility contract — per-request rng invariance
# and the committed benchmark baselines both replay them — so an existing
# site must never be renumbered; new sites take fresh salts.  ``None``
# means the site consumes the caller's key unfolded (the pre-table
# convention for the first matmul of a block, kept for bit-compat).
#
# Folds applied OUTSIDE this table (for context when adding salts):
# per-layer index folds at the scan roots, 10_000+idx for the hybrid
# shared block, 11/13 (attn/ffn inside a block), 17/19 (shared
# attn/mlp), 23+j (qkv per-token path), 29 (fused_sc attention draw),
# 0x5EED (sampling), 0xC047 (content chains).

SITES: dict = {
    "mlp_wi": None,          # raw block key (pre-table convention)
    "mlp_wo": 1,
    "attn_qkv": None,        # _project_qkv folds 23+j / splits internally
    "attn_wo": None,         # attention folds its own okey
    "ssm_out": 3,
    "moe_router": 31,
    "moe_wi": 37,
    "moe_wo": 41,
    "ssm_wz": 47,
    "ssm_wx": 53,
    "ssm_wB": 59,
    "ssm_wC": 61,
    "ssm_wdt": 67,
    "unembed": 71,
    "frontend_proj": 73,
    "mla_q": 79,
    "mla_kv_a": 83,
    "mla_kv_b": 89,
    "mla_uk": 97,
    "mla_uv": 101,
}


def site_key(key, site: str, data=None):
    """Per-site key folding: ``key`` folded with ``site``'s registered
    salt, then (optionally) with ``data`` — an extra int or int array for
    sub-site structure such as an expert index or a chunk index.  ``key``
    may be None (passed through), a raw (2,) key, or a (..., 2) array of
    per-row keys (the fold broadcasts — see :func:`fold_keys`)."""
    salt = SITES[site]
    k = key if salt is None else fold_keys(key, salt)
    return k if data is None else fold_keys(k, data)


def fold_keys(key, data):
    """``jax.random.fold_in`` broadcast over an array of raw PRNG keys.

    ``key`` may be None (passed through), one raw uint32 key of shape (2,),
    or an array of keys with leading batch dims, shape (..., 2).  ``data``
    is an int (same fold for every key) or an int array matching the
    leading dims (per-key fold — e.g. per-token positions).
    """
    if key is None:
        return None
    if key.ndim == 1:
        return jax.random.fold_in(key, data)
    flat = key.reshape(-1, key.shape[-1])
    data = jnp.broadcast_to(jnp.asarray(data, jnp.uint32), key.shape[:-1])
    folded = jax.vmap(jax.random.fold_in)(flat, data.reshape(-1))
    return folded.reshape(key.shape)


def _dense_rows(keys, x, w, sc_cfg):
    """Per-row SC dispatch: row i of ``x`` draws its stochastic bits (and
    its max-abs encoding scale) from ``keys[i]`` ALONE, so each row's
    output is independent of its batch neighbours — the property the
    continuous-batching serve engine relies on (same request + same key
    => same values whatever shares the batch).  Routed through
    ``sc.sc_dot_rows``: backends with a native batched rows path
    (``pallas_fused``) run one kernel launch, the rest vmap."""
    lead = x.shape[:-1]
    xf = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    kf = keys.reshape(-1, keys.shape[-1])
    w32 = w.astype(jnp.float32)
    yf = sc.sc_dot_rows(kf, xf, w32, sc_cfg)
    return yf.reshape(*lead, w.shape[-1]).astype(x.dtype)


def dense(x, w, cfg, key=None, bias=None, site: str = "dense",
          precision=None):
    """x @ w with the configured multiplication substrate.

    x: (..., K); w: (K, N) (or pre-reshaped 2-D view of a fused projection).
    Stochastic backends REQUIRE a PRNG key: a stochastic ``cfg.sc_backend``
    with ``key=None`` raises (naming ``site``) instead of silently falling
    back to the exact path — every caller must thread a key so the whole
    stack actually runs on the substrate it was configured for.  ``key``
    may also be an ARRAY of raw keys whose leading dims match ``x``'s (one
    key per row): the stochastic draw then vmaps per row, making every
    row's output (noise AND encoding scale) a function of its own key and
    data only — what the paged serve engine passes so results are
    invariant to batch composition.  Inside a ``sc.use_mesh(mesh)`` scope
    stochastic matmuls shard over the mesh via ``sc_dot_sharded`` (rows
    over the data axes, contraction over model with a psum merge) — the
    scope is consulted at trace time, so callers scale across devices with
    no signature changes (per-row keys are a single-mesh-slice feature and
    take precedence when both apply).  ``precision`` reaches the exact
    substrate's ``jnp.dot`` (the float32 router asks for ``HIGHEST``).
    """
    if cfg.sc_backend == "exact":
        y = jnp.dot(x, w.astype(x.dtype), precision=precision)
    elif key is None:
        raise ValueError(
            f"layers.dense at site {site!r}: sc_backend="
            f"{cfg.sc_backend!r} is stochastic but key=None — every "
            "stochastic matmul draws from a PRNG key; pass rng= to the "
            "model entry point (or set sc_backend='exact')")
    elif key.ndim > 1:
        # fast_backend upgrades pallas_bitexact to the bit-identical
        # fused engine — same key, same bits, one kernel launch
        sc_cfg = sc.ScConfig(
            backend=sc.fast_backend(cfg.sc_backend, cfg.sc_nbit),
            nbit=cfg.sc_nbit, device=sc.current_device_profile())
        y = _dense_rows(key, x, w, sc_cfg)
    else:
        sc_cfg = sc.ScConfig(
            backend=sc.fast_backend(cfg.sc_backend, cfg.sc_nbit),
            nbit=cfg.sc_nbit, device=sc.current_device_profile())
        scope = sc.active_mesh()
        if scope is not None:
            mesh, rules = scope
            y = sc.sc_dot_sharded(
                key, x.astype(jnp.float32), w.astype(jnp.float32), sc_cfg,
                mesh=mesh, rules=rules).astype(x.dtype)
        else:
            y = sc.sc_dot(key, x.astype(jnp.float32), w.astype(jnp.float32),
                          sc_cfg).astype(x.dtype)
    if bias is not None:
        y = y + bias.astype(y.dtype)
    return y


# ----------------------------- MLP (SwiGLU) --------------------------------


def mlp_specs(cfg, width: int = 0):
    """SwiGLU (or GELU) MLP of hidden ``width`` (0 -> ``cfg.d_ff``)."""
    d, f = cfg.d_model, width or cfg.d_ff
    wi_cols = 2 * f if cfg.mlp_variant == "swiglu" else f
    return {
        "wi": ParamSpec((d, wi_cols), ("embed", "mlp"), "scaled"),
        "wo": ParamSpec((f, d), ("mlp", "embed"), "scaled"),
    }


def mlp(x, p, cfg, key=None, constrain=None):
    cst = constrain or (lambda v, *a: v)
    with jax.named_scope("mlp_in"):
        h = dense(x, p["wi"], cfg, site_key(key, "mlp_wi"), site="mlp_wi")
    # TP over the hidden dim, full sequence inside the block (Megatron
    # pattern): without this pin Shardy reshards the multi-GB hidden between
    # seq-sharded and mlp-sharded layouts per invocation (observed 7.5 GB
    # collective-permutes on zamba2's shared block — EXPERIMENTS §Perf).
    h = cst(h, "batch", "seq", "mlp")
    if cfg.mlp_variant == "swiglu":
        gate, up = jnp.split(h, 2, axis=-1)
        act = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    else:
        act = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    act = cst(act, "batch", "seq", "mlp")
    with jax.named_scope("mlp_out"):
        return dense(act, p["wo"], cfg, site_key(key, "mlp_wo"),
                     site="mlp_wo")


# ----------------------------- RoPE -----------------------------------------


def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (b, s, h, d); positions: (b, s) int32."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                       # (d/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (b, s, d/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ----------------------------- Embedding ------------------------------------


def embed_specs(cfg):
    return {"table": ParamSpec((cfg.vocab, cfg.d_model), ("vocab", "embed"))}


def embed(tokens, p):
    return jnp.take(p["table"], tokens, axis=0)


def unembed(x, p, cfg, key=None):
    return dense(x, p["table"].T, cfg, key, site="unembed")


# ----------------------------- Expert matmul --------------------------------


def expert_dense(x, w, cfg, key=None, site: str = "moe_wi"):
    """Per-expert batched matmul: (b, e, c, d) @ (e, d, f) -> (b, e, c, f).

    The MoE capacity-buffer contraction.  Exact mode is one einsum (the
    Megablocks-style dispatch keeps it dense).  Stochastic backends scan
    over the expert axis — one ``sc_dot_rows`` launch per expert, traced
    ONCE by ``jax.lax.scan`` — so each (c, d)x(d, f) expert shape reaches
    the kernel autotuner as its own (possibly ragged) problem, and every
    capacity slot's draw derives from its own key folded with ``site``'s
    salt and the expert index alone.

    ``key`` is None (exact only — stochastic raises like :func:`dense`),
    one raw (2,) key (broadcast to every slot), or a (b, e, c, 2) buffer
    of per-slot keys the caller dispatched alongside ``x`` (the paged
    engine's per-token keys gathered through the same token->slot
    scatter, so a token keeps its own key whichever expert it lands in).
    """
    if cfg.sc_backend == "exact":
        return jnp.einsum("becd,edf->becf", x, w.astype(x.dtype))
    if key is None:
        raise ValueError(
            f"layers.expert_dense at site {site!r}: sc_backend="
            f"{cfg.sc_backend!r} is stochastic but key=None — pass a key "
            "so expert matmuls draw on the substrate")
    b, e, c, d = x.shape
    if key.ndim == 1:
        key = jnp.broadcast_to(key, (b, e, c, 2))
    eidx = jnp.broadcast_to(jnp.arange(e)[None, :, None], (b, e, c))
    keys = site_key(key, site, eidx)                    # (b, e, c, 2)
    sc_cfg = sc.ScConfig(
        backend=sc.fast_backend(cfg.sc_backend, cfg.sc_nbit),
        nbit=cfg.sc_nbit, device=sc.current_device_profile())

    def one_expert(_, inp):
        we, xe, ke = inp              # (d, f), (b, c, d), (b, c, 2)
        return None, _dense_rows(ke, xe, we, sc_cfg)

    _, y = jax.lax.scan(
        one_expert, None,
        (w, jnp.moveaxis(x, 1, 0), jnp.moveaxis(keys, 1, 0)))
    return jnp.moveaxis(y, 0, 1)                        # (b, e, c, f)
