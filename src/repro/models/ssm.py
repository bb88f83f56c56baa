"""Mamba2 (SSD — state-space duality) layer: chunked train/prefill scan and
O(1)/token recurrent decode. [arXiv:2405.21060]

Projections are kept *split* (wz/wx/wB/wC/wdt instead of one fused in_proj)
so tensor parallelism is clean: the wide d_inner tensors shard over the
``model`` axis (per-head sharding falls out since heads = d_inner/headdim),
while the small B/C/dt projections replicate — the SSM analogue of GQA's
"shard Q heads, replicate tiny KV".

The chunked SSD algorithm (chunk length L):
  intra-chunk:  y_t += Σ_{j≤t}  (C_t·B_j) · exp(cum_t − cum_j) · dt_j · x_j
  chunk state:  S_c  = Σ_j exp(cum_L − cum_j) · dt_j · B_j ⊗ x_j
  carry (scan): H_c  = exp(Σ_chunk dA) · H_{c−1} + S_c
  inter-chunk:  y_t += exp(cum_t) · C_t · H_{c−1}
with cum the within-chunk cumulative sum of dA = dt·A (A < 0). Decode keeps
H directly: H ← exp(dA)·H + dt·B⊗x, y = C·H + D·x.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers
from repro.models.params import ParamSpec


def ssm_specs(cfg):
    d, di, n, h = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    k = cfg.ssm_conv
    return {
        "wz": ParamSpec((d, di), ("embed", "ssm_inner"), "scaled"),
        "wx": ParamSpec((d, di), ("embed", "ssm_inner"), "scaled"),
        "wB": ParamSpec((d, n), ("embed", "ssm_state"), "scaled"),
        "wC": ParamSpec((d, n), ("embed", "ssm_state"), "scaled"),
        "wdt": ParamSpec((d, h), ("embed", "heads"), "scaled"),
        "conv_x": ParamSpec((k, di), ("conv", "ssm_inner"), "scaled"),
        "conv_B": ParamSpec((k, n), ("conv", "ssm_state"), "scaled"),
        "conv_C": ParamSpec((k, n), ("conv", "ssm_state"), "scaled"),
        "A_log": ParamSpec((h,), ("heads",), "zeros"),
        "D": ParamSpec((h,), ("heads",), "ones"),
        "dt_bias": ParamSpec((h,), ("heads",), "zeros"),
        "norm": ParamSpec((di,), ("ssm_inner",), "ones"),
        "out": ParamSpec((di, d), ("ssm_inner", "embed"), "scaled"),
    }


def _causal_conv(x, w, cache=None):
    """Depthwise causal conv over seq. x: (b,s,c), w: (k,c).

    With a cache (b, k-1, c) performs streaming decode (s==1) and returns
    the updated cache; without, pads with zeros (train/prefill).
    """
    k = w.shape[0]
    if cache is None:
        xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
        new_cache = None
    else:
        xp = jnp.concatenate([cache, x], axis=1)
        new_cache = xp[:, -(k - 1):, :] if k > 1 else cache
    out = sum(xp[:, i:i + x.shape[1], :] * w[i] for i in range(k))
    return out, new_cache


def _project(x, p, cfg, key=None):
    """Input/B/C/dt projections through the substrate, one site each.

    ``key`` is None, a raw (2,) key, or per-token (b, s, 2) keys — each
    projection folds its own site salt so the five draws are independent.
    """
    z = layers.dense(x, p["wz"], cfg, layers.site_key(key, "ssm_wz"),
                     site="ssm_wz")
    xin = layers.dense(x, p["wx"], cfg, layers.site_key(key, "ssm_wx"),
                       site="ssm_wx")
    B = layers.dense(x, p["wB"], cfg, layers.site_key(key, "ssm_wB"),
                     site="ssm_wB")
    C = layers.dense(x, p["wC"], cfg, layers.site_key(key, "ssm_wC"),
                     site="ssm_wC")
    dt = jax.nn.softplus(
        layers.dense(x, p["wdt"], cfg, layers.site_key(key, "ssm_wdt"),
                     site="ssm_wdt").astype(jnp.float32)
        + p["dt_bias"].astype(jnp.float32))
    return z, xin, B, C, dt


def ssd_chunked(x, dt, A, B, C, chunk: int):
    """Chunked SSD scan. x: (b,s,h,p); dt: (b,s,h); A: (h,)<0; B,C: (b,s,n)."""
    b, s, h, pdim = x.shape
    n = B.shape[-1]
    L = min(chunk, s)
    # Front-pad to a chunk multiple: zero inputs are exact no-ops for SSD
    # (they add nothing to any state or output — see ssm_block docstring).
    pad = (-s) % L
    if pad:
        x = jnp.pad(x, ((0, 0), (pad, 0), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (pad, 0), (0, 0)))
        B = jnp.pad(B, ((0, 0), (pad, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (pad, 0), (0, 0)))
        s = s + pad
    nc = s // L

    def ch(v, extra=()):
        return v.reshape((b, nc, L) + v.shape[2:])

    xc = ch(x).astype(jnp.float32)
    dtc = ch(dt)                                       # (b,nc,L,h)
    Bc = ch(B).astype(jnp.float32)                     # (b,nc,L,n)
    Cc = ch(C).astype(jnp.float32)
    dA = dtc * A                                       # (b,nc,L,h), negative
    cum = jnp.cumsum(dA, axis=2)                       # within-chunk cumsum

    # Intra-chunk (dual / attention-like form). The decay exponent is masked
    # BEFORE exp so non-causal pairs (positive exponents) cannot overflow.
    att = jnp.einsum("bcln,bcjn->bclj", Cc, Bc)        # (b,nc,L,L)
    diff = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # (b,nc,L,L,h)
    causal = jnp.tril(jnp.ones((L, L), bool))[None, None, :, :, None]
    decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))
    w = att[..., None] * decay                         # (b,nc,L,L,h)
    y_intra = jnp.einsum("bcljh,bcjh,bcjhp->bclhp", w, dtc, xc)

    # Chunk states + inter-chunk carry.
    last = cum[:, :, -1:, :]                           # (b,nc,1,h)
    sdecay = jnp.exp(last - cum)                       # (b,nc,L,h)
    S = jnp.einsum("bcln,bclh,bclhp->bchnp", Bc, sdecay * dtc, xc)
    chunk_decay = jnp.exp(last[:, :, 0, :])            # (b,nc,h)

    def carry_step(Hprev, inp):
        Sc, dc = inp
        Hnew = dc[..., None, None] * Hprev + Sc
        return Hnew, Hprev

    H0 = jnp.zeros((b, h, n, pdim), jnp.float32)
    H_final, Hprevs = jax.lax.scan(
        carry_step, H0,
        (jnp.moveaxis(S, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    Hprevs = jnp.moveaxis(Hprevs, 0, 1)                # (b,nc,h,n,p)

    y_inter = jnp.einsum("bcln,bclh,bchnp->bclhp",
                         Cc, jnp.exp(cum), Hprevs)
    y = (y_intra + y_inter).reshape(b, s, h, pdim)
    if pad:
        y = y[:, pad:]
    return y, H_final


def ssm_block(x, p, cfg, key=None, *, cache=None, constrain=None):
    """Full Mamba2 block. Returns (out, new_cache).

    cache semantics: None -> train (no cache out); the string "prefill" ->
    chunked pass that also returns a decode cache (conv tails + final SSD
    state); a dict(conv_x, conv_B, conv_C, state) -> one-token decode.

    Sharding: the SSD time scan is sequential, so the sequence axis CANNOT
    stay TP-sharded inside the block — instead the wide d_inner/head axis
    shards over `model` (the SSM analogue of head-TP) and the constraints
    below pin that layout so the partitioner doesn't reshard the multi-GB
    hidden tensors per layer.
    """
    cst = constrain or (lambda v_, *a: v_)
    b, s, _ = x.shape
    h, pdim, n = cfg.ssm_heads, cfg.ssm_headdim, cfg.ssm_state
    if key is not None and key.ndim == 1 and s > 1:
        # Chunked-pass key folding: one raw key fans out PER SSD CHUNK
        # (position t draws from fold(key, t // ssm_chunk)), so the
        # projections' stochastic draws align with the scan's chunk grid.
        # Decode (s == 1) keeps the raw key — the engine already varies
        # it per tick; per-token (b, s, 2) keys pass through untouched
        # (the paged path folds per absolute position upstream).
        ck = jnp.broadcast_to(jnp.arange(s)[None, :] // cfg.ssm_chunk,
                              (b, s))
        key = layers.fold_keys(jnp.broadcast_to(key, (b, s, 2)), ck)
    z, xin, B, C, dt = _project(x, p, cfg, key)
    z = cst(z, "batch", "seq", "ssm_inner")
    xin = cst(xin, "batch", "seq", "ssm_inner")
    A = -jnp.exp(p["A_log"].astype(jnp.float32))       # (h,) negative

    if cache is None or cache == "prefill":
        k = cfg.ssm_conv
        raw = (xin, B, C)
        xin, _ = _causal_conv(xin, p["conv_x"])
        B, _ = _causal_conv(B, p["conv_B"])
        C, _ = _causal_conv(C, p["conv_C"])
        xin = jax.nn.silu(xin.astype(jnp.float32)).astype(x.dtype)
        xh = cst(xin.reshape(b, s, h, pdim), "batch", "seq", "heads", None)
        y, H_final = ssd_chunked(xh, dt, A, B.astype(jnp.float32),
                                 C.astype(jnp.float32), cfg.ssm_chunk)
        y = y + p["D"].astype(jnp.float32)[None, None, :, None] \
            * xh.astype(jnp.float32)
        if cache == "prefill":
            rx, rB, rC = raw
            new_cache = {
                "conv_x": rx[:, -(k - 1):, :],
                "conv_B": rB[:, -(k - 1):, :],
                "conv_C": rC[:, -(k - 1):, :],
                "state": H_final,
            }
        else:
            new_cache = None
    else:
        xin, cx = _causal_conv(xin, p["conv_x"], cache["conv_x"])
        B, cB = _causal_conv(B, p["conv_B"], cache["conv_B"])
        C, cC = _causal_conv(C, p["conv_C"], cache["conv_C"])
        xin = jax.nn.silu(xin.astype(jnp.float32)).astype(x.dtype)
        xh = xin.reshape(b, 1, h, pdim).astype(jnp.float32)
        dA = jnp.exp(dt[:, 0] * A)                     # (b,h)
        Bf = B[:, 0].astype(jnp.float32)               # (b,n)
        Cf = C[:, 0].astype(jnp.float32)
        state = cache["state"]                         # (b,h,n,p)
        state = dA[..., None, None] * state + jnp.einsum(
            "bn,bh,bhp->bhnp", Bf, dt[:, 0], xh[:, 0])
        y = jnp.einsum("bn,bhnp->bhp", Cf, state)[:, None]
        y = y + p["D"].astype(jnp.float32)[None, None, :, None] * xh
        new_cache = {"conv_x": cx, "conv_B": cB, "conv_C": cC, "state": state}

    y = y.reshape(b, s, cfg.d_inner).astype(x.dtype)
    y = cst(y, "batch", "seq", "ssm_inner")
    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype)   # gate
    y = layers.rms_norm(y, p["norm"], cfg.rms_norm_eps)
    okey = layers.site_key(key, "ssm_out")
    return layers.dense(y, p["out"], cfg, okey, site="ssm_out"), new_cache


def ssm_stream(x, p, cfg, key, cache, valid):
    """Chunk-width-invariant SSM feed for the paged engine.

    Scans :func:`ssm_block`'s one-token recurrent update over the chunk
    axis, merging the cache only at VALID positions — so a request's
    state (and therefore its tokens) is bit-identical whether its
    context arrives in one chunk, many chunks, or is replayed after an
    eviction: token t's update is always the same FP op sequence
    ``f(state_{t-1}, x_t)``, never a reassociated chunked scan.  Invalid
    positions (chunk padding, idle rows) compute and discard — their
    cache merge is a no-op, matching the null-block convention of
    ``attention.paged_scatter``.

    x: (b, sc, d); key: None or per-token (b, sc, 2); cache: the dict
    of :func:`init_ssm_cache`; valid: (b, sc) bool.  Returns
    (y (b, sc, d), new_cache).
    """

    def merge(v, new, old):
        keep = v.reshape((-1,) + (1,) * (old.ndim - 1))
        return jnp.where(keep, new, old)

    def step(carry, inp):
        xt, kt, vt = (inp if key is not None
                      else (inp[0], None, inp[1]))   # (b,d), (b,2)|None, (b,)
        yt, nc = ssm_block(xt[:, None], p, cfg, kt, cache=carry)
        nc = jax.tree.map(lambda new, old: merge(vt, new, old), nc, carry)
        return nc, yt[:, 0]

    xs = ((jnp.moveaxis(x, 1, 0), jnp.moveaxis(valid, 1, 0))
          if key is None else
          (jnp.moveaxis(x, 1, 0), jnp.moveaxis(key, 1, 0),
           jnp.moveaxis(valid, 1, 0)))
    new_cache, y = jax.lax.scan(step, cache, xs)
    return jnp.moveaxis(y, 0, 1), new_cache


def init_ssm_cache(cfg, batch: int, dtype=jnp.float32):
    k, di, n = cfg.ssm_conv, cfg.d_inner, cfg.ssm_state
    return {
        "conv_x": jnp.zeros((batch, k - 1, di), dtype),
        "conv_B": jnp.zeros((batch, k - 1, n), dtype),
        "conv_C": jnp.zeros((batch, k - 1, n), dtype),
        "state": jnp.zeros((batch, cfg.ssm_heads, n, cfg.ssm_headdim),
                           jnp.float32),
    }
