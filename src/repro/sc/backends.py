"""The registered SC matmul backends.

Six realizations of the paper's in-memory MUL engine lifted to matmul
shape, all sharing the canonical encoding in :mod:`repro.sc.encoding` and
all reached exclusively through :func:`repro.sc.sc_dot` (a seventh,
``array`` — the array-level architecture simulator — lives in
:mod:`repro.arch.backend` and registers lazily on first use):

* ``exact``           — plain MXU matmul (deterministic reference).
* ``moment``          — CLT moment-matched jnp path: 3 dots + 1 Gaussian
                        draw reproduce the engine's error statistics at
                        O(1) cost per product (see the derivation below).
* ``bitexact``        — paper-faithful Monte-Carlo: every scalar product
                        samples a Binomial(nbit, P_x·P_w) pop-count.
* ``pallas_moment``   — the fused moment Pallas kernel (kernels/sc_mac.py):
                        the three moment dots ride one pass over the
                        operand tiles with VMEM-resident accumulators.
* ``pallas_bitexact`` — the packed Pallas engine (kernels/sc_mul.py)
                        lifted to matmul shape: one bank of 32-cell words
                        per (i, k, j) scalar product, two-pulse AND +
                        SWAR pop-count, then the signed reduction over K.
* ``pallas_fused``    — the fully fused engine (kernels/sc_fused.py):
                        encoding, counter-based RNG, thresholding and
                        pop-count accumulation in ONE autotuned kernel.
                        Draws the SAME counter-based stream as
                        ``pallas_bitexact`` (``sc/ctr_rng.py``), so the
                        two are bit-identical per key — this is the
                        default fast path ``models/layers.py:dense``
                        upgrades ``pallas_bitexact`` to.

Moment derivation (shared by ``moment`` / ``pallas_moment``): by CLT the
signed MAC output is Normal(mean, var) with

    mean = x @ w                          (signed, scaled)
    var  = scale²·[(p_x @ p_w) − (p_x² @ p_w²)] / nbit

First/second moments match ``bitexact`` exactly; the binomial→normal
deviation is < 1 % KS distance at nbit ≥ 256.

Memory classes: ``exact``/``moment``/``pallas_moment`` are O(MN) and run
at model scale; ``bitexact`` is O(M·K·N) and ``pallas_bitexact`` is
O(M·K·N·nbit/8) entropy bytes — validation-scale only, exactly like
running the real cell arrays would be.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.sc import autotune, ctr_rng, encoding
from repro.sc.config import ScConfig
from repro.sc.registry import register_backend, register_rows_backend


@register_backend("exact")
def exact(key, x, w, cfg: ScConfig):
    del key
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


@register_backend("moment")
def moment(key, x, w, cfg: ScConfig):
    sx, px, scx = encoding.encode(x, cfg)
    sw, pw, scw = encoding.encode(w, cfg)
    mean = jnp.dot(sx * px, sw * pw, preferred_element_type=jnp.float32)
    # Var of each product estimate = p(1-p)/nbit with p = p_x·p_w;
    # Σ_k p_k = px@pw, Σ_k p_k² = px²@pw² (p_x,p_w independent across k).
    sum_p = jnp.dot(px, pw, preferred_element_type=jnp.float32)
    sum_p2 = jnp.dot(px * px, pw * pw, preferred_element_type=jnp.float32)
    var = jnp.maximum(sum_p - sum_p2, 0.0) / cfg.nbit
    noise = jax.random.normal(key, mean.shape, dtype=mean.dtype)
    return (mean + noise * jnp.sqrt(var)) * (scx * scw)


@register_backend("bitexact")
def bitexact(key, x, w, cfg: ScConfig):
    sx, px, scx = encoding.encode(x, cfg)
    sw, pw, scw = encoding.encode(w, cfg)
    p_prod = px[..., :, None] * pw[None, ...]        # (M, K, N) = P_x·P_w
    sign = sx[..., :, None] * sw[None, ...]
    counts = jax.random.binomial(key, n=float(cfg.nbit), p=p_prod)
    est = counts.astype(jnp.float32) / cfg.nbit      # ≈ P_x·P_w per product
    return jnp.sum(sign * est, axis=-2) * (scx * scw)


@register_backend("pallas_moment")
def pallas_moment(key, x, w, cfg: ScConfig):
    from repro.kernels import sc_mac as sc_mac_kernel
    sx, px, scx = encoding.encode(x, cfg)
    sw, pw, scw = encoding.encode(w, cfg)
    xs = encoding.pad_to(sx * px, max(1, min(cfg.block_m, x.shape[0])), 0)
    xs = encoding.pad_to(xs, min(cfg.block_k, x.shape[1]), 1)
    ws = encoding.pad_to(sw * pw, min(cfg.block_k, x.shape[1]), 0)
    ws = encoding.pad_to(ws, max(1, min(cfg.block_n, w.shape[1])), 1)
    noise = jax.random.normal(key, (xs.shape[0], ws.shape[1]), jnp.float32)
    out = sc_mac_kernel.sc_mac_fused(
        xs, ws, noise, nbit=cfg.nbit, block_m=cfg.block_m,
        block_n=cfg.block_n, block_k=cfg.block_k)
    return out[: x.shape[0], : w.shape[1]] * (scx * scw)


# rows-per-tile of the packed MUL kernel; small because each row already
# carries NSLICES·(nbit/32) uniform words
_MUL_BLOCK_M = 8


@register_backend("pallas_bitexact")
def pallas_bitexact(key, x, w, cfg: ScConfig):
    from repro.kernels import sc_mul as sc_mul_kernel
    assert cfg.nbit % sc_mul_kernel.LANE_BITS == 0, \
        "pallas_bitexact needs nbit to be a multiple of 32 (packed words)"
    nwords = cfg.nbit // sc_mul_kernel.LANE_BITS
    sx, px, scx = encoding.encode(x, cfg)
    sw, pw, scw = encoding.encode(w, cfg)
    m, k = x.shape
    n = w.shape[1]
    # one packed MUL (its own bank of nbit cells) per (i, k, j) product
    px_flat = jnp.broadcast_to(px[:, :, None], (m, k, n)).reshape(-1)
    pw_flat = jnp.broadcast_to(pw[None, :, :], (m, k, n)).reshape(-1)
    pxf = encoding.pad_to(encoding.to_fx16(px_flat), _MUL_BLOCK_M, 0)
    pwf = encoding.pad_to(encoding.to_fx16(pw_flat), _MUL_BLOCK_M, 0)
    # entropy from the PINNED counter-based stream (sc/ctr_rng.py): the
    # fused engine regenerates exactly these words in-kernel, which is
    # what makes pallas_fused a bit-identical drop-in for this backend.
    kx, ky = jax.random.split(key)
    rx = ctr_rng.operand_stream(ctr_rng.raw_key(kx), pxf.shape[0], nwords)
    ry = ctr_rng.operand_stream(ctr_rng.raw_key(ky), pxf.shape[0], nwords)
    counts = sc_mul_kernel.sc_mul_popcount(
        pxf, pwf, rx, ry, block_m=_MUL_BLOCK_M)
    counts3 = counts[: m * k * n].reshape(m, k, n)
    # exact signed integer reduction over K: associative, so it matches
    # the fused kernel's per-tile accumulation bit-for-bit
    sign_i = sx.astype(jnp.int32)[:, :, None] * sw.astype(jnp.int32)[None]
    total = jnp.sum(sign_i * counts3, axis=1)
    return total.astype(jnp.float32) / cfg.nbit * (scx * scw)


def _fused_engine(keys4, x, w, cfg: ScConfig, scx, scw, *, row_keys):
    """The ONE scale/pad/launch/rescale recipe behind both fused entry
    points.  Sharing it is what keeps the documented bit-identity
    contracts (fused == packed; rows mode == per-row single calls)
    honest: per-call and per-row modes differ ONLY in the key rows, the
    encoding scale shape, and the kernel's ``row_keys`` flag.

    keys4: (M, 4) raw per-row key words [kx0, kx1, ky0, ky1];
    scx: () in per-call mode, (M, 1) in rows mode (``encode``'s max-abs
    formula either way).
    """
    from repro.kernels import sc_fused as sc_fused_kernel
    assert cfg.nbit % sc_fused_kernel.LANE_BITS == 0, \
        "pallas_fused needs nbit to be a multiple of 32 (packed words)"
    m, k = x.shape
    n = w.shape[1]
    tile = autotune.get_tile(m, k, n, cfg.nbit)
    total = sc_fused_kernel.sc_fused_popcount(
        keys4, x / scx, w / scw, k_orig=k, n_orig=n, nbit=cfg.nbit,
        levels=1 << cfg.operand_bits, quantize=cfg.quantize,
        row_keys=row_keys, **tile.kwargs())
    return total.astype(jnp.float32) / cfg.nbit * (scx * scw)


@register_backend("pallas_fused")
def pallas_fused(key, x, w, cfg: ScConfig):
    """One-kernel fast path: encode + draw + threshold + pop-count fused.

    Bit-identical to ``pallas_bitexact`` under the same key (shared
    counter-based stream, exact integer accumulation) while never
    materializing a bitstream outside VMEM.  Tile sizes come from the
    autotuner cache (heuristic on miss) and cannot affect the bits.
    """
    scx = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)   # encoding.encode scale
    scw = jnp.maximum(jnp.max(jnp.abs(w)), 1e-30)
    kx, ky = jax.random.split(key)
    keys4 = jnp.broadcast_to(
        jnp.concatenate([ctr_rng.raw_key(kx), ctr_rng.raw_key(ky)])[None],
        (x.shape[0], 4))
    return _fused_engine(keys4, x, w, cfg, scx, scw, row_keys=False)


@register_rows_backend("pallas_fused")
def pallas_fused_rows(keys, x, w, cfg: ScConfig):
    """Per-row-key fused path (the serve engine's vmap replacement).

    keys: (M, 2) raw keys — row i's bits AND encoding scale depend on
    ``keys[i]`` and ``x[i]`` alone, and equal the single-row call
    ``pallas_fused(keys[i], x[i:i+1], w, cfg)`` bit-for-bit (the kernel
    drops the row term from the product index in ``row_keys`` mode).
    """
    scx = jnp.maximum(jnp.max(jnp.abs(x), axis=1, keepdims=True), 1e-30)
    scw = jnp.maximum(jnp.max(jnp.abs(w)), 1e-30)
    split = jax.vmap(jax.random.split)(ctr_rng.raw_key(keys))   # (M, 2, 2)
    keys4 = jnp.concatenate([split[:, 0], split[:, 1]], axis=-1).astype(
        jnp.uint32)
    return _fused_engine(keys4, x, w, cfg, scx, scw, row_keys=True)
