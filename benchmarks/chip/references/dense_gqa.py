"""Plain forward of a dense decoder with grouped-query attention
(Qwen2, Yi and Llama blocks): ``jax.numpy`` only, float32, every matmul
at ``Precision.HIGHEST``, no kernels, no cache, no batching.

Block (pre-norm residual, as published for these families):

    h = x + Wo · attn(rope(Wq·n1(x) + bq), rope(Wk·n1(x) + bk), Wv·n1(x) + bv)
    y = h + W2 · (silu(W1g · n2(h)) * W1u · n2(h))

with RMSNorm ``n(x) = x / sqrt(mean(x^2) + eps) * g``, rotary embeddings
on the two halves of each head (``rotate_half``), causal softmax scaled
by 1/sqrt(head_dim), and query head ``j`` reading key/value head
``j // (heads / kv_heads)``.  The weights are read by name from the
benchmark's own parameter tree (``weights.py``): ``wi`` holds the gate
columns then the up columns.

``quant`` computes the same forward in a lower precision, for the
correctness control: ``"bf16"`` rounds every matmul operand to bfloat16
(float32 accumulation); ``"fp8"`` rounds weights per output channel
and activations per row, each scaled to its largest magnitude, to
float8 (e4m3) before each weight matmul.  Attention itself stays in
float32 in both.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


FP8_MAX = 448.0             # largest finite float8_e4m3fn


def _fp8(v, axis):
    scale = jnp.max(jnp.abs(v), axis=axis, keepdims=True) / FP8_MAX
    scale = jnp.where(scale == 0, 1.0, scale)
    return (v / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def matmul(x, w, quant=None):
    """x (..., K) @ w (K, N) in float32, or in the control's precision."""
    w = w.astype(F32)
    if quant == "bf16":
        return jnp.dot(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                       preferred_element_type=F32)
    if quant == "fp8":
        x, w = _fp8(x, -1), _fp8(w, 0)
    elif quant is not None:
        raise ValueError(f"unknown quant {quant!r}")
    return jnp.dot(x, w, precision=HIGHEST)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x: (T, heads, hd) at positions 0..T-1."""
    t, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def hidden(params, tokens, c: dict, quant=None):
    """Final-normed hidden states (T, d) for one sequence ``tokens`` (T,)."""
    d = c["hidden_size"]
    nh, kvh = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c.get("head_dim") or d // nh
    g = nh // kvh
    # where the program departs from the published value and has no
    # option for it, the reference follows the program (``departures``)
    eps = c.get("departures", {}).get("rms_norm_eps", c["rms_norm_eps"])
    theta = c["rope_theta"]
    t = tokens.shape[0]
    x = params["embed"]["table"][tokens].astype(F32)
    causal = jnp.tril(jnp.ones((t, t), bool))

    def layer(x, p):
        p = jax.tree.map(lambda v: v.astype(F32), p)
        a = p["attn"]
        h = _rms(x, p["ln1"], eps)
        q = matmul(h, a["wq"], quant) + a.get("bq", 0.0)
        k = matmul(h, a["wk"], quant) + a.get("bk", 0.0)
        v = matmul(h, a["wv"], quant) + a.get("bv", 0.0)
        q = _rope(q.reshape(t, nh, hd), theta).reshape(t, kvh, g, hd)
        k = _rope(k.reshape(t, kvh, hd), theta)
        v = v.reshape(t, kvh, hd)
        s = jnp.einsum("tkgd,skd->kgts", q, k, precision=HIGHEST)
        s = jnp.where(causal, s / jnp.sqrt(F32(hd)), -jnp.inf)
        o = jnp.einsum("kgts,skd->tkgd", jax.nn.softmax(s, -1), v,
                       precision=HIGHEST)
        x = x + matmul(o.reshape(t, nh * hd), a["wo"], quant)
        h = _rms(x, p["ln2"], eps)
        gate, up = jnp.split(matmul(h, p["ffn"]["wi"], quant), 2, -1)
        return x + matmul(jax.nn.silu(gate) * up, p["ffn"]["wo"], quant), None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    return _rms(x, params["final_norm"].astype(F32), eps)


def logits(params, h, c: dict, quant=None):
    """(n, vocab) float32 logits of hidden rows ``h`` (n, d)."""
    if c["tie_word_embeddings"]:
        return matmul(h, params["embed"]["table"].T, quant)
    return matmul(h, params["unembed"], quant)
