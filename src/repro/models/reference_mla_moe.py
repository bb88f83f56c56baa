"""Plain forward of DeepSeek-V3's block (Moonlight-16B-A3B): latent
attention (MLA) and a sigmoid-routed mixture of experts with shared
experts, after leading dense layers.  ``jax.numpy`` only, float32, every
matmul at ``Precision.HIGHEST``, no kernels, no cache, no batching.

Block (pre-norm residual), with RMSNorm ``n(x) = x / sqrt(mean(x^2) +
eps) * g``:

    q = Wq·n1(x) -> heads of [q_nope, q_pe];  [c, k_pe] = Wkv_a·n1(x)
    c = n_kv(c);  [k_nope, v] = Wkv_b·c per head;  rope on q_pe, k_pe
    a = x + Wo · softmax([q_nope, q_pe]·[k_nope, k_pe] / sqrt(nope + rope)) v
    s = sigmoid(Wr·n2(a));  experts = top-k of (s + b);  g = s[experts]
    g = g / sum(g) (norm_topk_prob), times routed_scaling_factor
    y = a + Σ_e g_e · E_e(n2(a)) + S(n2(a))

``E_e`` is a SwiGLU of ``moe_intermediate_size``, ``S`` the
``n_shared_experts`` taken as one SwiGLU of their summed width, and the
first ``first_k_dense_replace`` layers have a SwiGLU of
``intermediate_size`` in place of the experts.  Routing is computed
densely: every expert runs on every token and the unchosen ones weigh
zero, so nothing can be dropped.  The attention is the naive form, with
K and V decompressed per head.

Departures from the published code, noted:

* RoPE is applied rotate-half (the two halves of ``q_pe``/``k_pe``),
  where the published code interleaves pairs.  With random weights the
  two layouts are a permutation of ``Wq``'s and ``Wkv_a``'s rope columns.
* The weights are read by name from the program's parameter tree:
  ``wi`` holds the gate columns then the up columns; ``wkv_b`` holds
  [k_nope | v] per head; the router bias is ``router_bias``.

Its copy is ``benchmarks/chip/references/mla_moe.py``, with the same
interface as ``dense_gqa.py``: ``hidden(params, tokens, c, quant)`` and
``logits(params, h, c, quant)``, every size read from the Hugging Face
keys of ``c``.  ``quant`` computes the same forward in a lower
precision, for the correctness controls: ``"bf16"`` rounds every weight
matmul's operands to bfloat16 (float32 accumulation); ``"fp8"`` rounds
weights per output channel and activations per row, each scaled to its
largest magnitude, to float8 (e4m3).  Attention itself, the router's
sigmoid and its top-k stay in float32.  :func:`config_dict` gives the
keys of a program config, for the tests.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0             # largest finite float8_e4m3fn
VOCAB_CHUNK = 8192          # output-head columns per step of ``logits``


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
    """x: (T, heads, dim) at positions 0..T-1, rotate-half."""
    t, _, dim = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=F32) / dim))
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _swiglu(h, w, quant):
    gate, up = jnp.split(matmul(h, w["wi"], quant), 2, -1)
    return matmul(jax.nn.silu(gate) * up, w["wo"], quant)


def _attention(h, a, c, quant):
    t = h.shape[0]
    nh, r = c["num_attention_heads"], c["kv_lora_rank"]
    dn, dr = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    dv = c["v_head_dim"]
    q = matmul(h, a["wq"], quant).reshape(t, nh, dn + dr)
    ckv = matmul(h, a["wkv_a"], quant)
    kv_c = _rms(ckv[:, :r], a["kv_norm"].astype(F32), c["rms_norm_eps"])
    kv = matmul(kv_c, a["wkv_b"], quant).reshape(t, nh, dn + dv)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], c["rope_theta"])],
                        -1)
    k_pe = _rope(ckv[:, None, r:], c["rope_theta"])
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_pe, (t, nh, dr))], -1)
    s = jnp.einsum("thd,shd->hts", q, k, precision=HIGHEST)
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal, s / jnp.sqrt(F32(dn + dr)), -jnp.inf)
    o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), kv[..., dn:],
                   precision=HIGHEST)
    return matmul(o.reshape(t, nh * dv), a["wo"], quant)


def _experts(h, f, c, quant):
    """Routed experts, densely: every expert on every token, weighed by
    its gate (zero where unchosen); plus the shared experts."""
    e, k = c["n_routed_experts"], c["num_experts_per_tok"]
    scores = jax.nn.sigmoid(matmul(h, f["router"], quant))        # (T, E)
    _, chosen = jax.lax.top_k(scores + f["router_bias"].astype(F32), k)
    gates = jnp.take_along_axis(scores, chosen, -1)
    if c["norm_topk_prob"]:
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
    gates = gates * c["routed_scaling_factor"]
    weight = jnp.zeros_like(scores).at[
        jnp.arange(h.shape[0])[:, None], chosen].set(gates)       # (T, E)

    def one(y, i):
        out = _swiglu(h, {"wi": f["wi"][i], "wo": f["wo"][i]}, quant)
        return y + weight[:, i, None] * out, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(e))
    if c.get("n_shared_experts"):
        y = y + _swiglu(h, f["shared"], quant)
    return y


def hidden(params, tokens, c: dict, quant=None):
    """Final-normed hidden states (T, d) for one sequence ``tokens`` (T,)."""
    eps = c["rms_norm_eps"]
    x = params["embed"]["table"][tokens].astype(F32)

    def block(x, p, moe: bool):
        x = x + _attention(_rms(x, p["ln1"].astype(F32), eps), p["attn"], c,
                           quant)
        h = _rms(x, p["ln2"].astype(F32), eps)
        ffn = _experts if moe else (lambda h, f, c, q: _swiglu(h, f, q))
        return x + ffn(h, p["ffn"], c, quant), None

    if c["first_k_dense_replace"]:
        x, _ = jax.lax.scan(lambda x, p: block(x, p, False), x,
                            params["dense_blocks"])
    x, _ = jax.lax.scan(lambda x, p: block(x, p, True), x, params["blocks"])
    return _rms(x, params["final_norm"].astype(F32), eps)


def logits(params, h, c: dict, quant=None):
    """(n, vocab) float32 logits of hidden rows ``h`` (n, d), computed a
    block of ``VOCAB_CHUNK`` columns at a time (no float32 copy of the
    whole output head)."""
    w = (params["embed"]["table"].T if c["tie_word_embeddings"]
         else params["unembed"])
    vocab = w.shape[1]
    step = VOCAB_CHUNK if vocab % VOCAB_CHUNK == 0 else vocab
    cols = jax.lax.map(
        lambda i: matmul(h, jax.lax.dynamic_slice_in_dim(w, i * step, step,
                                                         1), quant),
        jnp.arange(vocab // step))                     # (blocks, n, step)
    return jnp.moveaxis(cols, 0, 1).reshape(h.shape[0], vocab)


def config_dict(cfg) -> dict:
    """The Hugging Face keys of a program config (``configs/base.py``)."""
    return {
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
        "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "rms_norm_eps": cfg.rms_norm_eps,
        "rope_theta": cfg.rope_theta,
        "first_k_dense_replace": cfg.first_k_dense,
        "n_routed_experts": cfg.n_experts,
        "num_experts_per_tok": cfg.top_k,
        "n_shared_experts": cfg.n_shared_experts,
        "norm_topk_prob": True,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "tie_word_embeddings": cfg.tie_embeddings,
    }
