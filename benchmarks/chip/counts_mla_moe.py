"""Operations and bytes of the latent-attention MoE block (DeepSeek-V3,
Moonlight), computed from shapes and VALID lengths as in ``counts.py``.

``c`` is a configuration file's dict (Hugging Face key names).  A row of
a tick is ``(ctx, n)``: ``ctx`` positions already cached and ``n`` fed
this tick; padding is never counted.  The work counted is the absorbed
form the serving step runs: queries taken into the latent space
(``q_nope·W_UK``), attention over the cached latents ``[c, k_pe]``, the
latent outputs taken up through ``W_UV``.
"""

from __future__ import annotations


def dims(c: dict) -> dict:
    nd = c["first_k_dense_replace"]
    return {"d": c["hidden_size"], "nh": c["num_attention_heads"],
            "r": c["kv_lora_rank"], "dn": c["qk_nope_head_dim"],
            "dr": c["qk_rope_head_dim"], "dv": c["v_head_dim"],
            "f": c["intermediate_size"], "fe": c["moe_intermediate_size"],
            "experts": c["n_routed_experts"],
            "k": c["num_experts_per_tok"], "shared": c["n_shared_experts"],
            "dense": nd, "moe": c["num_hidden_layers"] - nd,
            "layers": c["num_hidden_layers"], "vocab": c["vocab_size"]}


def attn_macs_per_position(c: dict) -> int:
    """Wq, Wkv_a, W_UK (absorbed query), W_UV (value up) and Wo."""
    m = dims(c)
    d, nh, r = m["d"], m["nh"], m["r"]
    return (d * nh * (m["dn"] + m["dr"]) + d * (r + m["dr"])
            + nh * m["dn"] * r + nh * r * m["dv"] + nh * m["dv"] * d)


def moe_macs_per_position(c: dict) -> int:
    """Router, the shared experts and the top-k routed experts."""
    m = dims(c)
    swiglu = 3 * m["d"] * m["fe"]
    return m["d"] * m["experts"] + (m["shared"] + m["k"]) * swiglu


def attention_flops(c: dict, ctx: int, n: int) -> int:
    """Absorbed attention of ``n`` new positions after ``ctx`` cached
    ones, all layers: 2·heads·((r + rope) + r) per valid key (scores
    over the whole latent row, values its first r lanes)."""
    m = dims(c)
    keys = n * ctx + n * (n + 1) // 2
    return 2 * m["nh"] * (2 * m["r"] + m["dr"]) * keys * m["layers"]


def attention_bytes(c: dict, ctx: int, n: int, kv_bytes: int,
                    q_bytes: int, out_bytes: int = 4) -> int:
    """The latents of the valid context read once (one latent row serves
    every head), the absorbed queries read and the latent outputs
    written, all layers."""
    m = dims(c)
    width = m["r"] + m["dr"]
    latent = (ctx + n) * width * kv_bytes
    q = n * m["nh"] * (width * q_bytes + m["r"] * out_bytes)
    return (latent + q) * m["layers"]


def gmm_work(c: dict, positions: int, act_bytes: int,
             w_bytes: int) -> tuple:
    """(FLOPs, bytes) of the grouped expert matmuls of one tick over all
    MoE layers: pairs = fed positions x top-k; 2·pairs·(d·2f + f·d);
    the weights of min(experts, pairs) experts and the rows in and out."""
    m = dims(c)
    pairs = positions * m["k"]
    if not pairs:
        return 0, 0
    d, fe = m["d"], m["fe"]
    flops = 2 * pairs * (d * 2 * fe + fe * d)
    weights = min(m["experts"], pairs) * 3 * d * fe * w_bytes
    rows = pairs * (d + 2 * fe + fe + d) * act_bytes
    return flops * m["moe"], (weights + rows) * m["moe"]


def step_flops(c: dict, rows) -> int:
    """Useful FLOPs of one tick in the absorbed form: every layer's
    attention projections, the dense layers' SwiGLU, the MoE layers'
    router, shared and routed experts, for every fed position; attention
    over the valid context; the output head of each row that produced
    logits."""
    m = dims(c)
    live = [(ctx, n) for ctx, n in rows if n]
    pos = sum(n for _, n in live)
    per_pos = (attn_macs_per_position(c) * m["layers"]
               + 3 * m["d"] * m["f"] * m["dense"]
               + moe_macs_per_position(c) * m["moe"])
    return (2 * per_pos * pos + 2 * m["d"] * m["vocab"] * len(live)
            + sum(attention_flops(c, ctx, n) for ctx, n in live))


def kernel_ns(red, kernel: str):
    """Device ns of the operations the trace names ``%<kernel>.<n>``:
    the kernel's own calls, not the operations that read their output
    (whose text names them as operands); None when none ran."""
    hit = [ns for name, ns in red.op_ns.items()
           if name.split(" = ")[0].startswith("%" + kernel + ".")]
    return sum(hit) if hit else None
