"""Operations and bytes, computed from shapes and VALID lengths.

``c`` is a configuration file's dict (Hugging Face key names).  A row of
a tick is ``(ctx, n)``: ``ctx`` tokens already in its cache and ``n``
tokens fed this tick.  Padding (chunk slots past ``n``, idle rows,
blocks past a row's length) is never counted, so a kernel that stops
walking padded blocks reads as a gain and not as a change of yardstick.
"""

from __future__ import annotations


def dims(c: dict) -> dict:
    d, nh = c["hidden_size"], c["num_attention_heads"]
    return {"d": d, "nh": nh, "kvh": c["num_key_value_heads"],
            "hd": c.get("head_dim") or d // nh, "f": c["intermediate_size"],
            "layers": c["num_hidden_layers"], "vocab": c["vocab_size"]}


def dense_sites(c: dict) -> list:
    """(name, K, N) of every weight matmul of one layer."""
    m = dims(c)
    d, q, kv = m["d"], m["nh"] * m["hd"], m["kvh"] * m["hd"]
    return [("wq", d, q), ("wk", d, kv), ("wv", d, kv), ("wo", q, d),
            ("wi", d, 2 * m["f"]), ("w2", m["f"], d)]


def layer_macs_per_position(c: dict) -> int:
    return sum(k * n for _, k, n in dense_sites(c))


def unembed_macs(c: dict) -> int:
    m = dims(c)
    return m["d"] * m["vocab"]


def attention_flops(c: dict, ctx: int, n: int) -> int:
    """QK^T and PV of ``n`` new positions after ``ctx`` cached ones, all
    layers: position ``ctx + i`` attends to ``ctx + i + 1`` keys."""
    m = dims(c)
    keys = n * ctx + n * (n + 1) // 2
    return 4 * m["nh"] * m["hd"] * keys * m["layers"]


def attention_bytes(c: dict, ctx: int, n: int, kv_bytes: int,
                    q_bytes: int, out_bytes: int = 4) -> int:
    """K and V of the valid context read once per kv head, the queries
    read and the outputs written, all layers."""
    m = dims(c)
    kv = 2 * m["kvh"] * (ctx + n) * m["hd"] * kv_bytes
    q = n * m["nh"] * m["hd"] * (q_bytes + out_bytes)
    return (kv + q) * m["layers"]


def step_flops(c: dict, rows) -> int:
    """Model FLOPs of one tick: every weight matmul of every fed
    position, attention over the valid context, and the output
    projection of each row that produced logits."""
    m = dims(c)
    live = [(ctx, n) for ctx, n in rows if n]
    pos = sum(n for _, n in live)
    return (2 * layer_macs_per_position(c) * m["layers"] * pos
            + 2 * unembed_macs(c) * len(live)
            + sum(attention_flops(c, ctx, n) for ctx, n in live))


def sc_site_work(c: dict, rows, operand_bytes: int = 4,
                 out_bytes: int = 4) -> tuple:
    """(FLOPs, bytes) of every SC dense site of one tick: 2·M·K·N and
    the x, w and output operands, with M the fed positions (the output
    projection: the rows that produced logits)."""
    m = dims(c)
    live = [(ctx, n) for ctx, n in rows if n]
    pos = sum(n for _, n in live)
    sites = [(pos, k, n) for _, k, n in dense_sites(c)] * m["layers"]
    sites.append((len(live), m["d"], m["vocab"]))
    flops = sum(2 * mm * k * n for mm, k, n in sites if mm)
    byts = sum((mm * k + k * n) * operand_bytes + mm * n * out_bytes
               for mm, k, n in sites if mm)
    return flops, byts
