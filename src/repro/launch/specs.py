"""ShapeDtypeStruct stand-ins + NamedSharding trees for every lowered step.

``input_specs(cfg, shape)`` produces the exact abstract inputs each
(architecture × input-shape) cell lowers with — weak-type-correct,
shardable, zero allocation. The companion ``*_shardings`` helpers derive
NamedSharding trees from the same logical rules the model uses, so the
dry-run, trainer, and server can never disagree on layout.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import lm, params as params_lib
from repro.sharding import rules as sharding_rules


def _sizes(mesh):
    # mesh.shape works for both concrete Mesh and AbstractMesh (tests build
    # the production sharding trees without 512 devices).
    return dict(mesh.shape)


def _dp_axes(mesh, n: int):
    """Data-parallel mesh axes usable for a batch of size n (or None)."""
    sizes = _sizes(mesh)
    axes = tuple(a for a in ("pod", "data") if a in sizes)
    if not axes:
        return None
    if n > 0 and n % math.prod(sizes[a] for a in axes) == 0:
        return axes
    if "data" in sizes and n > 0 and n % sizes["data"] == 0:
        return ("data",)
    return None


def _div(n: int, mesh, axis: str):
    sizes = _sizes(mesh)
    return axis if axis in sizes and n % sizes[axis] == 0 else None


# ---------------------------------------------------------------------------
# Abstract inputs per shape kind
# ---------------------------------------------------------------------------


def input_specs(cfg: ModelConfig, shape: ShapeConfig):
    """Abstract step inputs for one cell. Returns a dict:

    train   -> {batch: {inputs, labels}}
    prefill -> {inputs}
    decode  -> {cache, tokens, lengths}
    """
    b, s = shape.global_batch, shape.seq_len
    tok = jax.ShapeDtypeStruct((b, s), jnp.int32)
    if shape.kind == "train":
        if cfg.frontend == "embeddings":
            inputs = jax.ShapeDtypeStruct((b, s, cfg.d_model), cfg.act_dtype)
        else:
            inputs = tok
        return {"batch": {"inputs": inputs, "labels": tok}}
    if shape.kind == "prefill":
        if cfg.frontend == "embeddings":
            return {"inputs": jax.ShapeDtypeStruct((b, s, cfg.d_model),
                                                   cfg.act_dtype)}
        return {"inputs": tok}
    # decode: one new token against a cache of seq_len
    cache = jax.eval_shape(lambda: lm.init_cache(cfg, b, s))
    return {
        "cache": cache,
        "tokens": jax.ShapeDtypeStruct((b,), jnp.int32),
        "lengths": jax.ShapeDtypeStruct((b,), jnp.int32),
    }


# ---------------------------------------------------------------------------
# Sharding trees
# ---------------------------------------------------------------------------


def param_shardings(cfg: ModelConfig, mesh):
    specs = lm.lm_param_specs(cfg)
    return params_lib.tree_map_specs(
        lambda ps: NamedSharding(mesh, ps),
        params_lib.partition_specs(specs,
                                   sharding_rules.logical_rules(mesh)))


def opt_shardings(cfg: ModelConfig, mesh, param_sh):
    """AdamW m/v mirror the parameter shardings (f32/bf16 state)."""
    return {"m": param_sh, "v": param_sh,
            "step": NamedSharding(mesh, P())}


def batch_shardings(cfg: ModelConfig, mesh, batch: int):
    dp = _dp_axes(mesh, batch)
    ns = lambda *spec: NamedSharding(mesh, P(*spec))  # noqa: E731
    if cfg.frontend == "embeddings":
        return {"inputs": ns(dp, None, None), "labels": ns(dp, None)}
    return {"inputs": ns(dp, None), "labels": ns(dp, None)}


def cache_shardings(cfg: ModelConfig, mesh, batch: int, max_len: int):
    """Mirror lm.init_cache structure with layout-adaptive specs:

    * batch shards over (pod, data) when divisible;
    * the KV-cache SEQUENCE shards over `model` — decode reads the whole
      cache every step, so sharding its seq axis divides both the HBM
      footprint and the cache-read bandwidth by the TP degree (kv-head TP
      cannot: kv_heads < model axis on most assigned archs). When batch
      leaves (pod, data) unused (long_500k, batch=1) the sequence shards
      over EVERY available axis — full 256/512-way cache distribution;
    * ssm heads / d_inner shard over model when divisible.
    """
    sizes = _sizes(mesh)
    dp = _dp_axes(mesh, batch)
    seq_axes = []
    if dp is None:
        seq_axes += [a for a in ("pod", "data") if a in sizes]
    if "model" in sizes:
        seq_axes.append("model")
    total = math.prod(sizes[a] for a in seq_axes) if seq_axes else 1
    seq_ax = tuple(seq_axes) if seq_axes and max_len % total == 0 else None
    if seq_ax is not None and len(seq_ax) == 1:
        seq_ax = seq_ax[0]
    kv_ax = None if seq_ax else _div(cfg.n_kv_heads, mesh, "model")
    ns = lambda *spec: NamedSharding(mesh, P(*spec))  # noqa: E731

    if cfg.family in ("ssm", "hybrid"):
        inner_ax = _div(cfg.d_inner, mesh, "model")
        state_ax = _div(cfg.ssm_heads, mesh, "model")
        out = {"ssm": {
            "conv_x": ns(None, dp, None, inner_ax),
            "conv_B": ns(None, dp, None, None),
            "conv_C": ns(None, dp, None, None),
            "state": ns(None, dp, state_ax, None, None),
        }}
        if cfg.family == "hybrid":
            out["shared_k"] = ns(None, dp, seq_ax, kv_ax, None)
            out["shared_v"] = ns(None, dp, seq_ax, kv_ax, None)
        return out
    return {"k": ns(None, dp, seq_ax, kv_ax, None),
            "v": ns(None, dp, seq_ax, kv_ax, None)}


def logits_sharding(cfg: ModelConfig, mesh, batch: int, with_seq: bool):
    dp = _dp_axes(mesh, batch)
    v_ax = _div(cfg.vocab, mesh, "model")
    if with_seq:
        return NamedSharding(mesh, P(dp, None, v_ax))
    return NamedSharding(mesh, P(dp, v_ax))


def replicated(mesh):
    return NamedSharding(mesh, P())
