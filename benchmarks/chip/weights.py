"""Seeded weights, made on the device in one jitted call.

Every leaf of ``lm.lm_param_specs(cfg)`` is drawn in its own dtype (the
config's ``param_dtype`` unless the spec overrides it).  Stacked layer
leaves are drawn one layer slice at a time under ``lax.map``, so no
float32 copy of a whole stacked leaf ever exists: yi-6b's (32, 4096,
22016) MLP input leaf would otherwise need 11.5 GB of float32
temporaries next to the bfloat16 weights.

Scales follow the spec's ``init`` kind: ``normal`` 0.02, ``scaled``
1/sqrt(fan_in).  Leaves the program initialises to zeros (biases) or
ones (norm gains) are drawn as 0.02 noise around that value instead, so
that a fault which drops a bias or a gain changes the logits and the
correctness comparison can see it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def base_key(seed: int):
    """A PRNG key for any non-negative seed (also beyond 32 bits)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**31), seed // 2**31)


def _draw(key, spec, shape, dtype):
    if spec.init == "scaled":
        fan_in = spec.shape[-2] if len(spec.shape) >= 2 else spec.shape[-1]
        scale, offset = 1.0 / math.sqrt(fan_in), 0.0
    else:
        scale = 0.02
        offset = 1.0 if spec.init == "ones" else 0.0
    return (offset + scale * jax.random.normal(key, shape, dtype)).astype(dtype)


def _leaf(key, spec, dtype, stacked: int):
    if not stacked:
        return _draw(key, spec, spec.shape, dtype)
    inner = spec.shape[1:]
    return jax.lax.map(
        lambda i: _draw(jax.random.fold_in(key, i), spec, inner, dtype),
        jnp.arange(stacked))


def make_params(cfg, seed: int):
    """The parameter pytree for ``cfg``, drawn on the default device."""
    from repro.models import lm
    from repro.models.params import ParamSpec

    specs = lm.lm_param_specs(cfg)
    leaves, treedef = jax.tree.flatten(
        specs, is_leaf=lambda s: isinstance(s, ParamSpec))
    n_layers = lm.n_backbone_layers(cfg)
    # a leaf under "blocks" is stacked over the backbone layers
    paths = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda s: isinstance(s, ParamSpec))[0]]

    def build(key):
        out = []
        for i, (path, spec) in enumerate(zip(paths, leaves)):
            stacked = n_layers if path.startswith("['blocks']") else 0
            out.append(_leaf(jax.random.fold_in(key, i), spec,
                             spec.dtype or cfg.param_dtype, stacked))
        return jax.tree.unflatten(treedef, out)

    return jax.jit(build)(base_key(seed))
