"""Seeded on-device weights: the program's shapes and dtypes, one draw
per seed."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import weights
from repro.configs import get_config, get_smoke_config
from repro.models import lm, params as P


def _want(cfg):
    return jax.tree.map(lambda s: (s.shape, jnp.dtype(s.dtype)),
                        P.abstract_params(lm.lm_param_specs(cfg),
                                          cfg.param_dtype))


def _got(tree):
    return jax.tree.map(lambda a: (a.shape, jnp.dtype(a.dtype)), tree)


@pytest.mark.parametrize("arch,dtype", [("qwen2-0.5b", jnp.bfloat16),
                                        ("yi-6b", jnp.bfloat16),
                                        ("qwen2-0.5b", jnp.float32)])
def test_published_widths_match_the_param_specs(arch, dtype):
    """At full size, traced only (no 12 GB allocation on the CPU)."""
    cfg = get_config(arch).replace(param_dtype=dtype, act_dtype=dtype)
    shapes = jax.eval_shape(lambda: weights.make_params(cfg, 3))
    assert _got(shapes) == _want(cfg)


def test_small_weights_are_drawn_per_seed():
    cfg = get_smoke_config("yi-6b").replace(param_dtype=jnp.bfloat16)
    a = weights.make_params(cfg, 7)
    b = weights.make_params(cfg, 7)
    c = weights.make_params(cfg, 2**33 + 7)
    assert _got(a) == _want(cfg)
    flat_a, flat_b = jax.tree.leaves(a), jax.tree.leaves(b)
    assert all(np.array_equal(x, y) for x, y in zip(flat_a, flat_b))
    assert not np.array_equal(a["blocks"]["ffn"]["wi"],
                              c["blocks"]["ffn"]["wi"])
    # each layer slice is its own draw
    wi = np.asarray(a["blocks"]["ffn"]["wi"], np.float32)
    assert not np.array_equal(wi[0], wi[1])
    # scales: normal leaves at 0.02, gains around 1, biases around 0
    assert abs(float(np.std(np.asarray(a["embed"]["table"], np.float32)))
               - 0.02) < 0.005
    ln = np.asarray(a["blocks"]["ln1"], np.float32)
    assert abs(ln.mean() - 1.0) < 0.01 and ln.std() > 0
