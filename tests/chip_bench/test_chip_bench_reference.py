"""The plain reference agrees with the program's own forward pass at
small widths in float32 (biases and gains drawn away from 0 and 1), and
its lower-precision controls depart from it by more than rounding."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import harness, weights
from benchmarks.chip.references import dense_gqa as ref

HERE = os.path.dirname(os.path.abspath(__file__))
QWEN_LIKE = {}
YI_LIKE = {"tie_word_embeddings": False, "rope_theta": 5e6,
           "num_key_value_heads": 4, "program": {"arch": "yi-6b"}}


def _config(over):
    with open(os.path.join(HERE, "tiny_chat.json")) as f:
        c = json.load(f)
    c["program"].update(over.get("program", {}), dtype="float32")
    c.update({k: v for k, v in over.items() if k != "program"})
    return c


@pytest.mark.parametrize("over", [QWEN_LIKE, YI_LIKE], ids=["qwen2", "yi"])
def test_reference_matches_the_program_forward(over):
    from repro.models import lm
    c = _config(over)
    cfg = harness.model_config(c)
    params = weights.make_params(cfg, 5)
    toks = jnp.asarray(np.random.default_rng(0).integers(3, 4096, 40),
                       jnp.int32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(lm.forward(params, toks[None], cfg))[0]
    got = np.asarray(ref.logits(params, ref.hidden(params, toks, c), c))
    # float32 at HIGHEST on both sides: summation order only
    assert np.abs(got - want).max() < 1e-4 * np.abs(want).max()
    for quant in ("bf16", "fp8"):
        low = np.asarray(ref.logits(params, ref.hidden(params, toks, c,
                                                       quant), c, quant))
        assert np.abs(low - got).max() > 1e-3 * np.abs(got).max(), quant
