"""Operation and byte counts: valid lengths only, and the SC sites are
exactly the model's weight matmuls."""

import json
import math
import os

import numpy as np
import pytest

from benchmarks.chip import counts

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "benchmarks", "chip",
    "configs")


def config(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def brute_attention_pairs(ctx, n, max_len):
    """(query, key) pairs the causal mask admits, over a padded grid."""
    q = ctx + np.arange(max_len)[:, None]
    k = np.arange(max_len)[None, :]
    live = (np.arange(max_len) < n)[:, None]
    return int(((k <= q) & live).sum())


@pytest.mark.parametrize("ctx,n", [(0, 1), (0, 7), (31, 1), (100, 16),
                                   (2047, 128)])
def test_attention_flops_count_valid_context_only(ctx, n):
    c = config("qwen2-0.5b")
    m = counts.dims(c)
    pairs = brute_attention_pairs(ctx, n, max_len=ctx + 256)
    want = 4 * m["nh"] * m["hd"] * pairs * m["layers"]
    assert counts.attention_flops(c, ctx, n) == want


def test_attention_work_does_not_grow_with_padded_blocks():
    c = config("yi-6b")
    a = counts.attention_bytes(c, 300, 1, 2, 2)
    # the same row in a pool of any max_len reads the same K/V
    assert a == counts.attention_bytes(c, 300, 1, 2, 2)
    m = counts.dims(c)
    kv = 2 * m["kvh"] * 301 * m["hd"] * 2 * m["layers"]
    assert a - kv == m["nh"] * m["hd"] * (2 + 4) * m["layers"]


@pytest.mark.parametrize("name", ["qwen2-0.5b", "qwen2-0.5b-sc256", "yi-6b"])
def test_sc_sites_are_the_models_weight_matmuls(name):
    """Sum of K*N over the counted sites equals the weight matrices the
    program declares (``lm.lm_param_specs``), per layer and unembed."""
    from benchmarks.chip import harness
    from repro.models import lm
    c = config(name)
    cfg = harness.model_config(c)
    specs = lm.lm_param_specs(cfg)
    blk = specs["blocks"]
    per_layer = sum(math.prod(s.shape[1:])
                    for s in (*[blk["attn"][k] for k in
                                ("wq", "wk", "wv", "wo")],
                              blk["ffn"]["wi"], blk["ffn"]["wo"]))
    assert counts.layer_macs_per_position(c) == per_layer
    out = (specs["embed"]["table"] if cfg.tie_embeddings
           else specs["unembed"])
    assert counts.unembed_macs(c) == math.prod(out.shape)
    flops, _ = counts.sc_site_work(c, [(0, 5), (9, 0), (3, 1)])
    macs = per_layer * cfg.n_layers * 6 + math.prod(out.shape) * 2
    assert flops == 2 * macs


def test_step_flops_skip_idle_rows():
    c = config("qwen2-0.5b")
    live = counts.step_flops(c, [(10, 4)])
    assert counts.step_flops(c, [(10, 4), (0, 0), (500, 0)]) == live
    assert live == (2 * counts.layer_macs_per_position(c) * 24 * 4
                    + 2 * counts.unembed_macs(c)
                    + counts.attention_flops(c, 10, 4))
