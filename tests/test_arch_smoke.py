"""Per-architecture smoke tests (assignment requirement): every assigned
arch instantiates its REDUCED config and runs one forward + train step on
CPU, asserting output shapes and no NaNs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.models import lm
from repro.train import TrainConfig, make_train_step
from repro.train.step import train_state_init

B, S = 2, 32


def _batch(key, cfg):
    ki, kl = jax.random.split(key)
    if cfg.frontend == "embeddings":
        inputs = jax.random.normal(ki, (B, S, cfg.d_model), cfg.act_dtype)
    else:
        inputs = jax.random.randint(ki, (B, S), 0, cfg.vocab)
    return {"inputs": inputs,
            "labels": jax.random.randint(kl, (B, S), 0, cfg.vocab)}


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_shapes_and_finite(arch, key):
    cfg = get_smoke_config(arch).replace(param_dtype=jnp.float32,
                                         act_dtype=jnp.float32)
    from repro.models import params as P
    params = P.init_params(key, lm.lm_param_specs(cfg), cfg.param_dtype)
    batch = _batch(key, cfg)
    logits = lm.forward(params, batch["inputs"], cfg,
                        rng=jax.random.fold_in(key, 1))
    assert logits.shape == (B, S, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(logits)))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_train_step(arch, key):
    cfg = get_smoke_config(arch).replace(param_dtype=jnp.float32,
                                         act_dtype=jnp.float32)
    tcfg = TrainConfig()
    state = train_state_init(key, cfg, tcfg)
    step = jax.jit(make_train_step(cfg, tcfg))
    new_state, metrics = step(state, _batch(key, cfg))
    loss = float(metrics["loss"])
    assert np.isfinite(loss) and 0.0 < loss < 20.0
    assert int(new_state["opt"]["step"]) == 1
    # params actually moved
    moved = jax.tree.map(
        lambda a, b: float(jnp.abs(a - b).max()),
        state["params"], new_state["params"])
    assert max(jax.tree.leaves(moved)) > 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_matches_assignment(arch):
    """The FULL configs carry the exact assigned hyperparameters."""
    cfg = get_config(arch)
    expected = {
        "musicgen-large": (48, 2048, 32, 32, 8192, 2048),
        "moonlight-16b-a3b": (27, 2048, 16, 16, 11264, 163840),
        "llama4-maverick-400b-a17b": (48, 5120, 40, 8, 8192, 202048),
        "chameleon-34b": (48, 8192, 64, 8, 22016, 65536),
        "starcoder2-15b": (40, 6144, 48, 4, 24576, 49152),
        "qwen2-0.5b": (24, 896, 14, 2, 4864, 151936),
        "qwen3-14b": (40, 5120, 40, 8, 17408, 151936),
        "yi-6b": (32, 4096, 32, 4, 11008, 64000),
        "zamba2-7b": (81, 3584, 32, 32, 14336, 32000),
        "mamba2-370m": (48, 1024, 0, 0, 0, 50280),
    }
    if arch == "paper-sc":
        assert cfg.sc_mode != "exact" and cfg.sc_nbit == 1024
        return
    nl, d, h, kv, ff, v = expected[arch]
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.d_ff, cfg.vocab) == (nl, d, h, kv, ff, v)
    if arch == "moonlight-16b-a3b":
        assert (cfg.n_experts, cfg.top_k, cfg.moe_d_ff) == (64, 6, 1408)
        assert (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                cfg.v_head_dim) == (512, 128, 64, 128)
        assert (cfg.first_k_dense, cfg.n_shared_experts) == (1, 2)
        assert cfg.router == "noaux_tc"
        assert cfg.routed_scaling_factor == 2.446 and cfg.moe_dropless
        assert (cfg.rope_theta, cfg.rms_norm_eps) == (50000.0, 1e-5)
    if arch == "llama4-maverick-400b-a17b":
        assert (cfg.n_experts, cfg.top_k) == (128, 1)
    if arch == "zamba2-7b":
        assert cfg.ssm_state == 64 and cfg.family == "hybrid"
    if arch == "mamba2-370m":
        assert cfg.ssm_state == 128 and cfg.family == "ssm"
    if arch == "qwen2-0.5b":
        assert cfg.qkv_bias
    if arch == "qwen3-14b":
        assert cfg.qk_norm


def test_smoke_decode_and_prefill_all_archs(key):
    """Prefill then decode for a couple of representative archs of each
    family; logits finite and cache threads correctly."""
    for arch in ("qwen2-0.5b", "moonlight-16b-a3b", "zamba2-7b",
                 "mamba2-370m"):
        cfg = get_smoke_config(arch).replace(param_dtype=jnp.float32,
                                             act_dtype=jnp.float32)
        from repro.models import params as P
        params = P.init_params(key, lm.lm_param_specs(cfg), cfg.param_dtype)
        toks = jax.random.randint(key, (2, 8), 0, cfg.vocab)
        logits, cache, lengths = lm.prefill(params, toks, cfg, max_len=16)
        assert logits.shape == (2, cfg.vocab)
        assert bool(jnp.all(jnp.isfinite(logits)))
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        logits2, cache = lm.decode_step(params, cache, nxt, lengths, cfg)
        assert logits2.shape == (2, cfg.vocab)
        assert bool(jnp.all(jnp.isfinite(logits2)))
