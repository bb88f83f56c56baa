"""Compile the main path's kernels for a TPU v5e that is described, not
attached: qwen2-0.5b decode widths, one chip of a ``v5e:2x2`` topology.

The TPU compiler refuses what the Pallas interpreter accepts — blocks
not aligned to the (8, 128) tiling, more VMEM than a kernel may use,
casts and reductions Mosaic cannot lower — so these compiles guard every
kernel of the serving path at no chip time.  Nothing runs: each test
lowers from shapes and asserts the kernel is present as a
``tpu_custom_call`` in the compiled HLO.

The platform the code sees here is the CPU, which would interpret the
kernels; the ``chip`` fixture steers ``repro.kernels.interpret_mode`` to
the TPU answer for this file only.  The topology is described inside a
fixture, never at import time (only one process may load the TPU
library).
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import kernels, sc
from repro.configs import get_config
from repro.kernels import paged_attention as pa
from repro.kernels import sc_mac, sc_mul
from repro.models import lm, params as P

CFG = get_config("qwen2-0.5b").replace(
    param_dtype=jnp.float32, act_dtype=jnp.float32)
D, HD, KVH = CFG.d_model, CFG.resolved_head_dim, CFG.n_kv_heads
ROWS = 8            # decode rows: slots x chunk width
NBIT = 64
SLOTS, BLOCK, MAX_LEN = 4, 16, 128
NB = MAX_LEN // BLOCK
POOL = 1 + SLOTS * NB


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """One described chip, with kernels lowering through Mosaic.

    Traces cached for the CPU (interpreted kernels) are dropped on entry
    and the TPU traces on exit, so no other test reuses the wrong kind.
    The persistent compile cache stays off: entries written for a chip
    that is not attached could not be read back.
    """
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(kernels, "interpret_mode", lambda: False)
        jax.clear_caches()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.clear_caches()
            jax.config.update("jax_enable_compilation_cache", cache_on)


def _spec(chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("site,n", [("mlp_wi", 2 * CFG.d_ff),
                                    ("unembed", CFG.vocab)])
def test_sc_fused_rows_mode_compiles(chip, site, n):
    cfg = sc.ScConfig(backend="pallas_fused", nbit=NBIT)
    text = _compiled_text(
        lambda keys, x, w: sc.sc_dot_rows(keys, x, w, cfg),
        _spec(chip, (ROWS, 2), jnp.uint32),
        _spec(chip, (ROWS, D), jnp.float32),
        _spec(chip, (D, n), jnp.float32))
    assert "tpu_custom_call" in text, site
    assert "%sc_fused_popcount." in text, site


def test_sc_mul_compiles(chip):
    m, words = ROWS * D, NBIT // sc_mul.LANE_BITS
    u = _spec(chip, (m, sc_mul.NSLICES, words), jnp.uint32)
    p = _spec(chip, (m,), jnp.uint32)
    assert "tpu_custom_call" in _compiled_text(
        sc_mul.sc_mul_popcount, p, p, u, u)


def test_sc_mac_fused_compiles(chip):
    # pallas_moment's padding of a decode-width mlp_wi call
    k = 1024
    text = _compiled_text(
        lambda x, w, z: sc_mac.sc_mac_fused(x, w, z, nbit=NBIT),
        _spec(chip, (ROWS, k), jnp.float32),
        _spec(chip, (k, 2 * CFG.d_ff), jnp.float32),
        _spec(chip, (ROWS, 2 * CFG.d_ff), jnp.float32))
    assert "tpu_custom_call" in text


def _attn_args(chip):
    q = _spec(chip, (SLOTS, 1, CFG.n_heads, HD), jnp.float32)
    pages = _spec(chip, (POOL, KVH, BLOCK, HD), jnp.float32)
    table = _spec(chip, (SLOTS, NB), jnp.int32)
    lengths = _spec(chip, (SLOTS,), jnp.int32)
    return q, pages, table, lengths


def test_paged_attention_fused_compiles(chip):
    q, pages, table, lengths = _attn_args(chip)
    text = _compiled_text(
        pa.paged_attention_fused, q, pages, pages, table, lengths)
    assert "tpu_custom_call" in text
    assert "%paged_attention." in text        # the kernel's name
    assert "sc_fused_popcount" not in text


def test_paged_attention_fused_sc_compiles(chip):
    q, pages, table, lengths = _attn_args(chip)
    keys = _spec(chip, (SLOTS, 1, 2), jnp.uint32)
    text = _compiled_text(
        lambda *a: pa.paged_attention_fused_sc(*a, nbit=NBIT),
        keys, q, pages, pages, table, lengths)
    assert "tpu_custom_call" in text
    assert "%paged_attention_sc." in text
    assert "sc_fused_popcount" not in text


def test_decode_paged_exact_step_compiles(chip):
    """One jitted exact-substrate decode tick of the whole 24-layer model
    with the fused paged-attention kernel."""
    cfg = CFG.replace(paged_attn="fused")
    params = jax.tree.map(
        lambda s: _spec(chip, s.shape, s.dtype),
        P.abstract_params(lm.lm_param_specs(cfg), cfg.param_dtype))
    pages = jax.tree.map(
        lambda s: _spec(chip, s.shape, s.dtype),
        jax.eval_shape(lambda: lm.init_paged_cache(cfg, POOL, BLOCK)))
    compiled = jax.jit(
        lambda p, pg, bt, tok, ln, nv: lm.decode_paged(
            p, pg, bt, tok, ln, nv, cfg)).lower(
        params, pages, _spec(chip, (SLOTS, NB), jnp.int32),
        _spec(chip, (SLOTS, 1), jnp.int32),
        _spec(chip, (SLOTS,), jnp.int32),
        _spec(chip, (SLOTS,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the attention kernel and every model site are named in the program
    # (and so in the profiler's trace)
    assert "%paged_attention." in text
    for scope in ("qkv", "attn", "attn_out", "mlp_in", "mlp_out",
                  "unembed"):
        assert f"/{scope}/" in text, scope
    # the step fits one chip's 16 GB with room for the engine around it
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 12 << 30


# Moonlight-16B-A3B's widths: 16 heads over one 576-wide latent row (512
# of it the values), 64 experts of width 1408 at d_model 2048
MLA_HEADS, MLA_R, MLA_ROPE = 16, 512, 64


@pytest.mark.parametrize("width", [1, 16])
def test_mla_paged_attention_compiles(chip, width):
    q = _spec(chip, (SLOTS, width, MLA_HEADS, MLA_R + MLA_ROPE),
              jnp.bfloat16)
    pool = _spec(chip, (POOL, 1, BLOCK, MLA_R + MLA_ROPE), jnp.bfloat16)
    text = _compiled_text(
        lambda *a: pa.mla_paged_attention(*a, dv=MLA_R, scale=192 ** -0.5),
        q, pool, _spec(chip, (SLOTS, NB), jnp.int32),
        _spec(chip, (SLOTS,), jnp.int32))
    assert "%mla_paged_attention." in text


@pytest.mark.parametrize("k,n", [(2048, 2 * 1408), (1408, 2048)])
def test_moe_gmm_compiles(chip, k, n):
    from repro.kernels import moe_gmm
    rows = 64 * 6                         # a decode tick's (token, expert)
    text = _compiled_text(
        moe_gmm.moe_gmm, _spec(chip, (rows, k), jnp.bfloat16),
        _spec(chip, (64, k, n), jnp.bfloat16), _spec(chip, (64,), jnp.int32))
    assert "%moe_gmm." in text
