"""Pallas kernel validation (interpreted on the CPU; the mode follows the
platform): shape/dtype sweeps asserted against the pure-jnp oracles in
kernels/ref.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro import sc
from repro.kernels import ref
from repro.kernels.sc_mac import sc_mac_fused
from repro.kernels.sc_mul import NSLICES, sc_mul_bitexact, sc_mul_popcount
from repro.sc.encoding import to_fx16

# ---------------------------------------------------------------------------
# sc_mul: bit-exact against the oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,w,block_m", [
    (8, 1, 8), (8, 4, 8), (16, 8, 8), (32, 2, 16), (8, 32, 4), (64, 4, 32),
])
def test_sc_mul_kernel_matches_ref_exactly(key, m, w, block_m):
    kx, ky, kp = jax.random.split(key, 3)
    px = to_fx16(jax.random.uniform(kp, (m,)))
    py = to_fx16(jax.random.uniform(jax.random.fold_in(kp, 1), (m,)))
    rx = jax.random.bits(kx, (m, NSLICES, w), jnp.uint32)
    ry = jax.random.bits(ky, (m, NSLICES, w), jnp.uint32)
    out_k = sc_mul_popcount(px, py, rx, ry, block_m=block_m)
    out_r = ref.sc_mul_popcount_ref(px, py, rx, ry)
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))


def test_sc_mul_bias_edges(key):
    """p=0 -> all bits dead; p=1(0xFFFF) -> survival = partner's draw."""
    m, w = 8, 4
    rx = jax.random.bits(key, (m, NSLICES, w), jnp.uint32)
    ry = jax.random.bits(jax.random.fold_in(key, 1), (m, NSLICES, w),
                         jnp.uint32)
    zeros = jnp.zeros((m,), jnp.uint32)
    out = sc_mul_popcount(zeros, to_fx16(jnp.ones(m) * 0.5), rx, ry,
                          block_m=8)
    assert int(jnp.sum(out)) == 0


@given(seed=st.integers(0, 2**16), p1=st.floats(0.05, 0.95),
       p2=st.floats(0.05, 0.95))
@settings(max_examples=20, deadline=None)
def test_sc_mul_bernoulli_bias_is_correct(seed, p1, p2):
    """The Horner-ladder construction yields P(bit=1) = p to fixed-point
    resolution: pop-count fraction ~ p1*p2 within binomial noise."""
    key = jax.random.PRNGKey(seed)
    nbit = 32 * 64          # 2048 cells
    est = sc_mul_bitexact(
        key, jnp.array([p1]), jnp.array([p2]), nbit=nbit, block_m=8)
    sigma = np.sqrt(p1 * p2 * (1 - p1 * p2) / nbit)
    assert abs(float(est[0]) - p1 * p2) < 6 * sigma + 2e-4


def test_sc_mul_wrapper_pads_irregular_batch(key):
    est = sc_mul_bitexact(key, jnp.full((5,), 0.5), jnp.full((5,), 0.5),
                          nbit=256, block_m=8)
    assert est.shape == (5,)


# ---------------------------------------------------------------------------
# sc_mac: fused kernel vs analytic oracle (allclose)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,k,n,bm,bn,bk", [
    (128, 512, 128, 128, 128, 512),   # single tile
    (256, 1024, 128, 128, 128, 512),  # multi-tile all axes
    (64, 128, 64, 32, 32, 64),        # small blocks, multi-step k
    (8, 16, 8, 8, 8, 16),             # tiny
])
def test_sc_mac_fused_matches_ref(key, m, k, n, bm, bn, bk):
    kx, kw, kn = jax.random.split(key, 3)
    x = jax.random.uniform(kx, (m, k), jnp.float32, -1.0, 1.0)
    w = jax.random.uniform(kw, (k, n), jnp.float32, -1.0, 1.0)
    noise = jax.random.normal(kn, (m, n), jnp.float32)
    out = sc_mac_fused(x, w, noise, nbit=1024, block_m=bm, block_n=bn,
                       block_k=bk)
    expect = ref.sc_mac_ref(x, w, noise, nbit=1024)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sc_mac_fused_dtype_sweep(key, dtype):
    """bf16 operands upcast in the MXU accumulate path (f32 accumulators)."""
    x = jax.random.uniform(key, (32, 64), jnp.float32, -1, 1).astype(dtype)
    w = jax.random.uniform(jax.random.fold_in(key, 1), (64, 32), jnp.float32,
                           -1, 1).astype(dtype)
    noise = jax.random.normal(jax.random.fold_in(key, 2), (32, 32),
                              jnp.float32)
    out = sc_mac_fused(x.astype(jnp.float32), w.astype(jnp.float32), noise,
                       nbit=512, block_m=32, block_n=32, block_k=64)
    expect = ref.sc_mac_ref(x.astype(jnp.float32), w.astype(jnp.float32),
                            noise, nbit=512)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-2, atol=2e-2)


def test_pallas_moment_backend_irregular_shapes(key):
    """The pallas_moment backend pads to block multiples and un-pads the
    output."""
    x = jax.random.normal(key, (100, 300))
    w = jax.random.normal(jax.random.fold_in(key, 1), (300, 50))
    cfg = sc.ScConfig(backend="pallas_moment", nbit=4096,
                      block_m=64, block_n=64, block_k=128)
    out = sc.sc_dot(jax.random.fold_in(key, 2), x, w, cfg)
    assert out.shape == (100, 50)
    err = np.abs(np.asarray(out) - np.asarray(x @ w))
    scale = np.abs(np.asarray(x @ w)).max()
    assert err.mean() < 0.1 * scale


def test_pallas_moment_statistics_match_array_level(key):
    """Pallas moment kernel and the array-level moment backend draw from
    the same distribution: identical mean (exact product) and matching
    sigma."""
    x = jax.random.normal(key, (16, 128))
    w = jax.random.normal(jax.random.fold_in(key, 1), (128, 16))
    keys = jax.random.split(jax.random.fold_in(key, 2), 64)
    pcfg = sc.ScConfig(backend="pallas_moment", nbit=256,
                       block_m=16, block_n=16, block_k=128)
    mcfg = sc.ScConfig(backend="moment", nbit=256)
    fused = jax.vmap(lambda k_: sc.sc_dot(k_, x, w, pcfg))(keys)
    core = jax.vmap(lambda k_: sc.sc_dot(k_, x, w, mcfg))(keys)
    np.testing.assert_allclose(np.asarray(fused.mean(0)),
                               np.asarray(core.mean(0)), atol=0.5)
    s_f = np.asarray(fused.std(0)).mean()
    s_c = np.asarray(core.std(0)).mean()
    assert 0.7 < s_f / s_c < 1.4


def test_box_muller_produces_standard_normals(key):
    """The in-kernel PRNG epilogue's Box-Muller transform (CPU-checkable
    half of the TPU-only sc_mac_fused_prng path)."""
    from repro.kernels.sc_mac import _box_muller
    ka, kb = jax.random.split(key)
    bits_a = jax.random.bits(ka, (64, 4096), jnp.uint32)
    bits_b = jax.random.bits(kb, (64, 4096), jnp.uint32)
    z = np.asarray(_box_muller(bits_a, bits_b)).ravel()
    assert abs(z.mean()) < 0.02
    assert abs(z.std() - 1.0) < 0.02
    # tail sanity: P(|z|>2) ~ 4.6 %
    assert 0.03 < (np.abs(z) > 2).mean() < 0.06


def test_popcount32_ref_is_correct():
    v = jnp.array([0, 1, 0xFFFFFFFF, 0xAAAAAAAA, 0x12345678], jnp.uint32)
    got = np.asarray(ref.popcount32_ref(v))
    expect = np.array([bin(int(x)).count("1") for x in np.asarray(v)])
    np.testing.assert_array_equal(got, expect)
