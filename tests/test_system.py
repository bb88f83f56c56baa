"""End-to-end system behaviour: the full paper pipeline (§II-§V) and the
framework loop (train -> checkpoint -> restore -> serve)."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import SHAPES, get_smoke_config, supported_shapes
from repro.core import engine
from repro.data import SyntheticLMData, make_batch
from repro.distributed import compression
from repro.ft import Supervisor
from repro.serve import Request, ServeConfig, ServingEngine
from repro.train import TrainConfig, make_train_step
from repro.train.step import train_state_init
from repro.launch.mesh import make_mesh

F32 = dict(param_dtype=jnp.float32, act_dtype=jnp.float32)


def test_paper_pipeline_end_to_end(key):
    """Eq. 4 chain on real operands: binary -> ln LUT -> tau -> stochastic
    bits -> pop-count -> binary product, within the paper's error budget."""
    cfg = engine.EngineConfig(nbit=4096)
    x_int, y_int = 700, 300
    p_est, product = engine.sc_multiply(key, x_int, y_int, cfg)
    true_product = x_int * y_int                     # in [0, 2^20)
    # nbit=4096 -> sigma ~ 0.7% of full scale
    assert abs(int(product) - true_product) < 0.03 * (1 << 20)
    # and the probability estimate matches the encoded product
    p_true = (x_int / 1024) * (y_int / 1024)
    assert abs(float(p_est) - p_true) < 0.03


def test_mac_pipeline_matches_dot_product(key):
    """§III-C vectored MAC: sum of per-MUL pop-counts ~ dot(w, x)."""
    from repro.core import popcount
    cfg = engine.EngineConfig(nbit=2048)
    w = jnp.array([100, 300, 500, 700, 900])
    x = jnp.array([900, 700, 500, 300, 100])
    states = engine.mac_rows(key, w, x, cfg)
    total = int(popcount.csa_fa_popcount(states))
    est = total / cfg.nbit * (1024 * 1024)           # decode the MAC sum
    true = float(jnp.sum(w * x))
    assert abs(est - true) / true < 0.05


def test_train_checkpoint_serve_roundtrip(tmp_path, key):
    """Train a few steps -> checkpoint -> restore -> serve: tokens from the
    restored engine match tokens from the live engine."""
    cfg = get_smoke_config("qwen2-0.5b").replace(**F32)
    tcfg = TrainConfig()
    state = train_state_init(key, cfg, tcfg)
    step = jax.jit(make_train_step(cfg, tcfg))
    data = SyntheticLMData(vocab=cfg.vocab, seq_len=32, global_batch=4)
    sup = Supervisor(ckpt_dir=str(tmp_path), ckpt_every=4)
    state, _ = sup.run(state, step, 8,
                       make_batch=lambda i: make_batch(data, i))

    from repro import checkpoint
    restored, extra, at = checkpoint.restore(str(tmp_path), state)
    assert at == 8

    def serve_with(params):
        eng = ServingEngine(params, cfg, ServeConfig(slots=1, max_len=32))
        eng.submit(Request(rid=0, prompt=[5, 7, 9], max_new_tokens=4))
        return eng.run_until_drained()[0].generated

    assert serve_with(state["params"]) == serve_with(restored["params"])


def test_shard_map_compression_on_pod_mesh(key):
    """compressed_grads wires shard_map over a pod axis (size 1 on CPU —
    semantics identical, collectives degenerate) and returns grads close to
    the uncompressed path (int8 quantization error only)."""
    mesh = make_mesh((1, 1, 1), ("pod", "data", "model"))
    w0 = jax.random.normal(key, (8, 8))

    def grad_fn(params, batch):
        loss = jnp.mean((batch @ params["w"]) ** 2)
        return loss, jax.grad(
            lambda p: jnp.mean((batch @ p["w"]) ** 2))(params)

    batch = jax.random.normal(jax.random.fold_in(key, 1), (1, 4, 8))
    ef = compression.init_error_feedback({"w": w0}, n_pods=1)
    fn = compression.compressed_grads(grad_fn, mesh)
    loss, grads, new_ef = fn({"w": w0}, batch, {"w": ef["w"]})
    _, exact = grad_fn({"w": w0}, batch[0])
    err = np.abs(np.asarray(grads["w"]) - np.asarray(exact["w"])).max()
    scale = np.abs(np.asarray(exact["w"])).max()
    assert err < scale / 64                  # int8 grid error
    assert new_ef["w"].shape == (1, 8, 8)


def test_supported_shapes_matrix():
    """The 40-cell matrix: every arch runs 3 LM shapes; only ssm/hybrid run
    long_500k (documented skip for full-attention archs)."""
    from repro.configs import ARCH_IDS
    total_live = 0
    for arch in ARCH_IDS:
        if arch == "paper-sc":
            continue
        cfg = get_smoke_config(arch)
        shapes = supported_shapes(cfg)
        assert {"train_4k", "prefill_32k", "decode_32k"} <= set(shapes)
        if cfg.family in ("ssm", "hybrid"):
            assert "long_500k" in shapes
        else:
            assert "long_500k" not in shapes
        total_live += len(shapes)
    assert total_live == 32                  # + 8 documented skips = 40


def test_shape_configs_match_assignment():
    assert (SHAPES["train_4k"].seq_len, SHAPES["train_4k"].global_batch) \
        == (4096, 256)
    assert (SHAPES["prefill_32k"].seq_len,
            SHAPES["prefill_32k"].global_batch) == (32768, 32)
    assert (SHAPES["decode_32k"].seq_len,
            SHAPES["decode_32k"].global_batch) == (32768, 128)
    assert (SHAPES["long_500k"].seq_len,
            SHAPES["long_500k"].global_batch) == (524288, 1)
    assert SHAPES["decode_32k"].kind == "decode"
    assert SHAPES["long_500k"].kind == "decode"
