"""Data pipeline determinism + sharding rules + gradient compression."""

import jax
import jax.numpy as jnp
import numpy as np
from _hypothesis_compat import given, settings, st
from jax.sharding import PartitionSpec as P

from repro.data import SyntheticLMData, make_batch
from repro.data.pipeline import make_embedding_batch
from repro.distributed import compression
from repro.models.params import ParamSpec, partition_specs
from repro.sharding import act_spec
from repro.sharding.rules import logical_rules
from repro.launch.mesh import make_mesh

# ---------------------------------------------------------------------------
# Data pipeline
# ---------------------------------------------------------------------------

DATA = SyntheticLMData(vocab=1024, seq_len=64, global_batch=8, n_shards=2)


def test_batch_is_deterministic():
    b1 = make_batch(DATA, step=5, shard=0)
    b2 = make_batch(DATA, step=5, shard=0)
    np.testing.assert_array_equal(np.asarray(b1["inputs"]),
                                  np.asarray(b2["inputs"]))


def test_different_steps_and_shards_differ():
    b0 = make_batch(DATA, 0, 0)
    b1 = make_batch(DATA, 1, 0)
    s1 = make_batch(DATA, 0, 1)
    assert not np.array_equal(b0["inputs"], b1["inputs"])
    assert not np.array_equal(b0["inputs"], s1["inputs"])


def test_labels_are_shifted_inputs():
    b = make_batch(DATA, 3)
    np.testing.assert_array_equal(np.asarray(b["labels"][:, :-1]),
                                  np.asarray(b["inputs"][:, 1:]))


def test_tokens_in_vocab_range():
    b = make_batch(DATA, 2)
    toks = np.asarray(b["inputs"])
    assert toks.min() >= 0 and toks.max() < DATA.vocab


def test_shard_batch_size():
    assert DATA.shard_batch == 4
    assert make_batch(DATA, 0, 0)["inputs"].shape == (4, 64)


def test_embedding_batch_shapes():
    b = make_embedding_batch(DATA, d_model=32, step=0)
    assert b["inputs"].shape == (4, 64, 32)
    assert b["labels"].shape == (4, 64)


@given(step=st.integers(0, 1000))
@settings(max_examples=20, deadline=None)
def test_zipf_structure_has_repeats(step):
    """The Markov structure means adjacent-token repeats are common —
    that is the learnable signal."""
    b = make_batch(DATA, step)
    toks = np.asarray(b["inputs"])
    rep_frac = (toks[:, 1:] == toks[:, :-1]).mean()
    assert rep_frac > 0.1


# ---------------------------------------------------------------------------
# Sharding rules
# ---------------------------------------------------------------------------


def _mesh11():
    return make_mesh((1, 1), ("data", "model"))


def test_partition_specs_divisible_dims_shard():
    rules = {"embed": "data", "heads": "model",
             "__sizes__": {"data": 16, "model": 16}}
    specs = {"w": ParamSpec((4096, 1024), ("embed", "heads"))}
    ps = partition_specs(specs, rules)
    assert ps["w"] == P("data", "model")


def test_partition_specs_indivisible_dims_replicate():
    rules = {"embed": "data", "heads": "model",
             "__sizes__": {"data": 16, "model": 16}}
    specs = {"w": ParamSpec((100, 24), ("embed", "heads"))}  # 100%16, 24%16
    ps = partition_specs(specs, rules)
    assert ps["w"] == P(None, None)


def test_partition_specs_mixed():
    rules = {"embed": "data", "kv_heads": "model",
             "__sizes__": {"data": 16, "model": 16}}
    specs = {"wk": ParamSpec((4096, 256), ("embed", "kv_heads"))}
    ps = partition_specs(specs, rules)
    assert ps["wk"] == P("data", "model")


def test_act_spec_single_pod_mesh():
    mesh = _mesh11()
    spec = act_spec(mesh, "batch", "seq", "heads")
    assert spec == P("data", None, "model")


def test_logical_rules_pod_axis():
    mesh = make_mesh((1, 1, 1), ("pod", "data", "model"))
    rules = logical_rules(mesh, "act")
    assert rules["batch"] == ("pod", "data")
    assert rules["__sizes__"] == {"pod": 1, "data": 1, "model": 1}


# ---------------------------------------------------------------------------
# Gradient compression (int8 + error feedback)
# ---------------------------------------------------------------------------


def test_quantize_dequantize_bounded_error(key):
    g = jax.random.normal(key, (128,)) * 5.0
    q, scale = compression._quantize(g)
    deq = q.astype(jnp.float32) * scale
    max_err = float(jnp.abs(deq - g).max())
    assert max_err <= float(scale) * 0.5 + 1e-6     # half-LSB rounding


def test_error_feedback_accumulates_residual(key):
    """Over repeated steps with a CONSTANT gradient, error feedback makes
    the running mean of transmitted gradients converge to the true value
    (the EF-SGD contract)."""
    g = jax.random.normal(key, (64,)) * 0.01 + 0.003
    r = jnp.zeros_like(g)
    sent = []
    for _ in range(50):
        corrected = g + r
        q, scale = compression._quantize(corrected)
        deq = q.astype(jnp.float32) * scale
        r = corrected - deq
        sent.append(deq)
    avg_sent = np.asarray(jnp.stack(sent).mean(0))
    np.testing.assert_allclose(avg_sent, np.asarray(g), atol=5e-4)


def test_compressed_grads_passthrough_without_pod_axis(key):
    mesh = _mesh11()

    def grad_fn(params, batch):
        return jnp.sum(params["w"] * batch), {"w": batch}

    fn = compression.compressed_grads(grad_fn, mesh)
    loss, grads, ef = fn({"w": jnp.ones(4)}, jnp.ones(4) * 2.0, None)
    assert ef is None
    np.testing.assert_allclose(np.asarray(grads["w"]), 2.0)


def test_init_error_feedback_shapes():
    ef = compression.init_error_feedback({"w": jnp.zeros((3, 4))}, n_pods=2)
    assert ef["w"].shape == (2, 3, 4)
