"""Production sharding trees, pinned on an ABSTRACT 16x16 / 2x16x16 mesh —
validates the exact layouts the dry-run compiles with, without needing 512
devices."""

from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import SHAPES, get_config
from repro.launch import specs as S

POD = AbstractMesh((16, 16), ("data", "model"))
MULTI = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def spec_of(sharding):
    return sharding.spec


def test_param_shardings_fsdp_x_tp():
    cfg = get_config("yi-6b")
    sh = S.param_shardings(cfg, POD)
    # attention wq (d_model=4096, heads 32*128=4096): leading stacked-layer
    # axis never shards; embed->data, heads->model
    assert spec_of(sh["blocks"]["attn"]["wq"]) == P(None, "data", "model")
    # embedding (vocab 64000, embed): vocab->model, embed->data
    assert spec_of(sh["embed"]["table"]) == P("model", "data")
    # norms FSDP-shard their embed axis
    assert spec_of(sh["final_norm"]) == P("data")


def test_param_shardings_indivisible_dims_replicate():
    cfg = get_config("qwen2-0.5b")           # 14 heads, kv=2 on a 16 axis
    sh = S.param_shardings(cfg, POD)
    # qkv bias (stacked (24, 896)): layer axis None, heads axis -> model
    assert spec_of(sh["blocks"]["attn"]["bq"]) == P(None, "model")
    # kv-head dims that DON'T divide replicate per-tensor: wk kv_embed
    # = 2*64 = 128 -> divides 16, so it shards; zamba2 conv (k=4) does not
    z = S.param_shardings(get_config("zamba2-7b"), POD)
    assert spec_of(z["blocks"]["ssm"]["conv_x"])[1] is None


def test_moe_param_shardings_ep():
    cfg = get_config("llama4-maverick-400b-a17b")
    sh = S.param_shardings(cfg, POD)
    # experts -> model (EP), embed -> data (FSDP), expert_mlp replicated
    # (leading stacked-layer axis never shards)
    assert spec_of(sh["blocks"]["ffn"]["wi"]) == P(None, "model", "data", None)
    assert spec_of(sh["blocks"]["ffn"]["wo"]) == P(None, "model", None, "data")


def test_cache_shardings_decode_seq_over_model():
    cfg = get_config("qwen3-14b")
    sh = S.cache_shardings(cfg, POD, batch=128, max_len=32768)
    # (layers, batch, seq, kv, hd): batch->data, seq->model (the fleet-wide
    # decode fix), kv replicated (8 % 16 != 0 anyway)
    assert spec_of(sh["k"]) == P(None, ("data",), "model", None, None)


def test_cache_shardings_long_context_all_axes():
    cfg = get_config("mamba2-370m")
    sh = S.cache_shardings(cfg, POD, batch=1, max_len=524288)
    # ssm cache: no seq axis; state shards heads over model
    assert spec_of(sh["ssm"]["state"]) == P(None, None, "model", None, None)


def test_cache_shardings_hybrid_long500k():
    cfg = get_config("zamba2-7b")
    sh = S.cache_shardings(cfg, POD, batch=1, max_len=524288)
    # batch=1 -> the shared-attn KV cache seq shards over EVERY axis
    assert spec_of(sh["shared_k"]) == P(None, None, ("data", "model"),
                                        None, None)


def test_batch_shardings_multipod():
    cfg = get_config("yi-6b")
    sh = S.batch_shardings(cfg, MULTI, batch=256)
    assert spec_of(sh["inputs"]) == P(("pod", "data"), None)


def test_input_specs_shapes():
    cfg = get_config("yi-6b")
    t = S.input_specs(cfg, SHAPES["train_4k"])
    assert t["batch"]["inputs"].shape == (256, 4096)
    d = S.input_specs(cfg, SHAPES["decode_32k"])
    assert d["tokens"].shape == (128,)
    assert d["cache"]["k"].shape == (32, 128, 32768, 4, 128)
    # embeddings frontend (stub modality): 3-D float inputs
    mg = get_config("musicgen-large")
    e = S.input_specs(mg, SHAPES["prefill_32k"])
    assert e["inputs"].shape == (32, 32768, 2048)


def test_logits_sharding_vocab_tp():
    cfg = get_config("yi-6b")
    sh = S.logits_sharding(cfg, POD, batch=32, with_seq=False)
    assert spec_of(sh) == P(("data",), "model")
