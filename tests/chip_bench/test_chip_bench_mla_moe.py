"""The latent-attention MoE configuration: its file states the sizes the
program runs, the plain reference (``references/mla_moe.py``) agrees
with the program's own forward pass at small widths in float32, its
lower-precision controls depart by more than rounding, a run of the
small preset through ``run.py`` is correct where its fp8 control is not
(limit set from their readings on the CPU: sound runs read a mean logit
cosine of 0.9950-0.9987, the fp8 control 0.934-0.943), and the counts
its readers use."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import counts_mla_moe, harness, trace_reduce, weights
from benchmarks.chip.references import mla_moe as ref

HERE = os.path.dirname(os.path.abspath(__file__))

# Hugging Face key -> program field, for the keys harness.model_config
# does not map: the program's config must hold them as the file states
FIELDS = {"kv_lora_rank": "kv_lora_rank",
          "qk_nope_head_dim": "qk_nope_head_dim",
          "qk_rope_head_dim": "qk_rope_head_dim",
          "v_head_dim": "v_head_dim",
          "first_k_dense_replace": "first_k_dense",
          "n_routed_experts": "n_experts",
          "num_experts_per_tok": "top_k",
          "moe_intermediate_size": "moe_d_ff",
          "n_shared_experts": "n_shared_experts",
          "routed_scaling_factor": "routed_scaling_factor",
          "rms_norm_eps": "rms_norm_eps"}


def _load(name):
    with open(name) as f:
        return json.load(f)


MODEL_CONFIG = harness.model_config


def _program(c):
    """The program config a file describes (the tiny preset's sizes put
    in where harness.model_config does not map them)."""
    return MODEL_CONFIG(c).replace(
        **{attr: c[key] for key, attr in FIELDS.items()})


def test_config_file_states_the_sizes_the_program_runs():
    c = _load(os.path.join(harness.HERE, "configs", "moonlight-16b-a3b.json"))
    cfg = harness.model_config(c)
    for key, attr in FIELDS.items():
        assert getattr(cfg, attr) == c[key], key
    assert (c["scoring_func"], c["topk_method"]) == ("sigmoid", "noaux_tc")
    assert cfg.router == "noaux_tc" and c["norm_topk_prob"]
    assert (c["n_group"], c["topk_group"], c["q_lora_rank"]) == (1, 1, None)
    assert cfg.moe_dropless and cfg.mla
    assert (cfg.n_layers, cfg.d_ff, cfg.vocab) == (9, 11264, 163840)
    from repro.models import lm
    assert lm.n_backbone_layers(cfg) == 8


def test_reference_matches_the_program_forward():
    from repro.models import lm
    c = _load(os.path.join(HERE, "tiny_mla_moe.json"))
    c["program"]["dtype"] = "float32"
    cfg = _program(c)
    params = weights.make_params(cfg, 5)
    toks = jnp.asarray(np.random.default_rng(0).integers(3, 2048, 40),
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


def test_a_run_is_correct_where_its_control_is_not(monkeypatch, capsys):
    from test_chip_bench_window import drive, tiny_cell
    cell = tiny_cell(name="moonlight-16b-a3b.batch64",
                     config="tiny_mla_moe.json")
    monkeypatch.setattr(harness, "model_config", _program)
    args = ["--workload", cell.name, "--seed", "12", "--seconds", "1.5",
            "--trace", "0"]
    sound, _ = drive(monkeypatch, capsys, cell, args)
    control, _ = drive(monkeypatch, capsys, cell, args + ["--control", "1"])
    assert sound["correct"] is True and sound["failed"] == 0
    assert control["correct"] is False
    floor = control["checks"]["cos_mean_floor"]
    assert floor["value"] < floor["limit"]


def test_counts_of_the_absorbed_step():
    c = _load(os.path.join(harness.HERE, "configs", "moonlight-16b-a3b.json"))
    # one new position attending to itself: 2·16·(576 + 512) a layer
    assert counts_mla_moe.attention_flops(c, 0, 1) == 2 * 16 * 1088 * 9
    flops, byts = counts_mla_moe.gmm_work(c, 2, 2, 2)
    per_expert = 3 * 2048 * 1408
    assert flops == 2 * 12 * per_expert * 8
    assert byts == (12 * per_expert * 2 + 12 * (4096 + 3 * 1408) * 2) * 8
    # the step counts what the layers hold: routed top-6 and 2 shared
    live = counts_mla_moe.step_flops(c, [(10, 1), (0, 0)])
    assert live == (2 * (counts_mla_moe.attn_macs_per_position(c) * 9
                         + 3 * 2048 * 11264
                         + (2048 * 64 + 8 * per_expert) * 8)
                    + 2 * 2048 * 163840
                    + counts_mla_moe.attention_flops(c, 10, 1))


def test_kernel_time_is_the_kernel_calls_alone():
    red = trace_reduce.Reduction(
        window=(0, 10), chips=1, busy_ns=6, op_text={}, modules=[], gaps=[],
        op_ns={"%moe_gmm.3 = bf16[384,2816] custom-call(bf16[384,2048] %a)": 4,
               "%fusion.2 = bf16[384,1408] fusion(bf16[384,2816] "
               "%moe_gmm.3)": 1,
               "%mla_paged_attention.1 = f32[64,1,16,512] custom-call()": 1})
    assert counts_mla_moe.kernel_ns(red, "moe_gmm") == 4
    assert counts_mla_moe.kernel_ns(red, "mla_paged_attention") == 1
    assert counts_mla_moe.kernel_ns(red, "paged_attention") is None
