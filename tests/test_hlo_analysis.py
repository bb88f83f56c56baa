"""Unit tests for the roofline HLO analyzer on synthetic HLO text."""

import numpy as np

from repro.launch import hlo_analysis as H

SIMPLE = """
HloModule jit_f

ENTRY %main (p0: f32[128,256], p1: f32[256,64]) -> f32[128,64] {
  %p0 = f32[128,256]{1,0} parameter(0)
  %p1 = f32[256,64]{1,0} parameter(1)
  ROOT %dot.1 = f32[128,64]{1,0} dot(%p0, %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""


def test_dot_flops_counted():
    c = H.analyze_hlo(SIMPLE)
    assert c.flops == 2 * 128 * 64 * 256
    # io bytes: operands + output
    assert c.bytes == 4 * (128 * 256 + 256 * 64 + 128 * 64)


COLLECTIVE = """
HloModule jit_f

ENTRY %main (p0: f32[1024]) -> f32[1024] {
  %p0 = f32[1024]{0} parameter(0)
  %ar = f32[1024]{0} all-reduce(%p0), replica_groups={}
  ROOT %ag = f32[2048]{0} all-gather(%ar), dimensions={0}
}
"""


def test_collective_bytes_by_kind():
    c = H.analyze_hlo(COLLECTIVE)
    assert c.coll_by_kind["all-reduce"] == 4096
    assert c.coll_by_kind["all-gather"] == 4096   # operand bytes, not output
    assert c.coll_bytes == 8192


LOOP = """
HloModule jit_f

%cond (arg: (s32[], f32[8,8])) -> pred[] {
  %arg = (s32[], f32[8,8]) parameter(0)
  %iter = s32[] get-tuple-element(%arg), index=0
  %limit = s32[] constant(48)
  ROOT %lt = pred[] compare(%iter, %limit), direction=LT
}

%body (arg: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %arg = (s32[], f32[8,8]) parameter(0)
  %iter = s32[] get-tuple-element(%arg), index=0
  %x = f32[8,8]{1,0} get-tuple-element(%arg), index=1
  %d = f32[8,8]{1,0} dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %one = s32[] constant(1)
  %next = s32[] add(%iter, %one)
  ROOT %t = (s32[], f32[8,8]) tuple(%next, %d)
}

ENTRY %main (p0: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8]{1,0} parameter(0)
  %zero = s32[] constant(0)
  %init = (s32[], f32[8,8]) tuple(%zero, %p0)
  %w = (s32[], f32[8,8]) while(%init), condition=%cond, body=%body
  ROOT %out = f32[8,8]{1,0} get-tuple-element(%w), index=1
}
"""


def test_while_trip_count_multiplies_body():
    c = H.analyze_hlo(LOOP)
    # dot inside a 48-trip while: flops x 48 (the scan-over-layers pattern)
    assert c.flops == 48 * 2 * 8 * 8 * 8
    assert c.unresolved_loops == 0


FUSION_SLICE = """
HloModule jit_f

%fused (fp0: f32[48,64,64], fp1: s32[]) -> f32[64,64] {
  %fp0 = f32[48,64,64]{2,1,0} parameter(0)
  %fp1 = s32[] parameter(1)
  %zero = s32[] constant(0)
  ROOT %ds = f32[1,64,64]{2,1,0} dynamic-slice(%fp0, %fp1, %zero, %zero), dynamic_slice_sizes={1,64,64}
}

ENTRY %main (p0: f32[48,64,64], p1: s32[]) -> f32[64,64] {
  %p0 = f32[48,64,64]{2,1,0} parameter(0)
  %p1 = s32[] parameter(1)
  ROOT %f = f32[1,64,64]{2,1,0} fusion(%p0, %p1), kind=kLoop, calls=%fused
}
"""


def test_fusion_operand_charged_at_slice_size():
    """The scan-over-layers pattern: a fusion that reads one (1, 64, 64)
    slice of a stacked (48, 64, 64) operand is charged the SLICE bytes,
    not the full stack (what TPU HBM actually streams)."""
    c = H.analyze_hlo(FUSION_SLICE)
    slice_bytes = 4 * 64 * 64
    full_bytes = 48 * slice_bytes
    assert c.bytes_by_opcode["fusion"] < full_bytes
    assert c.bytes_by_opcode["fusion"] >= 2 * slice_bytes  # in + out


def test_roofline_terms_and_bound():
    cost = H.HloCost(flops=197e12, bytes=819e9 * 2, coll_by_kind={})
    rf = H.roofline_from_cost(cost, chips=1, model_flops=100e12)
    np.testing.assert_allclose(rf.compute_s, 1.0)
    np.testing.assert_allclose(rf.memory_s, 2.0)
    assert rf.bound == "memory"
    np.testing.assert_allclose(rf.useful_fraction, 100 / 197, rtol=1e-6)


def test_roofline_collective_bound():
    cost = H.HloCost(flops=1.0, bytes=1.0, coll_by_kind={"all-reduce": 50e9})
    rf = H.roofline_from_cost(cost, chips=1)
    assert rf.bound == "collective"
    np.testing.assert_allclose(rf.collective_s, 1.0)


def test_param_counts():
    from repro.configs import get_config
    cfg = get_config("yi-6b")
    n = H.param_count(cfg)
    assert 5.5e9 < n < 7.0e9          # "yi-6b"
    moe = get_config("moonlight-16b-a3b")
    total, active = H.param_count(moe), H.active_param_count(moe)
    # the published config: 671M embed + head, one dense layer (83M), 26
    # MoE layers of 64 x 8.65M routed + 17.3M shared + 13.8M latent attn
    assert 15.5e9 < total < 16.5e9    # "16B"
    assert 2.7e9 < active < 3.2e9     # "A3B": 6 of 64 routed experts
    assert active < total


def test_model_flops_includes_attention():
    from repro.configs import SHAPES, get_config
    cfg = get_config("yi-6b")
    f_train = H.model_flops_estimate(cfg, SHAPES["train_4k"])
    f_prefill = H.model_flops_estimate(cfg, SHAPES["prefill_32k"])
    n = H.param_count(cfg)
    # train: at least the 6*N*D weight term
    assert f_train > 6.0 * n * 4096 * 256
    # prefill at 32k: attention term must exceed the weight term
    weight_term = 2.0 * n * 32768 * 32
    assert f_prefill > 1.5 * weight_term
    # ssm arch: no attention term
    m = get_config("mamba2-370m")
    f = H.model_flops_estimate(m, SHAPES["prefill_32k"])
    assert f == 2.0 * H.param_count(m) * 32768 * 32
