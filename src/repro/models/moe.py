"""Mixture-of-Experts FFN: router, dropless grouped dispatch, capacity
dispatch (EP-ready), shared experts.

Routers (:func:`route`): softmax top-k, or DeepSeek-V3's sigmoid scores
with a selection-only bias; gates renormalized, then scaled.

Dispatch, by what the config states:

* **dropless** (``moe_dropless``, exact substrate): the (token, expert)
  pairs of the whole step are sorted by expert and each projection is
  ONE grouped matmul over them (``kernels/moe_gmm.py``, named
  ``moe_gmm``).  No token is dropped at any batch or chunk width.
* **capacity** (the SC substrates, and every config that is not
  dropless): routing, sort and capacity bookkeeping happen **per batch
  row** (axis 0 stays the data-sharded batch), so the sort is a local
  operation per shard — no global argsort collectives appear in the HLO.
  Dispatch is gather-based (Megablocks-style capacity buffers), NOT the
  GShard one-hot-einsum formulation: the (tokens × experts × capacity)
  dispatch tensor is never materialized.  Expert weights carry the
  "experts" logical axis -> TP/EP over the model mesh axis; the capacity
  buffer gets a sharding constraint on its expert axis, which XLA
  resolves into the canonical MoE all-to-all.  Overflow beyond the
  capacity factor drops tokens (standard capacity semantics; cf defaults
  to 1.25); a dropless config sizes capacity to the row, so nothing
  overflows there either.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import moe_gmm
from repro.models import layers
from repro.models.params import ParamSpec


def moe_specs(cfg):
    d, f, e = cfg.d_model, cfg.expert_d_ff, cfg.n_experts
    sp = {
        "router": ParamSpec((d, e), ("embed", None), "scaled"),
        # EP owns the model axis via "experts"; the expert-internal FFN dim
        # uses its own logical axis ("expert_mlp" -> replicated) since one
        # mesh axis cannot shard two dims of the same tensor.
        "wi": ParamSpec((e, d, 2 * f), ("experts", "embed", "expert_mlp"),
                        "scaled"),
        "wo": ParamSpec((e, f, d), ("experts", "expert_mlp", "embed"),
                        "scaled"),
    }
    if cfg.router == "noaux_tc":
        sp["router_bias"] = ParamSpec((e,), (None,), "zeros", jnp.float32)
    if cfg.n_shared_experts:
        sp["shared"] = layers.mlp_specs(cfg, cfg.n_shared_experts * f)
    return sp


def capacity(cfg, tokens_per_row: int) -> int:
    if cfg.moe_dropless:
        # a token picks top_k DISTINCT experts, so no expert receives more
        # than one slot per token of its row: nothing can overflow
        c = tokens_per_row
    else:
        c = int(tokens_per_row * cfg.top_k * cfg.capacity_factor
                / max(cfg.n_experts, 1))
    return max(8, -(-c // 8) * 8)          # round up to a multiple of 8


def route(x, p, cfg, key=None):
    """Router: x (b, s, d) -> (gates (b, s, k) float32, experts (b, s, k)).

    ``softmax``: top-k of the softmax over experts.  ``noaux_tc``
    (DeepSeek-V3, one group): scores s = sigmoid(x·Wr) in float32; the
    experts are the top-k of s + b, where ``router_bias`` b steers
    selection only; the gates are s of the chosen experts.  Either way
    the gates are normalized to sum 1, then scaled by
    ``routed_scaling_factor``.
    """
    logits = layers.dense(
        x.astype(jnp.float32), p["router"].astype(jnp.float32), cfg,
        layers.site_key(key, "moe_router"), site="moe_router",
        precision=jax.lax.Precision.HIGHEST)                  # (b, s, e)
    if cfg.router == "noaux_tc":
        scores = jax.nn.sigmoid(logits)
        _, eidx = jax.lax.top_k(scores + p["router_bias"], cfg.top_k)
        gates = jnp.take_along_axis(scores, eidx, axis=-1)
        eps = 1e-20
    else:
        gates, eidx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1),
                                    cfg.top_k)
        eps = 1e-9
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), eps)
    return gates * cfg.routed_scaling_factor, eidx


def moe_ffn(x, p, cfg, key=None, constrain=None):
    """x: (b, s, d) -> (b, s, d). ``constrain(x, *logical_axes)`` optional.

    ``key`` is None (exact substrate), one raw (2,) key, or a per-token
    (b, s, 2) key array (the paged engine's contract): per-token keys are
    GATHERED through the same token->slot dispatch as ``x``, so a token's
    expert matmuls draw from its own (request, position) key whatever
    slot it lands in — MoE outputs stay invariant to batch composition,
    chunking, and eviction/resume like every other site.  Shared experts
    see every token once.
    """
    with jax.named_scope("moe_route"):
        gates, eidx = route(x, p, cfg, key)
    with jax.named_scope("moe_experts"):
        if cfg.moe_dropless and cfg.sc_backend == "exact":
            out = _grouped_experts(x, p, gates, eidx)
        else:
            out = _capacity_experts(x, p, cfg, gates, eidx, key, constrain)
    if cfg.n_shared_experts:
        with jax.named_scope("moe_shared"):
            out = out + layers.mlp(x, p["shared"], cfg, key)
    return out


def _grouped_experts(x, p, gates, eidx):
    """Dropless dispatch: every (token, expert) pair of the step, sorted
    by expert, through one ``moe_gmm`` call per projection; the pairs
    return to token order and are summed under their gates."""
    b, s, d = x.shape
    k = eidx.shape[-1]
    flat_e = eidx.reshape(-1)                                  # (T·k,)
    order = jnp.argsort(flat_e, stable=True)
    sizes = jnp.zeros((p["wi"].shape[0],), jnp.int32).at[flat_e].add(1)
    xs = x.reshape(b * s, d)[order // k]                       # (T·k, d)
    h = moe_gmm.moe_gmm(xs, p["wi"], sizes)
    gate, up = jnp.split(h, 2, axis=-1)
    act = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    y = moe_gmm.moe_gmm(act, p["wo"], sizes)                   # (T·k, d)
    y = jnp.zeros_like(y).at[order].set(y).reshape(b * s, k, d)
    out = jnp.einsum("tkd,tk->td", y.astype(jnp.float32),
                     gates.reshape(b * s, k))
    return out.reshape(b, s, d).astype(x.dtype)


def _capacity_experts(x, p, cfg, gates, eidx, key, constrain):
    """Capacity-buffer dispatch per batch row (module notes)."""
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    cap = capacity(cfg, s)
    cst = constrain or (lambda v, *a: v)

    # --- per-row capacity assignment via local sort --------------------
    flat_e = eidx.reshape(b, s * k)                            # (b, sk)
    order = jnp.argsort(flat_e, axis=-1, stable=True)          # (b, sk)
    sorted_e = jnp.take_along_axis(flat_e, order, axis=-1)
    first = jax.vmap(lambda se: jnp.searchsorted(se, jnp.arange(e)))(sorted_e)
    rank = jnp.arange(s * k)[None, :] - jnp.take_along_axis(
        first, sorted_e, axis=-1)                              # pos in expert
    keep = rank < cap
    slot = jnp.where(keep, sorted_e * cap + rank, e * cap)     # drop -> slot E*C
    token = order // k                                         # source token

    # --- dispatch: (b, e, cap, d) capacity buffers ----------------------
    xg = jnp.take_along_axis(x, token[..., None], axis=1)      # (b, sk, d)
    buf = jnp.zeros((b, e * cap + 1, d), x.dtype)
    buf = jax.vmap(lambda bf, sl, xv: bf.at[sl].set(xv))(buf, slot, xg)
    buf = buf[:, : e * cap].reshape(b, e, cap, d)
    buf = cst(buf, "batch", "experts", None, None)             # EP a2a here

    ekey = key
    if key is not None and key.ndim == 3:
        # per-token keys ride the SAME dispatch as x: gather by source
        # token, scatter into capacity slots (empty slots keep zero keys;
        # their x rows are zero so their outputs are zero regardless)
        kg = jnp.take_along_axis(key, token[..., None], axis=1)
        kbuf = jnp.zeros((b, e * cap + 1, 2), key.dtype)
        kbuf = jax.vmap(lambda bf, sl, kv: bf.at[sl].set(kv))(kbuf, slot, kg)
        ekey = kbuf[:, : e * cap].reshape(b, e, cap, 2)

    # --- expert FFN (SwiGLU) through the substrate, per-expert keys -----
    h = layers.expert_dense(buf, p["wi"], cfg, ekey, site="moe_wi")
    gate, up = jnp.split(h, 2, axis=-1)
    act = jax.nn.silu(gate.astype(jnp.float32)).astype(buf.dtype) * up
    y = layers.expert_dense(act, p["wo"], cfg, ekey, site="moe_wo")
    y = cst(y, "batch", "experts", None, None)

    # --- combine: gather back per (token, k) slot, weight, scatter-add --
    yflat = jnp.pad(y.reshape(b, e * cap, d), ((0, 0), (0, 1), (0, 0)))
    ytk = jax.vmap(lambda yf, sl: yf[sl])(yflat, slot)         # (b, sk, d)
    gate_sorted = jnp.take_along_axis(gates.reshape(b, s * k), order, axis=-1)
    ytk = ytk * gate_sorted[..., None].astype(ytk.dtype)
    out = jnp.zeros((b, s, d), x.dtype)
    return jax.vmap(lambda ob, tk, yv: ob.at[tk].add(yv))(out, token, ytk)


def load_balancing_loss(router_probs, eidx, n_experts: int):
    """Switch-style aux loss: E · Σ_e f_e · P_e (optional, train.py wires it)."""
    b, s, k = eidx.shape
    counts = jnp.zeros((n_experts,)).at[eidx.reshape(-1)].add(1.0)
    f = counts / (b * s * k)
    pmean = router_probs.mean(axis=(0, 1))
    return n_experts * jnp.sum(f * pmean)
