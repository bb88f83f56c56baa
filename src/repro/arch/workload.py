"""Static workload extraction: a ModelConfig's SC-routed matmuls.

``dense_workload(cfg, tokens)`` enumerates every matmul a forward pass
routes through ``layers.dense`` (and therefore through ``sc_dot`` when
``cfg.sc_backend`` is stochastic and an rng is plumbed), with explicit
per-layer multiplicity —
the scanned layer body compiles once but the hardware executes it
``n_layers`` times, so a compile-time trace alone under-counts. This is
what lets the trace benchmark and ``profile_cell --sc-trace`` price a
PRODUCTION-shape forward pass without materializing any O(M·K·N) numerics.

Attention score/value einsums and the SSM state scan are not SC-routed
(they are not ``dense`` calls) and are deliberately absent.
"""

from __future__ import annotations

import dataclasses

from repro.arch.accounting import (
    TraceReport, merge_concurrent_reports, merge_reports)
from repro.arch.backend import schedule_call
from repro.arch.spec import ArraySpec
from repro.core.costmodel import CostParams


@dataclasses.dataclass(frozen=True)
class MatmulSite:
    """One dense() site: (tokens, k) @ (k, n), executed ``count`` times."""

    label: str
    m: int
    k: int
    n: int
    count: int

    @property
    def products(self) -> int:
        return self.m * self.k * self.n * self.count


def dense_workload(cfg, tokens: int) -> list[MatmulSite]:
    """All dense() matmuls of one forward pass over ``tokens`` tokens."""
    d, hd = cfg.d_model, cfg.resolved_head_dim
    h, kvh = cfg.n_heads, cfg.n_kv_heads
    sites: list[MatmulSite] = []
    add = lambda label, k, n, count=1: sites.append(
        MatmulSite(label, tokens, k, n, count))

    # Layer multiplicities come from the lm assembly itself so the static
    # pricing can never drift from what the scan actually executes.
    from repro.models import lm
    n_layers = cfg.n_layers
    if cfg.family in ("ssm", "hybrid"):
        # Mamba2 block projections (ssm.py): z, x, B, C, dt in; out proj.
        di, st = cfg.d_inner, cfg.ssm_state
        n_ssm = lm.n_backbone_layers(cfg)
        add("ssm.wz", d, di, n_ssm)
        add("ssm.wx", d, di, n_ssm)
        add("ssm.wB", d, st, n_ssm)
        add("ssm.wC", d, st, n_ssm)
        add("ssm.wdt", d, cfg.ssm_heads, n_ssm)
        add("ssm.out", di, d, n_ssm)
        if cfg.family == "hybrid":
            n_shared = lm.n_shared_invocations(cfg)
            _attn_sites(add, d, h, kvh, hd, n_shared, prefix="shared.")
            _mlp_sites(add, cfg, n_shared, prefix="shared.")
    else:
        if cfg.mla:
            _mla_sites(add, cfg, n_layers)
        else:
            _attn_sites(add, d, h, kvh, hd, n_layers)
        if cfg.family == "moe":
            # leading dense layers, then router + top_k routed expert
            # visits per token (dense equivalents) + the shared experts
            n_moe = lm.n_backbone_layers(cfg)
            if cfg.first_k_dense:
                _mlp_sites(add, cfg, cfg.first_k_dense)
            f = cfg.expert_d_ff
            add("moe.router", d, cfg.n_experts, n_moe)
            add("moe.wi", d, 2 * f, n_moe * cfg.top_k)
            add("moe.wo", f, d, n_moe * cfg.top_k)
            if cfg.n_shared_experts:
                fs = cfg.n_shared_experts * f
                add("moe.shared.wi", d, 2 * fs, n_moe)
                add("moe.shared.wo", fs, d, n_moe)
        else:
            _mlp_sites(add, cfg, n_layers)
    # Zoo sites outside the scanned blocks: the embeddings-frontend
    # projection and the unembed head both dispatch through dense() with
    # the threaded rng (sites "frontend_proj" / "unembed"), so they are
    # part of the SC-routed workload too (keep in sync with lm.forward).
    if cfg.frontend == "embeddings":
        add("frontend.proj", d, d)
    add("unembed", d, cfg.vocab)
    return sites


def _attn_sites(add, d, h, kvh, hd, count, prefix=""):
    add(prefix + "attn.wq", d, h * hd, count)
    add(prefix + "attn.wk", d, kvh * hd, count)
    add(prefix + "attn.wv", d, kvh * hd, count)
    add(prefix + "attn.wo", h * hd, d, count)


def _mla_sites(add, cfg, count):
    """Latent attention's projections as the naive forward runs them."""
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    add("attn.wq", d, h * (dn + dr), count)
    add("attn.wkv_a", d, r + dr, count)
    add("attn.wkv_b", r, h * (dn + dv), count)
    add("attn.wo", h * dv, d, count)


def _mlp_sites(add, cfg, count, prefix=""):
    wi_cols = 2 * cfg.d_ff if cfg.mlp_variant == "swiglu" else cfg.d_ff
    add(prefix + "mlp.wi", cfg.d_model, wi_cols, count)
    add(prefix + "mlp.wo", cfg.d_ff, cfg.d_model, count)


def price_workload(sites, nbit: int, spec: ArraySpec | None = None,
                   params: CostParams | None = None):
    """Schedule every site on the array and price the whole pass.

    Returns ``(per_site, total)`` where ``per_site`` is a list of
    ``(site, TraceReport)`` — the site's report already includes its
    ``count`` multiplicity — and ``total`` merges them all.
    """
    per_site: list[tuple[MatmulSite, TraceReport]] = []
    for s in sites:
        one = schedule_call(s.m, s.k, s.n, nbit, spec, params).report
        per_site.append((s, merge_reports([one] * s.count)))
    total = merge_reports(r for _, r in per_site)
    return per_site, total


def shard_site(site: MatmulSite, data: int = 1, model: int = 1) -> MatmulSite:
    """One mesh slice's share of ``site`` under the SC sharding rules:
    rows (m) split over the ``data`` span, contraction (k) over ``model``
    (ceil-division — indivisible dims cost the padded shard)."""
    ceil = lambda a, b: -(-a // b)
    return dataclasses.replace(site, m=ceil(site.m, max(data, 1)),
                               k=ceil(site.k, max(model, 1)))


def price_workload_sharded(sites, nbit: int, *, data: int = 1,
                           model: int = 1, spec: ArraySpec | None = None,
                           params: CostParams | None = None):
    """Price a workload executed mesh-sharded: ``data × model`` chips,
    each running one shard of every matmul concurrently.

    Each site is priced as its per-shard slice (rows ÷ ``data``,
    contraction ÷ ``model``; see :func:`shard_site`), the shard reports
    merge as CONCURRENT banks (makespan = slowest shard, energy and
    products add — the psum/adder-tree merge itself is free, like MERGE
    in the single-chip trace), and sites serialize as usual.  With
    ``data == model == 1`` this is exactly :func:`price_workload`.

    Returns ``(per_site, total)`` shaped like :func:`price_workload`.
    """
    n_shards = max(data, 1) * max(model, 1)
    per_site: list[tuple[MatmulSite, TraceReport]] = []
    for s in sites:
        piece = shard_site(s, data, model)
        one = schedule_call(piece.m, piece.k, piece.n, nbit,
                           spec, params).report
        sharded = merge_concurrent_reports([one] * n_shards)
        per_site.append((s, merge_reports([sharded] * s.count)))
    total = merge_reports(r for _, r in per_site)
    return per_site, total
