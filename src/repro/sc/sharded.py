"""Mesh-sharded SC execution: ``sc_dot`` split across device-mesh axes.

The paper's throughput comes from memory-level parallelism — every MRAM
row is an independent SC engine, and arrays scale by running many engines
at once.  This module is the software analogue one level up: a single
``sc_dot`` contraction is split across the axes of a JAX device mesh with
``shard_map``, so every mesh slice runs its own SC engines on its own
operand shard:

* the flattened row dimension M of ``x`` shards over the *batch* axes
  (``("pod", "data")`` by default — pure data parallelism, no collective
  needed on the forward pass);
* the contraction dimension K shards over the *contract* axes
  (``("model",)`` by default) — each shard pop-counts its own slice of the
  K products and the partial signed accumulations merge with a
  ``psum``, exactly as per-subarray POPCOUNTs merge through the adder
  tree inside one chip (§IV).

RNG semantics: every shard folds the caller's key with its index along
each axis that actually splits the operands (``fold_in`` per axis), so
shards draw independent stochastic bits while the whole computation stays
a deterministic function of (key, mesh, rules).  Axes of size one — and
axes that do not divide their dimension — are dropped by
:func:`resolve_rules` and do NOT perturb the key, so a degenerate 1×1
mesh (or rules naming no live axis) reproduces single-device ``sc_dot``
bit-for-bit with the same key.

Gradients: the straight-through VJP lives at the ``sc_dot`` dispatch
boundary and ``shard_map`` differentiates through it — the ``psum``
transposes to a broadcast, each shard computes the exact-product jacobian
for its operand block, and the assembled gradient equals the unsharded
exact-matmul gradient.

The model stack routes here automatically: ``models/layers.py:dense``
consults :func:`active_mesh` and calls :func:`sc_dot_sharded` whenever a
mesh scope (:func:`use_mesh`) is active, so training and serving scale
across devices with no caller changes.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math

import jax

from repro.sc.config import ScConfig
from repro.sc.registry import sc_dot


@dataclasses.dataclass(frozen=True)
class ScShardRules:
    """Which mesh axes shard an ``sc_dot``.

    ``batch`` axes split the flattened row dimension M of ``x`` (pure data
    parallelism); ``contract`` axes split the contraction dimension K (the
    partial accumulations merge with a ``psum``).  Axis names that are
    absent from the mesh, have size one, or do not divide their dimension
    are dropped per-call by :func:`resolve_rules`.
    """

    batch: tuple = ("pod", "data")
    contract: tuple = ("model",)


DEFAULT_RULES = ScShardRules()


def resolve_rules(mesh, m: int, k: int,
                  rules: ScShardRules | None = None) -> ScShardRules:
    """Concretize ``rules`` against ``mesh`` and the call shape.

    Keeps only axes that exist in the mesh with size > 1 and whose product
    divides the dimension they shard (M for batch axes, K for contract
    axes).  Indivisible dims therefore fall back to replication — the same
    per-tensor degradation the parameter sharding rules use.
    """
    rules = rules if rules is not None else DEFAULT_RULES
    sizes = dict(mesh.shape)

    def live(axes, dim):
        kept = []
        span = 1
        for ax in axes:
            sz = sizes.get(ax, 1)
            if sz > 1 and dim % (span * sz) == 0:
                kept.append(ax)
                span *= sz
        return tuple(kept)

    return ScShardRules(batch=live(tuple(rules.batch), m),
                        contract=live(tuple(rules.contract), k))


def _axis_span(mesh, axes) -> int:
    sizes = dict(mesh.shape)
    return math.prod(sizes[a] for a in axes) if axes else 1


def shard_counts(mesh, m: int, k: int,
                 rules: ScShardRules | None = None) -> tuple:
    """(batch shards, contract shards) a call would actually split into."""
    r = resolve_rules(mesh, m, k, rules)
    return _axis_span(mesh, r.batch), _axis_span(mesh, r.contract)


def sc_dot_sharded(key, x, w, cfg: ScConfig = ScConfig(), *, mesh,
                   rules: ScShardRules | None = None):
    """``x @ w`` through the SC substrate, sharded over ``mesh``.

    x: (..., K); w: (K, N); returns (..., N) exactly like ``sc_dot``.
    Leading dims of ``x`` flatten to M, which shards over ``rules.batch``;
    K shards over ``rules.contract`` with the partial signed pop-count
    accumulations merged by a ``psum`` (the straight-through VJP rides
    through it).  Every shard folds ``key`` with its mesh indices, so
    shards draw independent bits; when no axis survives
    :func:`resolve_rules` this is exactly ``sc_dot(key, x, w, cfg)`` —
    same key, same bits.
    """
    from jax.sharding import PartitionSpec as P

    lead = x.shape[:-1]
    k_dim = x.shape[-1]
    m = math.prod(lead) if lead else 1
    r = resolve_rules(mesh, m, k_dim, rules)
    if not r.batch and not r.contract:
        return sc_dot(key, x, w, cfg)

    n_shards = _axis_span(mesh, r.batch) * _axis_span(mesh, r.contract)
    x2 = x.reshape(m, k_dim)
    batch_spec = r.batch if r.batch else None
    contract_spec = r.contract if r.contract else None

    def local(key, xs, ws):
        for ax in r.batch + r.contract:
            key = jax.random.fold_in(key, jax.lax.axis_index(ax))
        y = sc_dot(key, xs, ws, cfg)
        if r.contract:
            y = jax.lax.psum(y, r.contract)
        return y

    mapped = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(batch_spec, contract_spec), P(contract_spec, None)),
        out_specs=P(batch_spec, None),
        check_vma=False)
    with shard_scope(n_shards):
        y = mapped(key, x2, w)
    return y.reshape(*lead, w.shape[-1])


# ---------------------------------------------------------------------------
# Ambient mesh scope — what makes dense() route here with no caller changes
# ---------------------------------------------------------------------------

_MESH_STACK: list = []


@contextlib.contextmanager
def use_mesh(mesh, rules: ScShardRules | None = None):
    """Scope within which the model stack shards every SC matmul.

    While active, ``models.layers.dense`` routes stochastic matmuls
    through :func:`sc_dot_sharded` on this mesh.  The scope must surround
    the *tracing* of the jitted computation (the first call), because
    that is when ``dense`` consults it.
    """
    _MESH_STACK.append((mesh, rules))
    try:
        yield mesh
    finally:
        _MESH_STACK.pop()


def active_mesh():
    """(mesh, rules) of the innermost :func:`use_mesh`, or ``None``."""
    return _MESH_STACK[-1] if _MESH_STACK else None


# ---------------------------------------------------------------------------
# Shard multiplicity scope — read by the `array` backend's trace records
# ---------------------------------------------------------------------------

_SHARD_COUNT: list[int] = [1]


@contextlib.contextmanager
def shard_scope(n: int):
    """Mark that sc_dot dispatches traced inside run on ``n`` concurrent
    mesh shards.  ``shard_map`` traces its body once for all shards, so
    the ``array`` backend stamps each CallRecord with this multiplicity
    and the accountant merges shard reports as *concurrent* banks
    (makespan = max, energy/products add) rather than serial calls."""
    _SHARD_COUNT.append(n)
    try:
        yield
    finally:
        _SHARD_COUNT.pop()


def current_shard_count() -> int:
    return _SHARD_COUNT[-1]
