"""Model-zoo benchmark: every family x backend x nbit on the SC substrate.

The zoo refactor routes EVERY matmul site — dense MLPs, the MoE router
and per-expert FFNs, the SSM projections, the embeddings-frontend
projection, the unembed — through the ``repro.sc`` registry, and serves
every family on the paged engine via the per-family cache plan.  This
bench is the matrix that proves it stays true:

  1. Accuracy vs nbit (paper Fig. 7 lifted to whole-model forwards):
     cosine similarity between each stochastic backend's logits and the
     exact reference, per family, per bit budget — ``*_acc`` leaves that
     ``tools/bench_compare.py`` gates with an absolute-drop band.
  2. Variance sweep (Fig. 8 analogue): the sigma of repeated stochastic
     forwards must shrink ~1/sqrt(nbit); recorded as a
     ``variance_shrink_speedup`` ratio with a hard assert.
  3. Decode: each family drains a request through ``PagedServingEngine``
     on the moment substrate (SSM/hybrid ride the state slots beside the
     block table) and its greedy tokens must match the fixed-slot
     engine — ``paged_matches_fixed`` is an exact-gated flag.

Writes ``BENCH_zoo.json`` (CI archives it and diffs against
``benchmarks/baselines/BENCH_zoo.json``).  ``--tiny`` shrinks nbits,
repeats, and sequence lengths for the smoke job.
"""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, section, timed, write_json
from repro.models import lm, params as params_lib
from repro.configs import get_smoke_config
from repro.serve import Request, ServeOptions, build_engine
from repro.serve.kv_cache import CachePlan

# one representative arch per cache-plan family; musicgen covers the
# embeddings frontend (frontend_proj site) on top of plain attention
FAMILIES = {
    "dense": "qwen2-0.5b",
    "moe": "moonlight-16b-a3b",
    "ssm": "mamba2-370m",
    "hybrid": "zamba2-7b",
    "multimodal": "musicgen-large",
}
BACKENDS = ("exact", "moment", "pallas_fused")
NBITS = (64, 256, 1024)
VAR_REPEATS = 32

_TINY = dict(nbits=(32, 64), pallas_nbits=(32,), var_repeats=12, seq=6,
             var_families=("dense",), iters=1, warmup=0)
_FULL = dict(nbits=NBITS, pallas_nbits=NBITS, var_repeats=VAR_REPEATS,
             seq=12, var_families=("dense", "moe"), iters=3, warmup=1)

# variance must shrink with nbit: sigma ratio across a 2x (tiny) / 16x
# (full) bit-budget step, floored well under the ~sqrt ideal
VAR_SHRINK_FLOOR_TINY = 1.05
VAR_SHRINK_FLOOR = 2.0


def _cfg(arch, **kw):
    return get_smoke_config(arch).replace(
        param_dtype=jnp.float32, act_dtype=jnp.float32, **kw)


def _inputs(key, cfg, s):
    if cfg.frontend == "embeddings":
        return jax.random.normal(key, (1, s, cfg.d_model), cfg.act_dtype)
    return jax.random.randint(key, (1, s), 3, cfg.vocab)


def _cos(a, b):
    a = np.asarray(a, np.float64).ravel()
    b = np.asarray(b, np.float64).ravel()
    return float(a @ b / max(np.linalg.norm(a) * np.linalg.norm(b), 1e-30))


def _forward_matrix(family, arch, knobs, key):
    """Accuracy-vs-nbit block of one family: logits cosine + wall time."""
    cfg0 = _cfg(arch)
    params = params_lib.init_params(key, lm.lm_param_specs(cfg0),
                                    cfg0.param_dtype)
    x = _inputs(jax.random.fold_in(key, 1), cfg0, knobs["seq"])
    rng = jax.random.fold_in(key, 2)
    exact = lm.forward(params, x, cfg0.replace(sc_backend="exact"), rng=rng)
    out = {}
    for backend in BACKENDS:
        per_nbit = {}
        # interpreted Pallas compiles dominate: --tiny trims the fused
        # leg to one bit budget and one timing call (log what's dropped)
        nbits = (knobs["pallas_nbits"] if backend.startswith("pallas")
                 else knobs["nbits"])
        if backend.startswith("pallas") and len(nbits) < len(knobs["nbits"]):
            print(f"  [{family}.{backend}: nbit sweep trimmed to "
                  f"{list(nbits)} under --tiny]")
        for nbit in nbits:
            cfg = cfg0.replace(sc_backend=backend, sc_nbit=nbit)
            fwd = lambda: lm.forward(params, x, cfg, rng=rng)
            wall = timed(fwd, iters=knobs["iters"], warmup=knobs["warmup"])
            acc = 1.0 if backend == "exact" else _cos(fwd(), exact)
            emit(f"zoo.{family}.{backend}.n{nbit}.logits_cos_acc",
                 round(acc, 4), f"cosine vs exact logits, seq={knobs['seq']}")
            per_nbit[f"n{nbit}"] = {"logits_cos_acc": round(acc, 4),
                                    "wall_us": round(wall, 1)}
            if backend == "exact":
                break                      # nbit is a no-op for exact
        out[backend] = per_nbit
    return out, params, cfg0


def _variance_sweep(family, params, cfg0, knobs, key):
    """Fig. 8 analogue: sigma of repeated moment forwards vs nbit."""
    x = _inputs(jax.random.fold_in(key, 1), cfg0, knobs["seq"])
    lo, hi = knobs["nbits"][0], knobs["nbits"][-1]
    sigma = {}
    for nbit in (lo, hi):
        cfg = cfg0.replace(sc_backend="moment", sc_nbit=nbit)
        outs = np.stack([
            np.asarray(lm.forward(params, x, cfg,
                                  rng=jax.random.fold_in(key, 100 + r)))
            for r in range(knobs["var_repeats"])])
        sigma[nbit] = float(outs.std(axis=0).mean())
    shrink = sigma[lo] / max(sigma[hi], 1e-30)
    ideal = float(np.sqrt(hi / lo))
    emit(f"zoo.{family}.variance_shrink_speedup", round(shrink, 2),
         f"sigma(n{lo})/sigma(n{hi}), ideal ~{ideal:.1f}x")
    floor = (VAR_SHRINK_FLOOR_TINY if knobs is _TINY else VAR_SHRINK_FLOOR)
    assert shrink >= floor, (
        f"{family}: variance shrank only {shrink:.2f}x from nbit={lo} to "
        f"nbit={hi} (floor {floor}x) — the substrate stopped averaging")
    return {f"sigma_n{lo}": round(sigma[lo], 5),
            f"sigma_n{hi}": round(sigma[hi], 5),
            "variance_shrink_speedup": round(shrink, 2)}


def _drain(engine, prompt):
    engine.submit(Request(rid=0, prompt=list(prompt), max_new_tokens=4))
    return engine.run_until_drained()[0].generated


def _decode_check(family, params, cfg0, key):
    """Serve the family through the paged engine: greedy token identity
    vs the fixed-slot engine on the exact substrate (the cache-plan
    contract — chunked prefill reshapes stochastic draws, so identity
    across *engines* is an exact-backend property; the rng invariants on
    stochastic substrates are paged-vs-paged, pinned in
    tests/test_serve_zoo.py), plus a moment-substrate paged drain."""
    prompt = [5, 9, 17, 3, 8]
    popts = ServeOptions(paged=True, slots=1, max_len=32, block_size=4,
                         prefill_chunk=3)
    cfg = cfg0.replace(sc_backend="exact")
    want = _drain(build_engine(params, cfg,
                               ServeOptions(slots=1, max_len=32)), prompt)
    got = _drain(build_engine(params, cfg, popts), prompt)
    ok = got == want
    plan = CachePlan.for_config(cfg)
    emit(f"zoo.{family}.paged_matches_fixed", int(ok),
         f"plan: {plan.paged_layers} paged / {plan.state_layers} state "
         "layers")
    assert ok, (f"{family}: paged tokens {got} != fixed-slot {want} — "
                "the cache plan broke token identity")
    mcfg = cfg0.replace(sc_backend="moment", sc_nbit=64)
    stoch = _drain(build_engine(params, mcfg, popts), prompt)
    emit(f"zoo.{family}.stochastic_decode_ok", int(len(stoch) == 4),
         "moment-substrate paged drain")
    return {"paged_matches_fixed": ok,
            "stochastic_decode_ok": len(stoch) == 4,
            "paged_layers": plan.paged_layers,
            "state_layers": plan.state_layers,
            "generated": len(got)}


def main(key=None, tiny: bool = False):
    key = key if key is not None else jax.random.PRNGKey(11)
    knobs = _TINY if tiny else _FULL
    results: dict = {}
    for i, (family, arch) in enumerate(FAMILIES.items()):
        fkey = jax.random.fold_in(key, i)
        section(f"{family} ({arch}): backends x nbit, seq={knobs['seq']}")
        backends, params, cfg0 = _forward_matrix(family, arch, knobs, fkey)
        entry = {"arch": arch, "backends": backends}
        if family in knobs["var_families"]:
            entry["variance"] = _variance_sweep(family, params, cfg0,
                                                knobs, fkey)
        if cfg0.frontend == "tokens":      # serve path is token-frontend
            entry["decode"] = _decode_check(family, params, cfg0, fkey)
        results[family] = entry
    write_json("BENCH_zoo.json",
               {"tiny": tiny,
                "workload": {"seq": knobs["seq"],
                             "nbits": list(knobs["nbits"]),
                             "var_repeats": knobs["var_repeats"]},
                "families": results})


if __name__ == "__main__":
    main(tiny="--tiny" in sys.argv)
