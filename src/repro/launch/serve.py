"""Serving launcher: continuous-batching engine over synthetic requests.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --smoke \
        --requests 8 --slots 4
    PYTHONPATH=src python -m repro.launch.serve --smoke --paged \
        --block-size 8 --max-blocks 64          # paged KV + chunked prefill
    PYTHONPATH=src python -m repro.launch.serve --smoke --paged \
        --fault-profile tiny                    # serve on a non-ideal device
    PYTHONPATH=src python -m repro.launch.serve --smoke --chaos \
        --fault-profile tiny                    # 2-shard fleet, drain/resume
    PYTHONPATH=src python -m repro.launch.serve --smoke --paged \
        --metrics-out metrics.prom --trace-out trace.jsonl   # telemetry

Engine flags are DERIVED from ``serve.ServeOptions`` field metadata
(``serve.add_cli_args``) — the launcher only hand-rolls its workload
knobs (--arch/--smoke/--requests/--max-new/--temperature/
--shared-prefix) and output paths.  Construction goes through
``serve.build_engine``; ``--chaos`` serves a 2-shard paged fleet under
``ft.FleetSupervisor`` with a deterministic mid-run shard degradation
and prints the drain/resume ledger.

``--metrics-out`` / ``--trace-out`` turn observability on: the global
``repro.obs`` registry is enabled (so substrate counters — sc dispatch,
autotune hits, arch pricing, device bit errors — record too), a tracer
is installed for the run, and after the drain the Prometheus exposition
and span JSONL land at the given paths (``.json`` metrics suffix writes
the JSON snapshot instead).  Render either with ``tools/obs_report.py``.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro import obs, serve
from repro.configs import get_config, get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.models import lm, params as params_lib
from repro.serve import Request
from repro.sharding import sc_shard_rules


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--shared-prefix", type=int, default=0, metavar="N",
                    help="prepend the same N-token system prompt to every "
                         "request (exercises the prefix cache; 0 = fully "
                         "random prompts)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the run's metrics after drain: Prometheus "
                         "text exposition, or the JSON snapshot when PATH "
                         "ends in .json (enables observability)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write per-request trace spans as JSONL after "
                         "drain (enables observability; convert with "
                         "tools/obs_report.py --chrome)")
    serve.add_cli_args(ap)          # every ServeOptions field as a flag
    args = ap.parse_args(argv)
    options = serve.from_cli_args(args)
    if options.chaos and not options.paged:
        options = options.replace(paged=True)   # chaos implies --paged
    try:
        options.validate()
    except ValueError as e:
        raise SystemExit(f"bad flag combination: {e}") from None

    cfg = (get_smoke_config(args.arch) if args.smoke
           else get_config(args.arch))
    cfg = cfg.replace(param_dtype=jnp.float32, act_dtype=jnp.float32)
    if cfg.frontend == "embeddings":
        raise SystemExit("serve demo uses token-frontend archs")
    enable_compile_cache()

    key = jax.random.PRNGKey(options.seed)
    params = params_lib.init_params(key, lm.lm_param_specs(cfg),
                                    cfg.param_dtype)
    mesh = rules = None
    if options.mesh:
        mesh = make_local_mesh(options.model_parallel)
        rules = sc_shard_rules(mesh)
        print(f"serving on mesh {dict(mesh.shape)}")
    # Observability: one registry holds the serve-layer AND substrate
    # series (the engine records into the global default registry, which
    # the sc/autotune/arch hooks also target), and the installed tracer
    # collects spans process-wide for the duration of the run.
    metrics = tracer = None
    if args.metrics_out or args.trace_out:
        metrics = obs.enable()
        tracer = obs.install_tracer(obs.Tracer())
    if options.fault_profile:
        p = options.resolve_profile()
        print(f"device profile '{options.fault_profile}': "
              f"sigma_delta={p.sigma_delta} sigma_ic={p.sigma_ic} "
              f"ber={p.ber_stuck0}/{p.ber_stuck1}/{p.ber_retention}")

    if options.chaos:
        fleet = _build_fleet(params, cfg, options, metrics, tracer)
        engine = None
    else:
        fleet = None
        engine = serve.build_engine(params, cfg, options, mesh=mesh,
                                    shard_rules=rules, metrics=metrics,
                                    tracer=tracer)
        if options.paged:
            print(f"paged engine: block_size={options.block_size} "
                  f"pool={engine.kv.cfg.num_blocks} blocks "
                  f"(chunked prefill {options.prefill_chunk}"
                  + (", prefix cache" if options.prefix_cache else "")
                  + (f", speculative k={options.spec_k}"
                     if options.speculative else "") + ")")

    rng = jax.random.PRNGKey(options.seed + 1)
    shared = []
    if args.shared_prefix:
        rng, k = jax.random.split(rng)
        shared = jax.random.randint(
            k, (args.shared_prefix,), 3, cfg.vocab).tolist()
    target = fleet if fleet is not None else engine
    for rid in range(args.requests):
        rng, k = jax.random.split(rng)
        plen = int(jax.random.randint(k, (), 4, 17))
        prompt = shared + jax.random.randint(
            k, (plen,), 3, cfg.vocab).tolist()
        target.submit(Request(rid=rid, prompt=prompt,
                              max_new_tokens=args.max_new,
                              temperature=args.temperature))

    t0 = time.time()
    finished = target.run_until_drained()
    dt = time.time() - t0
    total_tokens = sum(len(r.generated) for r in finished)
    print(f"served {len(finished)} requests, {total_tokens} tokens "
          f"in {dt:.1f}s ({total_tokens/max(dt,1e-9):.1f} tok/s)")
    if fleet is not None:
        print(f"  fleet: {fleet.shards} shards, "
              f"{fleet.drains} drained, {fleet.resumed} requests resumed, "
              f"{fleet.readmissions} readmitted")
    elif options.paged:
        print(f"  {engine.ticks} ticks, {engine.evictions} evictions, "
              f"{engine.kv.pool.free_blocks} blocks free at drain")
        lat = engine.decode_latency_ms()
        if lat:
            print(f"  decode p50={lat['decode_p50_ms']:.2f} "
                  f"p95={lat['decode_p95_ms']:.2f} ms/token")
        if options.prefix_cache:
            hit = engine.metrics.value(
                "serve_prefix_cache_hit_tokens_total") or 0
            pre = engine.metrics.value("serve_prefill_tokens_total") or 0
            rate = hit / max(hit + pre, 1)
            print(f"  prefix cache: {int(hit)} tokens adopted "
                  f"(hit rate {rate:.2f})")
        if options.speculative:
            drafted = engine.metrics.value(
                "serve_spec_drafted_tokens_total") or 0
            acc = engine.metrics.value(
                "serve_spec_accepted_tokens_total") or 0
            print(f"  speculative: {int(acc)}/{int(drafted)} drafted "
                  "tokens accepted")
    for r in sorted(finished, key=lambda r: r.rid)[:4]:
        print(f"  req {r.rid}: prompt[:6]={r.prompt[:6]} "
              f"generated={r.generated}")
    if args.metrics_out:
        if args.metrics_out.endswith(".json"):
            with open(args.metrics_out, "w") as f:
                f.write(metrics.snapshot_json())
        else:
            with open(args.metrics_out, "w") as f:
                f.write(metrics.exposition())
        print(f"  metrics -> {args.metrics_out}")
    if args.trace_out:
        tracer.write_jsonl(args.trace_out)
        print(f"  trace   -> {args.trace_out} ({len(tracer.spans)} spans)")
    if tracer is not None:
        obs.uninstall_tracer(tracer)
        obs.disable()
    return finished


def _build_fleet(params, cfg, options, metrics, tracer):
    """2-shard paged fleet under the FT supervisor with a deterministic
    chaos schedule: shard 1 degrades mid-run, its in-flight requests
    drain onto shard 0 and finish there."""
    from repro.ft import supervisor as ftsup
    shard_opts = options.replace(chaos=False, mesh=False)
    fleet = ftsup.FleetSupervisor(
        lambda shard: serve.build_engine(params, cfg, shard_opts,
                                         tracer=tracer),
        shards=2, metrics=metrics,
        chaos=ftsup.ChaosMonkey(at_tick=2, shard=1))
    print("chaos fleet: 2 shards, degradation scheduled at tick 2 "
          "on shard 1")
    return fleet


if __name__ == "__main__":
    main()
