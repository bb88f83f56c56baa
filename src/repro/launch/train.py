"""Training launcher.

Runs real training (CPU-scale smoke configs, or the paper-sc config whose
matmuls route through the SC engine) under the fault-tolerance supervisor,
with checkpointing and deterministic data. On a TPU cluster the same
entrypoint runs the full configs — the mesh builder and sharding trees are
identical; only device count changes.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --smoke \
        --steps 30 --batch 8 --seq 64 --ckpt-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse
import contextlib
import time

import jax
import jax.numpy as jnp

from repro import checkpoint, sc
from repro.sharding import sc_shard_rules
from repro.configs import get_config, get_smoke_config
from repro.data import SyntheticLMData, make_batch
from repro.data.pipeline import make_embedding_batch
from repro.ft import FaultInjector, Supervisor
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.optim import AdamWConfig
from repro.train import TrainConfig, make_train_step, train_state_init


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--sc-backend", default=None,
                    help="SC substrate backend (any name registered in "
                         "repro.sc: exact | moment | bitexact | "
                         "pallas_moment | pallas_bitexact)")
    ap.add_argument("--sc-mode", default=None,
                    choices=[None, "exact", "moment", "bitexact"],
                    help="DEPRECATED alias for --sc-backend")
    ap.add_argument("--inject-failure-at", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = (get_smoke_config(args.arch) if args.smoke
           else get_config(args.arch))
    cfg = cfg.replace(param_dtype=jnp.float32, act_dtype=jnp.float32)
    if args.sc_backend or args.sc_mode:
        cfg = cfg.replace(sc_backend=args.sc_backend or args.sc_mode)

    enable_compile_cache()
    mesh = make_local_mesh()
    tcfg = TrainConfig(
        optimizer=AdamWConfig(lr=args.lr, total_steps=args.steps,
                              warmup_steps=max(args.steps // 10, 1)),
        microbatches=args.microbatches, seed=args.seed)
    data = SyntheticLMData(vocab=cfg.vocab, seq_len=args.seq,
                           global_batch=args.batch, seed=args.seed)

    key = jax.random.PRNGKey(args.seed)
    state = train_state_init(key, cfg, tcfg)
    step_fn = jax.jit(make_train_step(cfg, tcfg, mesh), donate_argnums=(0,))

    # Mesh-sharded SC substrate: while this scope is active, every dense()
    # in the traced step shards its stochastic matmul over the mesh
    # (sc_dot_sharded; no-op on a single device — size-1 axes drop out).
    if cfg.sc_backend != "exact" and len(jax.devices()) > 1:
        substrate_scope = lambda: sc.use_mesh(mesh, sc_shard_rules(mesh))
    else:
        substrate_scope = contextlib.nullcontext

    def batch_fn(step):
        if cfg.frontend == "embeddings":
            return make_embedding_batch(data, cfg.d_model, step)
        return make_batch(data, step)

    start_step = 0
    if args.resume and checkpoint.latest_step(args.ckpt_dir) is not None:
        state, extra, _ = checkpoint.restore(args.ckpt_dir, state)
        start_step = extra["data_step"]
        print(f"resumed from step {start_step}")

    injector = (FaultInjector(fail_at_steps=(args.inject_failure_at,))
                if args.inject_failure_at is not None else None)
    sup = Supervisor(ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                     injector=injector)

    t0 = time.time()
    losses = []

    def logged_step(state, batch):
        with substrate_scope():
            state, metrics = step_fn(state, batch)
        losses.append(float(metrics["loss"]))
        step = len(losses) + start_step
        if step % 5 == 0 or step == 1:
            print(f"step {step:5d} loss {metrics['loss']:.4f} "
                  f"gnorm {metrics['grad_norm']:.3f} lr {metrics['lr']:.2e} "
                  f"({(time.time()-t0)/max(len(losses),1):.2f}s/step)",
                  flush=True)
        return state, metrics

    state, history = sup.run(state, logged_step, args.steps,
                             make_batch=batch_fn, start_step=start_step)
    print(f"done: first loss {history['loss'][0]:.4f} -> "
          f"last {history['loss'][-1]:.4f}; "
          f"recoveries={len(history['recoveries'])}")
    return state, history


if __name__ == "__main__":
    main()
