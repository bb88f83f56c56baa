"""Checkpointing (atomicity, elastic restore) + fault-tolerance supervisor."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import checkpoint
from repro.configs import get_smoke_config
from repro.data import SyntheticLMData, make_batch
from repro.ft import FaultInjector, StragglerMonitor, Supervisor, WorkerFailure
from repro.launch.mesh import make_mesh
from repro.train import TrainConfig, make_train_step
from repro.train.step import train_state_init

F32 = dict(param_dtype=jnp.float32, act_dtype=jnp.float32)


def _tree(key):
    return {"a": jax.random.normal(key, (4, 8)),
            "b": {"c": jnp.arange(5, dtype=jnp.int32),
                  "d": jnp.float32(3.25)}}


def test_save_restore_roundtrip(tmp_path, key):
    tree = _tree(key)
    checkpoint.save(str(tmp_path), 7, tree, extra={"data_step": 7})
    restored, extra, step = checkpoint.restore(str(tmp_path), tree)
    assert step == 7 and extra["data_step"] == 7
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), tree, restored)


def test_latest_step_ignores_tmp_and_partial(tmp_path, key):
    tree = _tree(key)
    checkpoint.save(str(tmp_path), 3, tree)
    checkpoint.save(str(tmp_path), 9, tree)
    # a crashed mid-save leaves a .tmp dir -> must be ignored
    os.makedirs(tmp_path / "step_00000012.tmp")
    # a dir without META.json (interrupted rename) -> ignored
    os.makedirs(tmp_path / "step_00000011")
    assert checkpoint.latest_step(str(tmp_path)) == 9


def test_save_overwrites_same_step(tmp_path, key):
    t1 = _tree(key)
    t2 = jax.tree.map(lambda v: v + 1, t1)
    checkpoint.save(str(tmp_path), 1, t1)
    checkpoint.save(str(tmp_path), 1, t2)
    restored, _, _ = checkpoint.restore(str(tmp_path), t1)
    np.testing.assert_allclose(np.asarray(restored["a"]),
                               np.asarray(t2["a"]))


def test_restore_missing_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        checkpoint.restore(str(tmp_path / "nope"), {"a": jnp.zeros(1)})


def test_restore_resharded_on_local_mesh(tmp_path, key):
    """Elastic restore: device_put with shardings from the current mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = make_mesh((1, 1), ("data", "model"))
    tree = {"w": jax.random.normal(key, (8, 8))}
    checkpoint.save(str(tmp_path), 2, tree)
    sh = {"w": NamedSharding(mesh, P("data", "model"))}
    restored, _, step = checkpoint.restore_resharded(str(tmp_path), tree, sh)
    assert step == 2
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(tree["w"]))
    assert restored["w"].sharding == sh["w"]


# ---------------------------------------------------------------------------
# Supervisor
# ---------------------------------------------------------------------------


def _train_setup(tmp_path, ckpt_every=5):
    cfg = get_smoke_config("qwen2-0.5b").replace(**F32)
    tcfg = TrainConfig()
    state = train_state_init(jax.random.PRNGKey(0), cfg, tcfg)
    step = jax.jit(make_train_step(cfg, tcfg))
    data = SyntheticLMData(vocab=cfg.vocab, seq_len=32, global_batch=4)
    return state, step, (lambda i: make_batch(data, i)), str(tmp_path)


def test_supervisor_recovers_from_injected_failure(tmp_path):
    """Crash at step 12 -> restore from step-10 checkpoint -> final state is
    IDENTICAL to an uninterrupted run (deterministic data + step replay)."""
    n = 16
    state0, step, batch_fn, ckpt_a = _train_setup(tmp_path / "a")
    sup_clean = Supervisor(ckpt_dir=ckpt_a, ckpt_every=5)
    clean_state, clean_hist = sup_clean.run(state0, step, n,
                                            make_batch=batch_fn)

    state0b, _, _, ckpt_b = _train_setup(tmp_path / "b")
    injector = FaultInjector(fail_at_steps=(12,))
    sup_fail = Supervisor(ckpt_dir=ckpt_b, ckpt_every=5, injector=injector)
    failed_state, hist = sup_fail.run(state0b, step, n, make_batch=batch_fn)

    assert len(hist["recoveries"]) == 1
    assert hist["recoveries"][0][0] == 10     # resumed from step-10 ckpt
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        clean_state["params"], failed_state["params"])


def test_supervisor_restart_before_first_checkpoint(tmp_path):
    state, step, batch_fn, ckpt = _train_setup(tmp_path)
    injector = FaultInjector(fail_at_steps=(2,))
    sup = Supervisor(ckpt_dir=ckpt, ckpt_every=100, injector=injector)
    final, hist = sup.run(state, step, 5, make_batch=batch_fn)
    assert hist["recoveries"] == [(2, 0)]     # restarted from scratch
    assert len(hist["loss"]) >= 5


def test_supervisor_gives_up_after_max_restarts(tmp_path):
    state, step, batch_fn, ckpt = _train_setup(tmp_path)

    class AlwaysFail(FaultInjector):
        def check(self, step):
            raise WorkerFailure("flaky node")

    sup = Supervisor(ckpt_dir=ckpt, ckpt_every=5, injector=AlwaysFail(),
                     max_restarts=3)
    with pytest.raises(RuntimeError, match="max_restarts"):
        sup.run(state, step, 10, make_batch=batch_fn)


def test_straggler_monitor_flags_slow_steps():
    mon = StragglerMonitor(threshold=3.0, ema_decay=0.5)
    assert not mon.observe(0, 1.0)      # first step builds the EMA
    assert not mon.observe(1, 1.1)
    assert mon.observe(2, 10.0)         # 10x the EMA -> straggler
    assert mon.events[0][0] == 2
    assert not mon.observe(3, 1.0)


def test_heartbeat_staleness():
    sup = Supervisor(ckpt_dir="/tmp/x", heartbeat_timeout_s=1e9)
    sup.heartbeat()
    assert not sup.heartbeat_stale()
    sup.heartbeat_timeout_s = 0.0
    assert sup.heartbeat_stale()


# ---------------------------------------------------------------------------
# Serve-fleet supervision: degraded-shard drain + resume (PR-10)
# ---------------------------------------------------------------------------

from repro.ft import (ChaosMonkey, EngineHealth, FleetSupervisor,  # noqa: E402
                      HealthMonitor)
from repro.models import lm, params as P  # noqa: E402
from repro.serve import Request, ServeOptions, build_engine  # noqa: E402

_OPTS = ServeOptions(paged=True, slots=2, max_len=48, block_size=4,
                     prefill_chunk=3, seed=0)


@pytest.fixture(scope="module")
def serve_setup():
    cfg = get_smoke_config("qwen2-0.5b").replace(**F32)
    params = P.init_params(jax.random.PRNGKey(1), lm.lm_param_specs(cfg),
                           cfg.param_dtype)
    return params, cfg


def _fleet(params, cfg, **kw):
    kw.setdefault("shards", 2)
    return FleetSupervisor(lambda s: build_engine(params, cfg, _OPTS), **kw)


def _reqs(rid0, n=2, max_new=3):
    return [Request(rid=rid0 + j, prompt=[3 + j, 9, 17, 3, 11, 5],
                    max_new_tokens=max_new,
                    temperature=0.8 if j % 2 else 0.0)
            for j in range(n)]


def test_windowed_monitor_judges_deltas_not_lifetime():
    """Readmission depends on windowed verdicts: counters are monotonic,
    so a lifetime monitor would blacklist a once-degraded shard forever."""
    win = HealthMonitor(window=True)
    assert win.observe(EngineHealth(ticks=2, errors=1, error_rate=0.5))
    # same lifetime errors, more ticks: the DELTA is clean -> healthy
    assert not win.observe(EngineHealth(ticks=6, errors=1, error_rate=1 / 6))
    life = HealthMonitor()
    assert life.observe(EngineHealth(ticks=6, errors=1, error_rate=1 / 6))


def test_fleet_degrade_drain_resume_readmit_cycle(serve_setup):
    params, cfg = serve_setup
    fleet = _fleet(params, cfg, cooldown=2)
    for r in _reqs(0, n=4, max_new=4):
        fleet.submit(r)
    fleet.step()
    ckpts = fleet.degrade(1)
    assert not fleet.healthy[1] and fleet.drains == 1
    assert len(ckpts) == 2                      # round-robin put 2 on shard 1
    assert not fleet.engines[1].scheduler.has_work()   # drained empty
    assert fleet.resumed == len(ckpts)          # all re-homed on shard 0
    assert fleet.metrics.value("ft_shard_drains_total", shard="1") == 1
    assert fleet.metrics.value("ft_requests_resumed_total", shard="0") \
        == len(ckpts)
    # idempotent per incident: a second degrade is a no-op
    assert fleet.degrade(1) == [] and fleet.drains == 1
    # cooldown polls readmit the shard; the windowed monitor then judges
    # it on post-readmission deltas only
    fleet.poll()
    fleet.poll()
    assert fleet.healthy[1] and fleet.readmissions == 1
    assert fleet.metrics.value("ft_shard_readmissions_total", shard="1") == 1
    done = fleet.run_until_drained()
    assert sorted(r.rid for r in done) == [0, 1, 2, 3]
    assert all(len(r.generated) == 4 for r in done)


def test_degrade_with_no_healthy_target_raises(serve_setup):
    params, cfg = serve_setup
    fleet = _fleet(params, cfg)
    fleet.degrade(0)
    with pytest.raises(RuntimeError, match="no healthy shard"):
        fleet.degrade(1)


def test_stale_heartbeat_drains_the_silent_shard(serve_setup):
    params, cfg = serve_setup
    fleet = _fleet(params, cfg)
    for r in _reqs(10, n=2):
        fleet.submit(r)
    fleet.last_heartbeat[1] = -1e18             # shard 1 went silent
    fleet.poll()
    assert not fleet.healthy[1] and fleet.healthy[0]
    assert fleet.drains == 1
    done = fleet.run_until_drained()
    assert sorted(r.rid for r in done) == [10, 11]


def test_chaos_telemetry_drives_the_drain(serve_setup):
    """ChaosMonkey bumps serve_errors_total — exactly what a crash loop
    emits — and the windowed monitor turns it into a drain on the next
    poll, with zero client-visible failures."""
    params, cfg = serve_setup
    fleet = _fleet(params, cfg,
                   chaos=ChaosMonkey(at_tick=2, shard=1, errors=2))
    for r in _reqs(20, n=4, max_new=4):
        fleet.submit(r)
    done = fleet.run_until_drained()
    assert fleet.drains == 1 and fleet.resumed >= 1
    assert sorted(r.rid for r in done) == [20, 21, 22, 23]


def test_warm_resume_restores_kv_instead_of_reprefilling(serve_setup):
    """A drained mid-flight request resumes WARM on a fresh engine: the
    KV payload scatters into the pool, the target never re-feeds the
    prompt, and the tokens match an uninterrupted run exactly."""
    params, cfg = serve_setup
    req = Request(rid=0, prompt=[5, 9, 17, 3, 11, 5], max_new_tokens=6)
    ref_eng = build_engine(params, cfg, _OPTS)
    ref_eng.submit(Request(rid=0, prompt=list(req.prompt),
                           max_new_tokens=6))
    ref = ref_eng.run_until_drained()[0].generated

    src = build_engine(params, cfg, _OPTS)
    src.submit(req)
    for _ in range(4):                          # past prefill, mid-decode
        src.step()
    ckpts = src.drain()
    assert len(ckpts) == 1 and ckpts[0]["kv"] is not None
    assert ckpts[0]["fed"] > 0

    dst = build_engine(params, cfg, _OPTS)
    assert dst.restore(ckpts[0]) is True        # warm path taken
    done = dst.run_until_drained()
    assert done[0].generated == ref
    # warm resume never re-prefills the prompt: the only prefill-counted
    # tokens on the target are the pending tail, strictly fewer than the
    # prompt itself
    refed = dst.metrics.value("serve_prefill_tokens_total") or 0
    assert refed < len(req.prompt)


def test_cold_resume_from_waiting_queue_recomputes(serve_setup):
    """Requests drained from the waiting queue (never admitted) resume
    cold — a plain re-submit, same tokens by the rng contract."""
    params, cfg = serve_setup
    src = build_engine(params, cfg, _OPTS.replace(slots=1))
    reqs = _reqs(30, n=3, max_new=3)
    for r in reqs:
        src.submit(r)
    src.step()                                  # admits rid 30 only
    ckpts = src.drain()
    assert len(ckpts) == 3
    assert sum(c["kv"] is not None for c in ckpts) == 1     # only the row
    dst = build_engine(params, cfg, _OPTS.replace(slots=1))
    warm = [dst.restore(c) for c in ckpts]
    assert warm.count(True) == 1
    done = dst.run_until_drained()
    ref_eng = build_engine(params, cfg, _OPTS.replace(slots=1))
    for r in _reqs(30, n=3, max_new=3):
        ref_eng.submit(r)
    ref = {r.rid: r.generated for r in ref_eng.run_until_drained()}
    assert {r.rid: r.generated for r in done} == ref


@pytest.mark.slow
def test_chaos_sweep_token_identity_50_seeds(serve_setup):
    """50 deterministic chaos episodes — injection tick and victim shard
    vary per seed — against an unfaulted reference fleet sharing the
    engine seed.  Every request finishes and every token (greedy AND
    sampled rows) matches the reference bit-for-bit: drain/resume is
    invisible to clients.  Engines are reused across episodes (fresh
    rids), so the sweep pays jit compilation once."""
    params, cfg = serve_setup
    fleet = _fleet(params, cfg, cooldown=2)
    ref = _fleet(params, cfg)
    for ep in range(50):
        while not all(fleet.healthy):      # supervisor idles between
            fleet.poll()                   # incidents; cooldowns elapse
        for r in _reqs(100 + ep * 10, n=2, max_new=3):
            fleet.submit(r)
        for r in _reqs(100 + ep * 10, n=2, max_new=3):
            ref.submit(r)
        fleet.chaos = ChaosMonkey(at_tick=fleet.ticks + 1 + ep % 3,
                                  shard=ep % 2, errors=2)
        fleet.run_until_drained()
        ref.run_until_drained()
    got = {r.rid: r.generated for r in fleet.finished}
    want = {r.rid: r.generated for r in ref.finished}
    assert len(got) == 100                       # nothing lost, ever
    assert got == want                           # token identity
    assert fleet.drains >= 40                    # the sweep really chaosed
    assert fleet.resumed >= 10
