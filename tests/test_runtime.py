"""Runtime substrate: checkpointing, fault handling, compression, pipelines."""
import os
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.runtime import checkpoint as CKPT
from repro.runtime import compression
from repro.runtime.fault import FleetMonitor, HostStatus, Supervisor, plan_remesh


def _tree():
    return {"a": {"w": np.arange(12, dtype=np.float32).reshape(3, 4)},
            "b": np.ones((5,), np.float32)}


def test_checkpoint_roundtrip(tmp_path):
    t = _tree()
    CKPT.save(str(tmp_path), 7, t, extra={"data": {"seed": 0, "step": 7}})
    restored, extra, step = CKPT.restore(str(tmp_path), t)
    assert step == 7 and extra["data"]["step"] == 7
    np.testing.assert_array_equal(restored["a"]["w"], t["a"]["w"])


def test_checkpoint_latest_and_gc(tmp_path):
    t = _tree()
    ck = CKPT.AsyncCheckpointer(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        ck.save(s, t)
    ck.wait()
    assert CKPT.latest_step(str(tmp_path)) == 4
    dirs = [d for d in os.listdir(tmp_path) if d.startswith("step_")]
    assert len(dirs) == 2              # gc keeps last 2


def test_checkpoint_shape_mismatch_rejected(tmp_path):
    t = _tree()
    CKPT.save(str(tmp_path), 1, t)
    bad = {"a": {"w": np.zeros((2, 2), np.float32)}, "b": t["b"]}
    with pytest.raises(ValueError):
        CKPT.restore(str(tmp_path), bad)


def test_checkpoint_atomic_under_partial_write(tmp_path):
    """A stale .tmp directory must never be visible as a restore point."""
    t = _tree()
    CKPT.save(str(tmp_path), 1, t)
    os.makedirs(tmp_path / "step_0000000002.tmp")   # simulated crash mid-save
    assert CKPT.latest_step(str(tmp_path)) == 1


def test_fleet_monitor_dead_and_straggler():
    mon = FleetMonitor(n_hosts=8, timeout_s=10.0, grace_steps=0)
    now = time.time()
    for h in range(8):
        dt = 1.0 if h != 3 else 5.0     # host 3 is 5x slower
        mon.heartbeat(HostStatus(h, step=100, step_time_s=dt, timestamp=now))
    assert mon.dead_hosts(now) == []
    assert mon.stragglers() == [3]
    assert mon.dead_hosts(now + 100) == list(range(8))


def test_plan_remesh_shrinks_data_axis():
    assert plan_remesh(512, model_axis=16, pods=2) == (2, 16, 16)
    assert plan_remesh(511, model_axis=16, pods=2) == (2, 8, 16)  # pow2 data
    assert plan_remesh(256, model_axis=16, pods=1) == (16, 16)
    assert plan_remesh(250, model_axis=16, pods=1) == (8, 16)
    assert plan_remesh(8, model_axis=16, pods=1) is None


def test_supervisor_restarts_and_resumes():
    calls = []

    def loop(start):
        calls.append(start)
        if len(calls) < 3:
            raise RuntimeError("injected")
        return 100

    def restore():
        return len(calls) * 10

    sup = Supervisor(loop, restore, max_restarts=5, backoff_s=0.0)
    assert sup.run() == 100
    assert calls == [0, 10, 20]        # resumed from 'checkpoints'


def test_supervisor_gives_up():
    def loop(start):
        raise RuntimeError("always")

    with pytest.raises(RuntimeError):
        Supervisor(loop, lambda: 0, max_restarts=2, backoff_s=0.0).run()


def test_compression_error_feedback_convergence():
    """1-bit EF SGD still minimizes a quadratic (residual carries info)."""
    A = jnp.asarray(np.random.default_rng(0).normal(size=(16, 16)))
    Q = A @ A.T / 16 + 0.5 * jnp.eye(16)
    x = jnp.ones((16,)) * 5.0
    res = compression.init_state({"x": x})

    def grad(x):
        return {"x": Q @ x}

    lr = 0.05
    params = {"x": x}
    for _ in range(300):
        q, res = compression.compress(grad(params["x"]), res)
        params = {"x": params["x"] - lr * q["x"]}
    assert float(jnp.linalg.norm(params["x"])) < 0.3


def test_data_pipeline_determinism_and_sharding():
    from repro.core.geometry import VolumeGeometry, parallel_beam
    from repro.data.pipeline import CTDataPipeline
    vol = VolumeGeometry(16, 16, 1)
    g = parallel_beam(12, 1, 24, vol)
    p1 = CTDataPipeline(g, batch_size=4, seed=1, shard_index=0, shard_count=2)
    p2 = CTDataPipeline(g, batch_size=4, seed=1, shard_index=1, shard_count=2)
    a1, m1 = p1.batch(0)
    b1, _ = p1.batch(0)
    np.testing.assert_array_equal(a1, b1)          # deterministic
    a2, _ = p2.batch(0)
    assert not np.allclose(a1, a2)                 # disjoint shards
    # state_dict replay
    p1.step = 5
    st = p1.state_dict()
    p3 = CTDataPipeline(g, batch_size=4, seed=1, shard_index=0, shard_count=2)
    p3.load_state_dict(st)
    np.testing.assert_array_equal(p1.batch(p1.step)[0], p3.batch(p3.step)[0])


def test_token_pipeline_shards_and_learnable_structure():
    from repro.data.tokens import TokenPipeline
    tp = TokenPipeline(1000, 64, 8, seed=0)
    b = tp.batch(0)
    assert b.shape == (8, 64) and b.max() < 1000
    span = 64 // 16
    np.testing.assert_array_equal(b[:, span:2 * span], b[:, :span])
    tp2 = TokenPipeline(1000, 64, 8, seed=0, shard_index=1, shard_count=2)
    assert not np.array_equal(tp2.batch(0), b[:4])


def test_compile_cache_dir_is_fixed(monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set, JAX keeps the setting; without
    it, the cache goes to the checkout's fixed .jax_cache directory."""
    import jax
    from repro.runtime import compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.enable() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = compile_cache.enable()
        assert jax.config.jax_compilation_cache_dir == path
        assert path == str(compile_cache.CHECKOUT_CACHE)
        assert (compile_cache.CHECKOUT_CACHE.parent / "src" / "repro").is_dir()
        assert compile_cache.enable() == path           # same every call
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
