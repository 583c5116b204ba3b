"""CT serving subsystem: bucketing, packed dispatch, tiers, warm path,
and per-request error isolation."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (Projector, ProjectorSpec, VolumeGeometry, fan_beam,
                        parallel_beam)
from repro.kernels import ops, tune
from repro.launch.ct_serve import (CTServer, ReconRequest, TIER_SOLVERS,
                                   solver_tier, _size_class)
from repro.recon import sirt
from repro.runtime import obs


@pytest.fixture(scope="module")
def world():
    vol = VolumeGeometry(16, 16, 1)
    g_par = parallel_beam(12, 1, 24, vol)
    g_fan = fan_beam(12, 1, 24, vol, sod=60.0, sdd=120.0)
    s_par, s_fan = ProjectorSpec(g_par), ProjectorSpec(g_fan)
    f = jnp.zeros(vol.shape).at[5:11, 5:11, :].set(0.02)
    return {"f": f, "par": (s_par, Projector(s_par)(f)),
            "fan": (s_fan, Projector(s_fan)(f))}


def test_solver_tiers():
    assert solver_tier("fbp") == "interactive"
    for s in TIER_SOLVERS["quality"]:
        assert solver_tier(s) == "quality"
    with pytest.raises(ValueError):
        solver_tier("mystery")


def test_size_classes():
    assert [_size_class(n, 16) for n in (1, 2, 3, 5, 16, 40)] == \
        [1, 2, 4, 8, 16, 16]
    assert _size_class(7, 4) == 4


def test_batched_matches_per_request(world):
    """A packed batch answers bit-identically to what the solver produces
    on each request alone."""
    spec, y = world["par"]
    srv = CTServer(max_batch=8)
    rids = [srv.submit(ReconRequest(spec=spec, sino=(i + 1) * y,
                                    solver="sirt",
                                    solver_kwargs={"n_iters": 5}))
            for i in range(5)]
    done = srv.drain()
    assert len(srv.dispatch_log) == 1
    rec = srv.dispatch_log[0]
    assert rec["size_class"] == 8 and sorted(rec["rids"]) == sorted(rids)
    for i, rid in enumerate(rids):
        resp = done[rid]
        assert resp.ok and resp.batch_size == 5
        direct = sirt(spec, (i + 1) * y, n_iters=5)
        np.testing.assert_allclose(np.asarray(resp.image),
                                   np.asarray(direct.image),
                                   rtol=1e-5, atol=1e-7)
        np.testing.assert_allclose(np.asarray(resp.result.residual_history),
                                   np.asarray(direct.residual_history),
                                   rtol=1e-5, atol=1e-7)


def test_heterogeneous_specs_never_share_a_batch(world):
    """Requests with different geometry content — or the same geometry but
    different solver kwargs — must land in separate packed dispatches."""
    (s_par, y_par), (s_fan, y_fan) = world["par"], world["fan"]
    srv = CTServer(max_batch=16)
    kinds = {}
    for i in range(12):
        if i % 3 == 0:
            r = ReconRequest(spec=s_par, sino=y_par, solver="fbp")
        elif i % 3 == 1:
            r = ReconRequest(spec=s_fan, sino=y_fan, solver="fbp")
        else:
            r = ReconRequest(spec=s_par, sino=y_par, solver="fbp",
                             solver_kwargs={"filter_name": "hann"})
        kinds[srv.submit(r)] = i % 3
    done = srv.drain()
    assert all(done[r].ok for r in kinds)
    assert len(srv.dispatch_log) == 3
    for rec in srv.dispatch_log:
        assert len({kinds[r] for r in rec["rids"]}) == 1, \
            "heterogeneous requests packed into one batch"


def test_tier_priority(world):
    """Interactive requests are dispatched before quality requests even
    when the quality queue is older."""
    spec, y = world["par"]
    srv = CTServer(max_batch=8)
    q = srv.submit(ReconRequest(spec=spec, sino=y, solver="sirt",
                                solver_kwargs={"n_iters": 3}))
    i = srv.submit(ReconRequest(spec=spec, sino=y, solver="fbp"))
    done = srv.drain()
    assert done[q].ok and done[i].ok
    assert [rec["tier"] for rec in srv.dispatch_log] == \
        ["interactive", "quality"]


def test_submit_validation_is_isolated(world):
    spec, y = world["par"]
    srv = CTServer(max_batch=4)
    good = srv.submit(ReconRequest(spec=spec, sino=y, solver="fbp"))
    bad_shape = srv.submit(ReconRequest(spec=spec, sino=jnp.zeros((2, 2, 2)),
                                        solver="fbp"))
    bad_solver = srv.submit(ReconRequest(spec=spec, sino=y, solver="magic"))
    done = srv.drain()
    assert done[good].ok
    assert not done[bad_shape].ok and "shape" in done[bad_shape].error
    assert not done[bad_solver].ok and "solver" in done[bad_solver].error
    # invalid requests never reached a packed batch
    dispatched = {r for rec in srv.dispatch_log for r in rec["rids"]}
    assert dispatched == {good}


def test_executor_failure_isolates_poisoned_request(world):
    """When a packed dispatch fails, batch mates are re-run individually:
    only the poisoned request is answered with an error."""
    spec, y = world["par"]
    srv = CTServer(max_batch=4)
    srv.warm(spec, "fbp", batch_sizes=(1, 4))
    key = srv.bucket_key(ReconRequest(spec=spec, sino=y, solver="fbp"))
    real_single = srv._executor(key, 1)

    def exploding_batch(batch):
        raise RuntimeError("batch executor blew up")

    def picky_single(batch):
        if float(np.asarray(batch).sum()) < 0:
            raise RuntimeError("poisoned request")
        return real_single(batch)

    srv._executors[(key, 4)] = exploding_batch
    srv._executors[(key, 1)] = picky_single

    good = [srv.submit(ReconRequest(spec=spec, sino=y, solver="fbp"))
            for _ in range(3)]
    poisoned = srv.submit(ReconRequest(spec=spec, sino=-jnp.abs(y),
                                       solver="fbp"))
    done = srv.drain()
    expect = np.asarray(Projector(spec).fbp(y))
    for rid in good:
        assert done[rid].ok, done[rid].error
        np.testing.assert_allclose(np.asarray(done[rid].image), expect,
                                   rtol=1e-5, atol=1e-7)
    assert not done[poisoned].ok
    assert "poisoned" in done[poisoned].error
    # the failed packed dispatch and every size-1 re-run stay visible
    packed = [rec for rec in srv.dispatch_log if not rec["rerun"]]
    assert len(packed) == 1 and "blew up" in packed[0]["error"]
    reruns = [rec for rec in srv.dispatch_log if rec["rerun"]]
    assert all(rec["size_class"] == 1 for rec in reruns)
    assert sorted(r for rec in reruns for r in rec["rids"]) == \
        sorted(good + [poisoned])


def test_warm_server_compiles_nothing_on_request_path(world, monkeypatch):
    """The warm-path guarantee: after warm(), traffic across every batch
    size class triggers zero autotune sweeps, zero new op-cache entries
    and zero lowerings or compilations at all, of the system's programs
    or of eager device ops (with the tune disk cache enabled, as in
    production)."""
    monkeypatch.setenv("REPRO_TUNE_CACHE", "1")
    (s_par, y_par), (s_fan, y_fan) = world["par"], world["fan"]
    srv = CTServer(max_batch=4)
    srv.warm(s_par, "fbp")
    srv.warm(s_fan, "fbp")
    srv.warm(s_par, "sirt", {"n_iters": 3})

    sweeps0 = tune.sweep_count()
    stats0 = ops.cache_stats()
    executors0 = set(srv._executors)
    compiled0 = obs.snapshot()["compile"]

    rids = []
    for n in (1, 2, 3, 4, 4):          # every size class, twice the largest
        for _ in range(n):
            rids.append(srv.submit(
                ReconRequest(spec=s_par, sino=y_par, solver="fbp")))
        srv.drain()
    rids.append(srv.submit(ReconRequest(spec=s_fan, sino=y_fan,
                                        solver="fbp")))
    rids.append(srv.submit(ReconRequest(spec=s_par, sino=y_par,
                                        solver="sirt",
                                        solver_kwargs={"n_iters": 3})))
    done = srv.drain()
    assert all(done[r].ok for r in rids)

    assert tune.sweep_count() == sweeps0, "autotune swept on the request path"
    stats1 = ops.cache_stats()
    assert stats1["size"] == stats0["size"], "new op-cache entry built"
    assert stats1["misses"] == stats0["misses"], \
        "op-cache miss on request path"
    assert set(srv._executors) == executors0, "new executor compiled"
    compiled1 = obs.snapshot()["compile"]
    assert compiled0["compiles"] > 0, "warm() compiled no system program"
    assert compiled1["lowerings"] == compiled0["lowerings"], \
        "a system program was lowered on the request path"
    assert compiled1["compiles"] == compiled0["compiles"], \
        "a system program was compiled on the request path"
    other0, other1 = compiled0["other"], compiled1["other"]
    assert other1["lowerings"] == other0["lowerings"], \
        "an eager op or other jit was lowered on the request path"
    assert other1["compiles"] == other0["compiles"], \
        "an eager op or other jit was compiled on the request path"


def test_serving_spans_carry_request_ids(world, monkeypatch):
    """Submit, queue wait and dispatch are spans keyed by the request's
    id, and the dispatch log's ``wall_s`` is the dispatch span's length."""
    rec = obs.Recorder()
    monkeypatch.setattr(obs, "_REC", rec)
    spec, y = world["par"]
    srv = CTServer(max_batch=4)
    rids = [srv.submit(ReconRequest(spec=spec, sino=y, solver="fbp"))
            for _ in range(3)]
    srv.drain()
    recent = obs.snapshot()["recent"]
    by = {}
    for r in recent:
        by.setdefault(r["name"], []).append(r)
    assert sorted(r["attrs"]["rid"] for r in by["serve.submit"]) == rids
    assert sorted(r["attrs"]["rid"] for r in by["serve.queue_wait"]) == rids
    (disp,) = by["serve.dispatch"]
    (step,) = by["serve.step"]
    assert sorted(disp["attrs"]["rids"]) == rids
    assert disp["parent"] == step["id"]
    assert srv.dispatch_log[0]["wall_s"] == disp["end"] - disp["start"]
    assert all(w["end"] <= disp["start"] for w in by["serve.queue_wait"])
