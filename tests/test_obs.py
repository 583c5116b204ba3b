"""The program's span and counter table (``repro.runtime.obs``): nesting
and self time, counters, the profiler's host clock, which compilations
count as the system's, and that running a compiled program records
nothing."""
import glob
import json

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache as cc

from repro.kernels import ops, tune
from repro.runtime import obs


@pytest.fixture
def rec(monkeypatch):
    """A fresh table in place of the process's, JAX's listeners included."""
    r = obs.Recorder()
    monkeypatch.setattr(obs, "_REC", r)
    return r


@pytest.fixture
def disk_cache(tmp_path):
    """JAX's persistent compilation cache in a directory of the test's own,
    for every compilation however short."""
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir", "jax_enable_compilation_cache",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cc.reset_cache()
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    cc.reset_cache()


def test_span_nesting_and_self_time(rec):
    with obs.span("outer", rid=7) as a:
        with obs.span("inner") as b:
            pass
        with obs.span("inner") as c:
            pass
    s = obs.snapshot()["spans"]
    assert s["outer"]["count"] == 1 and s["inner"]["count"] == 2
    assert s["outer"]["total_s"] == pytest.approx(a.seconds)
    assert s["inner"]["total_s"] == pytest.approx(b.seconds + c.seconds)
    assert s["outer"]["self_s"] == pytest.approx(
        a.seconds - b.seconds - c.seconds, abs=1e-12)
    assert s["inner"]["self_s"] == s["inner"]["total_s"]
    recent = {r["id"]: r for r in obs.snapshot()["recent"]}
    assert recent[b.id]["parent"] == a.id and recent[c.id]["parent"] == a.id
    assert recent[a.id]["parent"] is None
    assert recent[a.id]["attrs"] == {"rid": 7}
    assert recent[a.id]["start"] <= recent[b.id]["start"] \
        <= recent[c.id]["end"] <= recent[a.id]["end"]


def test_recorded_span_and_spanned(rec):
    @obs.spanned("wrapped")
    def f(x):
        return x + 1

    assert f(1) == 2 and f.__name__ == "f"
    obs.record("wait", 1.0, 1.5, rid=3)
    s = obs.snapshot()
    assert s["spans"]["wrapped"]["count"] == 1
    assert s["spans"]["wait"] == {"count": 1, "total_s": 0.5, "self_s": 0.5}
    assert s["recent"][-1]["attrs"] == {"rid": 3}


def test_ring_is_bounded(monkeypatch):
    monkeypatch.setattr(obs, "RING", 4)
    r = obs.Recorder()
    for _ in range(10):
        with r.span("s"):
            pass
    assert len(r.snapshot()["recent"]) == 4
    assert r.snapshot()["spans"]["s"]["count"] == 10


def test_counters_and_the_views_that_read_them(rec, monkeypatch):
    obs.count("x")
    obs.count("x", 4)
    assert obs.counter("x") == 5 and obs.counter("never") == 0
    assert obs.snapshot()["counters"]["x"] == 5
    assert tune.sweep_count() == 0
    monkeypatch.setattr(tune, "_on_tpu", lambda: False)
    monkeypatch.setattr(tune, "_AUTOTUNED", {})
    from repro.configs.leap_ct import limited_angle_geometry
    tune.autotune(limited_angle_geometry())
    assert tune.sweep_count() == obs.counter("tune.sweep") == 1
    assert ops.cache_stats()["misses"] == obs.counter("ops.cache_miss")
    assert ops.cache_stats()["hits"] == obs.counter("ops.cache_hit")


def test_dump_writes_the_snapshot(rec, tmp_path):
    with obs.span("a", shape=(1, 2)):
        obs.count("c")
    obs.dump(tmp_path / "obs.json")
    got = json.loads((tmp_path / "obs.json").read_text())
    assert got["spans"]["a"]["count"] == 1 and got["counters"] == {"c": 1}


def test_span_is_a_host_event_of_the_profiler(rec, tmp_path):
    from jax.profiler import ProfileData, TraceAnnotation
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation("outside.annotation"):
            with obs.span("inside.span"):
                jnp.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = ProfileData.from_file(path[0])
    host = {ev.name: (ev.start_ns, ev.start_ns + ev.duration_ns)
            for plane in pd.planes if not plane.name.startswith("/device:")
            for line in plane.lines for ev in line.events}
    assert "inside.span" in host and "outside.annotation" in host
    (s, e), (os_, oe) = host["inside.span"], host["outside.annotation"]
    assert os_ <= s <= e <= oe                 # one clock for both


def _system(x):
    with obs.span("t.build"):
        return x * 2.0


def _plain(x):
    return x + 1.0


def test_compiles_tagged_by_program_spans(rec, disk_cache):
    x = jnp.ones(8)
    jax.jit(_system).lower(x).compile()
    jax.jit(_plain).lower(x).compile()
    c = obs.snapshot()["compile"]
    assert set(c["programs"]) == {"jit(_system)"}
    p = c["programs"]["jit(_system)"]
    assert p["lowerings"] == 1 and p["compiles"] == 1
    assert p["cache_misses"] == 1 and p["cache_hits"] == 0
    assert p["lower_s"] > 0 and p["backend_s"] > 0
    assert c["compiles"] == 1 and c["cache_misses"] == 1
    assert c["other"]["compiles"] >= 1 and c["other"]["lowerings"] >= 1

    jax.clear_caches()                          # the next one is a cache hit
    ops.clear_cache()
    jax.jit(_system).lower(x).compile()
    c = obs.snapshot()["compile"]
    p = c["programs"]["jit(_system)"]
    assert p["compiles"] == 2 and p["cache_hits"] == 1
    assert p["cache_misses"] == 1 and c["cache_read_s"] > 0
    assert obs.snapshot()["spans"]["t.build"]["count"] == 2


def test_compile_inside_a_span_is_not_its_self_time(rec):
    x = jnp.ones(3)
    with obs.span("outer") as sp:
        jax.jit(_system)(x).block_until_ready()
    s = obs.snapshot()
    c = s["compile"]
    assert c["compiles"] == 1
    inner = s["spans"]["t.build"]["total_s"]
    assert s["spans"]["outer"]["self_s"] == pytest.approx(
        sp.seconds - inner - c["lower_s"] - c["backend_s"], abs=1e-9)


def test_running_a_compiled_step_records_nothing(rec):
    from repro.configs.leap_ct import limited_angle_geometry
    from repro.core.spec import ProjectorSpec
    spec = ProjectorSpec(limited_angle_geometry(), backend="ref")
    x = jnp.ones(spec.geom.vol.shape)

    def step(v):
        return ops.back_project(ops.forward_project(v, spec), spec)

    compiled = jax.jit(step).lower(x).compile()
    before = obs.snapshot()
    assert before["compile"]["compiles"] == 1
    assert before["spans"]["ops.get_bundle"]["count"] == 2
    for _ in range(3):
        compiled(x).block_until_ready()
    assert obs.snapshot() == before


def test_clearing_caches_keeps_the_table():
    with obs.span("kept"):
        obs.count("kept")
    n = obs.snapshot()["spans"]["kept"]["count"]
    ops.clear_cache()
    jax.clear_caches()
    assert obs.snapshot()["spans"]["kept"]["count"] == n
    assert obs.counter("kept") >= 1
