"""Compile-latency plane tests (ISSUE 7): persistent-compilation-cache
round trips (a simulated second-process init HITS; corrupt/missing
cache dirs degrade to logged misses, never crashes), AOT package
export -> zero-compile serve boot (``compile_count == 0`` pinned,
outputs bit-identical AOT vs JIT), fingerprint-mismatch fallback, the
``aot`` CLI, the warmup summary line, the cache-miss-fed
``recompile_storm`` rule, and the Kohonen per-build re-trace fix."""

import json
import logging
import os
import shutil

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from znicz_tpu import compilecache  # noqa: E402
from znicz_tpu.observe import probe  # noqa: E402

#: every jax config knob configure() touches, restored by the fixture
_CACHE_KEYS = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
               "jax_persistent_cache_min_compile_time_secs",
               "jax_persistent_cache_min_entry_size_bytes",
               "jax_raise_persistent_cache_errors")


@pytest.fixture
def cc(monkeypatch):
    """A clean compilecache: no env override, no prior configure()
    decision; jax config + module state restored afterwards so the rest
    of the suite keeps whatever cache policy it booted with."""
    prev_cfg = {k: getattr(jax.config, k) for k in _CACHE_KEYS}
    prev_state = (compilecache._configured, compilecache._active_dir)
    monkeypatch.delenv(compilecache.JAX_ENV_VAR, raising=False)
    monkeypatch.delenv(compilecache.ENV_VAR, raising=False)
    monkeypatch.delenv(compilecache.ENV_MIN_S, raising=False)
    compilecache._reset_for_tests()
    yield compilecache
    for k, v in prev_cfg.items():
        jax.config.update(k, v)
    compilecache._configured, compilecache._active_dir = prev_state
    # un-latch jax's cache-used/backing-store state too: without this
    # the rest of the suite keeps consulting whatever (deleted) tmp dir
    # the last test here enabled
    compilecache._reset_jax_cache_state()


def _fresh_fn(salt: float):
    """A jit program whose HLO is unique per ``salt`` — cache entries
    from other tests (or the suite's own warm cache) cannot collide."""
    c = jnp.float32(salt)

    def fn(x):
        return jnp.tanh(x * c) + c * 3.0, x @ (x.T * c)

    return jax.jit(fn)


# -- persistent cache --------------------------------------------------------

def test_placed_dir_is_the_dir_and_is_never_replaced(cc, monkeypatch,
                                                     tmp_path):
    """$JAX_COMPILATION_CACHE_DIR set: that is the directory, it
    outranks an explicit argument and the off switch, and nothing this
    module does leaves another value in jax's config."""
    placed = str(tmp_path / "placed")
    monkeypatch.setenv(compilecache.JAX_ENV_VAR, placed)
    monkeypatch.setenv(compilecache.ENV_VAR, "off")
    seen = []
    real_update = jax.config.update

    def spy(name, value):
        if name == "jax_compilation_cache_dir":
            seen.append(value)
        real_update(name, value)

    monkeypatch.setattr(jax.config, "update", spy)
    assert cc.configure() == placed
    assert jax.config.jax_compilation_cache_dir == placed
    assert cc.configure(cache_dir=str(tmp_path / "other"),
                        force=True) == placed
    assert cc.ensure() == placed
    with cc.suspended():
        pass
    assert jax.config.jax_compilation_cache_dir == placed
    assert cc.active_dir() == placed
    # the only other value ever written is suspended()'s transient ""
    assert set(seen) <= {placed, ""}
    _fresh_fn(0.619)(jnp.ones((2, 4), jnp.float32))
    assert any(f.endswith("-cache") for f in os.listdir(placed))


def test_explicit_arg_wins_over_off_switch(cc, monkeypatch, tmp_path):
    monkeypatch.setenv(compilecache.ENV_VAR, "off")
    explicit = tmp_path / "explicit"
    assert cc.configure(cache_dir=str(explicit)) == str(explicit)
    assert explicit.is_dir()
    assert jax.config.jax_compilation_cache_dir == str(explicit)


def test_env_off_disables(cc, monkeypatch):
    monkeypatch.setenv(compilecache.ENV_VAR, "off")
    assert cc.configure() is None
    assert jax.config.jax_compilation_cache_dir == ""
    # disabled is still a decision: ensure() must not re-enable
    assert cc.ensure() is None


def test_default_dir_is_the_checkouts_and_stable_across_processes(cc):
    """Unset: ``<checkout>/.data/cache/jax``, the same string in every
    process — the path is part of what two processes must agree on to
    hit, so nothing in it may vary (no pid, no timestamp, no tempfile
    name, no home directory)."""
    import subprocess
    import sys
    import tempfile

    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(checkout, ".data", "cache", "jax")
    assert compilecache._resolve_dir(None) == want
    env = {k: v for k, v in os.environ.items()
           if k not in (compilecache.JAX_ENV_VAR, compilecache.ENV_VAR)}
    code = ("from znicz_tpu import compilecache as c; "
            "print(c._resolve_dir(None))")
    seen = {subprocess.run([sys.executable, "-c", code], cwd=cwd, env=dict(
        env, PYTHONPATH=checkout), capture_output=True, text=True,
        check=True, timeout=60).stdout.strip()
        for cwd in (checkout, tempfile.gettempdir())}
    assert seen == {want}
    assert not want.startswith(tempfile.gettempdir())
    assert os.path.expanduser("~/.cache") not in want


def test_ensure_is_idempotent(cc, monkeypatch, tmp_path):
    first = cc.configure(cache_dir=str(tmp_path / "e"))
    assert first == str(tmp_path / "e")
    # a second ensure() (every Workflow.run calls it) is a no-op even
    # if the env changes mid-process — the decision was made
    monkeypatch.setenv(compilecache.ENV_VAR, "off")
    assert cc.ensure() == first


def test_min_compile_time_change_applies_without_force(cc, tmp_path):
    cc.configure(cache_dir=str(tmp_path / "m"), min_compile_time_s=0.0)
    # idempotence is keyed on the WHOLE resolution, not just the dir —
    # a changed threshold must land in jax, not silently early-return
    cc.configure(cache_dir=str(tmp_path / "m"), min_compile_time_s=5.0)
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 5.0
    assert cc.active_dir() == str(tmp_path / "m")


def test_malformed_min_s_env_degrades_to_zero(cc, monkeypatch, tmp_path,
                                              caplog):
    monkeypatch.setenv(compilecache.ENV_MIN_S, "1s")
    with caplog.at_level(logging.WARNING, "znicz_tpu.compilecache"):
        assert cc.configure(cache_dir=str(tmp_path / "m")) == \
            str(tmp_path / "m")
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    assert any("is not a number" in r.message for r in caplog.records)


def test_suspended_blocks_cache_and_restores(cc, tmp_path):
    cc.configure(cache_dir=str(tmp_path / "s"))
    x = jnp.asarray(np.ones((2, 4), np.float32))
    _, misses0 = probe.compile_cache_stats()
    with cc.suspended():
        assert jax.config.jax_compilation_cache_dir == ""
        _fresh_fn(0.311)(x)
    # the suspended compile went past the persistent cache entirely
    assert probe.compile_cache_stats()[1] == misses0
    assert jax.config.jax_compilation_cache_dir == str(tmp_path / "s")
    _fresh_fn(0.433)(x)
    assert probe.compile_cache_stats()[1] > misses0  # cache back in play


def test_cache_round_trip_second_init_hits(cc, tmp_path):
    """The tentpole contract: a second process booting the same program
    against the same cache dir loads instead of compiling.  The second
    process is simulated by ``jax.clear_caches()`` + a fresh ``jit``
    wrapper — the only warmth left is the persistent cache."""
    cc.configure(cache_dir=str(tmp_path / "rt"))
    x = jnp.asarray(np.linspace(-1, 1, 32, dtype=np.float32).reshape(4, 8))
    hits0, misses0 = probe.compile_cache_stats()
    cold = [np.asarray(o) for o in _fresh_fn(0.731)(x)]
    hits1, misses1 = probe.compile_cache_stats()
    assert misses1 > misses0          # the cold compile was observed
    assert hits1 == hits0             # nothing to hit yet
    assert any(f.endswith("-cache") for f in os.listdir(tmp_path / "rt"))
    jax.clear_caches()
    warm = [np.asarray(o) for o in _fresh_fn(0.731)(x)]
    hits2, _ = probe.compile_cache_stats()
    assert hits2 > hits1              # warm init HIT, assertably
    for a, b in zip(cold, warm):
        np.testing.assert_array_equal(a, b)


def test_enable_after_cache_off_compiles_is_consulted(cc, monkeypatch,
                                                      tmp_path):
    """jax latches whether-the-cache-is-used once per process: a compile
    while the cache is off (the tier-1 conftest default) must not make a
    later configure() a silent no-op — configure resets jax's latched
    state so the new directory IS consulted.  (Found by exactly this
    ordering under the full suite.)"""
    monkeypatch.setenv(compilecache.ENV_VAR, "off")
    cc.configure()
    x = jnp.asarray(np.ones((2, 4), np.float32))
    _fresh_fn(0.271)(x)               # latches jax's cache-unused state
    cc.configure(cache_dir=str(tmp_path / "late"), force=True)
    _, misses0 = probe.compile_cache_stats()
    _fresh_fn(0.829)(x)
    _, misses1 = probe.compile_cache_stats()
    assert misses1 > misses0          # the late-enabled cache was consulted
    assert any(f.endswith("-cache")
               for f in os.listdir(tmp_path / "late"))


def test_unusable_cache_dir_degrades_to_logged_off(cc, tmp_path, caplog):
    blocker = tmp_path / "a_file"
    blocker.write_text("not a directory")
    with caplog.at_level(logging.WARNING, "znicz_tpu.compilecache"):
        assert cc.configure(cache_dir=str(blocker / "sub")) is None
    assert any("persistent caching disabled" in r.message
               for r in caplog.records)
    # jax still compiles and runs — degraded means slower, not broken
    out = _fresh_fn(0.113)(jnp.ones((2, 4), jnp.float32))
    assert np.isfinite(np.asarray(out[0])).all()


def test_corrupt_cache_entries_never_crash(cc, tmp_path):
    cache = tmp_path / "corrupt"
    cc.configure(cache_dir=str(cache))
    x = jnp.asarray(np.ones((3, 5), np.float32))
    want = [np.asarray(o) for o in _fresh_fn(0.557)(x)]
    for name in os.listdir(cache):
        if name.endswith("-cache"):
            (cache / name).write_bytes(b"garbage, not an executable")
    jax.clear_caches()
    # jax_raise_persistent_cache_errors is pinned False: the corrupt
    # entry is a logged miss and the program recompiles
    got = [np.asarray(o) for o in _fresh_fn(0.557)(x)]
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a, b)


def test_engine_boot_triggers_ensure(cc, monkeypatch, tmp_path):
    from znicz_tpu.serve import BatchEngine

    monkeypatch.setenv(compilecache.JAX_ENV_VAR, str(tmp_path / "boot"))
    assert not compilecache._configured
    BatchEngine(lambda x: x, max_batch=2, input_shape=(2,))
    assert compilecache.active_dir() == str(tmp_path / "boot")


# -- AOT serving artifacts ---------------------------------------------------

@pytest.fixture(scope="module")
def tiny_pkg(tmp_path_factory):
    """One trained-and-exported forward package shared by the AOT
    tests (each test copies it before mutating)."""
    from znicz_tpu.core import prng
    from znicz_tpu.core.backends import XLADevice
    from znicz_tpu.standard_workflow import StandardWorkflow
    from znicz_tpu.utils.export import export_forward

    prng.seed_all(23)
    w = StandardWorkflow(
        name="AotPkg", loss_function="softmax",
        layers=[{"type": "all2all_tanh", "->": {"output_sample_shape": 8}},
                {"type": "softmax", "->": {"output_sample_shape": 3}}],
        loader_name="synthetic_classifier",
        loader_config={"n_classes": 3, "sample_shape": (6,), "n_train": 60,
                       "n_valid": 0, "minibatch_size": 20},
        decision_config={"max_epochs": 1})
    w.initialize(device=XLADevice())
    w.run()
    pkg = str(tmp_path_factory.mktemp("aot") / "tiny.npz")
    export_forward(w, pkg)
    return pkg


def _aot_copy(tiny_pkg, tmp_path, max_batch=4) -> str:
    from znicz_tpu.utils.export import attach_aot

    pkg = str(tmp_path / "pkg.npz")
    shutil.copy(tiny_pkg, pkg)
    attach_aot(pkg, max_batch=max_batch)
    return pkg


def test_aot_boot_zero_compiles_and_bit_identical(tiny_pkg, tmp_path):
    from znicz_tpu.serve import BatchEngine
    from znicz_tpu.utils.export import ExportedForward

    pkg = _aot_copy(tiny_pkg, tmp_path)
    fwd = ExportedForward(pkg)
    assert fwd.aot_fallback_reason is None
    assert sorted(fwd.precompiled_buckets) == [1, 2, 4]
    engine = BatchEngine(fwd, max_batch=4)
    assert engine.warmup() == 0               # THE zero-JIT boot contract
    assert engine.compile_count == 0
    assert engine.aot_count == 3
    assert engine.stats()["aot_count"] == 3
    # forward results bit-identical AOT vs JIT (same compiled HLO)
    jit_fwd = ExportedForward(pkg, aot=False)
    assert jit_fwd.precompiled_buckets == {}
    rng = np.random.default_rng(5)
    for n in (1, 2, 3, 4):                    # 3 pads to bucket 4
        x = rng.normal(size=(n, 6)).astype(np.float32)
        np.testing.assert_array_equal(engine.run(x), jit_fwd(x)[:n]
                                      if n in (1, 2, 4) else
                                      jit_fwd(np.concatenate(
                                          [x, np.zeros((1, 6),
                                                       np.float32)]))[:n])
    assert engine.compile_count == 0          # traffic compiled nothing


def test_aot_dispatch_skips_wrong_rank_input(tiny_pkg, tmp_path):
    """An input whose leading dim equals a precompiled bucket but whose
    RANK does not match (bucket,)+input_shape must take the general jit
    path — behavior with AOT present is identical to without (here:
    the same jit-path shape error, not a failure from inside a
    deserialized executable that was pinned to another rank)."""
    from znicz_tpu.utils.export import ExportedForward

    # max_batch=6 -> buckets (1, 2, 4, 6): bucket 6 COLLIDES with the
    # package's 1-D sample length 6
    pkg = _aot_copy(tiny_pkg, tmp_path, max_batch=6)
    fwd = ExportedForward(pkg)
    assert 6 in fwd.precompiled_buckets
    x1d = np.zeros(6, np.float32)       # un-batched: never a valid input
    jit_fwd = ExportedForward(pkg, aot=False)
    with pytest.raises(TypeError) as jit_err:
        jit_fwd(x1d)
    with pytest.raises(TypeError) as aot_err:
        fwd(x1d)
    assert str(aot_err.value) == str(jit_err.value)
    # and a rank-correct bucket-sized batch still rides the executable
    ok = np.zeros((6, 6), np.float32)
    np.testing.assert_array_equal(fwd(ok), jit_fwd(ok))


def test_aot_fingerprint_mismatch_falls_back_to_jit(tiny_pkg, tmp_path,
                                                    caplog):
    from znicz_tpu.serve import BatchEngine
    from znicz_tpu.utils.export import ExportedForward

    pkg = _aot_copy(tiny_pkg, tmp_path)
    with np.load(pkg, allow_pickle=False) as zf:
        meta = json.loads(str(zf["__arch__"]))
        arrays = {k: zf[k] for k in zf.files if k != "__arch__"}
    meta["aot"]["fingerprint"]["device_kind"] = "TPU v9"
    with open(pkg, "wb") as f:
        np.savez_compressed(f, __arch__=np.array(json.dumps(meta)),
                            **arrays)
    with caplog.at_level(logging.WARNING, "znicz_tpu.export"):
        fwd = ExportedForward(pkg)
    assert fwd.precompiled_buckets == {}
    assert "device_kind mismatch" in fwd.aot_fallback_reason
    assert any("AOT executables ignored" in r.message
               for r in caplog.records)
    # degraded, not broken: warmup JIT-compiles every bucket and serves
    engine = BatchEngine(fwd, max_batch=4)
    assert engine.warmup() == 3
    assert engine.aot_count == 0
    y = engine.run(np.zeros((2, 6), np.float32))
    assert y.shape == (2, 3)


def test_aot_corrupt_payload_falls_back(tiny_pkg, tmp_path):
    from znicz_tpu.utils.export import ExportedForward

    pkg = _aot_copy(tiny_pkg, tmp_path)
    with np.load(pkg, allow_pickle=False) as zf:
        meta = json.loads(str(zf["__arch__"]))
        arrays = {k: zf[k] for k in zf.files if k != "__arch__"}
    arrays["__aot__2"] = np.frombuffer(b"truncated rubbish", np.uint8)
    with open(pkg, "wb") as f:
        np.savez_compressed(f, __arch__=np.array(json.dumps(meta)),
                            **arrays)
    fwd = ExportedForward(pkg)
    assert fwd.precompiled_buckets == {}
    assert "deserialization failed" in fwd.aot_fallback_reason
    assert fwd(np.zeros((2, 6), np.float32)).shape == (2, 3)


def test_aot_cli_round_trip(tiny_pkg, tmp_path, capsys):
    from znicz_tpu.__main__ import main as cli_main
    from znicz_tpu.utils.export import ExportedForward

    pkg = str(tmp_path / "cli.npz")
    shutil.copy(tiny_pkg, pkg)
    rc = cli_main(["aot", pkg, "--max-batch", "4"])
    assert rc == 0
    doc = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert doc["buckets"] == [1, 2, 4]
    assert doc["platform"] == "cpu"
    assert sorted(ExportedForward(pkg).precompiled_buckets) == [1, 2, 4]


def test_aot_cli_rejects_non_package(tmp_path, capsys):
    from znicz_tpu.__main__ import main as cli_main

    bad = tmp_path / "bad.npz"
    np.savez(bad, x=np.zeros(3))
    assert cli_main(["aot", str(bad)]) == 2


def test_serve_cli_no_aot_flag(tiny_pkg, tmp_path):
    from znicz_tpu.serve.engine import load_backend

    pkg = _aot_copy(tiny_pkg, tmp_path)
    assert load_backend(pkg, aot=False).precompiled_buckets == {}
    assert sorted(load_backend(pkg).precompiled_buckets) == [1, 2, 4]


def test_export_forward_aot_max_batch(tiny_pkg, tmp_path):
    """export_forward(aot_max_batch=) is attach_aot at export time."""
    from znicz_tpu.utils.export import ExportedForward

    pkg = _aot_copy(tiny_pkg, tmp_path, max_batch=2)
    fwd = ExportedForward(pkg)
    assert sorted(fwd.precompiled_buckets) == [1, 2]
    assert fwd.meta["aot"]["max_batch"] == 2


# -- surfacing ---------------------------------------------------------------

def test_warmup_emits_single_summary_line(caplog):
    from znicz_tpu.serve import BatchEngine

    engine = BatchEngine(lambda x: np.asarray(x) * 2.0, max_batch=4,
                         input_shape=(3,))
    with caplog.at_level(logging.INFO, "BatchEngine"):
        engine.warmup()
    lines = [r.message for r in caplog.records
             if r.message.startswith("warmup:")]
    assert len(lines) == 1
    assert "3 buckets" in lines[0]
    assert "3 compiled" in lines[0]
    assert "0 aot-precompiled" in lines[0]


def test_recompile_storm_fed_by_cache_miss_counter():
    from znicz_tpu.observe import watchtower as wt

    rule = wt.recompile_storm(max_in_window=2.0, window_s=60.0,
                              metric="znicz_compile_cache_misses_total",
                              action=lambda r, v: None)
    tower = wt.Watchtower(step_every=1)
    tower.add_rule(rule)
    tower.observe_now(ts=1.0)
    for _ in range(4):
        probe.compile_cache_event("miss")
    tower.observe_now(ts=2.0)
    assert rule.matching
    assert rule.trips == 1                # 4 cold compiles in the window
    assert rule.last_value == 4.0


def test_compile_cache_counters_move_through_disabled_probes(cc, tmp_path):
    """Unlike the per-signal probes, cache accounting survives
    observe.set_enabled(False): the warm/cold contract must stay
    assertable through a bench's bare arm."""
    from znicz_tpu import observe

    observe.set_enabled(False)
    try:
        _, m0 = probe.compile_cache_stats()
        probe.compile_cache_event("miss")
        assert probe.compile_cache_stats()[1] == m0 + 1
    finally:
        observe.set_enabled(True)


# -- kohonen per-build re-trace (ISSUE 7 satellite) --------------------------

def test_kohonen_forward_builds_share_one_traced_program():
    from znicz_tpu.units.kohonen import KohonenForward, _winners_jit

    a, b = KohonenForward(None, shape=(4, 4)), KohonenForward(None,
                                                              shape=(4, 4))
    a.xla_init()
    b.xla_init()
    assert a._xla_fn is b._xla_fn is _winners_jit
    x = jnp.asarray(np.random.default_rng(0).normal(
        size=(5, 16)).astype(np.float32))
    w = jnp.asarray(np.random.default_rng(1).normal(
        size=(16, 16)).astype(np.float32))
    first = np.asarray(a._xla_fn(x, w))
    size_after_first = _winners_jit._cache_size()
    second = np.asarray(b._xla_fn(x, w))
    # the second build reuses the first build's traced program — the
    # per-build re-trace the old per-instance jit(lambda) paid is gone
    assert _winners_jit._cache_size() == size_after_first
    np.testing.assert_array_equal(first, second)
