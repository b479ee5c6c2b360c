"""CLI / Launcher / genetics / ensemble tests (SURVEY.md §3.3: Main,
Launcher, genetics, ensemble rows)."""

import json
import os
import textwrap

import numpy as np
import pytest

from znicz_tpu.__main__ import main as cli_main
from znicz_tpu.core import prng
from znicz_tpu.core.backends import XLADevice
from znicz_tpu.core.config import Tune, root, set_by_path
from znicz_tpu.launcher import Launcher
from znicz_tpu.models import wine
from znicz_tpu.utils.ensemble import Ensemble
from znicz_tpu.utils.genetics import Genetics


@pytest.fixture(autouse=True)
def _hermetic_site_config(monkeypatch, tmp_path_factory):
    """Isolate every CLI test from the developer machine's site-config
    layer (env var or ~/.config file)."""
    monkeypatch.setenv("ZNICZ_TPU_SITE_CONFIG", "")
    yield


WINE_WORKFLOW = textwrap.dedent("""
    import json
    from znicz_tpu.core.config import root
    from znicz_tpu.models import wine

    def run(load, main):
        epochs = root.wine.get("max_epochs", 3)
        w, _ = load(wine.build, max_epochs=epochs, n_train=60, n_valid=30,
                    minibatch_size=10)
        main()
        out = root.wine.get("result_file", None)
        if out:
            with open(out, "w") as f:
                json.dump({"epochs": len(w.decision.metrics_history),
                           "best": w.decision.best_metric}, f)
    """)


def test_launcher_load_main_contract():
    prng.seed_all(3)
    launcher = Launcher(device=XLADevice())
    wine.run(lambda b, **kw: launcher.load(b, max_epochs=3, n_train=60,
                                           n_valid=30, minibatch_size=10,
                                           **kw),
             launcher.main)
    assert bool(launcher.workflow.decision.complete)
    assert len(launcher.workflow.decision.metrics_history) == 3


def test_launcher_snapshot_resume(tmp_path):
    prng.seed_all(3)
    w = wine.build(max_epochs=4, n_train=60, n_valid=30, minibatch_size=10,
                   snapshotter_config={"directory": str(tmp_path),
                                       "prefix": "w", "only_improved": False,
                                       "keep_all": True})
    w.initialize(device=XLADevice())
    w.run()
    snap = tmp_path / "w_2.npz"
    assert snap.exists()

    prng.seed_all(3)
    launcher = Launcher(device=XLADevice(), snapshot=str(snap))
    launcher.load(wine.build, max_epochs=4, n_train=60, n_valid=30,
                  minibatch_size=10)
    launcher.main()
    assert launcher.workflow.decision.metrics_history == \
        w.decision.metrics_history


def test_cli_end_to_end(tmp_path):
    wf = tmp_path / "wine_wf.py"
    wf.write_text(WINE_WORKFLOW)
    cfg = tmp_path / "wine_config.py"
    cfg.write_text("root.wine.max_epochs = 2\n")
    result_file = tmp_path / "result.json"
    rc = cli_main([str(wf), str(cfg), "--random-seed", "5", "-d", "auto",
                   "-o", f"root.wine.result_file={result_file}"])
    assert rc == 0
    result = json.loads(result_file.read_text())
    assert result["epochs"] == 2
    assert result["best"] is not None
    del root.wine


def test_cli_optimize(tmp_path, capsys):
    """An lr-only Tune over a fused StandardWorkflow must route through
    the VMAPPED population evaluator (SURVEY.md §3.4 hyperparameter
    parallelism), not the sequential full-run loop."""
    wf = tmp_path / "wine_opt.py"
    wf.write_text(textwrap.dedent("""
        from znicz_tpu.core.config import root
        from znicz_tpu.models import wine

        def run(load, main):
            load(wine.build, max_epochs=2, n_train=60, n_valid=30,
                 minibatch_size=10, lr=float(root.wine_opt.lr))
            main()
        """))
    set_by_path(root, "wine_opt.lr", Tune(0.3, 0.01, 1.0))
    rc = cli_main([str(wf), "--optimize", "2", "-d", "auto"])
    assert rc == 0
    assert "'_evaluator': 'vmapped'" in capsys.readouterr().out
    del root.wine_opt


def test_cli_optimize_structural_tune_falls_back(tmp_path, capsys):
    """A Tune that changes workflow STRUCTURE (hidden layer size) cannot
    batch — the probe must detect it and fall back to sequential runs."""
    wf = tmp_path / "wine_hidden.py"
    wf.write_text(textwrap.dedent("""
        from znicz_tpu.core.config import root
        from znicz_tpu.models import wine

        def run(load, main):
            load(wine.build, max_epochs=1, n_train=30, n_valid=10,
                 minibatch_size=10,
                 hidden=int(root.wine_hidden.hidden))
            main()
        """))
    set_by_path(root, "wine_hidden.hidden", Tune(8, 4, 16))
    rc = cli_main([str(wf), "--optimize", "1", "-d", "auto"])
    assert rc == 0
    assert "'_evaluator': 'sequential'" in capsys.readouterr().out
    del root.wine_hidden


def test_genetics_pure_function():
    tunes = {"x": Tune(0.0, -10.0, 10.0), "y": Tune(0.0, -5.0, 5.0)}
    prng.seed_all(4)
    ga = Genetics(lambda ind: (ind["x"] - 3.0) ** 2 + ind["y"] ** 2,
                  tunes=tunes, population_size=12, mutation_rate=0.5)
    best, fit = ga.run(generations=8)
    assert fit < 1.0, (best, fit)
    assert abs(best["x"] - 3.0) < 1.5


def test_ga_evaluations_share_one_seed_and_private_stream(monkeypatch):
    """Every GA evaluation must see IDENTICAL session-stream state (fitness
    comparability), and the reseed must not restart the GA's own draws
    (r1 advisor: utils/genetics.py reseed drift)."""
    from znicz_tpu.utils import genetics as gmod

    set_by_path(root, "ga_seed_test.lr", Tune(0.3, 0.01, 1.0))
    seen = []

    class FakeModule:
        @staticmethod
        def run(load, main):
            # what a workflow does first: draw from the session stream
            seen.append(float(prng.get().uniform(0.0, 1.0, (1,))[0]))
            w, _ = load(lambda **kw: _FakeWorkflow())
            main()

    class _FakeDecision:
        best_metric = 1.0

    class _FakeWorkflow:
        decision = _FakeDecision()

        def initialize(self, **kw):
            pass

        def run(self):
            pass

        def stop(self):
            pass

    class _FakeLauncher:
        device = None

    prng.seed_all(9)
    gmod.optimize(FakeModule, _FakeLauncher(), generations=2,
                  population_size=4)
    # 8 evaluations + 1 vmap-compatibility probe build (the fake
    # workflow is not a fused StandardWorkflow, so the probe rejects it
    # after the base build and evaluation runs sequentially)
    assert len(seen) == 9
    assert len(set(seen)) == 1, \
        f"evaluations saw drifting session seeds: {seen}"
    del root.ga_seed_test

    # the GA's private stream is untouched by seed_all
    ga = Genetics(lambda ind: 0.0, tunes={"x": Tune(0.0, -1.0, 1.0)})
    before = float(ga._gen.uniform(0.0, 1.0, (1,))[0])
    prng.seed_all(9)
    after = float(ga._gen.uniform(0.0, 1.0, (1,))[0])
    assert before != after  # stream advanced, was not reset to the start


def _staged_fc_step(n_steps=6, batch=40):
    """Small fused FC workflow + device-staged train/valid batches."""
    import jax.numpy as jnp
    from znicz_tpu.models.mnist_fc import build_fused
    from znicz_tpu.parallel.mesh import data_parallel_mesh

    prng.seed_all(51)
    w = build_fused(max_epochs=1, layers=(32,), minibatch_size=batch,
                    n_train=240, n_valid=80, mesh=data_parallel_mesh(4))
    w.initialize(device=XLADevice())
    rng = np.random.default_rng(2)
    xs = jnp.asarray(rng.normal(size=(n_steps, batch, 28, 28)),
                     jnp.float32)
    # learnable rule so lr actually matters: label = quadrant sign pattern
    ys = jnp.asarray(
        (np.asarray(xs)[:, :, :14, :].sum((2, 3)) >
         np.asarray(xs)[:, :, 14:, :].sum((2, 3))).astype(np.int32))
    ms = jnp.ones((n_steps, batch), bool)
    return w, xs, ys, ms


def test_vmapped_population_matches_sequential_and_scales():
    """SURVEY.md §3.4 hyperparameter parallelism: the population is a
    batched axis.  Each vmapped individual's fitness equals the same
    hyperparams trained sequentially, and scoring P=8 individuals in one
    dispatch beats 8 sequential scans wall-clock."""
    import time

    import jax
    import jax.numpy as jnp
    from znicz_tpu.utils.genetics import make_population_evaluator

    w, xs, ys, ms = _staged_fc_step()
    step = w.step
    ex, ey, em = xs[0], ys[0], ms[0]
    evaluator = make_population_evaluator(step)
    P = 8
    lrs = np.linspace(0.0, 0.35, P).astype(np.float32)
    base = step.hyper_params()
    hyper_pop = jax.tree.map(
        lambda v: jnp.broadcast_to(jnp.float32(v), (P,)), base)
    for i in range(len(base)):
        hyper_pop[i]["lr"] = jnp.asarray(lrs)
        hyper_pop[i]["lr_b"] = jnp.asarray(lrs)

    t0 = time.perf_counter()
    fits = np.asarray(jax.device_get(evaluator(
        hyper_pop, xs, ys, ms, ex, ey, em)))
    t_vmap_cold = time.perf_counter() - t0
    assert fits.shape == (P,)
    # lr=0 learns nothing; a healthy lr must beat it
    assert fits.min() < fits[0], fits

    # parity: sequential per-individual scans give identical fitness
    def run_sequential(i):
        hyper_i = jax.tree.map(lambda v: v[i], hyper_pop)
        # fresh copies: _train_fn donates its params/key arguments
        params = jax.tree.map(jnp.copy, step._params)
        key_i = jax.random.fold_in(step._key, i)
        for k in range(xs.shape[0]):
            params, key_i, _ = step._train_fn(
                params, key_i, hyper_i, xs[k], ys[k], ms[k])
        return int(jax.device_get(step._eval_fn(params, ex, ey, em))
                   ["n_err"])

    run_sequential(0)           # warm: compiles _train_fn/_eval_fn
    t_seq = float("inf")
    for _rep in range(2):       # best-of-2: robust to transient host load
        t0 = time.perf_counter()
        seq = [run_sequential(i) for i in (0, 3, 7)]
        t_seq = min(t_seq, time.perf_counter() - t0)
    assert seq == [int(f) for f in fits[[0, 3, 7]]], (seq, fits)

    # scaling: one warmed batched dispatch for 8 beats 3 sequential runs
    t_vmap = float("inf")
    for _rep in range(2):
        t0 = time.perf_counter()
        jax.device_get(evaluator(hyper_pop, xs, ys, ms, ex, ey, em))
        t_vmap = min(t_vmap, time.perf_counter() - t0)
    assert t_vmap < t_seq, (t_vmap, t_seq, t_vmap_cold)


def test_ga_with_vmapped_evaluator_converges_to_good_lr():
    """Genetics(evaluate_many=...) scores whole generations in one
    compiled dispatch and still finds a working learning rate."""
    import jax
    import jax.numpy as jnp
    from znicz_tpu.utils.genetics import make_population_evaluator

    w, xs, ys, ms = _staged_fc_step()
    step = w.step
    ex, ey, em = xs[0], ys[0], ms[0]
    base = step.hyper_params()
    evaluator = make_population_evaluator(step)

    def evaluate_many(pop):
        P = len(pop)
        hyper_pop = jax.tree.map(
            lambda v: jnp.broadcast_to(jnp.float32(v), (P,)), base)
        lrs = jnp.asarray([ind["lr"] for ind in pop], jnp.float32)
        for i in range(len(base)):
            hyper_pop[i] = dict(hyper_pop[i], lr=lrs, lr_b=lrs)
        return np.asarray(jax.device_get(evaluator(
            hyper_pop, xs, ys, ms, ex, ey, em)))

    prng.seed_all(6)
    ga = Genetics(evaluate=None, evaluate_many=evaluate_many,
                  tunes={"lr": Tune(0.0, 0.0, 0.4)},
                  population_size=8, mutation_rate=0.5)
    best, fit = ga.run(generations=4)
    assert 0.0 < best["lr"] <= 0.4
    assert fit <= evaluate_many([{"lr": 0.0}] * 1)[0], (best, fit)


def test_ensemble_committee(tmp_path):
    ens = Ensemble(wine.build, n_members=3, base_seed=50, max_epochs=3,
                   n_train=60, n_valid=30, minibatch_size=10)
    ens.train(XLADevice())
    report = ens.test_classification()
    assert report["n"] == 30
    # the committee must not be worse than the worst member
    assert report["committee_err"] <= max(report["member_errs"])
    # predictions shapes
    loader = ens.members[0].loader
    data = loader.original_data.map_read()[:8]
    assert ens.predict_classes(data).shape == (8,)
    assert ens.predict_mean(data).shape[0] == 8


def test_cli_ensemble_train(tmp_path, monkeypatch):
    """--ensemble-train N runs N seeded members and writes the summary
    JSON (reference: veles --ensemble-train)."""
    wf = tmp_path / "wine_ens.py"
    wf.write_text(WINE_WORKFLOW)
    monkeypatch.chdir(tmp_path)
    rc = cli_main([str(wf), "--ensemble-train", "3", "-d", "auto",
                   "--random-seed", "7"])
    assert rc == 0
    out = json.loads((tmp_path / "ensemble_wine.json").read_text())
    assert out["n_members"] == 3
    assert len(out["members"]) == 3
    assert len({m["seed"] for m in out["members"]}) == 3
    assert out["best"] <= out["mean"]


def test_cli_ensemble_train_rejects_bad_usage(tmp_path, monkeypatch):
    wf = tmp_path / "wine_ens2.py"
    wf.write_text(WINE_WORKFLOW)
    monkeypatch.chdir(tmp_path)
    assert cli_main([str(wf), "--ensemble-train", "0", "-d", "auto"]) == 2
    assert cli_main([str(wf), "--ensemble-train", "2", "-d", "auto",
                     "--publish", "markdown"]) == 2


def test_forge_cli_roundtrip(tmp_path, capsys):
    """`znicz_tpu forge upload/list/fetch` — the reference's forge CLI
    over the local registry."""
    import numpy as np

    from znicz_tpu.__main__ import main

    pkg = tmp_path / "pkg.npz"
    np.savez(pkg, w=np.arange(4.0))
    reg = str(tmp_path / "registry")

    assert main(["forge", "--registry", reg, "upload", str(pkg),
                 "--name", "demo", "--version", "1.0"]) == 0
    assert main(["forge", "--registry", reg, "upload", str(pkg),
                 "--name", "demo", "--version", "1.10"]) == 0
    assert main(["forge", "--registry", reg, "list"]) == 0
    out = capsys.readouterr().out
    assert "demo: 1.0, 1.10" in out          # semantic version order

    dest = tmp_path / "fetched.npz"
    assert main(["forge", "--registry", reg, "fetch", "demo",
                 "-o", str(dest)]) == 0      # latest = 1.10
    assert dest.exists()
    with np.load(dest) as loaded:
        np.testing.assert_array_equal(loaded["w"], np.arange(4.0))


def test_forge_cli_errors_are_one_liners(tmp_path, capsys):
    """Registry failures exit 2 with a one-line stderr message, not a
    traceback (CLI convention)."""
    from znicz_tpu.__main__ import main

    reg = str(tmp_path / "reg")
    assert main(["forge", "--registry", reg, "fetch", "nosuch"]) == 2
    err = capsys.readouterr().err
    assert "forge:" in err and "nosuch" in err

    assert main(["forge", "--registry", reg, "upload",
                 str(tmp_path / "missing.npz"),
                 "--name", "x", "--version", "1"]) == 2
    assert "forge:" in capsys.readouterr().err


def test_site_config_layering(tmp_path, monkeypatch):
    """Reference layering: site config applies BEFORE workflow configs,
    so workflow-level settings win; $ZNICZ_TPU_SITE_CONFIG selects it."""
    site = tmp_path / "site_config.py"
    site.write_text("root.wine.max_epochs = 9\n"
                    "root.site_probe.marker = 'site'\n")
    wf = tmp_path / "wf.py"
    wf.write_text(WINE_WORKFLOW)
    cfg = tmp_path / "wine_config.py"
    cfg.write_text("root.wine.max_epochs = 2\n")   # overrides the site value
    result_file = tmp_path / "result.json"
    monkeypatch.setenv("ZNICZ_TPU_SITE_CONFIG", str(site))
    try:
        rc = cli_main([str(wf), str(cfg), "--random-seed", "5", "-d", "auto",
                       "-o", f"root.wine.result_file={result_file}"])
        assert rc == 0
        assert json.loads(result_file.read_text())["epochs"] == 2
        assert root.site_probe.marker == "site"    # site layer did run
    finally:
        for key in ("site_probe", "wine"):
            if key in root:
                delattr(root, key)

    from znicz_tpu.__main__ import apply_site_config

    # explicit-but-missing path: loud error, not a silent skip
    monkeypatch.setenv("ZNICZ_TPU_SITE_CONFIG", str(tmp_path / "nope.py"))
    with pytest.raises(SystemExit, match="does not exist"):
        apply_site_config()
    # empty string disables the layer even if a home-dir file exists
    monkeypatch.setenv("ZNICZ_TPU_SITE_CONFIG", "")
    assert apply_site_config() is None
    # no env var + no home-dir file: silently none
    monkeypatch.delenv("ZNICZ_TPU_SITE_CONFIG")
    monkeypatch.setenv("HOME", str(tmp_path / "nohome"))
    assert apply_site_config() is None
