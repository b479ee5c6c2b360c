"""CPU rehearsal of the ``granite4h_micro_train_pp4_t8192`` cell: ``run.py``
end to end over a tiny overlay of its configuration and traffic (every
mechanism kept: state-space layers around a position-free grouped-query
attention layer, SwiGLU, the four multipliers off 1, the tied head, a row
that spans several chunks), the traced run's per-layer metrics with the
builder kind and the reader this cell adds, the scopes under the patterns
the cell lists, the control that must come out as not correct (the
reference in fp8), two planted faults that must too (a chunk's carry
dropped, a chunk opened on its predecessor's state), the refusal a program that
cannot read the family gives before the reference runs, the operation
count's arithmetic, and a compile-only rehearsal of the step at the real
widths for a v5e that is described and not attached, which reports the
compiled step's memory.
"""

import copy
import json
import math
import os

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import benchlib                                       # noqa: E402
import run                                            # noqa: E402
import tiny                                           # noqa: E402

CELL = "granite4h_micro_train_pp4_t8192"
CONFIG, TRAFFIC = "granite_4_0_h_micro", "train_tokens_pp4_t8192"
TINY_GRANITE = {
    "hidden_size": 64, "shared_intermediate_size": 96,
    "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 61,
    "num_hidden_layers": 3, "layer_types": ["mamba", "attention", "mamba"],
    "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 8,
    "mamba_chunk_size": 8, "attention_multiplier": 0.125,
    "hyper": {"lr": 0.05},
}
TINY_TOKENS = {"n_rows": 12, "minibatch_size": 2, "seq_len": 32,
               "k_steps": 2}
HBM_USABLE = 15.75 * 2 ** 30      # what the runtime leaves of 16 GiB


@pytest.fixture(scope="module")
def overlay(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("granite_overlay"))
    for kind, name, changes in (("configs", CONFIG, TINY_GRANITE),
                                ("traffic", TRAFFIC, TINY_TOKENS)):
        doc = copy.deepcopy(benchlib.load_json(
            os.path.join(tiny.BENCH_DIR, kind, name + ".json")))
        doc.update(changes)
        if kind == "configs":           # the model's keys stay as listed
            doc["builders"]["lm_train_keys"]["loss_chunks"] = 2
        os.makedirs(os.path.join(root, kind), exist_ok=True)
        with open(os.path.join(root, kind, name + ".json"), "w") as f:
            json.dump(doc, f)
    return root


def _run(overlay, seed=7, seconds=1.0, trace=0, control=False):
    return run.execute(["--workload", CELL, "--seed", str(seed), "--seconds",
                        str(seconds), "--trace", str(trace)],
                       roots_extra=[overlay], allow_cpu=True, control=control)


def test_cell_runs_end_to_end_tiny(overlay):
    rc, result, outcome = _run(overlay, seed=2147483711)
    assert rc == 0 and result["correct"] is True, outcome["lines"]
    assert set(result["metrics"]) == {"train_samples_per_s", "setup_s"}
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(outcome["samples"]["readings"]) == {
        "loss_gap", "grad_norm_gap", "delta_norm_gap", "grad_diff_gap"}
    ssm = outcome["samples"]["ssm"]
    assert set(ssm) == {"decay_mean", "final_state_rms"}
    assert 0.0 < ssm["decay_mean"] < 1.0 and ssm["final_state_rms"] > 0.0
    # the first step's readings are of the same rows on both sides
    first = outcome["samples"]["ssm_first_step"]
    for key in ("decay_mean", "final_state_rms"):
        assert first["program"][key] == pytest.approx(
            first["reference"][key], rel=5e-3), first
    assert any(ln.startswith("ssm (last class pass)")
               for ln in outcome["lines"])
    assert "moe" not in outcome["samples"]


def test_traced_run_reports_every_metric_that_lists_the_cell(overlay):
    rc, result, outcome = _run(overlay, seed=13, seconds=2.0, trace=1)
    assert rc == 0 and result["correct"] is True, outcome["lines"]
    got = set(result["metrics"])
    # the device-trace readers find no TPU plane on the CPU; the program's
    # counters and spans are all there
    assert {"graph_ms_per_step", "train_step_rate_median", "ssm_decay_mean",
            "ssm_final_state_rms"} <= got
    bench = benchlib.benchmark_json(benchlib.Roots())
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [])}
    assert listed == {"attn_device_ms_per_step", "mlp_device_ms_per_step",
                      "ce_device_ms_per_step", "ssm_proj_device_ms_per_step",
                      "ssm_conv_device_ms_per_step",
                      "ssm_scan_device_ms_per_step", "ssm_decay_mean",
                      "ssm_final_state_rms"}
    for name in listed:                 # each has its file and its reader
        spec = benchlib.Roots().data("metrics", name)
        benchlib.Roots().module("readers", spec["reader"])


def test_the_reader_reads_nothing_from_a_program_without_the_counters():
    """What the parent commit's traced runs of the other cells give the
    new reader: no ``ssm`` samples, so no number and no error."""
    import types

    reader = benchlib.Roots().module("readers", "ssm_counter")
    spec = benchlib.Roots().data("metrics", "ssm_decay_mean")
    assert reader.read(types.SimpleNamespace(samples={}, metric=spec)) is None
    rc = types.SimpleNamespace(samples={"ssm": {"decay_mean": 0.25}},
                               metric=spec)
    assert reader.read(rc) == 0.25


def test_the_step_books_the_mixer_under_the_three_patterns_the_cell_adds():
    """The mixer's operations carry ``block<i>.ssm``, ``.ssm.conv`` and
    ``.ssm.scan``; each new pattern reads its own scope whole and no
    other's; attention, SwiGLU, head pass and update keep theirs."""
    import re

    import jax
    import jax.numpy as jnp

    from znicz_tpu.observe import probe
    from znicz_tpu.parallel import transformer as tfm
    from znicz_tpu.parallel.mesh import make_mesh

    def matches(metric, scope):
        spec = benchlib.Roots().data("metrics", metric)
        return any(re.compile(p).fullmatch(scope)
                   for p in spec["params"]["patterns"])

    for metric, own in (("ssm_proj_device_ms_per_step", "block7.ssm"),
                        ("ssm_conv_device_ms_per_step", "block7.ssm.conv"),
                        ("ssm_scan_device_ms_per_step", "block7.ssm.scan")):
        for scope in ("block7.ssm", "block7.ssm.conv", "block7.ssm.scan",
                      "block5.attn", "block7.mlp", "ce"):
            assert matches(metric, scope) == (scope == own), (metric, scope)
    assert not matches("attn_device_ms_per_step", "block7.ssm")
    cfg = {**benchlib.Roots().data("configs", CONFIG), **TINY_GRANITE}
    arch = tfm.arch_from_config(
        {k: cfg[k] for k in cfg["builders"]["lm_train_keys"]["model_keys"]})
    mesh = make_mesh({"data": 1, "seq": 1, "model": 1}, jax.devices()[:1])
    step, _ = tfm.make_train_step(mesh, arch, lr=0.05, stats=True,
                                  loss_chunks=2)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.float32),
        tfm.param_shapes(arch), is_leaf=lambda x: isinstance(x, tuple))
    tok = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    text = step.lower(params, tok, tok).compile().as_text()
    _, scopes = probe.parse_scopes(text)
    seen = {probe_name.rstrip(")").rsplit("(", 1)[-1]
            for probe_name in scopes.values() if probe_name}
    assert {"embed", "ce", "update", "block0.ssm", "block0.ssm.conv",
            "block0.ssm.scan", "block0.mlp", "block1.attn", "block1.mlp",
            "block2.ssm", "block2.ssm.conv", "block2.ssm.scan"} <= seen
    bare = [n for n, sc in scopes.items() if not sc]
    assert len(bare) < 0.02 * len(scopes), bare


def test_operation_count_is_the_issues_arithmetic():
    """10 SwiGLUs 24.7 TFLOP, nine mixers' projections 11.4, the head pass
    10.1, the attention layer 1.1 with its projections, the nine scans'
    least 0.7 (2.1 % of a state-space layer): 48.0 TFLOP a step of 8,192
    tokens."""
    roots = benchlib.Roots()
    cfg = roots.data("configs", CONFIG)
    ref = roots.module("reference", "granitemoehybrid")
    t = int(roots.data("traffic", TRAFFIC)["seq_len"])
    dm = ref.dims(cfg)
    swiglu = 10 * 3 * t * 6.0 * 2048 * 8192
    proj = 9 * 3 * t * 2.0 * 2048 * (8512 + 4096)
    head = 3 * t * 2.0 * 2048 * 100352
    attn = 3 * (t * 2.0 * 2048 * 64 * (2 * 32 + 2 * 8) +
                t * t * 32 * 2.0 * 64)
    scan = 9 * 3 * t * ref.scan_flops_per_token(dm, 256)
    assert swiglu == pytest.approx(24.7e12, rel=5e-3)
    assert proj == pytest.approx(11.4e12, rel=5e-3)
    assert head == pytest.approx(10.1e12, rel=5e-3)
    assert scan == pytest.approx(0.704e12, rel=2e-3)
    assert ref.train_flops_per_sample(cfg, t) == pytest.approx(
        swiglu + proj + head + attn + scan, rel=1e-12)
    from znicz_tpu.ops.pallas import attention as pattn
    assert pattn.form_of(t, 64)[0] == "blocked"
    assert pattn.form_of(4096, 64)[0] != "blocked"


def test_fp8_control_fails_a_limit(overlay):
    rc, result, outcome = _run(overlay, seed=5, control=True)
    assert rc == 0 and result["correct"] is True, outcome["lines"]
    ref = benchlib.Roots().module("reference", "granitemoehybrid")
    control = outcome["samples"]["control_readings"]
    assert any(control[k] > ref.LIMITS[k] for k in control), control


@pytest.mark.parametrize("fault", ["a chunk's carry dropped",
                                   "a chunk opened on the state before"])
def test_a_planted_fault_comes_out_as_not_correct(overlay, monkeypatch,
                                                  fault):
    """The scan's own faults, planted in the program: the state entering
    every chunk set to zero (each chunk starts a sequence of its own), and
    every chunk opened on the state its predecessor opened on (the carry one
    chunk late).  A carry rounded to bfloat16 moves the readings by 1e-4
    (``tests/test_granitemoehybrid_arch.py`` holds the scan to 5e-5 for
    that); the cell's limits are set for a bfloat16 program."""
    import jax
    import jax.numpy as jnp

    from znicz_tpu.parallel import ssm

    states = ssm._chunk_states

    def faulty(x, dt, a, bm):
        opening, last = states(x, dt, a, bm)
        if fault == "a chunk's carry dropped":
            return jnp.zeros_like(opening), last
        return jnp.roll(opening, 1, axis=1), last

    monkeypatch.setattr(ssm, "_chunk_states", faulty)
    # jax.checkpoint keeps the layer's trace by function and shapes: without
    # this the sound trace of an earlier test would be run again
    jax.clear_caches()
    try:
        rc, result, outcome = _run(overlay, seed=11)
    finally:
        jax.clear_caches()
    assert rc == 0 and result["correct"] is False, outcome["lines"]


def test_a_program_that_cannot_read_the_family_is_refused_at_once(
        overlay, monkeypatch, capsys):
    """What the parent commit does with this cell: ``arch_from_config``
    refuses the ``model_type`` by name, and the run ends with exit code 1
    and no result line before the reference has run."""
    from znicz_tpu.parallel import transformer as tfm

    ref = benchlib.Roots().module("reference", "granitemoehybrid")
    monkeypatch.delitem(tfm._FAMILIES, "granitemoehybrid")
    monkeypatch.setattr(ref, "first_steps", lambda *a, **k: pytest.fail(
        "the reference ran before the refusal"))
    rc, result, outcome = _run(overlay, seed=3)
    assert rc == 1 and result is None and outcome is None
    assert "granitemoehybrid" in capsys.readouterr().err


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", True)


def test_step_compiles_for_a_v5e_at_the_real_widths_and_fits(topo,
                                                             monkeypatch):
    """The cell's step (ten layers, 1 x 8,192 tokens, 100,352 ids) compiled
    for the described chip: 951,991,232 parameters, the three blocked flash
    kernels once each, and arguments plus temporaries that fit the chip
    with room (9.0 GiB: the update of a leaf runs as its gradient lands, so
    masters and gradients are never both whole)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from znicz_tpu.ops.pallas import attention as pattn
    from znicz_tpu.parallel import transformer as tfm
    from znicz_tpu.parallel.mesh import make_mesh

    # the step asks jax.default_backend(), which is the CPU here
    monkeypatch.setattr(tfm, "_flash_eligible", lambda mesh, interp: True)
    cfg = benchlib.Roots().data("configs", CONFIG)
    traffic = benchlib.Roots().data("traffic", TRAFFIC)
    opts = cfg["builders"]["lm_train_keys"]
    arch = tfm.arch_from_config({k: cfg[k] for k in opts["model_keys"]})
    mesh = make_mesh({"data": 1, "seq": 1, "model": 1}, topo.devices[:1])
    step, _ = tfm.make_train_step(
        mesh, arch, lr=float(cfg["hyper"]["lr"]), masked=True, donate=True,
        loss_chunks=opts["loss_chunks"], stats=True,
        compute_dtype=jnp.bfloat16)
    rep = NamedSharding(mesh, P())
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=rep),
        tfm.param_shapes(arch), is_leaf=lambda x: isinstance(x, tuple))
    n_params = sum(math.prod(s.shape) for s in jax.tree.leaves(params))
    assert n_params == 951_991_232
    b, t = int(traffic["minibatch_size"]), int(traffic["seq_len"])
    tok = jax.ShapeDtypeStruct((b, t), jnp.int32,
                               sharding=NamedSharding(mesh, P("data", "seq")))
    mask = jax.ShapeDtypeStruct((b,), jnp.bool_,
                                sharding=NamedSharding(mesh, P("data")))
    compiled = step.lower(params, tok, tok, mask).compile()
    m = compiled.memory_analysis()
    live = m.argument_size_in_bytes + m.temp_size_in_bytes
    print(f"compiled step for a described v5e: arguments "
          f"{m.argument_size_in_bytes / 2 ** 30:.3f} GiB (donated), "
          f"temporaries {m.temp_size_in_bytes / 2 ** 30:.3f} GiB, together "
          f"{live / 2 ** 30:.3f} GiB of {HBM_USABLE / 2 ** 30:.2f}")
    assert live < 0.9 * HBM_USABLE, f"{live / 2 ** 30:.2f} GiB: {m}"
    text = compiled.as_text()
    for name in (pattn.KVB_FWD_KERNEL_NAME, pattn.KVB_DKV_KERNEL_NAME,
                 pattn.KVB_DQ_KERNEL_NAME):
        calls = [ln for ln in text.splitlines()
                 if "tpu_custom_call" in ln and name in ln]
        assert len(calls) == 1, (name, len(calls))
