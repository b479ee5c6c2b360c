"""What the ``ouro`` family brought to ``parallel/transformer.py`` (a stack
run several times over the same weights, the final norm closing every loop
step, an exit gate and a loss weighted token by token by the exit
distribution, the sandwich's second norm on each sub-layer's output), at
tiny widths on the CPU against the benchmark's plain reference
(``benchmark/reference/ouro.py``): the step's loss and every leaf's
gradient, with and without the Pallas kernels interpreted, the loop tied to
the model (the looped gradient is the sum over four copies of an unrolled
stack), one loop step as the plain cross-entropy, the exit distribution and
the entropy term, the refusals by name, and the step unit's loop counters."""

import os
import sys
import types
from unittest import mock

import numpy as np
import pytest

import jax
import jax.numpy as jnp

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import ouro as ref                          # noqa: E402

from znicz_tpu.ops.pallas import attention as pattn        # noqa: E402
from znicz_tpu.parallel import transformer as tfm          # noqa: E402
from znicz_tpu.parallel.arch import mechanisms_of_params   # noqa: E402
from znicz_tpu.parallel.blocks import _glu                 # noqa: E402
from znicz_tpu.parallel.params import (                    # noqa: E402
    _layer_shapes, init_params)
from znicz_tpu.parallel.plan import _loop_saves            # noqa: E402
from znicz_tpu.parallel.mesh import make_mesh              # noqa: E402

MODEL_KEYS = ["model_type", "hidden_size", "intermediate_size", "hidden_act",
              "num_attention_heads", "num_key_value_heads", "head_dim",
              "num_hidden_layers", "layer_types", "total_ut_steps",
              "exit_entropy_weight", "rms_norm_eps", "rope_theta",
              "rope_scaling", "tie_word_embeddings", "vocab_size"]
TINY = {
    "model_type": "ouro", "hidden_size": 32, "intermediate_size": 48,
    "hidden_act": "silu", "num_attention_heads": 4, "num_key_value_heads": 4,
    "head_dim": 8, "num_hidden_layers": 2,
    "layer_types": ["full_attention"] * 2, "total_ut_steps": 4,
    "exit_entropy_weight": 0.1, "rms_norm_eps": 1e-6, "rope_theta": 1000000,
    "rope_scaling": None, "tie_word_embeddings": False, "vocab_size": 53,
    "hyper": {"lr": 0.05},
}
TRAFFIC = {"minibatch_size": 2, "seq_len": 16}


def _cfg(**over):
    cfg = {**TINY, **over}
    cfg["layer_types"] = ["full_attention"] * cfg["num_hidden_layers"]
    return cfg


def _arch(cfg):
    return tfm.arch_from_config({k: cfg[k] for k in MODEL_KEYS})


def _mesh1():
    return make_mesh({"data": 1, "seq": 1, "model": 1}, jax.devices()[:1])


def _on_mesh(fn, *args):
    """``fn(*args)`` inside the singleton mesh's ``shard_map``: the dense
    attention core names the ``seq`` axis."""
    from jax.sharding import PartitionSpec as P

    return tfm.shard_map(fn, mesh=_mesh1(),
                         in_specs=tuple(P() for _ in args),
                         out_specs=P())(*args)


def _named(cfg, tree):
    out = {}
    for group, path in ref.leaf_groups(cfg).items():
        node = tree
        for key in path:
            node = node[key]
        out.update(ref._flat(node, group))
    return out


def _first_step(cfg, seed, traffic=TRAFFIC, **step_kw):
    """The timed step's first loss, stats and every leaf's gradient as
    plain SGD applied it."""
    lr = cfg["hyper"]["lr"]
    step, _ = tfm.make_train_step(_mesh1(), _arch(cfg), lr=lr, stats=True,
                                  compute_dtype=jnp.float32, **step_kw)
    p0 = ref.init_params(seed, cfg)
    b, t = traffic["minibatch_size"], traffic["seq_len"]
    rows = ref.make_tokens(seed, cfg, t, 0, b)
    p1, loss, stats = step(p0, jnp.asarray(rows[:, :-1]),
                           jnp.asarray(rows[:, 1:]))
    grads = jax.tree.map(lambda a, c: np.asarray(a - c) / lr, p0, p1)
    return float(loss), {k: float(v) for k, v in stats.items()}, \
        _named(cfg, grads)


def _check_gradients(grads, want, norm_rel=2e-3, diff_rel=5e-3):
    assert set(grads) == set(want["grad_norm"])
    for name, g in grads.items():
        assert np.linalg.norm(g) == pytest.approx(
            want["grad_norm"][name], rel=norm_rel, abs=2e-6), name
    for name, g in want["grad_first"].items():
        scale = max(np.linalg.norm(g), 1e-6)
        assert np.linalg.norm(grads[name] - g) / scale < diff_rel, name


def _check_loop_stats(stats, want_loop):
    assert stats["loop_exit_step_mean"] == pytest.approx(
        want_loop["exit_step_mean"], rel=1e-5)
    assert stats["loop_exit_entropy"] == pytest.approx(
        want_loop["exit_entropy"], rel=1e-5)
    for r, value in enumerate(want_loop["loss_step"], 1):
        assert stats[f"loop_loss_step{r}"] == pytest.approx(value, rel=1e-5)


@pytest.mark.parametrize("chunks", [None, 4], ids=["one_chunk", "chunked"])
def test_looped_step_loss_and_every_gradient_follow_the_reference(chunks):
    """Two layers x four loop steps: the step's first loss, its loop
    counters and every leaf's first gradient (each the sum over the leaf's
    four uses) are the plain reference's, with the head pass whole and in
    chunks of tokens."""
    cfg = _cfg()
    want = ref.first_steps(7, cfg, TRAFFIC, 1, steps=1)
    loss, stats, grads = _first_step(cfg, 7, loss_chunks=chunks)
    assert loss == pytest.approx(want["loss"][0], rel=2e-5)
    _check_gradients(grads, want)
    _check_loop_stats(stats, want["loop"][0])
    assert {"exit_w", "exit_b", "norm_g", "B1.ln1o_g", "B0.ln2o_g"} <= set(
        want["grad_first"])


def test_looped_step_with_the_kernels_interpreted_follows_the_reference():
    """``engine.pallas_interpret`` at heads of 128: the key/value-blocked
    flash kernels in the layer's own layout (the whole-row form refuses as
    it does at 4,096) and the in-place row kernel for the rotary embedding
    over the whole head, under the loop's recomputation policy."""
    from test_lfm2_arch import _pallas_interpret

    cfg = _cfg(hidden_size=256, intermediate_size=384, head_dim=128,
               num_attention_heads=2, num_key_value_heads=2,
               total_ut_steps=2)
    traffic = {"minibatch_size": 2, "seq_len": 128}
    want = ref.first_steps(3, cfg, traffic, 1, steps=1)
    refuse = mock.patch.object(pattn, "unsupported_reason",
                               lambda t, dh: "refused for the test")
    with _pallas_interpret(True), refuse:
        assert pattn.direct_layout(128, 128)
        loss, stats, grads = _first_step(cfg, 3, traffic, loss_chunks=2)
    assert stats["attn_flash"] == 4 and stats["attn_direct"] == 4
    assert loss == pytest.approx(want["loss"][0], rel=2e-5)
    _check_gradients(grads, want)
    _check_loop_stats(stats, want["loop"][0])


def test_loop_keeps_the_named_arrays_and_recomputes_the_wide_products():
    """What a layer application saves under the loop's policy: its input,
    the queries, keys and values and each sub-layer's output, all ``(b, t,
    d)``; never an array of the SwiGLU's width, never an f32 copy of the
    stream.  An unlooped stack checkpoints nothing."""
    from jax._src.ad_checkpoint import saved_residuals

    cfg = _cfg(num_hidden_layers=1)
    arch = _arch(cfg)
    run = tfm._Run(arch.heads, arch.kv_heads)
    blk = tfm._block_fn(arch)
    assert blk is not tfm._block
    assert tfm._block_fn(_arch(_cfg(total_ut_steps=1))) is tfm._block
    p = ref.init_params(2, cfg)["blocks"][0]
    x = jnp.ones((2, 16, 32), jnp.float32)
    saved = saved_residuals(lambda x, p: _on_mesh(
        lambda x, p: blk(x, p, arch, run, 0)[0], x, p), x, p)
    acts = [aval.shape for aval, _ in saved if aval.shape[:2] == (2, 16)]
    assert sorted(acts) == sorted([(2, 16, 32)] * 3 + [(2, 16, 4, 8)] * 3)
    plain = saved_residuals(lambda x, p: _on_mesh(
        lambda x, p: tfm._block(x, p, arch, run, 0)[0], x, p), x, p)
    assert any(aval.shape == (2, 16, 48) for aval, _ in plain)
    assert _loop_saves(types.SimpleNamespace(name="pallas_call"))


def _unrolled_loss(copies, top, tokens, labels, arch):
    """The model's loss over ``loop_steps`` COPIES of the stack, each with
    its own weights, written with the program's block and the reference's
    exit distribution: no loop anywhere."""
    run = tfm._Run(arch.heads, arch.kv_heads)
    x = top["emb"][tokens]
    gs, nlls = [], []
    for blocks in copies:
        for i, p in enumerate(blocks):
            x, _, _ = tfm._block(x, p, arch, run, i)
        x = tfm._rms_norm(x, top["norm_g"], arch.eps)
        gs.append((x @ top["exit_w"])[..., 0] + top["exit_b"][0])
        logp = jax.nn.log_softmax(x @ top["head"], axis=-1)
        nlls.append(-jnp.take_along_axis(logp, labels[..., None],
                                         axis=-1)[..., 0])
    p = ref.exit_distribution(jnp.stack(gs))
    per_token = (p * jnp.stack(nlls)).sum(0) + arch.exit_beta * (
        p * jnp.log(p)).sum(0)
    return per_token.mean()


def test_looped_gradient_is_the_sum_over_four_copies_of_an_unrolled_stack():
    """What ties the loop to the model: an unrolled stack of four copies
    that hold the same weights gives each copy a gradient of its own, and
    the looped step's gradient of a layer's leaf is their sum; the loss is
    the same number."""
    cfg = _cfg()
    arch = _arch(cfg)
    loss, _, grads = _first_step(cfg, 11)
    p0 = ref.init_params(11, cfg)
    rows = ref.make_tokens(11, cfg, TRAFFIC["seq_len"], 0, 2)
    top = {k: v for k, v in p0.items() if k != "blocks"}
    copies = [p0["blocks"]] * arch.loop_steps
    with jax.default_matmul_precision("highest"):
        value, (g_copies, g_top) = jax.jit(jax.value_and_grad(
            lambda copies, top: _on_mesh(
                lambda *a: _unrolled_loss(*a, arch), copies, top,
                jnp.asarray(rows[:, :-1]), jnp.asarray(rows[:, 1:])),
            (0, 1)))(copies, top)
    assert len(g_copies) == 4
    assert float(value) == pytest.approx(loss, rel=2e-5)
    for li in range(arch.n_layers):
        for leaf in p0["blocks"][li]:
            parts = [np.asarray(g[li][leaf]) for g in g_copies]
            total = sum(parts)
            # every copy contributes, and none is the whole
            assert all(np.linalg.norm(part) > 0 for part in parts)
            assert np.linalg.norm(parts[0] - total) > 1e-3 * np.linalg.norm(
                total)
            np.testing.assert_allclose(
                grads[f"B{li}.{leaf}"], total, rtol=0,
                atol=5e-3 * max(np.linalg.norm(total), 1e-6) /
                np.sqrt(total.size) + 1e-7)
    for leaf in ("norm_g", "exit_w", "exit_b", "head"):
        assert np.linalg.norm(grads[leaf] - np.asarray(g_top[leaf])) < \
            5e-3 * np.linalg.norm(g_top[leaf]) + 1e-7, leaf


def test_one_loop_step_is_the_plain_cross_entropy_of_an_unlooped_stack():
    """``total_ut_steps`` 1: no gate in the pytree, no scan in the
    program, the loss is the mean next-token cross-entropy of the stack's
    logits, and the reference (the constant distribution, entropy 0) gives
    the same loss and gradients."""
    cfg = _cfg(total_ut_steps=1)
    arch = _arch(cfg)
    assert arch.loop_steps == 1 and not arch.exit_gate
    assert "looped stack" not in arch.mechanisms()
    assert "sandwich norm" in arch.mechanisms()
    p0 = ref.init_params(5, cfg)
    assert "exit_w" not in p0 and set(p0) == set(tfm.param_shapes(arch))
    rows = ref.make_tokens(5, cfg, TRAFFIC["seq_len"], 0, 2)
    tokens, labels = jnp.asarray(rows[:, :-1]), jnp.asarray(rows[:, 1:])
    step, _ = tfm.make_train_step(_mesh1(), arch, lr=0.05, stats=True,
                                  compute_dtype=jnp.float32)
    jaxpr = str(jax.make_jaxpr(step)(p0, tokens, labels))
    # the dense attention core scans its ring; nothing else does, and
    # nothing is checkpointed
    assert jaxpr.count("scan[") == 2 * arch.n_layers
    assert "remat" not in jaxpr
    looped = str(jax.make_jaxpr(tfm.make_train_step(
        _mesh1(), _arch(_cfg()), lr=0.05, compute_dtype=jnp.float32)[0])(
            ref.init_params(5, _cfg()), tokens, labels))
    assert looped.count("scan[") > jaxpr.count("scan[")
    assert "remat" in looped
    logits = tfm.make_logits_fn(_mesh1(), arch,
                                compute_dtype=jnp.float32)(p0, tokens)
    plain = -jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                                 labels[..., None], axis=-1).mean()
    want = ref.first_steps(5, cfg, TRAFFIC, 1, steps=1)
    loss, stats, grads = _first_step(cfg, 5)
    assert not any(k.startswith("loop_") for k in stats)
    assert loss == pytest.approx(float(plain), rel=2e-5)
    assert loss == pytest.approx(want["loss"][0], rel=2e-5)
    assert want["loop"][0]["exit_entropy"] == 0.0
    assert want["loop"][0]["exit_step_mean"] == 1.0
    _check_gradients(grads, want)


def test_exit_distribution_sums_to_one_and_the_last_step_takes_the_rest():
    g = jax.random.normal(jax.random.PRNGKey(0), (4, 3, 7)) * 2.0
    p = np.asarray(ref.exit_distribution(g))
    lam = 1.0 / (1.0 + np.exp(-np.asarray(g)))
    np.testing.assert_allclose(p.sum(0), 1.0, atol=1e-6)
    # p_4 = S_3: what no earlier gate took, whatever the fourth gate says
    np.testing.assert_allclose(p[3], np.prod(1.0 - lam[:3], axis=0),
                               rtol=1e-5)
    np.testing.assert_allclose(p[1], lam[1] * (1.0 - lam[0]), rtol=1e-5)
    other = np.asarray(ref.exit_distribution(g.at[3].set(-g[3])))
    np.testing.assert_array_equal(other, p)


def test_entropy_term_enters_with_a_minus_sign_times_beta():
    """``L(beta) = L(0) - beta * mean H(p)``: the counters give ``H``, so
    the two losses differ by exactly the weighted entropy, and the last
    gate, which nothing reads, takes no gradient."""
    base, stats0, grads0 = _first_step(_cfg(exit_entropy_weight=0.0), 9)
    with_h, stats, _ = _first_step(_cfg(exit_entropy_weight=0.25), 9)
    assert stats["loop_exit_entropy"] == pytest.approx(
        stats0["loop_exit_entropy"], rel=1e-6)
    assert 0 < stats["loop_exit_entropy"] <= np.log(4)
    assert 1 <= stats["loop_exit_step_mean"] <= 4
    assert with_h == pytest.approx(
        base - 0.25 * stats["loop_exit_entropy"], rel=1e-5)
    assert with_h < base
    # the weighted sum lies between the loop steps' own cross-entropies
    steps = [stats0[f"loop_loss_step{r}"] for r in range(1, 5)]
    assert min(steps) <= base <= max(steps)
    assert np.linalg.norm(grads0["exit_w"]) > 0
    assert _arch(_cfg()).exit_beta == 0.1


def test_second_norm_acts_on_the_sublayers_output_not_on_the_stream():
    """With both output gains at zero a layer is the identity (a norm on
    the stream would zero it or rescale it); with the attention's output
    gain alone at zero the layer is the SwiGLU's sandwich on its input."""
    cfg = _cfg(num_hidden_layers=1)
    arch = _arch(cfg)
    run = tfm._Run(arch.heads, arch.kv_heads)
    p = dict(ref.init_params(2, cfg)["blocks"][0])
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32)) * 3.0
    off = {**p, "ln1o_g": jnp.zeros(32), "ln2o_g": jnp.zeros(32)}
    def block(x, p):
        return tfm._block(x, p, arch, run, 0)[0]

    y = _on_mesh(block, x, off)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
    half = {**p, "ln1o_g": jnp.zeros(32)}
    y = _on_mesh(block, x, half)
    m = tfm._rms_norm(x, p["ln2_g"], arch.eps)
    want = x + tfm._rms_norm(_glu(m, p["w1"], p["w3"], p["w2"]),
                             p["ln2o_g"], arch.eps)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-5)
    # the added term has the norm's scale whatever the stream's is
    added = np.asarray(y - x)
    assert np.sqrt((added ** 2).mean()) == pytest.approx(1.0, abs=0.15)
    assert set(_layer_shapes(arch, 0)) >= {"ln1_g", "ln1o_g", "ln2_g",
                                           "ln2o_g"}


def test_an_unknown_model_type_is_refused_by_name():
    assert set(tfm._FAMILIES) == {"lfm2_moe", "glm4_moe_lite", "ouro",
                                  "KeyeVL2", "granitemoehybrid",
                                  "nemotron_h", "afmoe", "solar_open2"}
    with pytest.raises(ValueError, match="model_type 'mamba2'.*lfm2_moe, "
                                         "glm4_moe_lite, ouro, KeyeVL2, "
                                         "granitemoehybrid, nemotron_h, "
                                         "afmoe, solar_open2"):
        tfm.arch_from_config({**TINY, "model_type": "mamba2"})


@pytest.mark.parametrize("key,value,word", [
    ("layer_types", ["full_attention", "sliding_attention"], "layer_types"),
    ("use_sliding_window", True, "sliding_window"),
    ("rope_scaling", {"type": "yarn"}, "rope_scaling"),
    ("hidden_act", "gelu", "hidden_act"),
    ("total_ut_steps", 0, "loop_steps")])
def test_keys_the_stack_cannot_honour_are_refused_by_name(key, value, word):
    with pytest.raises(ValueError, match=word):
        tfm.arch_from_config({**TINY, key: value})


def test_a_looped_stack_has_one_recomputation_path_and_no_routed_layer():
    """What is not written is refused: routed experts under the loop
    (their counters are means over layers, and the loop would sum them
    over loop steps); and what a layer application saves is the loop's
    own choice: no builder takes a recomputation keyword."""
    import dataclasses
    import inspect

    arch = _arch(_cfg())
    with pytest.raises(ValueError, match="no routed experts"):
        dataclasses.replace(arch, sandwich=False, n_experts=4, moe_ff=8,
                            ffns=("glu", "moe_routed"))
    for make in (tfm.make_train_step, tfm.make_eval_loss,
                 tfm.make_logits_fn):
        assert not {"remat", "remat_policy", "causal"} & \
            set(inspect.signature(make).parameters)
    with pytest.raises(TypeError, match="remat"):
        tfm.make_train_step(_mesh1(), arch, lr=0.05, remat=True)


@pytest.mark.parametrize("limit_gib", [None, 15.75, 64.0, 1024.0])
def test_a_looped_stack_keeps_nothing_beside_its_list_at_any_limit(
        limit_gib, monkeypatch):
    """``checkpoint_plan`` of the benchmark cell's looped stack (and of the
    tiny one): the SwiGLU's wide products are named and refused whatever
    memory the device reports, since what a layer application keeps crosses
    the scan and is stacked; the step's policy is ``_loop_saves`` itself."""
    import json

    with open(os.path.join(BENCH, "configs", "ouro_2_6b.json")) as f:
        cfg = json.load(f)
    opts = cfg["builders"]["lm_train_keys"]
    cell = tfm.arch_from_config({k: cfg[k] for k in opts["model_keys"]})
    limit = None if limit_gib is None else int(limit_gib * 2 ** 30)
    for arch, tokens in ((cell, 8192), (_arch(_cfg()), 32)):
        assert tfm.checkpoint_plan(arch, tokens, 2, limit,
                                   opts["loss_chunks"]) == {"glu_wide": 0}
        assert tfm._report_plan(arch, tokens, 2, limit,
                                opts["loss_chunks"]) == ()
    monkeypatch.setattr(tfm, "_memory_limit", lambda mesh: limit)
    assert tfm._run_of(_mesh1(), cell).hbm_limit == limit
    assert tfm.step_choices(_mesh1(), cell, 2, 4096, 8)[
        "checkpoint_kept_bytes"] == {"glu_wide": 0}
    assert tfm._saves(()) is _loop_saves


def test_the_new_kinds_refuse_a_sharded_mesh_by_name(cpu_devices):
    arch = _arch(_cfg())
    for axes in ({"data": 1, "seq": 1, "model": 2},
                 {"data": 1, "seq": 2, "model": 1}):
        with pytest.raises(ValueError, match="looped stack, exit gate, "
                                             "sandwich norm"):
            tfm.make_train_step(make_mesh(axes, jax.devices()[:2]), arch)


@pytest.mark.parametrize("steps,word", [
    (4, "looped stack"), (4, "exit gate"), (1, "sandwich norm")])
def test_serving_and_export_refuse_the_new_mechanisms_by_name(tmp_path,
                                                              steps, word):
    from znicz_tpu.serve.kvcache import KVDecoder
    from znicz_tpu.utils.export import export_lm

    arch = _arch(_cfg(total_ut_steps=steps))
    assert word in arch.mechanisms()
    params = init_params(np.random.default_rng(1), arch)
    assert word in mechanisms_of_params(params)
    with pytest.raises(NotImplementedError, match=word):
        KVDecoder(params, heads=4)
    with pytest.raises(ValueError, match=word):
        export_lm(params, str(tmp_path / "m.npz"), heads=4)


def test_init_params_follow_the_shape_table():
    arch = _arch(_cfg())
    params = init_params(np.random.default_rng(3), arch)
    shapes = tfm.param_shapes(arch)
    assert jax.tree.map(np.shape, params) == shapes
    assert shapes["exit_w"] == (32, 1) and shapes["exit_b"] == (1,)
    assert np.all(params["exit_b"] == 0) and np.any(params["exit_w"] != 0)
    assert np.all(params["blocks"][0]["ln1o_g"] == 1)
    assert jax.tree.structure(tfm.param_specs(arch)) == \
        jax.tree.structure(jax.tree.map(lambda a: 0, params))


# -- the step unit ------------------------------

def test_step_unit_publishes_the_loop_counters_of_the_reference(tmp_path):
    """``TransformerLMStep(arch=...)`` under the benchmark's control graph
    on the reference's seeded weights and rows: an epoch of three steps
    gives ``loop_counters`` that are the means of the reference's three
    steps' readings; the gauges carry them; ``loss_terms`` stays an MTP
    stack's."""
    from builders import lm_train_keys
    from znicz_tpu.core.backends import XLADevice
    from znicz_tpu.observe import registry

    cfg = {**_cfg(), "builders": {"lm_train_keys": {
        "model_keys": MODEL_KEYS, "loss_chunks": 2}}}
    want = ref.first_steps(13, cfg, TRAFFIC, 1, steps=3)
    rows = ref.make_tokens(13, cfg, TRAFFIC["seq_len"], 0, 6)
    w = lm_train_keys.build_workflow(rows, cfg, TRAFFIC)
    w.decision.max_epochs = 1
    w.step._params = ref.init_params(13, cfg)
    w.initialize(device=XLADevice())
    w.run()
    step = w.step
    assert step.arch.loop_steps == 4 and step.loss_terms == {}
    loop = step.loop_counters
    assert set(loop) == {"exit_step_mean", "exit_entropy", "loss_step1",
                         "loss_step2", "loss_step3", "loss_step4"}

    def mean(key, r=None):
        return np.mean([s[key] if r is None else s[key][r]
                        for s in want["loop"]])

    assert loop["exit_step_mean"] == pytest.approx(mean("exit_step_mean"),
                                                   rel=2e-4)
    assert loop["exit_entropy"] == pytest.approx(mean("exit_entropy"),
                                                 rel=2e-4)
    for r in range(4):
        assert loop[f"loss_step{r + 1}"] == pytest.approx(
            mean("loss_step", r), rel=2e-4)
    assert w.decision.metrics_history[-1]["metric_train"] == pytest.approx(
        np.mean(want["loss"]), rel=2e-4)
    for name, key, labels in (
            ("znicz_lm_loop_exit_step_mean", "exit_step_mean", {}),
            ("znicz_lm_loop_exit_entropy", "exit_entropy", {}),
            ("znicz_lm_loop_loss_step", "loss_step3", {"step": "3"})):
        fam = registry.REGISTRY.get(name)
        assert fam is not None and \
            fam.labels(unit=step.name, **labels).get() == loop[key]
    with pytest.raises(ValueError, match="looped stack"):
        step.export_lm(str(tmp_path / "pkg.npz"))
    state = step.state_dict()
    step.load_state_dict(state)
    state["params"].pop("exit_b")
    with pytest.raises(ValueError, match="architecture"):
        step.load_state_dict(state)
