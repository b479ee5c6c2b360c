"""What the ``solar_open2`` family brought to the language-model path
(Kimi-delta linear-attention layers, whose state follows the delta rule under
a decay a key channel, beside gated position-free grouped-query layers, a
shared expert beside routed ones in every layer), at tiny widths on the CPU
on seeded random weights against the benchmark's plain reference
(``benchmark/reference/solar_open2.py``, which walks a linear layer's state
position by position and whose experts are a masked sum): the reader's
fields and refusals, each by name; the whole step against the reference in
float32 and bfloat16 with both layer kinds, rows the chunk does not divide
and rows of several chunks; a lower precision and four other models each
failing a tolerance; **the share test**; the refusals by mechanism and the
step unit's counters."""

import dataclasses
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import solar_open2 as ref                   # noqa: E402

from znicz_tpu.parallel import blocks, kda                 # noqa: E402
from znicz_tpu.parallel import transformer as tfm          # noqa: E402
from znicz_tpu.parallel.arch import (                      # noqa: E402
    _FAMILIES, Arch, mechanisms_of_params)
from znicz_tpu.parallel.mesh import make_mesh              # noqa: E402
from znicz_tpu.parallel.params import param_shapes         # noqa: E402
from znicz_tpu.parallel.plan import _recomputes_by_policy  # noqa: E402

#: the published shape at toy widths: a linear, a grouped-query and a linear
#: layer; 4 linear heads of 8 with 4 taps; 4 query on 2 key/value heads of 8;
#: 16 experts 24 wide of which this share holds 4, top-3, one shared expert
TINY = {
    "model_type": "solar_open2", "hidden_size": 32, "intermediate_size": 48,
    "moe_intermediate_size": 24, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 8, "num_hidden_layers": 3,
    "gqa_layers": [1], "gqa_interval": 3, "use_rope": False,
    "partial_rotary_factor": 1, "use_gqa_gate": True,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 8,
                           "num_heads": 4, "num_kv_heads": None},
    "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
    "first_k_dense_replace": 0, "n_routed_experts": 4, "router_width": 16,
    "experts_held": {"first": 4, "count": 4}, "num_experts_per_tok": 3,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "rms_norm_eps": 1e-5, "rope_theta": 10000,
    "tie_word_embeddings": False, "vocab_size": 53,
    "hyper": {"lr": 0.05},
}
#: rows of 44 positions in chunks of 16: two whole chunks and a filled one
TRAFFIC = {"minibatch_size": 2, "seq_len": 44}
CHUNK = 16
MECHANISM = "delta-rule linear attention \\(a decay a key channel\\)"


def _cfg(**over):
    return {**TINY, **over}


def _arch(cfg, chunk=CHUNK):
    return dataclasses.replace(tfm.arch_from_config(
        {k: v for k, v in cfg.items() if k != "hyper"}), kda_chunk=chunk)


def _mesh1():
    return make_mesh({"data": 1, "seq": 1, "model": 1}, jax.devices()[:1])


def _named(cfg, tree):
    out = {}
    for group, path in ref.leaf_groups(cfg).items():
        node = tree
        for key in path:
            node = node[key]
        out.update(ref._flat(node, group))
    return out


def _program_first_steps(cfg, seed, dtype, steps=3, arch=None, params=None,
                         traffic=TRAFFIC):
    """What the benchmark's builder reads off the timed step: losses, each
    leaf's first gradient as plain SGD applied it, each leaf's change, each
    step's counters."""
    arch, lr = arch or _arch(cfg), cfg["hyper"]["lr"]
    step, _ = tfm.make_train_step(_mesh1(), arch, lr=lr, stats=True,
                                  loss_chunks=2, compute_dtype=dtype)
    p0 = ref.init_params(seed, cfg) if params is None else params
    b, t = traffic["minibatch_size"], traffic["seq_len"]
    params, losses, counters, grads = p0, [], [], None
    for s in range(steps):
        rows = ref.make_tokens(seed, cfg, t, s * b, (s + 1) * b)
        params, loss, stats = step(params, jnp.asarray(rows[:, :-1]),
                                   jnp.asarray(rows[:, 1:]))
        losses.append(float(loss))
        counters.append({k: float(v) for k, v in stats.items()})
        if s == 0:
            grads = jax.tree.map(lambda a, c: np.asarray(a - c) / lr, p0,
                                 params)
    deltas = jax.tree.map(lambda a, c: float(jnp.linalg.norm(a - c)), p0,
                          params)
    return losses, _named(cfg, grads), _named(cfg, deltas), counters


# -- (a) the reader ----------------------------------------------------------

def test_the_family_reads_into_the_arch_and_its_leaves():
    cfg = _cfg()
    arch = _arch(cfg)
    assert "solar_open2" in _FAMILIES
    assert arch.mixers == ("kda", "attention", "kda")
    assert arch.ffns == ("moe_routed",) * 3
    assert (arch.kda_heads, arch.kda_head_dim, arch.kda_rank,
            arch.conv_taps, arch.kda_neg_eigval) == (4, 8, 8, 4, True)
    assert (arch.heads, arch.kv_heads, arch.head_dim) == (4, 2, 8)
    # no layer rotates, no QK-norm, a gated output
    assert arch.rope_theta is None and not any(
        arch.rotates(i) for i in range(3))
    assert arch.attn_gate and not arch.qk_norm and not arch.sandwich
    assert (arch.n_experts, arch.experts_first, arch.experts_held,
            arch.top_k) == (16, 4, 4, 3)
    assert (arch.score, arch.expert_bias, arch.norm_topk,
            arch.routed_scale) == ("sigmoid", True, True, 1.0)
    assert arch.shared_ff == 24 and arch.expert_form == "glu"
    assert arch.final_norm and not arch.tied
    words = arch.mechanisms()
    assert MECHANISM.replace("\\", "") in words
    assert "gated attention output" in words and \
        "rotary embedding" not in words
    # gqa_layers defaults to every (gqa_interval + 1)-th layer from 0
    by_interval = {k: v for k, v in cfg.items()
                   if k not in ("gqa_layers", "hyper")}
    assert tfm.arch_from_config(
        {**by_interval, "num_hidden_layers": 6}).mixers == \
        ("attention", "kda", "kda", "kda", "attention", "kda")
    # the leaves are the reference's, layer by layer
    shapes = param_shapes(arch)
    seeded = ref.init_params(1, cfg)
    for li, blk in enumerate(shapes["blocks"]):
        assert {k: tuple(v.shape) for k, v in
                seeded["blocks"][li].items()} == blk, li
    assert shapes["blocks"][0]["kda_in"] == (32, 96)
    assert shapes["blocks"][0]["kda_f2"] == (8, 32)
    assert shapes["blocks"][1]["wg"] == (32, 32)
    # a params pytree shows the layer by its leaf
    assert MECHANISM.replace("\\", "") in mechanisms_of_params(
        jax.tree.map(np.asarray, seeded))
    # a stack with a carried state is checkpointed by policy
    assert _recomputes_by_policy(arch)


def test_the_published_configuration_counts_its_parameters():
    with open(os.path.join(BENCH, "configs", "solar_open2_250b.json")) as f:
        cfg = json.load(f)
    keys = cfg["builders"]["lm_train_keys"]["model_keys"]
    arch = tfm.arch_from_config({k: cfg[k] for k in keys})
    assert (arch.d, arch.heads, arch.kv_heads, arch.head_dim) == \
        (4096, 64, 8, 128)
    assert (arch.kda_heads, arch.kda_head_dim, arch.kda_rank,
            arch.conv_taps, arch.kda_chunk) == (64, 128, 128, 4, 64)
    assert (arch.moe_ff, arch.shared_ff) == (1280, 1280)
    assert (arch.n_experts, arch.experts_held, arch.top_k) == (320, 10, 8)
    assert arch.mixers == ("attention", "kda", "kda", "kda")
    shapes = param_shapes(arch)
    count = sum(int(np.prod(s)) for s in jax.tree.leaves(
        shapes, is_leaf=lambda x: isinstance(x, tuple)))
    assert count == 1_420_941_120                 # 11.37 GB at 8 bytes each
    mixer = sum(int(np.prod(s)) for k, s in shapes["blocks"][1].items()
                if k.startswith("kda_"))
    assert mixer == 137_740_480
    assert sorted(cfg["reduced"]) == sorted([
        "num_hidden_layers", "gqa_layers", "n_routed_experts", "vocab_size"])
    assert set(cfg["reduced"]) == set(cfg["published"]) == \
        set(cfg["how_reduced"])
    # the delta rule counts by the recurrence's own products: 7 K^2 a head a
    # position, 3 % of a step of about 39 T
    parts = ref.forward_flops_per_token(cfg, 8192)
    assert 3.8e13 < ref.train_flops_per_sample(cfg, 8192) < 3.95e13
    rule = 3 * 3 * 8192 * 7.0 * 64 * 128 * 128
    assert 0.01 < rule / ref.train_flops_per_sample(cfg, 8192) < 0.02
    assert parts["kda"] > 7.0 * 64 * 128 * 128


@pytest.mark.parametrize("change,match", [
    ({"use_rope": True}, "use_rope"),
    ({"use_rope": True, "partial_rotary_factor": 0.5},
     "partial_rotary_factor"),
    ({"kda_use_full_proj": True}, "kda_use_full_proj"),
    ({"use_gqa_gate": False}, "use_gqa_gate"),
    ({"linear_attn_config": {**TINY["linear_attn_config"],
                             "num_kv_heads": 2}}, "num_kv_heads"),
    ({"linear_attn_config": {**TINY["linear_attn_config"],
                             "short_conv_kernel_size": 0}},
     "short_conv_kernel_size"),
    ({"first_k_dense_replace": 1}, "first_k_dense_replace"),
    ({"n_group": 2}, "n_group"),
    ({"topk_group": 2}, "topk_group"),
    ({"scoring_func": "softmax"}, "scoring_func"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"attention_bias": True}, "attention_bias"),
    ({"gqa_layers": [1, 3]}, "gqa_layers"),
    ({"experts_held": {"first": 14, "count": 4}}, "experts_held"),
])
def test_keys_the_stack_cannot_honour_are_refused_by_name(change, match):
    with pytest.raises(ValueError, match=match):
        _arch(_cfg(**change))


def test_the_arch_refuses_what_the_layer_is_not_written_for():
    arch = _arch(_cfg())
    for change, match in (
            ({"kda_chunk": 48}, "power of two"),
            ({"kda_heads": 0}, "kda_heads"),
            ({"conv_taps": 0}, "conv_taps"),
            ({"mtp": True}, "kda|MTP"),
            ({"loop_steps": 2}, "kda|looped"),
            ({"sandwich": True}, "sandwich"),
            ({"embed_mult": 2.0}, "multipliers")):
        with pytest.raises(ValueError, match=match):
            dataclasses.replace(arch, **change)


# -- (b) the whole step against the reference -------------------------------

def _check_gradients(grads, want, norm_rel, diff_rel):
    assert set(grads) == set(want["grad_norm"])
    for name, g in grads.items():
        assert np.linalg.norm(g) == pytest.approx(
            want["grad_norm"][name], rel=norm_rel, abs=2e-7), name
    for name, g in want["grad_first"].items():
        scale = max(np.linalg.norm(g), 1e-7)
        assert np.linalg.norm(grads[name] - g) / scale < diff_rel, name


def test_first_three_steps_follow_the_reference_in_float32():
    """A linear, a grouped-query and a linear layer, this share's four of
    sixteen experts in each, rows of 44 positions in chunks of 16 (two whole
    chunks and a filled one): three steps' losses (2e-6), every leaf's first
    gradient (norms to 2e-4, the small leaves' differences to 5e-4: float32
    rounding through three layers, the chunked rule against the walk; the
    program's router adds 1e-6 to the selected scores' sum where the
    reference adds 1e-20) and every leaf's change after three steps; the
    linear layers' readings, which the walk gives position by position, to
    1e-5.  The decay's rates and bias, beta's projection, the taps and the
    head norm's gain are among the small leaves compared whole: they see the
    state and nothing else does."""
    cfg = _cfg()
    want = ref.first_steps(11, cfg, TRAFFIC, 1)
    losses, grads, deltas, counters = _program_first_steps(
        cfg, 11, jnp.float32)
    np.testing.assert_allclose(losses, want["loss"], rtol=2e-6)
    _check_gradients(grads, want, norm_rel=2e-4, diff_rel=5e-4)
    assert {"B0.kda_a_log", "B0.kda_dt_b", "B0.kda_b", "B2.kda_conv_k",
            "B2.kda_norm_g"} <= set(want["grad_first"])
    for name, dn in deltas.items():
        assert dn == pytest.approx(want["delta_norm"][name], rel=2e-4,
                                   abs=1e-8), name
    # the selection bias steers and is never updated
    assert all(deltas[f"B{li}.ebias"] == 0 for li in range(3))
    for got, walked in zip(counters, want["kda"]):
        assert got["kda_layers"] == 2.0
        for key, name in (("kda_decay", "decay_mean"),
                          ("kda_beta", "beta_mean"),
                          ("kda_state_rms", "final_state_rms")):
            assert got[key] / 2 == pytest.approx(walked[name], rel=1e-5)
        assert 0 < walked["decay_mean"] < 1 < walked["final_state_rms"] * 10
        assert 0 < got["pairs_held"] <= 3 * 88 * 3


def test_the_delta_rules_kernels_train_to_the_same_losses():
    """Two linear heads of 128 (one lane tile each) over rows of 100
    positions in chunks of 64 (one whole chunk and a filled one), in float32:
    with the step's kernels interpreted the rule runs as
    ``kda_delta_fwd`` / ``kda_delta_bwd`` (``kda_delta_kernel_share`` 1.0) and
    two steps' losses, every leaf's first gradient and every leaf's change
    are the ``jax.numpy`` form's to the tolerances this file holds that form
    to the reference with."""
    from test_lfm2_arch import _pallas_interpret

    cfg = _cfg(linear_attn_config={
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 2,
        "num_kv_heads": None})
    traffic = {"minibatch_size": 2, "seq_len": 100}
    arch = _arch(cfg, 64)
    runs = {}
    for interpret in (False, True):
        jax.clear_caches()
        with _pallas_interpret(interpret):
            assert tfm.step_choices(_mesh1(), arch, 2, 100, 2)[
                "kda_delta_kernel_share"] == float(interpret)
            runs[interpret] = _program_first_steps(
                cfg, 14, jnp.float32, steps=2, arch=arch, traffic=traffic)
    jax.clear_caches()
    (losses, grads, deltas, counters), plain = runs[True], runs[False]
    np.testing.assert_allclose(losses, plain[0], rtol=2e-6)
    for name, g in plain[1].items():
        scale = max(np.linalg.norm(g), 1e-7)
        assert np.linalg.norm(grads[name] - g) / scale < 5e-4, name
        assert deltas[name] == pytest.approx(plain[2][name], rel=2e-4,
                                             abs=1e-8), name
    for got, want in zip(counters, plain[3]):
        for key in ("kda_decay", "kda_beta", "kda_state_rms", "kda_layers"):
            assert got[key] == pytest.approx(want[key], rel=1e-5), key


@pytest.mark.parametrize("chunk,seq_len", [(64, 44), (8, 48)])
def test_the_chunk_is_a_tile(chunk, seq_len):
    """One chunk wider than the row, and six chunks a row: the first step's
    loss and gradients follow the reference to the same tolerances."""
    cfg, traffic = _cfg(), {"minibatch_size": 2, "seq_len": seq_len}
    want = ref.first_steps(12, cfg, traffic, 1, steps=1)
    losses, grads, _, _ = _program_first_steps(
        cfg, 12, jnp.float32, steps=1, arch=_arch(cfg, chunk),
        traffic=traffic)
    np.testing.assert_allclose(losses, want["loss"], rtol=2e-6)
    _check_gradients(grads, want, norm_rel=2e-4, diff_rel=5e-4)


def test_first_three_steps_follow_the_reference_in_bfloat16():
    """The same step with bfloat16 compute over the float32 masters (the
    log-decays, their running sums and factors, beta, the L2 norms, the
    triangular inverse, the carried state, the router's product, the norms
    and the loss stay float32): the loss to 1.5e-3, each leaf's gradient
    norm to 7 %, the small leaves' first gradients to 20 % of their norm,
    each leaf's change after three steps to 20 %: 1.6 to 3 times what
    bfloat16 operands read here on this seed (4.9e-4, 4.8 %, 12 %, 6.0 %),
    and the reference computed in the control precision, fp8, put in the
    program's place fails the loss's and the difference's (4.7e-3, 96 %).
    The seed is one on which bfloat16 flips few selections in three steps:
    of 88 tokens a step one flipped (token, expert) pair moves a router's
    gradient by tens of per cent (seeds 21-32 read 0.11 to 0.66 in a
    difference, most often a ``gate``, their controls 0.77 to 1.9), which is
    the flip and not the rounding; at the cell's 8,192 tokens the chip's
    comparison holds the routers' leaves too
    (``benchmark/reference/solar_open2.py::LIMITS``)."""
    cfg = _cfg()
    want = ref.first_steps(26, cfg, TRAFFIC, 1)
    losses, grads, deltas, _ = _program_first_steps(cfg, 26, jnp.bfloat16)
    np.testing.assert_allclose(losses, want["loss"], rtol=1.5e-3)
    _check_gradients(grads, want, norm_rel=7e-2, diff_rel=0.2)
    for name, dn in deltas.items():
        assert dn == pytest.approx(want["delta_norm"][name], rel=0.2,
                                   abs=1e-7), name
    control = ref.first_steps(26, cfg, TRAFFIC, 1, precision="fp8")
    assert max(abs(a / b - 1) for a, b in
               zip(control["loss"], want["loss"])) > 1.5e-3
    with pytest.raises(AssertionError):
        _check_gradients(control["grad_first"] | {
            k: g for k, g in grads.items()
            if k not in control["grad_first"]}, want, 7e-2, 0.2)


def test_a_lower_precision_fails_the_float32_tolerance():
    """Computed in bfloat16 the step leaves the float32 tolerances: the
    small leaves' first gradients (``diff_rel`` 5e-4) by two orders."""
    cfg = _cfg()
    want = ref.first_steps(11, cfg, TRAFFIC, 1, steps=1)
    _, grads, _, _ = _program_first_steps(cfg, 11, jnp.bfloat16, steps=1)
    with pytest.raises(AssertionError):
        _check_gradients(grads, want, norm_rel=2e-4, diff_rel=5e-4)


@pytest.fixture
def fresh_traces():
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("fault,leaf", [
    ("beta_under_one", "B0.kda_b"), ("no_gate", "B1.wo"),
    ("decay_a_head", "B2.kda_f2"), ("dropped_carry", "B0.kda_in"),
    ("l2_eps_of_one", "B2.kda_in")])
def test_another_model_fails_the_float32_tolerance(monkeypatch, fresh_traces,
                                                   fault, leaf):
    """Each of what this family is NOT, put in the program's place on the
    same seeded weights, leaves the float32 tolerances of the step against
    the reference by more than ten times, in the loss (2e-6) and in the
    gradient norm (2e-4) of a leaf it touches: ``beta`` without the factor 2
    (no negative eigenvalues), an ungated grouped-query output, a decay a
    HEAD (the channels' mean) where it is a key channel, a carry dropped at
    every chunk's edge, q and k normalised with an eps of one (shorter than
    unit keys)."""
    cfg = _cfg()
    arch, params = _arch(cfg), ref.init_params(11, cfg)
    want = ref.first_steps(11, cfg, TRAFFIC, 1, steps=1)
    if fault == "beta_under_one":
        arch = dataclasses.replace(arch, kda_neg_eigval=False)
    elif fault == "no_gate":
        arch = dataclasses.replace(arch, attn_gate=False)
        params = jax.tree.map(lambda a: a, params)
        del params["blocks"][1]["wg"]
    elif fault == "decay_a_head":
        rule = kda.delta
        monkeypatch.setattr(kda, "delta", lambda q, k, v, g, beta, chunk: rule(
            q, k, v, jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape),
            beta, chunk))
    elif fault == "dropped_carry":
        states = kda._chunk_states
        monkeypatch.setattr(kda, "_chunk_states", lambda *a: tuple(
            jnp.zeros_like(s) if i == 2 else s     # the opening states
            for i, s in enumerate(states(*a))))
    else:
        normed = kda.l2_normed
        monkeypatch.setattr(kda, "l2_normed", lambda x: normed(x, 1.0))
    step, _ = tfm.make_train_step(_mesh1(), arch, lr=cfg["hyper"]["lr"],
                                  loss_chunks=2, compute_dtype=jnp.float32)
    rows = ref.make_tokens(11, cfg, TRAFFIC["seq_len"], 0, 2)
    new, loss = step(params, jnp.asarray(rows[:, :-1]),
                     jnp.asarray(rows[:, 1:]))
    li, name = leaf.split(".")
    grad = np.asarray(params["blocks"][int(li[1:])][name] -
                      new["blocks"][int(li[1:])][name]) / cfg["hyper"]["lr"]
    assert abs(float(loss) / want["loss"][0] - 1) > 10 * 2e-6
    assert abs(np.linalg.norm(grad) / want["grad_norm"][leaf] - 1) > \
        10 * 2e-4


# -- (c) the share test --------------------------------------------------

def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """The guide's share test: a layer's experts cut over 8 chips, each
    holding 2 of its 16.  The routed parts that the 8 shares of the PROGRAM's
    pairs stage give (``moe.moe_routed_ffn`` told ``first`` and handed its
    two experts' weights), with the shared expert, which every chip computes
    alike, counted once, add up to what the REFERENCE's uncut layer gives
    (all 16 experts held): to 2e-5 of the layer's output (float32 sums in
    another order; a weight normalised over a share's own experts would be
    off by the share's part of the sum, tens of per cent)."""
    from znicz_tpu.parallel import moe

    cfg = _cfg(n_routed_experts=16, experts_held={"first": 0, "count": 16})
    full = ref.init_params(3, cfg)["blocks"][0]
    dm = ref.dims(cfg)
    r = np.random.default_rng(4)
    m = jnp.asarray(r.normal(size=(64, 32)).astype(np.float32))
    same = lambda v: v                                    # noqa: E731
    with jax.default_matmul_precision("highest"):
        want = ref._experts(full, m, dm, same, same)
        shared = blocks._glu(m, full["sw1"], full["sw3"], full["sw2"])
        total = shared
        for chip in range(8):
            held = slice(2 * chip, 2 * chip + 2)
            part, stats = moe.moe_routed_ffn(
                m, full["gate"], full["ebias"], full["ew1"][held],
                full["ew3"][held], full["ew2"][held], first=2 * chip,
                top_k=3, score="sigmoid", norm_topk=True, scale=1.0)
            total = total + part
            assert float(stats["pairs_held"]) > 0
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(total - want).max()) < 2e-5 * scale
    # every pair went to exactly one share
    assert float(jnp.abs(want - shared).max()) > 0.1 * scale


# -- (d) refusals by mechanism, and the step unit ---------------------------

def test_a_sharded_mesh_refuses_the_layer_by_name(cpu_devices):
    for axes in ({"data": 1, "seq": 1, "model": 2},
                 {"data": 1, "seq": 2, "model": 1}):
        mesh = make_mesh(axes, jax.devices()[:2])
        with pytest.raises(ValueError, match=MECHANISM):
            tfm.make_train_step(mesh, _arch(_cfg()))


def test_serving_refuses_the_layer_by_name():
    from znicz_tpu.serve.kvcache import KVDecoder

    params = jax.tree.map(np.asarray, ref.init_params(1, _cfg()))
    with pytest.raises(NotImplementedError, match=MECHANISM):
        KVDecoder(params, heads=4)


def test_the_unit_publishes_the_delta_rule_counters(tmp_path, caplog):
    """``TransformerLMStep(arch=...)`` under the benchmark's control graph
    on the reference's seeded weights and rows: an epoch of three steps
    publishes the routed layers' counters and the linear layers'
    (``kda_counters`` and their four gauges, which follow the reference's
    walk), says once which form the convolution got, and refuses to export,
    the layer by name."""
    import logging

    from builders import lm_train_keys
    from znicz_tpu.core.backends import XLADevice
    from znicz_tpu.observe import registry

    cfg = {**_cfg(), "builders": {"lm_train_keys": {
        "model_keys": [k for k in TINY if k != "hyper"], "loss_chunks": 2}}}
    want = ref.first_steps(13, cfg, TRAFFIC, 1, steps=3)
    rows = ref.make_tokens(13, cfg, TRAFFIC["seq_len"], 0, 6)
    w = lm_train_keys.build_workflow(rows, cfg, TRAFFIC)
    w.decision.max_epochs = 1
    w.step._params = ref.init_params(13, cfg)
    from znicz_tpu.parallel import ssm
    ssm._report_refusal.cache_clear()
    with caplog.at_level(logging.INFO, logger="znicz_tpu.transformer"):
        w.initialize(device=XLADevice())
        w.run()
    step = w.step
    got = step.kda_counters
    assert got["layers"] == 2.0
    for key in ("decay_mean", "beta_mean", "final_state_rms"):
        assert got[key] == pytest.approx(
            np.mean([s[key] for s in want["kda"]]), rel=1e-4), key
    assert 0 < got["decay_mean"] < 1 and 0 < got["beta_mean"] < 2
    for key, value in got.items():
        fam = registry.REGISTRY.get(f"znicz_lm_kda_{key}")
        assert fam is not None and fam.labels(unit=step.name).get() == value
    assert step.kda_conv_kernel_share == 0.0 and step.ssm_counters == {}
    # ... nor the rule's: the unit's mirror and the process registry
    assert step.kda_delta_kernel_share == 0.0
    fam = registry.REGISTRY.get("znicz_lm_kda_delta_kernel_share")
    assert fam is not None and fam.labels(unit=step.name).get() == 0.0
    said = [r.getMessage() for r in caplog.records
            if "convolution kernels refused" in r.getMessage()]
    assert len(said) == 1 and "width=96" in said[0] and \
        "jax.numpy" in said[0]
    assert 0 < step.moe_counters["pairs_held_per_step"] <= 3 * 88 * 3
    assert w.decision.metrics_history[-1]["metric_train"] == pytest.approx(
        np.mean(want["loss"]), rel=2e-4)
    with pytest.raises(ValueError, match=MECHANISM):
        step.export_lm(str(tmp_path / "pkg.npz"))


def test_the_gpt_block_and_its_arch_are_what_they_were():
    """The defaults of the new fields describe no linear-attention layer."""
    arch = Arch(d=8, heads=2, kv_heads=2, head_dim=4, ff=16, vocab=11,
                mixers=("attention",) * 2, ffns=("mlp",) * 2)
    assert (arch.kda_heads, arch.kda_head_dim, arch.kda_rank,
            arch.kda_neg_eigval, arch.kda_chunk) == (0, 0, 0, False, 64)
    assert not _recomputes_by_policy(arch) and not arch.mechanisms()
