"""What the ``nemotron_h`` family brought to the language-model path (layers
of ONE sub-layer behind one norm, Mamba-2 with groups, plain squared-ReLU
experts of two weights beside a shared expert of the same form, position-free
grouped-query attention), at tiny widths with the published ratios (two
groups, an inner width that is not ``expand x hidden_size``, experts whose
width 128 does not divide) on the CPU on seeded random weights against the
benchmark's plain reference (``benchmark/reference/nemotron_h.py``, whose
recurrence walks the positions one by one with its groups and whose experts
are a masked sum): the reader's fields and refusals; the scan with groups
against the walked recurrence; the whole step against the reference in
float32 and bfloat16; a lower precision, another expert form, one group for
two and a rotary embedding each failing a tolerance; **the share test**; the
plain pairs stage's four gradient products; the step unit's counters."""

import dataclasses
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

from reference import nemotron_h as ref                    # noqa: E402

from znicz_tpu.parallel import blocks, moe, ssm           # noqa: E402
from znicz_tpu.parallel import transformer as tfm          # noqa: E402
from znicz_tpu.parallel.arch import (                      # noqa: E402
    _FAMILIES, mechanisms_of_params)
from znicz_tpu.parallel.mesh import make_mesh              # noqa: E402
from znicz_tpu.parallel.params import (                    # noqa: E402
    init_params, ssm_in_width)
from znicz_tpu.parallel.plan import _KEPT_IF_ROOM          # noqa: E402

#: the published ratios at toy widths: 8 state-space heads of 6 (an inner
#: width of 48 where ``expand x hidden_size`` is 64) in 2 groups, 4 query on
#: 2 key/value heads, 16 experts 24 wide (128 does not divide it) of which
#: this share holds 4, top-3, a shared expert twice as wide
TINY = {
    "model_type": "nemotron_h", "hidden_size": 32, "intermediate_size": 24,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "attention_bias": False, "mlp_bias": False, "mamba_proj_bias": False,
    "use_bias": False, "use_conv_bias": True, "mlp_hidden_act": "relu2",
    "mamba_hidden_act": "silu", "layer_norm_epsilon": 1e-5,
    "num_hidden_layers": 5, "hybrid_override_pattern": "MEM*E",
    "mamba_num_heads": 8, "mamba_head_dim": 6, "ssm_state_size": 16,
    "n_groups": 2, "conv_kernel": 4, "chunk_size": 8, "expand": 2,
    "n_routed_experts": 4, "router_width": 16,
    "experts_held": {"first": 4, "count": 4}, "num_experts_per_tok": 3,
    "moe_intermediate_size": 24, "moe_shared_expert_intermediate_size": 48,
    "n_shared_experts": 1, "n_group": 1, "topk_group": 1,
    "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "rope_theta": 10000, "partial_rotary_factor": 1, "sliding_window": None,
    "tie_word_embeddings": False, "vocab_size": 53,
    "hyper": {"lr": 0.05},
}
TRAFFIC = {"minibatch_size": 2, "seq_len": 32}
MECHANISMS = ("state-space groups", "layers of one sub-layer",
              "squared-ReLU experts")


def _cfg(**over):
    return {**TINY, **over}


def _arch(cfg):
    return tfm.arch_from_config({k: v for k, v in cfg.items()
                                 if k != "hyper"})


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


def _program_first_steps(cfg, seed, dtype, steps=3, arch=None, params=None):
    """What the benchmark's builder reads off the timed step: losses, each
    leaf's first gradient as plain SGD applied it, each leaf's change, each
    step's counters."""
    arch, lr = arch or _arch(cfg), cfg["hyper"]["lr"]
    step, _ = tfm.make_train_step(_mesh1(), arch, lr=lr, stats=True,
                                  loss_chunks=2, compute_dtype=dtype)
    p0 = ref.init_params(seed, cfg) if params is None else params
    b, t = TRAFFIC["minibatch_size"], TRAFFIC["seq_len"]
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
    assert "nemotron_h" in _FAMILIES
    assert arch.mixers == ("mamba", "none", "mamba", "attention", "none")
    assert arch.ffns == ("none", "moe_routed", "none", "none", "moe_routed")
    assert arch.kinds(1) == ("none", "moe_routed")
    assert arch.routed_layers() == 2
    assert (arch.ssm_heads, arch.ssm_head_dim, arch.ssm_state, arch.ssm_groups,
            arch.conv_taps, arch.ssm_chunk) == (8, 6, 16, 2, 4, 8)
    assert (arch.n_experts, arch.experts_first, arch.experts_held, arch.top_k,
            arch.moe_ff, arch.shared_ff) == (16, 4, 4, 3, 24, 48)
    assert (arch.score, arch.expert_bias, arch.norm_topk, arch.routed_scale,
            arch.expert_form) == ("sigmoid", True, True, 2.5, "relu2")
    assert (arch.heads, arch.kv_heads, arch.head_dim) == (4, 2, 8)
    assert arch.rope_theta is None and not arch.tied and arch.final_norm
    assert arch.norm == "rms" and arch.eps == 1e-5
    names = arch.mechanisms()
    for word in MECHANISMS + ("state-space layer (Mamba-2)", "shared expert",
                              "routed experts (moe_routed_ffn)",
                              "grouped-query attention"):
        assert any(word in n for n in names), (word, names)
    assert "rotary embedding" not in names and "SwiGLU" not in names
    shapes = tfm.param_shapes(arch)
    # the inner width is heads x head_dim (48), whatever ``expand`` says
    assert shapes["blocks"][0] == {
        "ln1_g": (32,), "ssm_in": (32, 2 * 48 + 2 * 2 * 16 + 8),
        "ssm_conv_k": (4, 48 + 64), "ssm_conv_b": (48 + 64,),
        "ssm_dt_b": (8,), "ssm_a_log": (8,), "ssm_d": (8,),
        "ssm_g": (2, 24), "ssm_out": (48, 32)}
    assert ssm_in_width(8, 6, 16, 2) == 168
    assert shapes["blocks"][1] == {
        "ln2_g": (32,), "gate": (32, 16), "ew1": (4, 32, 24),
        "ew2": (4, 24, 32), "ebias": (16,), "sw1": (32, 48), "sw2": (48, 32)}
    assert set(shapes["blocks"][3]) == {"ln1_g", "wq", "wk", "wv", "wo"}
    seeded = ref.init_params(1, cfg)
    assert jax.tree.map(np.shape, seeded) == jax.tree.map(
        tuple, shapes, is_leaf=lambda x: isinstance(x, tuple))
    found = mechanisms_of_params(seeded)
    for word in MECHANISMS:
        assert any(word in n for n in found), (word, found)
    # the framework's own initialiser follows the same table
    drawn = init_params(np.random.default_rng(0), arch)
    assert jax.tree.map(np.shape, drawn) == jax.tree.map(np.shape, seeded)


def test_the_published_configuration_counts_its_parameters():
    """``benchmark/configs/nemotron_3_nano_30b_a3b.json`` through the
    reader: nine layers ``MEMEM*EME`` at the published widths, 986,254,848
    parameters on the chip (ISSUE 45's table, part by part)."""
    import json
    import math

    with open(os.path.join(BENCH, "configs",
                           "nemotron_3_nano_30b_a3b.json")) as f:
        cfg = json.load(f)
    arch = tfm.arch_from_config(
        {k: cfg[k] for k in cfg["builders"]["lm_train_keys"]["model_keys"]})
    assert cfg["reduced"] == ["num_hidden_layers", "hybrid_override_pattern",
                              "n_routed_experts", "vocab_size"]
    shapes = tfm.param_shapes(arch)
    count = [sum(math.prod(s) for s in blk.values())
             for blk in shapes["blocks"]]
    kinds = cfg["hybrid_override_pattern"]
    assert kinds == "MEMEM*EME"
    assert {k: c for k, c in zip(kinds, count)} == {
        "M": 38744896, "E": 179948288, "*": 23399040}
    assert sum(count) + 2 * 16384 * 2688 + 2688 == 986254848
    assert (arch.ssm_groups, arch.moe_ff, arch.shared_ff, arch.top_k,
            arch.n_experts, arch.experts_held) == (8, 1856, 3712, 6, 128, 16)


@pytest.mark.parametrize("change,match", [
    ({"hybrid_override_pattern": "ME-*E"}, "dense MLP"),
    ({"hybrid_override_pattern": "MEX*E"}, "hybrid_override_pattern"),
    ({"num_hidden_layers": 6}, "num_hidden_layers"),
    ({"n_group": 2}, "n_group"), ({"topk_group": 2}, "topk_group"),
    ({"attention_bias": True}, "attention_bias"),
    ({"mlp_bias": True}, "mlp_bias"),
    ({"mamba_proj_bias": True}, "mamba_proj_bias"),
    ({"use_bias": True}, "use_bias"),
    ({"use_conv_bias": False}, "use_conv_bias"),
    ({"mlp_hidden_act": "silu"}, "mlp_hidden_act"),
    ({"mamba_hidden_act": "gelu"}, "mamba_hidden_act"),
    ({"sliding_window": 4096}, "sliding_window"),
    ({"n_groups": 3}, "ssm_groups"),
    ({"experts_held": {"first": 14, "count": 4}}, "experts_held"),
    ({"model_type": "nemotron"}, "model_type"),
])
def test_keys_the_stack_cannot_honour_are_refused_by_name(change, match):
    with pytest.raises(ValueError, match=match):
        _arch(_cfg(**change))


def test_the_arch_refuses_what_a_one_sub_layer_stack_is_not_written_for():
    arch = _arch(_cfg())
    with pytest.raises(ValueError, match="no sub-layer"):
        dataclasses.replace(arch, mixers=("none",) * 5)
    with pytest.raises(ValueError, match="one sub-layer"):
        dataclasses.replace(arch, mtp=True)
    with pytest.raises(ValueError, match="expert_form"):
        dataclasses.replace(arch, expert_form="geglu")


# -- (b) the scan with groups against the walked recurrence ------------------

def _scan_operands(seed, t, groups, heads=4, pd=6, n=16):
    r = np.random.default_rng(seed)
    x = r.normal(size=(2, t, heads, pd)).astype(np.float32)
    dt = np.exp(r.uniform(np.log(1e-3), np.log(0.2), (2, t, heads))
                ).astype(np.float32)
    a = -r.uniform(1.0, 16.0, heads).astype(np.float32)
    bm, cm = (r.normal(size=(2, t, groups, n)).astype(np.float32)
              for _ in range(2))
    d = r.normal(size=heads).astype(np.float32)
    return tuple(jnp.asarray(v) for v in (x, dt, a, bm, cm, d))


def _literal(x, dt, a, bm, cm, d):
    ys, lasts = zip(*(ref.recurrence(x[r], dt[r], a, bm[r], cm[r], d)
                      for r in range(x.shape[0])))
    return jnp.stack(ys), jnp.stack(lasts)


@pytest.mark.parametrize("t,chunk,groups", [
    (48, 8, 2),           # six chunks a row, two heads a group
    (48, 48, 4),          # the chunk is the whole row, a head a group
    (44, 16, 2),          # the last chunk is filled (44 = 2 * 16 + 12)
    (48, 256, 2),         # the tile is wider than the row
])
def test_the_grouped_scan_is_the_walked_recurrence_in_values_and_gradients(
        t, chunk, groups):
    """``ssm.ssd`` with ``B`` and ``C`` a group against ``lax.scan`` over
    the positions, head ``h`` reading group ``h // (H / G)``: ``y``, the
    state behind the last position, and the gradient of a random functional
    of ``y`` in every operand.  Float32 on both sides: 5e-5 is rounding (a
    decay is the exp of a difference of two running sums); a head reading
    another group's ``B`` is of order one (the next test)."""
    ops = _scan_operands(5, t, groups)
    w = jnp.asarray(np.random.default_rng(6).normal(
        size=ops[0].shape).astype(np.float32))
    with jax.default_matmul_precision("highest"):
        y, last = ssm.ssd(*ops, chunk)
        want_y, want_last = _literal(*ops)
        got = jax.grad(lambda *o: (ssm.ssd(*o, chunk)[0] * w).sum(),
                       argnums=range(6))(*ops)
        want = jax.grad(lambda *o: (_literal(*o)[0] * w).sum(),
                        argnums=range(6))(*ops)
    scale = float(jnp.abs(want_y).max())
    assert float(jnp.abs(y - want_y).max()) < 5e-5 * scale
    np.testing.assert_allclose(last, want_last, rtol=2e-5, atol=2e-6)
    for name, g, g_want in zip("x dt A B C D".split(), got, want):
        err = float(jnp.linalg.norm(g - g_want) / jnp.linalg.norm(g_want))
        assert err < 5e-5, (name, err)


def test_one_group_is_the_program_it_was_and_groups_are_not_one_group():
    """With one group ``B`` and ``C`` carry no group axis: the scan lowers
    to the same text as the parent's (no reshape of a head axis appears),
    and equals the grouped form fed one group's ``B`` and ``C`` for every
    group; two different groups read as one fail the scan's tolerance by
    orders."""
    x, dt, a, bm, cm, d = _scan_operands(7, 32, 2)
    with jax.default_matmul_precision("highest"):
        one, _ = ssm.ssd(x, dt, a, bm[:, :, 0], cm[:, :, 0], d, 8)
        same, _ = ssm.ssd(x, dt, a, bm[:, :, :1].repeat(2, 2),
                          cm[:, :, :1].repeat(2, 2), d, 8)
        two, _ = ssm.ssd(x, dt, a, bm, cm, d, 8)
    np.testing.assert_allclose(one, same, rtol=1e-5, atol=1e-5)
    assert float(jnp.abs(two - one).max() / jnp.abs(two).max()) > 0.1
    text = jax.jit(lambda *o: ssm.ssd(*o, 8)).lower(
        x, dt, a, bm[:, :, 0], cm[:, :, 0], d).as_text()
    grouped = jax.jit(lambda *o: ssm.ssd(*o, 8)).lower(
        x, dt, a, bm, cm, d).as_text()
    # a head axis is cut into (groups, heads a group) in the grouped text
    # alone: (b, c, q, G, R, P)
    assert "4x8x2x2x6x" in grouped and "4x8x2x2x6x" not in text
    assert text.count("reshape") < grouped.count("reshape")


# -- (c) the whole step against the reference -------------------------------

def _check_gradients(grads, want, norm_rel, diff_rel):
    assert set(grads) == set(want["grad_norm"])
    for name, g in grads.items():
        assert np.linalg.norm(g) == pytest.approx(
            want["grad_norm"][name], rel=norm_rel, abs=2e-7), name
    for name, g in want["grad_first"].items():
        scale = max(np.linalg.norm(g), 1e-7)
        assert np.linalg.norm(grads[name] - g) / scale < diff_rel, name


def test_first_three_steps_follow_the_reference_in_float32():
    """``M E M * E``, two groups, four chunks a row, this share's four of
    sixteen experts: three steps' losses (2e-6), every leaf's first gradient
    (norms to 2e-4, the small leaves' differences to 5e-4: float32 rounding
    through five layers; the program's router adds 1e-6 to the selected
    scores' sum where the reference adds the family's 1e-20, 3e-7 of a
    weight) and every leaf's change after three steps.  A wrong group, a
    gated expert or a weight normalised over the held experts alone moves
    them by percents (the tests below)."""
    cfg = _cfg()
    want = ref.first_steps(11, cfg, TRAFFIC, 1)
    losses, grads, deltas, counters = _program_first_steps(
        cfg, 11, jnp.float32)
    np.testing.assert_allclose(losses, want["loss"], rtol=2e-6)
    _check_gradients(grads, want, norm_rel=2e-4, diff_rel=5e-4)
    for name, dn in deltas.items():
        assert dn == pytest.approx(want["delta_norm"][name], rel=2e-4,
                                   abs=1e-8), name
    # the selection bias steers and is never updated
    assert deltas["B1.ebias"] == 0 and deltas["B4.ebias"] == 0
    # one pass carries both kinds' counters, each over its own layers: the
    # state-space sums beside their count of M layers (2 of 5) ...
    for got, exp in zip(counters, want["ssm"]):
        assert got["ssm_layers"] == 2.0
        assert got["ssm_decay"] / 2 == pytest.approx(exp["decay_mean"],
                                                     rel=1e-5)
        assert got["ssm_state_rms"] / 2 == pytest.approx(
            exp["final_state_rms"], rel=1e-4)
        # ... and the routed layers' means over the E layers (2 of 5)
        assert 1.0 <= got["load_max_over_mean"] < 4.0
        assert 0.25 < got["act_zero"] < 0.75
        assert 0 < got["pairs_held"] <= 2 * 64 * 3
        assert got["compact"] in (0.0, 0.5, 1.0)


def test_first_three_steps_follow_the_reference_in_bfloat16():
    """The same step with bfloat16 compute over the float32 masters (the
    router's product, decays, running sums, the carried state and the norms
    stay float32): the loss to 1.5e-3, each leaf's gradient norm to 7 %, the
    small leaves' first gradients to 12 % of their norm, each leaf's change
    after three steps to 20 %: about three times what bfloat16 operands (8
    mantissa bits, 0.4 % a rounding) read through five layers at these
    widths on this seed (4.0e-4, 2.6 %, 4.1 %, 9.7 %: the worst a
    state-space layer's eight step-size biases), and the reference computed
    in the control precision, fp8, put in the program's place fails every
    one of them (4.0e-3, 14 %, 37 %, 47 %).  The seed is one on which
    bfloat16 flips few selections in three steps: of 64 tokens a step one
    flipped (token, expert) pair moves a router's or a small state-space
    leaf's gradient by tens of per cent (seeds 14, 17, 19 and 22 read 0.1 to
    0.28 in a norm or a difference), which is the flip and not the rounding;
    at the cell's 16,384 tokens the chip's comparison holds the routers'
    leaves too (``benchmark/reference/nemotron_h.py::LIMITS``)."""
    cfg = _cfg()
    want = ref.first_steps(21, cfg, TRAFFIC, 1)
    losses, grads, deltas, _ = _program_first_steps(cfg, 21, jnp.bfloat16)
    np.testing.assert_allclose(losses, want["loss"], rtol=1.5e-3)
    _check_gradients(grads, want, norm_rel=7e-2, diff_rel=0.12)
    for name, dn in deltas.items():
        assert dn == pytest.approx(want["delta_norm"][name], rel=0.2,
                                   abs=1e-7), name
    control = ref.first_steps(21, cfg, TRAFFIC, 1, precision="fp8")
    assert max(abs(a / b - 1) for a, b in
               zip(control["loss"], want["loss"])) > 1.5e-3
    with pytest.raises(AssertionError):
        _check_gradients(control["grad_first"] | {
            k: g for k, g in grads.items()
            if k not in control["grad_first"]}, want, 7e-2, 0.12)


def test_a_lower_precision_fails_the_float32_tolerance():
    """Computed in bfloat16 the step leaves the float32 tolerances: the
    small leaves' first gradients (``diff_rel`` 5e-4) by two orders."""
    cfg = _cfg()
    want = ref.first_steps(11, cfg, TRAFFIC, 1, steps=1)
    _, grads, _, _ = _program_first_steps(cfg, 11, jnp.bfloat16, steps=1)
    with pytest.raises(AssertionError):
        _check_gradients(grads, want, norm_rel=2e-4, diff_rel=5e-4)


@pytest.mark.parametrize("fault,leaf", [
    ("silu_expert", "B1.ew1"), ("gated_expert", "B1.ew2"),
    ("one_group", "B0.ssm_in"), ("rotary", "B3.wk")])
def test_another_model_fails_the_float32_tolerance(monkeypatch, fault, leaf):
    """Each of what this family is NOT, put in the program's place on the
    same seeded weights, leaves the float32 tolerances of the step against
    the reference by more than ten times, in the loss (2e-6) and in the
    gradient norm (2e-4) of the leaf it touches first: a silu in the place
    of the squared ReLU (``ew1``), a gated unit whose third weight is all
    ones (``ew2``), one group for two, every head reading group 0's ``B``
    and ``C`` (``ssm_in``), a rotary embedding on the attention layer
    (``wk``)."""
    cfg = _cfg()
    arch, params = _arch(cfg), ref.init_params(11, cfg)
    want = ref.first_steps(11, cfg, TRAFFIC, 1, steps=1)
    if fault == "silu_expert":
        monkeypatch.setattr(blocks, "relu2", jax.nn.silu)
    elif fault == "gated_expert":
        arch = dataclasses.replace(arch, expert_form="glu")
        params = jax.tree.map(lambda a: a, params)
        for li in (1, 4):
            blk = params["blocks"][li]
            blk["ew3"], blk["sw3"] = (jnp.ones_like(blk[k])
                                      for k in ("ew1", "sw1"))
    elif fault == "one_group":
        ssd = ssm.ssd

        def group0(x, dt, a, bm, cm, skip, chunk):
            bm, cm = (jnp.repeat(v[:, :, :1], v.shape[2], axis=2)
                      for v in (bm, cm))
            return ssd(x, dt, a, bm, cm, skip, chunk)

        monkeypatch.setattr(ssm, "ssd", group0)
    else:
        arch = dataclasses.replace(arch, rope_theta=1e4)
    jax.clear_caches()          # a layer's trace is kept by function
    try:
        losses, grads, _, _ = _program_first_steps(
            cfg, 11, jnp.float32, steps=1, arch=arch, params=params)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert abs(losses[0] / want["loss"][0] - 1) > 10 * 2e-6
    assert abs(np.linalg.norm(grads[leaf]) / want["grad_norm"][leaf]
               - 1) > 10 * 2e-4


# -- (d) the share test --------------------------------------------------

def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """The guide's share test: an ``E`` layer cut over 8 chips, each holding
    2 of its 16 experts.  The routed parts that the 8 shares of the PROGRAM's
    layer give (``blocks._block`` told ``experts_first`` and handed its two
    experts' weights), with the shared expert, which every chip computes
    alike, counted once, add up to what the REFERENCE's uncut layer gives
    (all 16 experts held): to 2e-5 of the layer's output (float32 sums in
    another order; a weight normalised over a share's own experts would be
    off by the share's part of the sum, tens of per cent)."""
    cfg = _cfg(n_routed_experts=16, experts_held={"first": 0, "count": 16})
    full = ref.init_params(3, cfg)["blocks"][1]
    dm = ref.dims(cfg)
    r = np.random.default_rng(4)
    x = jnp.asarray(r.normal(size=(2, 32, 32)).astype(np.float32))
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref._layer(full, x[b], dm, "experts",
                                     lambda v: v, lambda v: v)[0]
                          for b in range(2)])
        shared = blocks._relu2_mlp(
            blocks._rms_norm(x, full["ln2_g"], 1e-5), full["sw1"],
            full["sw2"])
        total = x + shared
        for chip in range(8):
            share = _cfg(n_routed_experts=2,
                         experts_held={"first": 2 * chip, "count": 2})
            arch = _arch(share)
            p = {**full, "ew1": full["ew1"][2 * chip:2 * chip + 2],
                 "ew2": full["ew2"][2 * chip:2 * chip + 2]}
            out, _, stats = blocks._block(x, p, arch,
                                          tfm._run_of(_mesh1(), arch), 1)
            total = total + (out - x - shared)
            assert float(stats["pairs_held"]) > 0
    scale = float(jnp.abs(want - x).max())
    assert float(jnp.abs(total - want).max()) < 2e-5 * scale
    # every pair went to exactly one share
    assert float(jnp.abs(want - x - shared).max()) > 0.1 * scale


# -- (e) the plain pairs stage ---------------------------------------------

def _count(jaxpr, names) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name in names
        for value in eqn.params.values():
            for one in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(one, "jaxpr", one)
                if hasattr(inner, "eqns"):
                    n += _count(inner, names)
    return n


@pytest.mark.parametrize("form,forward,backward", [("relu2", 2, 4),
                                                   ("glu", 3, 6)])
def test_the_pairs_stage_makes_its_forms_products_and_none_twice(
        form, forward, backward):
    """Grouped products in the traced gradient of a routed layer whose pairs
    take the compact buffer: two forward and four backward of the plain
    form, three and six of the gated unit, once in each branch of the
    choice of buffer (the full branch takes its forward again in the
    backward pass: ``forward`` more)."""
    r = np.random.default_rng(2)
    tokens, d, f, held, experts, k = 512, 16, 24, 2, 16, 2
    x = jnp.asarray(r.normal(size=(tokens, d)).astype(np.float32))
    gate = jnp.asarray(r.normal(size=(d, experts)).astype(np.float32))
    w1, w3 = (jnp.asarray(r.normal(size=(held, d, f)).astype(np.float32))
              for _ in range(2))
    w2 = jnp.asarray(r.normal(size=(held, f, d)).astype(np.float32))
    assert moe.compact_rows(tokens * k, held, experts) < tokens * k

    def loss(x, w1, w3, w2):
        y, _ = moe.moe_routed_ffn(
            x, gate, None, w1, w3 if form == "glu" else None, w2, first=0,
            top_k=k, act=jax.nn.silu if form == "glu" else moe.relu2)
        return (y * y).sum()

    names = ("ragged_dot", "ragged_dot_general")
    assert jax.default_backend() == "cpu"       # no kernel: lax.ragged_dot
    fwd = jax.make_jaxpr(loss)(x, w1, w3, w2)
    assert _count(fwd.jaxpr, names) == 2 * forward
    both = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 3)))(x, w1, w3, w2)
    assert _count(both.jaxpr, names) == 2 * forward + backward + (
        forward + backward)


def test_the_plain_pairs_stage_is_the_masked_sum_in_values_and_gradients():
    """``moe_routed_ffn`` with two weights and the squared ReLU against the
    reference's masked dense sum over the held experts, in float32: the
    output, the gradient to the tokens and to both weights (5e-5: sums in
    another order), in the compact and in the full buffer, and the share
    of hidden entries the activation zeroes counted over the held pairs."""
    r = np.random.default_rng(9)
    d, f, held, experts, k = 16, 24, 4, 16, 3
    gate = jnp.asarray(r.normal(size=(d, experts)).astype(np.float32))
    w1 = jnp.asarray(r.normal(size=(held, d, f)).astype(np.float32)) / 4
    w2 = jnp.asarray(r.normal(size=(held, f, d)).astype(np.float32)) / 5
    dm = {"top_k": k, "norm_topk": True, "scale": 2.5, "first": 4}
    p = {"gate": gate, "ebias": jnp.zeros(experts), "ew1": w1, "ew2": w2}

    def plain(x, w1, w2):
        y, stats = moe.moe_routed_ffn(x, gate, None, w1, None, w2, first=4,
                                      top_k=k, scale=2.5, act=moe.relu2)
        return (y * y).sum(), (y, stats)

    def masked(x, w1, w2):
        y = ref._experts({**p, "ew1": w1, "ew2": w2}, x, dm,
                         lambda v: v, lambda v: v)
        return (y * y).sum(), y

    for tokens in (256, 16):       # the compact buffer; one buffer only
        x = jnp.asarray(r.normal(size=(tokens, d)).astype(np.float32))
        with jax.default_matmul_precision("highest"):
            (_, (y, stats)), got = jax.value_and_grad(
                plain, argnums=(0, 1, 2), has_aux=True)(x, w1, w2)
            (_, want_y), want = jax.value_and_grad(
                masked, argnums=(0, 1, 2), has_aux=True)(x, w1, w2)
        np.testing.assert_allclose(y, want_y, rtol=2e-5, atol=2e-5)
        for name, a, b in zip(("x", "w1", "w2"), got, want):
            err = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
            assert err < 5e-5, (tokens, name, err)
        assert float(stats["compact"]) == float(tokens == 256)
        # counted by hand over the pairs routed to the held experts
        s = jax.nn.sigmoid(x @ gate)
        _, choice = jax.lax.top_k(s, k)
        zeros = pairs = 0
        for e in range(held):
            rows = x[(choice == 4 + e).any(-1)]
            zeros += int(((rows @ w1[e]) <= 0).sum())
            pairs += rows.shape[0]
        assert float(stats["pairs_held"]) == pairs
        assert float(stats["act_zero"]) == pytest.approx(
            zeros / (pairs * f), rel=1e-5)


def test_a_gated_layer_reports_no_zeroed_share():
    """The gated unit's program is the one it was: no ``act_zero`` among its
    counters (the unit publishes 0 for it)."""
    r = np.random.default_rng(1)
    x = jnp.asarray(r.normal(size=(32, 16)).astype(np.float32))
    gate = jnp.asarray(r.normal(size=(16, 8)).astype(np.float32))
    w1 = jnp.asarray(r.normal(size=(4, 16, 24)).astype(np.float32))
    w2 = jnp.asarray(r.normal(size=(4, 24, 16)).astype(np.float32))
    _, stats = moe.moe_routed_ffn(x, gate, None, w1, w1, w2, first=0,
                                  top_k=2)
    assert set(stats) == {"pairs_held", "load_max_over_mean", "compact",
                          "tile_fill"}


# -- (f) what a checkpointed one-sub-layer layer keeps --------------------

def test_a_checkpointed_expert_layer_keeps_its_route_and_up_products():
    """An ``E`` layer of a stack with state-space layers is checkpointed by
    ``plan._loop_saves``: its residuals hold the router's choice
    (``moe_route``) and the experts' up-projection's result (``moe_up``),
    nothing ``shared_ff`` wide; with the optional kind kept, the shared
    expert's wide product too."""
    cfg = _cfg()
    arch = _arch(cfg)
    blk = tfm._block_fn(arch)
    assert blk is not tfm._block
    run = tfm._run_of(_mesh1(), arch)
    p = jax.tree.map(jnp.asarray, ref.init_params(1, cfg)["blocks"][1])
    x = jnp.ones((2, 128, 32), jnp.float32) * jnp.arange(128)[None, :, None]
    rows = moe.compact_rows(2 * 128 * 3, 4, 16)

    def residuals(fn):
        _, vjp = jax.vjp(lambda p_, x_: fn(x_, p_, arch, run, 1)[0], p, x)
        return [tuple(v.shape) for v in jax.tree.leaves(vjp)
                if hasattr(v, "shape")]

    shapes = residuals(blk)
    assert (rows, 24) in shapes                     # moe_up
    assert shapes.count((2 * 128 * 3,)) >= 3        # weights, order, inverse
    assert (2, 128, 48) not in shapes               # the shared expert's
    assert (2, 128, 48) in residuals(tfm._block_fn(arch, _KEPT_IF_ROOM))


# -- (g) refusals by mechanism, and the step unit ---------------------------

def test_a_sharded_mesh_refuses_the_new_mechanisms_by_name(cpu_devices):
    mesh = make_mesh({"data": 1, "seq": 1, "model": 2}, jax.devices()[:2])
    with pytest.raises(ValueError, match="layers of one sub-layer"):
        tfm.make_train_step(mesh, _arch(_cfg()))


def test_serving_refuses_the_new_mechanisms_by_name():
    from znicz_tpu.serve.kvcache import KVDecoder

    params = jax.tree.map(np.asarray, ref.init_params(1, _cfg()))
    for word in MECHANISMS:
        with pytest.raises(NotImplementedError, match=word):
            KVDecoder(params, heads=4)


def test_the_unit_publishes_both_kinds_counters_in_one_pass(tmp_path):
    """``TransformerLMStep(arch=...)`` under the benchmark's control graph
    on the reference's seeded weights and rows: an epoch of three steps
    publishes the routed layers' counters (means over the ``E`` layers,
    the new ``act_zero_share`` among them) and the state-space layers'
    (means over the ``M`` layers, equal to the reference's), the gauge that
    says which form the grouped products got, and refuses to export."""
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
    w.initialize(device=XLADevice())
    w.run()
    step = w.step
    assert step.ssm_counters["decay_mean"] == pytest.approx(
        np.mean([s["decay_mean"] for s in want["ssm"]]), rel=1e-4)
    assert step.ssm_counters["final_state_rms"] == pytest.approx(
        np.mean([s["final_state_rms"] for s in want["ssm"]]), rel=2e-3)
    moe_c = step.moe_counters
    assert set(moe_c) == {"pairs_held_per_step", "load_max_over_mean",
                          "compact_share", "tile_fill", "act_zero_share"}
    assert 0.25 < moe_c["act_zero_share"] < 0.75
    assert 0 < moe_c["pairs_held_per_step"] <= 2 * 64 * 3
    assert w.decision.metrics_history[-1]["metric_train"] == pytest.approx(
        np.mean(want["loss"]), rel=2e-4)
    fam = registry.REGISTRY.get("znicz_lm_moe_act_zero_share")
    assert fam.labels(unit=step.name).get() == moe_c["act_zero_share"]
    # the CPU as it is: lax.ragged_dot made every grouped product
    assert step.moe_gmm_kernel_share == 0.0
    fam = registry.REGISTRY.get("znicz_lm_moe_gmm_kernel_share")
    assert fam.labels(unit=step.name).get() == 0.0
    assert step.checkpoint_kept_bytes == dict.fromkeys(_KEPT_IF_ROOM[:2], 0)
    with pytest.raises(ValueError, match="layers of one sub-layer"):
        step.export_lm(str(tmp_path / "pkg.npz"))
