"""The layer kinds the ``lfm2_moe`` family brought to ``parallel/
transformer.py`` (gated short convolution, GQA with QK-norm and RoPE,
SwiGLU, the routed expert layer that holds a share of the experts), at tiny
widths on the CPU against the benchmark's plain reference
(``benchmark/reference/lfm2_moe.py``): every kind of layer forward and
gradient, the whole cut model's first three steps, the shares adding up to
the uncut layer, no pair dropped, the refusals by name, and the step unit's
deferred loss read."""

import contextlib
import os
import re
import sys
from unittest import mock

import numpy as np
import pytest

import jax
import jax.numpy as jnp

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import lfm2_moe as ref                      # noqa: E402

from znicz_tpu.core import prng                            # noqa: E402
from znicz_tpu.parallel import moe, transformer as tfm     # noqa: E402
from znicz_tpu.parallel.arch import gpt_arch               # noqa: E402
from znicz_tpu.parallel.params import init_params          # noqa: E402
from znicz_tpu.parallel.mesh import make_mesh              # noqa: E402

TINY = {
    "model_type": "lfm2_moe", "hidden_size": 32, "intermediate_size": 48,
    "moe_intermediate_size": 24, "num_attention_heads": 4,
    "num_key_value_heads": 2, "conv_L_cache": 3, "conv_bias": False,
    "norm_eps": 1e-5, "norm_topk_prob": True, "num_experts_per_tok": 2,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 53,
    "router_width": 8, "experts_held": {"first": 2, "count": 4},
    "hyper": {"lr": 0.05},
}
TRAFFIC = {"minibatch_size": 2, "seq_len": 16}


def _cfg(layer_types, n_dense, **over):
    return {**TINY, "layer_types": list(layer_types),
            "num_hidden_layers": len(layer_types),
            "num_dense_layers": n_dense, **over}


def _arch(cfg):
    model = {k: v for k, v in cfg.items()
             if k not in ("router_width", "hyper")}
    return tfm.arch_from_config({**model, "num_experts": cfg["router_width"]})


def _mesh1():
    return make_mesh({"data": 1, "seq": 1, "model": 1}, jax.devices()[:1])


def _program_first_steps(cfg, seed, steps=3):
    """What the benchmark's builder reads off the timed step: losses, and
    each leaf's first gradient as plain SGD applied it."""
    arch, lr = _arch(cfg), cfg["hyper"]["lr"]
    step, _ = tfm.make_train_step(_mesh1(), arch, lr=lr,
                                  compute_dtype=jnp.float32)
    p0 = ref.init_params(seed, cfg)
    b, t = TRAFFIC["minibatch_size"], TRAFFIC["seq_len"]
    params, losses, grads = p0, [], None
    for s in range(steps):
        rows = ref.make_tokens(seed, cfg, t, s * b, (s + 1) * b)
        params, loss = step(params, jnp.asarray(rows[:, :-1]),
                            jnp.asarray(rows[:, 1:]))
        losses.append(float(loss))
        if s == 0:
            grads = jax.tree.map(lambda a, c: np.asarray(a - c) / lr, p0,
                                 params)
    return losses, grads, jax.tree.map(
        lambda a, c: float(jnp.linalg.norm(a - c)), p0, params)


def _named(tree):
    out = {k: tree[k] for k in ("emb", "norm_g")}
    for li, blk in enumerate(tree["blocks"]):
        out.update({f"B{li}.{k}": v for k, v in blk.items()})
    return out


@pytest.mark.parametrize("kind,n_dense", [
    ("conv", 1), ("full_attention", 1), ("conv", 0), ("full_attention", 0)],
    ids=["sconv+swiglu", "gqa+swiglu", "sconv+experts", "gqa+experts"])
def test_each_layer_kind_forward_and_gradient(kind, n_dense):
    """A one-layer model of each mixer and each ffn: the step's first loss
    (the forward pass) and every leaf's first gradient are the plain
    reference's."""
    cfg = _cfg([kind], n_dense)
    want = ref.first_steps(7, cfg, TRAFFIC, 1, steps=1)
    losses, grads, _ = _program_first_steps(cfg, 7, steps=1)
    assert losses[0] == pytest.approx(want["loss"][0], rel=2e-5)
    grads = _named(grads)
    assert set(grads) == set(want["grad_norm"])
    for name, g in grads.items():
        assert np.linalg.norm(g) == pytest.approx(
            want["grad_norm"][name], rel=2e-3, abs=2e-6), name
    for name, g in want["grad_first"].items():
        scale = max(np.linalg.norm(g), 1e-6)
        assert np.linalg.norm(grads[name] - g) / scale < 5e-3, name
    if n_dense == 0:
        # the selection bias steers and is never trained
        assert np.all(grads["B0.ebias"] == 0)


def test_cut_model_first_three_steps_follow_the_reference():
    """The benchmark's cut (a dense convolution layer, then attention and
    three convolution layers with experts), tiny: three steps' losses and
    every leaf's change."""
    cfg = _cfg(["conv", "full_attention", "conv", "conv", "conv"], 1)
    want = ref.first_steps(11, cfg, TRAFFIC, 1)
    losses, _, deltas = _program_first_steps(cfg, 11)
    np.testing.assert_allclose(losses, want["loss"], rtol=5e-5)
    deltas = _named(deltas)
    assert set(deltas) == set(want["delta_norm"])
    for name, value in deltas.items():
        assert value == pytest.approx(want["delta_norm"][name], rel=5e-3,
                                      abs=1e-7), name


def _layer_inputs(seed=3, tokens=24, **over):
    cfg = _cfg(["conv"], 0, experts_held={"first": 0, "count": 8}, **over)
    dm = ref.dims(cfg)
    p = ref.init_leaf_group(seed, cfg, 0)
    v = jax.random.normal(jax.random.PRNGKey(seed), (tokens, dm["d"]))
    return dm, p, v


def test_the_shares_add_up_to_the_uncut_layer():
    """8 experts split 4 x 2: the four shares' expert parts, plus the
    residual stream that every chip holds alike counted once, are the
    uncut reference's layer."""
    dm, p, v = _layer_inputs()
    ident = lambda a: a                                     # noqa: E731
    with jax.default_matmul_precision("highest"):
        uncut = v + ref._sparse_ffn(p, v, dm, ident, ident)
        parts, pairs = [], 0.0
        for first in (0, 2, 4, 6):
            held = slice(first, first + 2)
            y, stats = moe.moe_routed_ffn(
                v, p["gate"], p["ebias"], p["ew1"][held], p["ew3"][held],
                p["ew2"][held], first=first, top_k=dm["top_k"])
            parts.append(y)
            pairs += float(stats["pairs_held"])
            # one share alone is that share of the reference
            share = ref._sparse_ffn(
                {**p, "ew1": p["ew1"][held], "ew3": p["ew3"][held],
                 "ew2": p["ew2"][held]}, v,
                {**dm, "first": first, "held": 2}, ident, ident)
            np.testing.assert_allclose(y, share, atol=2e-6)
    np.testing.assert_allclose(v + sum(parts), uncut, atol=5e-6)
    assert pairs == v.shape[0] * dm["top_k"]          # every pair, once


def test_no_pair_is_dropped_when_every_token_selects_the_same_experts():
    dm, p, v = _layer_inputs()
    bias = jnp.where(jnp.arange(8) < 2, 10.0, 0.0)     # all choose 0 and 1
    ident = lambda a: a                                     # noqa: E731
    with jax.default_matmul_precision("highest"):
        y, stats = moe.moe_routed_ffn(
            v, p["gate"], bias, p["ew1"][:2], p["ew3"][:2], p["ew2"][:2],
            first=0, top_k=2)
        want = ref._sparse_ffn(
            {**p, "ebias": bias, "ew1": p["ew1"][:2], "ew3": p["ew3"][:2],
             "ew2": p["ew2"][:2]}, v, {**dm, "first": 0, "held": 2},
            ident, ident)
    assert float(stats["pairs_held"]) == 2 * v.shape[0]
    assert float(stats["load_max_over_mean"]) == pytest.approx(1.0)
    np.testing.assert_allclose(y, want, atol=2e-6)
    # and a chip that holds none of the selected experts adds nothing
    y, stats = moe.moe_routed_ffn(
        v, p["gate"], bias, p["ew1"][4:6], p["ew3"][4:6], p["ew2"][4:6],
        first=4, top_k=2)
    assert float(stats["pairs_held"]) == 0 and not np.asarray(y).any()


def _steered_layer(two_held: int, one_held: int, tokens: int = 512, **over):
    """A layer of 8 experts, top-2, whose router a feature of each token
    steers: the first ``two_held`` tokens select experts 0 and 1, the
    next ``one_held`` experts 0 and 5, the rest 4 and 5.  A chip that
    holds experts 0 and 1 then counts ``2 * two_held + one_held`` pairs,
    exactly."""
    dm, p, _ = _layer_inputs(**over)
    kind = np.full(tokens, 2)
    kind[:two_held] = 0
    kind[two_held:two_held + one_held] = 1
    v = 0.1 * jax.random.normal(jax.random.PRNGKey(9), (tokens, dm["d"]))
    v = v.at[:, :3].set(jax.nn.one_hot(kind, 3))
    gate = 0.1 * p["gate"]
    gate = gate.at[:3].set(8.0 * jnp.asarray(
        [[1, 1, 0, 0, 0, 0, 0, 0], [1, 0, 0, 0, 0, 1, 0, 0],
         [0, 0, 0, 0, 1, 1, 0, 0]], jnp.float32) - 4.0)
    return dm, {**p, "gate": gate, "ebias": jnp.zeros(8)}, v


def _share_loss(p, v, first, held, ct, rows_of=None):
    """Loss, output, counters and the gradients to ``x``, ``gate``,
    ``ew1``, ``ew3``, ``ew2`` of one share of the layer; ``rows_of``
    stands in for ``moe.compact_rows`` (the full buffer alone: ``lambda
    n, h, e: n``)."""
    def loss(x, gate, w1, w3, w2):
        y, stats = moe.moe_routed_ffn(x, gate, p["ebias"], w1, w3, w2,
                                      first=first, top_k=2)
        return (y * ct).sum(), (y, stats)

    sl = slice(first, first + held)
    with mock.patch.object(moe, "compact_rows",
                           rows_of or moe.compact_rows), \
            jax.default_matmul_precision("highest"):
        (_, (y, stats)), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4), has_aux=True))(
            v, p["gate"], p["ew1"][sl], p["ew3"][sl], p["ew2"][sl])
    return y, stats, grads


def test_compact_buffer_gives_the_full_buffers_output_and_gradients():
    """2 of 8 experts held, 1,024 pairs: the compact buffer (512 rows)
    carries the layer, and its output and every gradient are the full
    buffer's."""
    dm, p, _ = _layer_inputs()
    v = jax.random.normal(jax.random.PRNGKey(4), (512, dm["d"]))
    ct = jax.random.normal(jax.random.PRNGKey(5), v.shape)
    assert moe.compact_rows(1024, 2, 8) == 512
    y, stats, grads = _share_loss(p, v, 2, 2, ct)
    y_full, stats_full, grads_full = _share_loss(
        p, v, 2, 2, ct, rows_of=lambda n, h, e: n)
    assert float(stats["compact"]) == 1.0
    assert float(stats_full["compact"]) == 0.0
    assert 0 < float(stats["pairs_held"]) == float(stats_full["pairs_held"])
    np.testing.assert_allclose(y, y_full, atol=2e-6)
    for name, g, want in zip(("x", "gate", "ew1", "ew3", "ew2"), grads,
                             grads_full):
        assert np.abs(np.asarray(want)).max() > 1e-3, name
        np.testing.assert_allclose(g, want, atol=2e-5, err_msg=name)


@pytest.mark.parametrize("one_held,compact", [(-1, 1.0), (0, 1.0), (1, 0.0)],
                         ids=["just_under", "equal", "just_over"])
def test_the_buffer_follows_the_count_and_no_pair_is_dropped(one_held,
                                                             compact):
    """511, 512 and 513 pairs to the held experts against a compact
    buffer of 512: compact, compact, full; all three are the reference's
    layer, and the gradients are the full buffer's."""
    two_held = 256 if one_held >= 0 else 255
    dm, p, v = _steered_layer(two_held, abs(one_held))
    ct = jax.random.normal(jax.random.PRNGKey(6), v.shape)
    y, stats, grads = _share_loss(p, v, 0, 2, ct)
    assert float(stats["pairs_held"]) == 512 + one_held
    assert float(stats["compact"]) == compact
    ident = lambda a: a                                     # noqa: E731
    with jax.default_matmul_precision("highest"):
        want = ref._sparse_ffn(
            {**p, "ew1": p["ew1"][:2], "ew3": p["ew3"][:2],
             "ew2": p["ew2"][:2]}, v, {**dm, "first": 0, "held": 2},
            ident, ident)
    np.testing.assert_allclose(y, want, atol=2e-6)
    _, _, grads_full = _share_loss(p, v, 0, 2, ct,
                                   rows_of=lambda n, h, e: n)
    for g, want in zip(grads, grads_full):
        np.testing.assert_allclose(g, want, atol=2e-5)


#: widths the grouped-product kernels accept (multiples of 128 lanes)
LANES = {"hidden_size": 128, "moe_intermediate_size": 128}


@contextlib.contextmanager
def _pallas_interpret(on: bool):
    """``engine.pallas_interpret``: set, the Pallas kernels run wherever
    the program would pick them on a TPU, interpreted."""
    from znicz_tpu.core.config import root

    prev = root.common.engine.get("pallas_interpret", False)
    root.common.engine.pallas_interpret = on
    try:
        yield
    finally:
        root.common.engine.pallas_interpret = prev


@pytest.fixture
def interpreted_kernels():
    with _pallas_interpret(True):
        yield


@pytest.mark.parametrize("n_dense", [1, 0],
                         ids=["sconv+swiglu", "sconv+experts"])
def test_the_sconv_kernels_give_every_leaf_the_numpy_stacks_gradients(
        n_dense):
    """A one-layer stack of the gated short convolution at a width of whole
    lane tiles, with the step's kernels interpreted (``sconv_gate_fwd`` /
    ``sconv_gate_bwd`` between the layer's two products) against the same
    stack in ``jax.numpy``: the first loss and every leaf's first gradient,
    to the tolerance this file holds a stack to against the reference."""
    cfg = _cfg(["conv"], n_dense, hidden_size=128)
    arch = _arch(cfg)
    b, t = TRAFFIC["minibatch_size"], TRAFFIC["seq_len"]
    assert tfm.step_choices(_mesh1(), arch, b, t)["sconv_kernel_share"] == 0
    want_loss, want, _ = _program_first_steps(cfg, 7, steps=1)
    with _pallas_interpret(True):
        assert tfm.step_choices(_mesh1(), arch, b, t)[
            "sconv_kernel_share"] == 1.0
        loss, grads, _ = _program_first_steps(cfg, 7, steps=1)
    assert loss[0] == pytest.approx(want_loss[0], rel=2e-5)
    grads, want = _named(grads), _named(want)
    assert set(grads) == set(want)
    for name, g in want.items():
        scale = max(np.linalg.norm(g), 1e-6)
        assert np.linalg.norm(grads[name]) == pytest.approx(
            np.linalg.norm(g), rel=2e-3, abs=2e-6), name
        assert np.linalg.norm(grads[name] - g) / scale < 5e-3, name


def _grouped_forms(p, v):
    """How often each form of the grouped product stands in the traced
    layer and its gradients: ``(kernel calls, lax.ragged_dot calls)``."""
    def loss(x, w1, w3, w2):
        return moe.moe_routed_ffn(x, p["gate"], p["ebias"], w1, w3, w2,
                                  first=0, top_k=2)[0].sum()

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2, 3)))(
        v, p["ew1"][:2], p["ew3"][:2], p["ew2"][:2]))
    # a kernel call is a jitted function of ops/pallas/grouped.py by name
    return len(re.findall(r"name=gmm_(?:rows_t|rows|weights)\b", text)), \
        text.count("ragged_dot")


@pytest.mark.parametrize("one_held,compact", [(-1, 1.0), (0, 1.0), (1, 0.0)],
                         ids=["just_under", "equal", "just_over"])
def test_the_kernels_compact_branch_equals_the_full_one(
        one_held, compact, interpreted_kernels):
    """The grouped-product kernels in ``lax.ragged_dot``'s place (forced
    through interpret mode): 511, 512 and 513 pairs against a compact
    buffer of 512 give the reference's layer, and the output and all
    five gradients are the full buffer's."""
    two_held = 256 if one_held >= 0 else 255
    dm, p, v = _steered_layer(two_held, abs(one_held), **LANES)
    assert moe._gmm_kernels(512, 128, 128, 2, v.dtype) is True
    ct = jax.random.normal(jax.random.PRNGKey(6), v.shape)
    y, stats, grads = _share_loss(p, v, 0, 2, ct)
    assert float(stats["pairs_held"]) == 512 + one_held
    assert float(stats["compact"]) == compact
    ident = lambda a: a                                     # noqa: E731
    with jax.default_matmul_precision("highest"):
        want = ref._sparse_ffn(
            {**p, "ew1": p["ew1"][:2], "ew3": p["ew3"][:2],
             "ew2": p["ew2"][:2]}, v, {**dm, "first": 0, "held": 2},
            ident, ident)
    np.testing.assert_allclose(y, want, atol=5e-6)
    y_full, _, grads_full = _share_loss(p, v, 0, 2, ct,
                                        rows_of=lambda n, h, e: n)
    np.testing.assert_allclose(y, y_full, atol=5e-6)
    for name, g, want in zip(("x", "gate", "ew1", "ew3", "ew2"), grads,
                             grads_full):
        assert np.abs(np.asarray(want)).max() > 1e-3, name
        np.testing.assert_allclose(g, want, atol=5e-5, err_msg=name)


def test_the_kernels_take_every_grouped_product_of_both_branches(
        interpreted_kernels):
    """All nine or none: with the kernels picked, no ``lax.ragged_dot``
    is left in either branch, forward or backward."""
    _, p, v = _steered_layer(256, 0, **LANES)
    kernels, ragged = _grouped_forms(p, v)
    # compact: 3 forward + 6 by hand; full: 3 forward, 3 again in the
    # backward pass with 2 rules each
    assert ragged == 0 and kernels == 9 + 3 + 3 + 6


def test_on_the_cpu_the_grouped_products_are_ragged_dot():
    """No TPU and no ``pallas_interpret``: ``lax.ragged_dot``, at widths
    the kernels would take too; and widths they refuse fall back under
    ``pallas_interpret`` as well."""
    assert jax.default_backend() == "cpu"
    assert moe._gmm_kernels(512, 128, 128, 2, jnp.float32) is None
    assert moe._row_tile(512, 128, 128, 2, jnp.float32) == moe._ROW_TILE
    _, p, v = _steered_layer(256, 0, **LANES)
    kernels, ragged = _grouped_forms(p, v)
    assert kernels == 0 and ragged > 0
    with _pallas_interpret(True):
        assert moe._gmm_kernels(512, 32, 24, 2, jnp.float32) is None
        assert moe._row_tile(512, 128, 128, 2, jnp.float32) == 128


@pytest.mark.parametrize("kernels", [False, True],
                         ids=["ragged_dot", "kernels"])
def test_tile_fill_is_reckoned_with_the_tile_of_the_form_that_ran(kernels):
    """512 pairs in two groups of 256: one tile of 512 visited twice by
    ``lax.ragged_dot`` (0.5), four tiles of 128 visited once (1.0)."""
    _, p, v = _steered_layer(256, 0, **LANES)
    with _pallas_interpret(kernels):
        _, stats = moe.moe_routed_ffn(
            v, p["gate"], p["ebias"], p["ew1"][:2], p["ew3"][:2],
            p["ew2"][:2], first=0, top_k=2)
    assert float(stats["pairs_held"]) == 512
    assert float(stats["tile_fill"]) == (1.0 if kernels else 0.5)
    assert "tile_fill" in moe.MEAN_STATS


@pytest.mark.parametrize("held,branches", [(8, 0), (2, 2)],
                         ids=["all_held", "a_share"])
def test_a_chip_that_holds_every_expert_traces_no_choice(held, branches):
    """``held == E``: the compact buffer would be no smaller, so there is
    one path and no ``cond`` in the program, forward or backward; a
    share has one in each."""
    dm, p, _ = _layer_inputs()
    v = jax.random.normal(jax.random.PRNGKey(4), (512, dm["d"]))

    def loss(x, w1):
        y, _ = moe.moe_routed_ffn(x, p["gate"], p["ebias"], w1,
                                  p["ew3"][:held], p["ew2"][:held],
                                  first=0, top_k=2)
        return y.sum()

    jaxpr = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(
        v, p["ew1"][:held]))
    assert jaxpr.count(" cond[") == branches


@pytest.mark.parametrize("wrong", ["softmax", "no_bias", "no_norm"])
def test_router_variants_differ_from_the_models(wrong):
    """The three ways to get the router wrong move the layer's output by
    far more than rounding: what the benchmark's limits stand on."""
    dm, p, v = _layer_inputs()
    kw = {"softmax": {"score": "softmax"}, "no_bias": {},
          "no_norm": {"norm_topk": False}}[wrong]
    bias = None if wrong == "no_bias" else p["ebias"]
    right, _ = moe.moe_routed_ffn(v, p["gate"], p["ebias"], p["ew1"],
                                  p["ew3"], p["ew2"], first=0, top_k=2)
    got, _ = moe.moe_routed_ffn(v, p["gate"], bias, p["ew1"], p["ew3"],
                                p["ew2"], first=0, top_k=2, **kw)
    assert float(jnp.linalg.norm(got - right) /
                 jnp.linalg.norm(right)) > 0.05


@pytest.mark.parametrize("group", [1, 2, 4])
def test_flash_kernels_take_grouped_query_heads(group):
    """The Pallas flash kernels (interpreted) with ``group`` query heads to
    a key/value head: output and all three gradients are dense
    attention's over repeated key/value heads."""
    from znicz_tpu.ops.pallas import attention as pattn

    b, t, kv, dh = 2, 128, 2, 64
    h = kv * group
    ks = jax.random.split(jax.random.PRNGKey(group), 4)
    q = jax.random.normal(ks[0], (b, t, h, dh))
    k = jax.random.normal(ks[1], (b, t, kv, dh))
    v = jax.random.normal(ks[2], (b, t, kv, dh))
    ct = jax.random.normal(ks[3], (b, t, h, dh))

    def dense(q, k, v):
        kr, vr = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kr) / np.sqrt(dh)
        mask = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
        a = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", a, vr)

    def flash(q, k, v):
        return pattn.flash_attention(q, k, v, causal=True, interpret=True)

    np.testing.assert_allclose(flash(q, k, v), dense(q, k, v), atol=2e-5)
    got = jax.grad(lambda *a: (flash(*a) * ct).sum(), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (dense(*a) * ct).sum(), (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=5e-5)


def test_published_configuration_is_read_as_the_issue_counted_it():
    import json

    with open(os.path.join(BENCH, "configs", "lfm2_24b_a2b.json")) as f:
        cfg = json.load(f)
    arch = _arch({k: v for k, v in cfg.items() if k in TINY or k in (
        "layer_types", "num_hidden_layers", "num_dense_layers")})
    assert (arch.d, arch.heads, arch.kv_heads, arch.head_dim) == \
        (2048, 32, 8, 64)
    assert arch.mixers == ("sconv", "attention", "sconv", "sconv", "sconv")
    assert arch.ffns == ("glu",) + ("moe_routed",) * 4
    assert (arch.n_experts, arch.experts_held, arch.top_k) == (64, 16, 4)
    shapes = jax.tree.leaves(tfm.param_shapes(arch),
                             is_leaf=lambda x: isinstance(x, tuple))
    assert sum(int(np.prod(s)) for s in shapes) == 788_052_352
    assert ref.train_flops_per_sample(cfg, 4096) / 4096 == \
        pytest.approx(1.383e9, rel=2e-3)


def test_gpt_shaped_block_is_one_instance_of_the_same_definition():
    arch = gpt_arch(2, 32, 4, 64, 17)
    assert not arch.mechanisms()
    assert tfm.param_shapes(arch) == tfm.param_shapes(2, 32, 64, 17)
    assert tfm.param_specs(arch) == tfm.param_specs(2)
    moe_arch = gpt_arch(2, 32, 4, 64, 17, n_experts=4)
    assert tfm.param_specs(moe_arch) == tfm.param_specs(2, moe=True)
    assert tfm.param_shapes(moe_arch) == \
        tfm.param_shapes(2, 32, 64, 17, n_experts=4)
    a = init_params(np.random.default_rng(5), arch)
    b = init_params(np.random.default_rng(5), 2, 32, 4, 64, 17)
    assert all(np.array_equal(x, y) for x, y in zip(jax.tree.leaves(a),
                                                    jax.tree.leaves(b)))


# -- refusals by name ------------------------------

def test_new_layer_kinds_refuse_a_sharded_mesh_by_name(cpu_devices):
    arch = _arch(_cfg(["conv", "full_attention"], 1))
    for axes in ({"data": 1, "seq": 1, "model": 2},
                 {"data": 1, "seq": 2, "model": 1}):
        with pytest.raises(ValueError, match="gated short convolution"):
            tfm.make_train_step(make_mesh(axes, jax.devices()[:2]), arch)
    # the data axis is theirs to use
    tfm.make_train_step(make_mesh({"data": 2, "seq": 1, "model": 1},
                                  jax.devices()[:2]), arch)


def test_serving_and_export_refuse_the_new_layer_kinds_by_name(tmp_path):
    from znicz_tpu.serve.kvcache import KVDecoder
    from znicz_tpu.utils.export import export_lm

    params = init_params(np.random.default_rng(1),
                         _arch(_cfg(["conv", "full_attention"], 1)))
    with pytest.raises(NotImplementedError, match="routed experts"):
        KVDecoder(params, heads=4)
    with pytest.raises(ValueError, match="gated short convolution"):
        export_lm(params, str(tmp_path / "m.npz"), heads=4)


def test_unknown_model_type_is_refused_by_name():
    with pytest.raises(ValueError, match="some_other_moe"):
        tfm.arch_from_config({"model_type": "some_other_moe"})


# -- the step unit ------------------------------

#: epoch losses of this seeded run: validation, then training.  Pinned
#: with the loss read after every step (the parent commit of ISSUE 28) and
#: again at PR 35, whose chunked cross-entropy makes f32 gradients that
#: differ from the checkpointed form's in their last bits (``dlogits`` is
#: rounded before the loss's cotangent scales it, which is another order
#: where the cotangent is no power of two: a training pass's last
#: minibatch here, 3 rows of 16; and XLA sums the bias gradients behind it
#: in another order): 765 steps carry that to 2.1e-6 in the second epoch
#: and 2.3e-5 in the third.  Until then: (3.852034360367731,
#: 1.0002862910210664), (0.8723333572709797, 0.8278742403543786),
#: (0.7837592527829522, 0.7916457109260933)
OLD_EPOCHS = [(3.8520348824675774, 1.0002862358980835),
              (0.8723315645264211, 0.8278743417830027),
              (0.7837594476493516, 0.791664035712099)]


def test_deferred_loss_read_gives_the_old_epoch_losses(tmp_path):
    """One blocking read a class pass, the totals landing at the pass's
    last minibatch, and the epochs' losses as they were to the digit the
    Decision logs."""
    from znicz_tpu.core.backends import XLADevice
    from znicz_tpu.models import char_lm
    from znicz_tpu.observe.trace import TRACER

    prng.seed_all(11)
    w = char_lm.build(max_epochs=3, seq_len=32, minibatch_size=16,
                      n_layers=2, d=32, heads=2,
                      data_dir=str(tmp_path / "corp"), loss_chunks=4)
    w.initialize(device=XLADevice())
    seen = []
    real = type(w.decision).accumulate

    def spy(self, cls):
        seen.append((bool(self.last_minibatch), int(self.minibatch_size),
                     isinstance(self.minibatch_mse, float)))
        return real(self, cls)

    type(w.decision).accumulate = spy
    TRACER.clear()
    try:
        w.run()
    finally:
        type(w.decision).accumulate = real
    hist = w.decision.metrics_history
    for h, (valid, train) in zip(hist, OLD_EPOCHS):
        assert h["metric_validation"] == pytest.approx(valid, rel=2e-6)
        assert h["metric_train"] == pytest.approx(train, rel=2e-6)
    # minibatches before the last count nothing and fetch nothing
    assert all(size == 0 and not fetched
               for last, size, fetched in seen if not last)
    assert all(size > 0 and fetched for last, size, fetched in seen if last)
    reads = sum(1 for e in TRACER.export_dict()["traceEvents"]
                if e.get("name") == "lm.loss_read")
    assert reads == sum(1 for last, _, _ in seen if last) == 9


def _arch_workflow(arch_cfg: dict, data_dir: str, max_epochs: int = 2,
                   seq_len: int = 16, minibatch_size: int = 8):
    """``models/char_lm.py``'s control graph with the step built from a
    model's own keys."""
    from znicz_tpu.core.plumbing import Repeater
    from znicz_tpu.loader.sequence import CharSequenceLoader
    from znicz_tpu.units.decision import DecisionMSE
    from znicz_tpu.units.lm import TransformerLMStep
    from znicz_tpu.units.nn_units import NNWorkflow

    w = NNWorkflow(name="ArchLM")
    w.repeater = Repeater(w)
    w.loader = CharSequenceLoader(w, data_dir=data_dir, seq_len=seq_len,
                                  minibatch_size=minibatch_size,
                                  valid_fraction=0.1)
    step = w.step = TransformerLMStep(w, loader=w.loader, arch=arch_cfg,
                                      lr=0.05)
    dec = w.decision = DecisionMSE(w, max_epochs=max_epochs)
    w.forwards, w.gds = [step], []
    w.repeater.link_from(w.start_point)
    w.loader.link_from(w.repeater)
    step.link_from(w.loader)
    dec.link_from(step)
    w.repeater.link_from(dec)
    w.end_point.link_from(dec)
    w.end_point.gate_block = ~dec.complete
    dec.link_attrs(w.loader, "minibatch_class", "last_minibatch",
                   "class_lengths", "epoch_number")
    dec.link_attrs(step, "minibatch_mse", "minibatch_size")
    return w


@pytest.mark.parametrize("seq_len,batch", [(16, 8), (64, 16)],
                         ids=["one_buffer", "compact_buffer"])
def test_step_unit_runs_an_architecture_and_publishes_its_counters(
        tmp_path, seq_len, batch):
    """At 128 tokens a step a compact buffer would be no smaller than the
    256 pairs (one path, share 0); at 1,024 tokens 1,536 rows stand for
    2,048 and carry the layers."""
    from znicz_tpu.core.backends import XLADevice
    from znicz_tpu.observe import registry

    cfg = _cfg(["conv", "full_attention", "conv"], 1)
    model = {k: v for k, v in cfg.items()
             if k not in ("router_width", "hyper", "vocab_size")}
    prng.seed_all(5)
    w = _arch_workflow({**model, "num_experts": cfg["router_width"]},
                       str(tmp_path / "corp"), seq_len=seq_len,
                       minibatch_size=batch)
    w.initialize(device=XLADevice())
    w.run()
    step = w.step
    hist = w.decision.metrics_history
    assert np.isfinite(hist[-1]["metric_validation"])
    assert hist[-1]["metric_train"] < hist[0]["metric_validation"]
    assert step.arch.vocab == w.loader.vocab_size
    pairs = step.moe_counters["pairs_held_per_step"]
    assert 0 < pairs < 2 * batch * seq_len * 2  # two routed layers, top-2
    assert step.moe_counters["load_max_over_mean"] >= 1.0
    share = step.moe_counters["compact_share"]
    if moe.compact_rows(2 * batch * seq_len, 4, 8) < 2 * batch * seq_len:
        assert 0.5 <= share <= 1.0
    else:
        assert share == 0.0
    fam = registry.REGISTRY.get("znicz_lm_moe_pairs_held_total")
    assert fam is not None and fam.labels(unit=step.name).get() > 0
    fam = registry.REGISTRY.get("znicz_lm_moe_compact_share")
    assert fam is not None and fam.labels(unit=step.name).get() == share
    fill = step.moe_counters["tile_fill"]
    assert 0.0 < fill <= 1.0
    fam = registry.REGISTRY.get("znicz_lm_moe_tile_fill")
    assert fam is not None and fam.labels(unit=step.name).get() == fill
    with pytest.raises(ValueError, match="gated short convolution"):
        step.export_lm(str(tmp_path / "pkg.npz"))
    # a snapshot restores into the same architecture and no other
    state = step.state_dict()
    step.load_state_dict(state)
    state["params"]["blocks"][0].pop("conv_k")
    with pytest.raises(ValueError, match="architecture"):
        step.load_state_dict(state)


def test_step_unit_publishes_that_its_attention_folds(tmp_path):
    """A head of 64 at 128 positions gets the whole-row flash kernels
    (interpreted), which read operands folded head-major: the share of
    attention layers that read the layer's own layout is 0."""
    from znicz_tpu.core.backends import XLADevice
    from znicz_tpu.observe import registry
    from znicz_tpu.ops.pallas import attention as pattn

    cfg = _cfg(["conv", "full_attention"], 1, hidden_size=128,
               num_attention_heads=2, num_key_value_heads=1)
    model = {k: v for k, v in cfg.items()
             if k not in ("router_width", "hyper", "vocab_size")}
    prng.seed_all(5)
    assert pattn.form_of(128, 64) == ("rows", None)
    with _pallas_interpret(True):
        w = _arch_workflow({**model, "num_experts": cfg["router_width"]},
                           str(tmp_path / "corp"), max_epochs=1,
                           seq_len=128, minibatch_size=2)
        w.initialize(device=XLADevice())
        w.run()
    step = w.step
    assert np.isfinite(w.decision.metrics_history[-1]["metric_train"])
    assert step.attn_direct_layout_share == 0.0
    fam = registry.REGISTRY.get("znicz_lm_attn_direct_layout_share")
    assert fam is not None and fam.labels(unit=step.name).get() == 0.0
    # the whole-row form runs no key/value-blocked tile in any pass
    assert step.attn_kvb_block_rows == {"fwd": 0.0, "dkv": 0.0, "dq": 0.0}
    fam = registry.REGISTRY.get("znicz_lm_attn_kvb_block_rows")
    assert [fam.labels(**{"unit": step.name, "pass": name}).get()
            for name in pattn._KVB_PASSES] == [0.0, 0.0, 0.0]

