"""What the ``granitemoehybrid`` family brought to ``parallel/transformer.py``
and ``parallel/ssm.py`` (Mamba-2 state-space layers beside position-free
grouped-query attention, four static multipliers), at tiny widths on the CPU
on seeded random weights against the benchmark's plain reference
(``benchmark/reference/granitemoehybrid.py``, whose recurrence walks the
positions one by one): the chunked scan against the literal recurrence in
values and every gradient; the whole step against the reference in float32
and in bfloat16; each multiplier, the missing rotary embedding and the
convolution's bias moving the loss; the refusals by name; the step unit's
counters.

The guide's share test (a chip's share of a divided layer adding up to the
whole) does not apply: no layer is divided, the chip holds one pipeline
stage's whole layers."""

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

from reference import granitemoehybrid as ref              # noqa: E402

from znicz_tpu.parallel import ssm, transformer as tfm     # noqa: E402
from znicz_tpu.parallel.arch import (                      # noqa: E402
    gpt_arch, mechanisms_of_params)
from znicz_tpu.parallel.params import (                    # noqa: E402
    init_params, ssm_in_width)
from znicz_tpu.parallel.plan import (                      # noqa: E402
    PLAN_MARGIN, _KEPT_IF_ROOM, _loop_saves, step_footprint)
from znicz_tpu.parallel.mesh import make_mesh              # noqa: E402

TINY = {
    "model_type": "granitemoehybrid", "hidden_size": 32,
    "shared_intermediate_size": 64, "hidden_act": "silu",
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "attention_bias": False, "attention_multiplier": 0.0625,
    "embedding_multiplier": 12, "residual_multiplier": 0.22,
    "logits_scaling": 8, "position_embedding_type": "nope",
    "normalization_function": "rmsnorm", "rms_norm_eps": 1e-5,
    "num_hidden_layers": 3, "layer_types": ["mamba", "attention", "mamba"],
    "num_local_experts": 0, "num_experts_per_tok": 0, "mamba_n_heads": 8,
    "mamba_d_head": 8, "mamba_d_state": 16, "mamba_d_conv": 4,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_chunk_size": 8,
    "mamba_conv_bias": True, "mamba_proj_bias": False,
    "tie_word_embeddings": True, "vocab_size": 53,
    "hyper": {"lr": 0.05},
}
TRAFFIC = {"minibatch_size": 2, "seq_len": 32}
MECHANISM = "state-space layer \\(Mamba-2\\)"


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


def _program_first_steps(cfg, seed, dtype, traffic=TRAFFIC, steps=3,
                         arch=None):
    """What the benchmark's builder reads off the timed step: losses, each
    leaf's first gradient as plain SGD applied it, each leaf's change, each
    step's counters."""
    arch, lr = arch or _arch(cfg), cfg["hyper"]["lr"]
    step, _ = tfm.make_train_step(_mesh1(), arch, lr=lr, stats=True,
                                  loss_chunks=2, compute_dtype=dtype)
    p0 = ref.init_params(seed, cfg)
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


# -- (a) the chunked scan against the literal recurrence ---------------------

def _scan_operands(seed, t, heads=4, pd=8, n=16, skip=True):
    r = np.random.default_rng(seed)
    x = r.normal(size=(2, t, heads, pd)).astype(np.float32)
    dt = np.exp(r.uniform(np.log(1e-3), np.log(0.2), (2, t, heads))
                ).astype(np.float32)
    a = -r.uniform(1.0, 16.0, heads).astype(np.float32)
    bm, cm = (r.normal(size=(2, t, n)).astype(np.float32) for _ in range(2))
    d = r.normal(size=heads).astype(np.float32) if skip else \
        np.zeros(heads, np.float32)
    return tuple(jnp.asarray(v) for v in (x, dt, a, bm, cm, d))


def _literal(x, dt, a, bm, cm, d):
    ys, lasts = zip(*(ref.recurrence(x[r], dt[r], a, bm[r], cm[r], d)
                      for r in range(x.shape[0])))
    return jnp.stack(ys), jnp.stack(lasts)


@pytest.mark.parametrize("t,chunk,skip", [
    (48, 8, True),        # six chunks a row, a skip that bites
    (48, 48, True),       # the chunk is the whole row
    (48, 256, True),      # the tile is wider than the row
    (44, 16, True),       # the last chunk is filled (44 = 2 * 16 + 12)
    (48, 16, False),      # no skip
])
def test_chunked_scan_is_the_literal_recurrence_in_values_and_gradients(
        t, chunk, skip):
    """``ssm.ssd`` against ``lax.scan`` over the positions: ``y``, the
    state behind the last position, and the gradient of a random
    functional of ``y`` in every operand (``x``, ``dt``, ``A``, ``B``,
    ``C``, ``D``).  Float32 on both sides: 5e-5 is rounding (the chunked
    form takes a decay as the exp of a difference of two running sums, whose
    float32 error grows with the sum: 2^-24 x 50 here); a carry that is
    dropped, shifted or rounded to bfloat16 is far above it (the next
    test)."""
    ops = _scan_operands(5, t, skip=skip)
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
        if name == "D" and not skip:
            continue                    # both are exact sums of x * w
        err = float(jnp.linalg.norm(g - g_want) / jnp.linalg.norm(g_want))
        assert err < 5e-5, (name, err)


@pytest.mark.parametrize("fault", ["dropped", "bfloat16", "shifted"])
def test_a_faulty_carry_fails_the_same_tolerance(monkeypatch, fault):
    """The tolerance above is tight enough: with each chunk's opening state
    set to zero, rounded to bfloat16, or taken from the chunk before, ``y``
    leaves the literal recurrence by far more than 5e-5."""
    states = ssm._chunk_states

    def faulty(x, dt, a, bm):
        opening, last = states(x, dt, a, bm)
        if fault == "dropped":
            return jnp.zeros_like(opening), last
        if fault == "bfloat16":
            return opening.astype(jnp.bfloat16).astype(jnp.float32), last
        return jnp.roll(opening, 1, axis=1), last

    ops = _scan_operands(5, 48)
    monkeypatch.setattr(ssm, "_chunk_states", faulty)
    with jax.default_matmul_precision("highest"):
        y, _ = ssm.ssd(*ops, 8)
        want, _ = _literal(*ops)
    err = float(jnp.abs(y - want).max() / jnp.abs(want).max())
    assert err > 5e-4, err


@pytest.mark.parametrize("form,t,q,heads,pd,n", [
    ("jax.numpy", 64, 16, 4, 8, 12),
    # the kernels' shape, interpreted: a chunk and a state of 128, eight
    # heads of 32 (so that no kept array's other axes read 128 twice)
    ("kernels", 384, 128, 8, 32, 128),
])
def test_the_scan_keeps_chunk_states_and_no_decay_matrix_for_its_gradients(
        form, t, q, heads, pd, n):
    """What the backward pass of ``ssd`` holds, in either form: the operands
    and each chunk's opening state; no array with two chunk-length axes (the
    ``(H, Q, Q)`` decay and score matrices are made again), and none larger
    than ``x``, ``B`` and ``C`` together (the kernels take the three side by
    side, as the layer has them)."""
    from test_lfm2_arch import _pallas_interpret

    ops = _scan_operands(5, t, heads=heads, pd=pd, n=n)
    chunks = t // q
    with _pallas_interpret(form == "kernels"):
        _, vjp = jax.vjp(lambda *o: ssm.ssd(*o, q)[0], *ops)
    shapes = [tuple(v.shape) for v in jax.tree.leaves(vjp)
              if hasattr(v, "shape")]
    states = (2, chunks, heads * pd, n) if form == "kernels" else \
        (2, chunks, heads, pd, n)
    assert states in shapes                         # the opening states
    assert not [s for s in shapes if s.count(q) >= 2], shapes
    assert max(int(np.prod(s)) for s in shapes) <= \
        2 * t * (heads * pd + 2 * n)


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
    """``mamba``, ``attention``, ``mamba`` with all four multipliers off 1,
    four chunks a row: three steps' losses, every leaf's first gradient
    (norms to 2e-4, the small leaves' differences to 5e-4: float32 rounding
    through three layers; a multiplier misplaced or a chunk's carry dropped
    moves them by percents) and every leaf's change after three steps."""
    cfg = _cfg()
    want = ref.first_steps(11, cfg, TRAFFIC, 1)
    losses, grads, deltas, counters = _program_first_steps(
        cfg, 11, jnp.float32)
    np.testing.assert_allclose(losses, want["loss"], rtol=2e-6)
    _check_gradients(grads, want, norm_rel=2e-4, diff_rel=5e-4)
    for name, dn in deltas.items():
        assert dn == pytest.approx(want["delta_norm"][name], rel=2e-4,
                                   abs=1e-8), name
    # (f) the step's counters are the reference's readings
    for got, exp in zip(counters, want["ssm"]):
        assert got["ssm_layers"] == 2.0
        assert got["ssm_decay"] / 2 == pytest.approx(exp["decay_mean"],
                                                     rel=1e-5)
        assert got["ssm_state_rms"] / 2 == pytest.approx(
            exp["final_state_rms"], rel=1e-4)


def test_first_three_steps_follow_the_reference_in_bfloat16():
    """The same step with bfloat16 compute over the float32 masters
    (decays, running sums, the carried state and the norms stay float32):
    the loss to 2e-3, each leaf's gradient norm to 6 %, the small leaves'
    first gradients to 12 % of their norm: what bfloat16 operands (8
    mantissa bits, 0.4 % a rounding) give through three layers at these
    widths, and five times under what the fp8 control reads."""
    cfg = _cfg()
    want = ref.first_steps(11, cfg, TRAFFIC, 1)
    losses, grads, deltas, _ = _program_first_steps(cfg, 11, jnp.bfloat16)
    np.testing.assert_allclose(losses, want["loss"], rtol=2e-3)
    _check_gradients(grads, want, norm_rel=6e-2, diff_rel=0.12)
    for name, dn in deltas.items():
        assert dn == pytest.approx(want["delta_norm"][name], rel=6e-2,
                                   abs=1e-7), name


def test_a_dropped_carry_fails_the_whole_steps_tolerance(monkeypatch):
    """The float32 tolerance of the step against the reference is tight
    enough for the scan: with every chunk opening on a zero state the
    state-space leaves' gradients leave it."""
    states = ssm._chunk_states
    monkeypatch.setattr(ssm, "_chunk_states", lambda *o: (
        jnp.zeros_like(states(*o)[0]), states(*o)[1]))
    jax.clear_caches()          # the layer's trace is kept by function
    try:
        cfg = _cfg()
        want = ref.first_steps(11, cfg, TRAFFIC, 1, steps=1)
        _, grads, _, _ = _program_first_steps(cfg, 11, jnp.float32, steps=1)
    finally:
        jax.clear_caches()
    with pytest.raises(AssertionError):
        _check_gradients(grads, want, norm_rel=2e-4, diff_rel=5e-4)


# -- (c) nothing is read and ignored ------------------------------------

def _loss(cfg, arch=None, params=None, seed=3):
    arch = arch or _arch(cfg)
    fn = tfm.make_eval_loss(_mesh1(), arch, loss_chunks=2,
                            compute_dtype=jnp.float32)
    rows = ref.make_tokens(seed, cfg, 32, 0, 2)
    params = params if params is not None else ref.init_params(seed, cfg)
    return float(fn(params, jnp.asarray(rows[:, :-1]),
                    jnp.asarray(rows[:, 1:])))


@pytest.mark.parametrize("key,value", [
    ("embedding_multiplier", 6), ("attention_multiplier", 0.25),
    ("residual_multiplier", 0.5), ("logits_scaling", 4)])
def test_each_multiplier_moves_the_loss_as_it_moves_the_references(key,
                                                                   value):
    base, cfg = _cfg(), _cfg(**{key: value})
    moved = _loss(cfg)
    assert abs(moved - _loss(base)) > 1e-5
    assert moved == pytest.approx(
        ref.first_steps(3, cfg, TRAFFIC, 1, steps=1)["loss"][0], rel=2e-6)


def test_a_rotary_embedding_would_move_the_loss():
    """The attention layer has NO positional encoding: giving the same
    stack one changes the loss (and the reference, which has none, agrees
    with the stack as read)."""
    cfg = _cfg()
    arch = _arch(cfg)
    assert arch.rope_theta is None
    assert abs(_loss(cfg, dataclasses.replace(arch, rope_theta=1e4)) -
               _loss(cfg)) > 1e-5


def test_the_convolutions_bias_moves_the_loss():
    cfg = _cfg()
    params = ref.init_params(3, cfg)
    changed = jax.tree.map(lambda a: a, params)
    changed["blocks"][0]["ssm_conv_b"] = \
        changed["blocks"][0]["ssm_conv_b"] + 0.5
    assert abs(_loss(cfg, params=changed) - _loss(cfg)) > 1e-4


def test_the_multipliers_emit_nothing_at_their_defaults():
    """A stack without multipliers lowers to the same text whether or not
    the fields exist: at 1.0 / None no multiply is emitted (what keeps the
    other families' steps as they were)."""
    arch = gpt_arch(1, 16, 2, 32, 11)
    assert (arch.embed_mult, arch.residual_mult, arch.attn_mult,
            arch.logits_div) == (1.0, 1.0, None, 1.0)
    cfg = _cfg(embedding_multiplier=1.0, residual_multiplier=1.0,
               logits_scaling=1.0, attention_multiplier=0.25)
    plain = _arch(cfg)                 # 0.25 = 1 / sqrt(16): the kernels' own
    fn = tfm.make_eval_loss(_mesh1(), plain, compute_dtype=jnp.float32)
    tok = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    params = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s, jnp.float32),
                          tfm.param_shapes(plain),
                          is_leaf=lambda x: isinstance(x, tuple))
    text = fn.lower(params, tok, tok).as_text()
    scaled = tfm.make_eval_loss(
        _mesh1(), dataclasses.replace(plain, residual_mult=0.5),
        compute_dtype=jnp.float32).lower(params, tok, tok).as_text()
    assert text.count("multiply") < scaled.count("multiply")
    none = tfm.make_eval_loss(
        _mesh1(), dataclasses.replace(plain, attn_mult=None),
        compute_dtype=jnp.float32).lower(params, tok, tok).as_text()
    # q times exactly 1.0 is the one multiply a given multiplier costs
    assert none.count("multiply") == text.count("multiply") - 1


# -- (d) the refusals by name ------------------------------------------

@pytest.mark.parametrize("change,match", [
    ({"num_local_experts": 8}, "num_local_experts"),
    ({"mamba_n_groups": 8}, "mamba_n_groups"),
    ({"layer_types": ["mamba", "sliding_attention", "mamba"]},
     "sliding_attention"),
    ({"layer_types": ["mamba", "conv", "mamba"]}, "conv"),
    ({"normalization_function": "layernorm"}, "normalization_function"),
    ({"position_embedding_type": "rope"}, "position_embedding_type"),
    ({"attention_bias": True}, "attention_bias"),
    ({"mamba_proj_bias": True}, "mamba_proj_bias"),
    ({"mamba_conv_bias": False}, "mamba_conv_bias"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"mamba_expand": 4}, "mamba_expand"),
    ({"num_hidden_layers": 4}, "num_hidden_layers"),
    ({"model_type": "granitemoe"}, "model_type"),
])
def test_keys_the_stack_cannot_honour_are_refused_by_name(change, match):
    with pytest.raises(ValueError, match=match):
        _arch(_cfg(**change))


def test_the_family_reads_into_the_arch_and_its_leaves():
    cfg = _cfg()
    arch = _arch(cfg)
    assert arch.mixers == ("mamba", "attention", "mamba")
    assert set(arch.ffns) == {"glu"} and arch.ff == 64
    assert (arch.ssm_heads, arch.ssm_head_dim, arch.ssm_state,
            arch.conv_taps, arch.ssm_chunk) == (8, 8, 16, 4, 8)
    assert (arch.embed_mult, arch.residual_mult, arch.attn_mult,
            arch.logits_div) == (12.0, 0.22, 0.0625, 8.0)
    assert arch.rope_theta is None and arch.tied and arch.final_norm
    assert arch.kv_heads == 2 and not arch.qk_norm
    names = arch.mechanisms()
    assert "state-space layer (Mamba-2)" in names
    assert any(n.startswith("static multipliers") for n in names)
    assert "rotary embedding" not in names
    shapes = tfm.param_shapes(arch)
    assert shapes["blocks"][0]["ssm_in"] == (32, 2 * 64 + 2 * 16 + 8)
    assert shapes["blocks"][0]["ssm_conv_k"] == (4, 64 + 32)
    assert "wq" in shapes["blocks"][1] and "ssm_in" not in shapes["blocks"][1]
    assert jax.tree.map(np.shape, ref.init_params(1, cfg)) == \
        jax.tree.map(tuple, shapes, is_leaf=lambda x: isinstance(x, tuple))
    assert "state-space layer (Mamba-2)" in \
        mechanisms_of_params(ref.init_params(1, cfg))
    # the multipliers are written for these sub-layers alone
    with pytest.raises(ValueError, match="multipliers"):
        dataclasses.replace(arch, mtp=True)
    with pytest.raises(ValueError, match="multipliers"):
        dataclasses.replace(gpt_arch(1, 16, 2, 32, 11), residual_mult=0.5)
    with pytest.raises(ValueError, match="ssm_heads"):
        dataclasses.replace(arch, ssm_heads=0)


def test_init_params_follow_the_shape_table_and_mamba2s_start():
    from znicz_tpu.core import prng

    prng.seed_all(5)
    arch = _arch(_cfg())
    params = init_params(prng.get(), arch)
    assert jax.tree.map(np.shape, params) == jax.tree.map(
        tuple, tfm.param_shapes(arch), is_leaf=lambda x: isinstance(x, tuple))
    blk = params["blocks"][0]
    rates = np.exp(blk["ssm_a_log"])
    assert rates.min() >= 1.0 and rates.max() <= 16.0
    steps = np.log1p(np.exp(blk["ssm_dt_b"]))          # softplus
    assert steps.min() >= 1e-3 * 0.999 and steps.max() <= 1e-1 * 1.001
    assert (blk["ssm_d"] == 1).all() and (blk["ssm_conv_b"] == 0).all()
    # the small leaves stay float32 in a bfloat16 forward
    cast = tfm._cast_params(jax.tree.map(jnp.asarray, params), arch,
                            jnp.bfloat16)
    for k, v in cast["blocks"][0].items():
        assert v.dtype == (jnp.float32 if k in ssm.F32_LEAVES
                           else jnp.bfloat16), k


def test_the_stack_recomputes_its_wide_arrays_by_its_own_policy():
    """``_block_fn`` picks ``_loop_saves`` for a stack with state-space
    layers from the architecture alone (no keyword): a layer's residuals are
    arrays of ``(tokens, d)`` and the chunk states, none ``(tokens, ff)`` or
    ``(tokens, in_width)`` wide."""
    arch = _arch(_cfg())
    blk = tfm._block_fn(arch)
    assert blk is not tfm._block
    run = tfm._run_of(_mesh1(), arch)
    p = jax.tree.map(jnp.asarray, ref.init_params(1, _cfg())["blocks"][0])
    x = jnp.ones((2, 32, 32), jnp.float32)
    _, vjp = jax.vjp(lambda p_, x_: blk(x_, p_, arch, run, 0)[0], p, x)
    shapes = [tuple(v.shape) for v in jax.tree.leaves(vjp)
              if hasattr(v, "shape")]
    assert (2, 4, 8, 8, 16) in shapes           # ssm_state
    # ssm_y, (tokens, inner) wide as the gate reads it, and nothing else
    # that wide or (tokens, ff) wide (both 64 here: the gate, the gated
    # product, the SwiGLU's three), nothing in_width wide
    assert [s for s in shapes if len(s) == 3 and
            s[-1] in (64, ssm_in_width(8, 8, 16))] == [(2, 32, 64)]
    assert (2, 32, 8, 8) not in shapes
    # with every optional kind kept the wide arrays are residuals: the
    # input projection and the SwiGLU's two products (the convolution's
    # ``jax.numpy`` form keeps nothing of its own: its result is made again)
    full = tfm._block_fn(arch, _KEPT_IF_ROOM)
    _, vjp = jax.vjp(lambda p_, x_: full(x_, p_, arch, run, 0)[0], p, x)
    wide = [tuple(v.shape) for v in jax.tree.leaves(vjp)
            if hasattr(v, "shape") and len(v.shape) == 3]
    assert wide.count((2, 32, 64)) >= 2                     # m w1, m w3
    assert (2, 32, ssm_in_width(8, 8, 16)) in wide
    assert (2, 32, 64 + 2 * 16) not in wide
    # nothing kept beside its list: the policy is the list's own
    assert tfm._saves(()) is _loop_saves
    assert tfm._saves(("ssm_in",)) is tfm._saves(("ssm_in",))


GIB = 2 ** 30


def _cell_arch():
    """The benchmark cell's architecture (``benchmark/configs/
    granite_4_0_h_micro.json``): ten layers at the published widths."""
    import json

    with open(os.path.join(BENCH, "configs",
                           "granite_4_0_h_micro.json")) as f:
        cfg = json.load(f)
    opts = cfg["builders"]["lm_train_keys"]
    return tfm.arch_from_config({k: cfg[k] for k in opts["model_keys"]}), \
        opts["loss_chunks"]


@pytest.mark.parametrize("tokens,limit_gib,kept", [
    # the cell on a v5e: the wide products and the input projection, beside
    # the convolution kernel's result in the footprint (13.66 GiB reckoned
    # of the 13.75 the margin leaves)
    (8192, 15.75, ("glu_wide", "ssm_in")),
    (8192, 15.75 / 2, ()),           # half the memory: today's list
    (16384, 15.75, ()),              # twice the tokens keep less
    (4096, 15.75, ("glu_wide", "ssm_in")),       # a shorter row
    (8192, 32.0, ("glu_wide", "ssm_in")),
    (8192, 12.5, ()),                # the first kind refused ends the walk
    (8192, None, ()),                # no limit reported: a CPU
])
def test_the_plan_keeps_what_the_counted_bytes_leave_room_for(tokens,
                                                              limit_gib, kept):
    """``checkpoint_plan`` from static shapes and a given memory limit, at
    the benchmark cell's widths: kinds in their fixed order while their
    bytes fit the limit less the reckoned footprint and the margin."""
    arch, chunks = _cell_arch()
    limit = None if limit_gib is None else int(limit_gib * GIB)
    plan = tfm.checkpoint_plan(arch, tokens, 2, limit, chunks)
    assert tuple(plan) == _KEPT_IF_ROOM[:2]       # the kinds the stack has
    assert tuple(k for k, v in plan.items() if v) == kept
    sizes = {"glu_wide": 10 * 2 * tokens * 8192 * 2,
             "ssm_in": 9 * tokens * 8512 * 2}
    assert all(plan[k] == sizes[k] for k in kept)
    if limit is not None:
        room = limit - step_footprint(arch, tokens, 2, chunks) - \
            PLAN_MARGIN
        assert sum(plan.values()) <= max(room, 0)
        refused = [k for k in plan if k not in kept]
        assert not refused or \
            sum(plan.values()) + sizes[refused[0]] > room


def test_the_plan_is_for_the_policys_stacks_alone():
    """A stack whose layers are not checkpointed by ``_loop_saves`` has no
    plan (nothing to name in its INFO line or its gauge), whatever the
    memory."""
    plain = dataclasses.replace(_arch(_cfg()),
                                mixers=("attention",) * 3)
    assert tfm.checkpoint_plan(plain, 8192, 2, 64 * GIB) == {}


def _loss_and_grads(cfg, dtype, seed=5):
    """The step's own loss (``_forward_ce`` on the one-device mesh) and its
    gradient to every leaf, not read back through an update."""
    from jax.sharding import PartitionSpec as P
    from znicz_tpu.parallel.compat import shard_map

    arch, mesh = _arch(cfg), _mesh1()
    run = tfm._run_of(mesh, arch)

    def local(ps, tok, lab):
        return tfm._forward_ce(ps, tok, lab, None, arch, run, dtype,
                               loss_chunks=2)[0]

    rows = P("data", "seq")
    fn = shard_map(local, mesh=mesh, out_specs=P(),
                   in_specs=(tfm.param_specs(arch, False), rows, rows))
    tokens = ref.make_tokens(seed, cfg, TRAFFIC["seq_len"], 0,
                             TRAFFIC["minibatch_size"])
    loss, grads = jax.jit(jax.value_and_grad(fn))(
        jax.tree.map(jnp.asarray, ref.init_params(seed, cfg)),
        jnp.asarray(tokens[:, :-1]), jnp.asarray(tokens[:, 1:]))
    return float(loss), _named(cfg, jax.tree.map(np.asarray, grads)), run


@pytest.mark.parametrize("dtype,rel", [(jnp.float32, 2e-6),
                                       (jnp.bfloat16, 5e-2)])
def test_a_full_plan_moves_no_loss_and_no_gradient(monkeypatch, dtype, rel):
    """The step with every optional kind kept (a device that reports room
    for all of it) against the step that keeps today's list (no limit
    reported): the kept arrays are the forward pass's own, so the loss and
    every leaf's gradient agree to float32 rounding (in bfloat16 to its
    rounding: XLA fuses a chain it makes again otherwise than the one it
    made first)."""
    cfg = _cfg()
    got = []
    for limit in (None, 64 * GIB):
        monkeypatch.setattr(tfm, "_memory_limit", lambda mesh: limit)
        assert tuple(k for k, v in tfm.step_choices(
            _mesh1(), _arch(cfg), TRAFFIC["minibatch_size"],
            TRAFFIC["seq_len"], 2)["checkpoint_kept_bytes"].items()
            if v) == (_KEPT_IF_ROOM[:2] if limit else ())
        loss, grads, run = _loss_and_grads(cfg, dtype)
        assert run.hbm_limit == limit
        got.append((loss, grads))
    (loss0, grads0), (loss1, grads1) = got
    assert loss1 == pytest.approx(loss0, rel=2e-6 if rel < 1e-3 else 0)
    assert set(grads0) == set(grads1)
    for name, g0 in grads0.items():
        scale = float(np.abs(g0).max())
        np.testing.assert_allclose(grads1[name], g0, rtol=0,
                                   atol=rel * scale, err_msg=name)


def test_the_plan_is_said_once_with_the_bytes_that_decided_it(caplog,
                                                              monkeypatch):
    """The step's INFO line: each kind kept or refused with its bytes, the
    limit, the footprint and the margin."""
    import logging

    arch, chunks = _cell_arch()
    tfm._report_plan.cache_clear()
    with caplog.at_level(logging.INFO, logger="znicz_tpu.transformer"):
        kept = tfm._report_plan(arch, 8192, 2, int(15.75 * GIB), chunks)
        assert tfm._report_plan(arch, 8192, 2, int(15.75 * GIB),
                                chunks) is kept
    assert kept == ("glu_wide", "ssm_in")
    lines = [r.getMessage() for r in caplog.records
             if "checkpointed layers" in r.getMessage()]
    assert len(lines) == 1
    for word in ("glu_wide kept (2.500 GiB)", "ssm_in kept (1.169 GiB)",
                 "limit 15.750 GiB", "footprint 9.991",
                 "footprint", "margin 2.000"):
        assert word in lines[0], lines[0]


# -- (e) refusals by mechanism --------------------------------------------

def test_the_state_space_layer_refuses_a_sharded_mesh_by_name(cpu_devices):
    for axes in ({"data": 1, "seq": 1, "model": 2},
                 {"data": 1, "seq": 2, "model": 1}):
        mesh = make_mesh(axes, jax.devices()[:2])
        with pytest.raises(ValueError, match=MECHANISM):
            tfm.make_train_step(mesh, _arch(_cfg()))


def test_serving_refuses_the_state_space_layer_by_name():
    from znicz_tpu.serve.kvcache import KVDecoder

    params = ref.init_params(1, _cfg())
    with pytest.raises(NotImplementedError, match=MECHANISM):
        KVDecoder(jax.tree.map(np.asarray, params), heads=4)


# -- (f) the step unit ------------------------------------------------------

def test_the_unit_publishes_the_state_space_counters(tmp_path):
    """``TransformerLMStep(arch=...)`` under the benchmark's control graph
    on the reference's seeded weights and rows: an epoch of three steps
    folds the layers' readings into the pass's sums and publishes them
    once: the unit's mirror (``ssm_counters``) and the ``znicz_lm_ssm_*``
    gauges, equal to the reference's means over the same three steps."""
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
    got = step.ssm_counters
    assert set(got) == {"decay_mean", "final_state_rms"}
    assert got["decay_mean"] == pytest.approx(
        np.mean([s["decay_mean"] for s in want["ssm"]]), rel=1e-4)
    assert got["final_state_rms"] == pytest.approx(
        np.mean([s["final_state_rms"] for s in want["ssm"]]), rel=2e-3)
    assert w.decision.metrics_history[-1]["metric_train"] == pytest.approx(
        np.mean(want["loss"]), rel=2e-4)
    assert step.loss_terms == {} and step.loop_counters == {}
    assert step.dsa_counters == {} and step.moe_counters == {}
    for key, value in got.items():
        fam = registry.REGISTRY.get(f"znicz_lm_ssm_{key}")
        assert fam is not None and fam.labels(unit=step.name).get() == value
    # the plan's gauge: a CPU reports no memory limit, every kind refused
    assert step.checkpoint_kept_bytes == dict.fromkeys(_KEPT_IF_ROOM[:2], 0)
    fam = registry.REGISTRY.get("znicz_lm_checkpoint_kept_bytes")
    for name in _KEPT_IF_ROOM[:2]:
        assert fam.labels(unit=step.name, name=name).get() == 0
    with pytest.raises(ValueError, match=MECHANISM):
        step.export_lm(str(tmp_path / "pkg.npz"))
    state = step.state_dict()
    step.load_state_dict(state)
    state["params"]["blocks"][0].pop("ssm_a_log")
    with pytest.raises(ValueError, match="architecture"):
        step.load_state_dict(state)
