"""The chunked cross-entropy against a replicated head
(``parallel/head.py::_ce_weighted``): a ``custom_vjp`` whose forward
rule makes the gradients of ``x``, ``head`` and the weights in the pass that
makes the logits, and whose backward rule only scales them.  Held here, on
the CPU at tiny widths, against ``jax.grad`` of the dense unchunked form;
inside a ``lax.scan`` with weights that depend on the state (a looped
stack's exit gate); by the number of products with the vocabulary axis in
the differentiated step; by the eval pass's loss, which is the old chunked
form's bit for bit; and by the gauge the step unit publishes."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from znicz_tpu.parallel import head as tfm_head, transformer as tfm
from znicz_tpu.parallel.arch import gpt_arch
from znicz_tpu.parallel.head import _ce_chunked, ce_grad_in_forward
from znicz_tpu.parallel.params import init_params
from znicz_tpu.parallel.mesh import make_mesh

VOCAB = 53


def _operands(n_tok, d, dtype, seed=0, zero_from=None):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(k[0], (n_tok, d), jnp.float32).astype(dtype)
    head = (0.3 * jax.random.normal(k[1], (d, VOCAB), jnp.float32)
            ).astype(dtype)
    labels = jax.random.randint(k[2], (n_tok,), 0, VOCAB)
    w = jax.random.uniform(k[3], (n_tok,), jnp.float32, 0.2, 1.5)
    if zero_from is not None:               # masked rows weigh nothing
        w = w.at[zero_from:].set(0.0)
    return x, head, labels, w


def _dense(x, head, labels, w):
    """The unchunked form, as ``_ce_from_hidden`` writes it."""
    logp = jax.nn.log_softmax((x @ head).astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return (nll * w).sum(), nll


def _old_chunked(x, head, labels, w, n_chunks):
    """The chunked form this core replaced: each chunk's logits under
    ``jax.checkpoint`` in a ``lax.map``."""
    @jax.checkpoint
    def chunk_nll(xc, lc, wc):
        logp = jax.nn.log_softmax((xc @ head).astype(jnp.float32), axis=-1)
        return (-jnp.take_along_axis(logp, lc[:, None], axis=-1)[:, 0]
                * wc).sum()

    return lax.map(lambda inp: chunk_nll(*inp),
                   _ce_chunked(x, labels, w, n_chunks)).sum()


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-12)


@pytest.mark.parametrize("n_tok", [32, 29], ids=["whole", "padded"])
@pytest.mark.parametrize("n_chunks", [1, 2, 8])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
def test_core_gradients_are_the_dense_forms(dtype, tol, n_chunks, n_tok):
    """Sum, per-token readings and the gradients in ``x``, ``head`` AND
    ``w`` against ``jax.grad`` of the dense unchunked form, at a cotangent
    that is no power of two; rows of weight 0 (masked ones, and those that
    pad the last chunk) take no gradient and give none."""
    x, head, labels, w = _operands(n_tok, 16, dtype, zero_from=n_tok - 5)

    def scaled(fn):
        return lambda x, head, w: 0.3 / 7 * fn(x, head, labels, w)[0]

    total, nll = tfm._ce_weighted(x, head, labels, w, n_chunks)
    want_total, want_nll = _dense(x, head, labels, w)
    assert total.dtype == nll.dtype == jnp.float32 and nll.shape == (n_tok,)
    np.testing.assert_allclose(total, want_total, rtol=5 * tol)
    np.testing.assert_allclose(nll, want_nll, rtol=5 * tol, atol=tol)
    got = jax.grad(scaled(lambda *a: tfm._ce_weighted(*a, n_chunks)),
                   argnums=(0, 1, 2))(x, head, w)
    want = jax.grad(scaled(_dense), argnums=(0, 1, 2))(x, head, w)
    for g, r, name in zip(got, want, ("x", "head", "w")):
        assert g.dtype == r.dtype and g.shape == r.shape, name
        assert _rel(g, r) < tol, name
    assert not np.asarray(got[0], np.float32)[n_tok - 5:].any()
    # the readings take no gradient: a loss of them alone has none
    for g in jax.grad(lambda x, head, w: tfm._ce_weighted(
            x, head, labels, w, n_chunks)[1].sum(), (0, 1, 2))(x, head, w):
        assert not np.asarray(g, np.float32).any()


@pytest.mark.parametrize("n_chunks", [2, 8])
def test_loss_is_the_old_chunked_forms_bit_for_bit(n_chunks):
    """Not differentiated (the eval pass) the core is the chunked form it
    replaced, bit for bit; differentiated, its value is the same number."""
    for dtype in (jnp.float32, jnp.bfloat16):
        x, head, labels, w = _operands(29, 16, dtype, seed=3)
        old = jax.jit(_old_chunked, static_argnums=4)(
            x, head, labels, w, n_chunks)
        new = jax.jit(lambda *a: tfm._ce_weighted(*a, n_chunks)[0])(
            x, head, labels, w)
        assert np.asarray(new) == np.asarray(old)
        val, _ = jax.jit(jax.value_and_grad(
            lambda x: tfm._ce_weighted(x, head, labels, w, n_chunks)[0]))(x)
        np.testing.assert_allclose(val, old, rtol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_gradients_are_the_old_forms_bit_for_bit_at_a_power_of_two(dtype):
    """``dlogits`` is cast before the cotangent scales it, the old form
    scaled first: the same bits wherever the cotangent is a power of two
    (``1 / tokens`` of the benchmark's steps), a rounding apart elsewhere."""
    x, head, labels, w = _operands(29, 16, dtype, seed=1, zero_from=26)

    def grads(fn, ct):
        return jax.jit(jax.grad(
            lambda x, head, w: ct * fn(x, head, w), (0, 1, 2)))(x, head, w)

    def new(x, head, w):
        return tfm._ce_weighted(x, head, labels, w, 4)[0]

    def old(x, head, w):
        return _old_chunked(x, head, labels, w, 4)

    for g, r in zip(grads(new, 1 / 512), grads(old, 1 / 512)):
        assert (np.asarray(g, np.float32) == np.asarray(r, np.float32)).all()
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    for g, r in zip(grads(new, 0.3 / 511), grads(old, 0.3 / 511)):
        assert _rel(g.astype(jnp.float32), r.astype(jnp.float32)) < tol


def test_rule_holds_inside_a_scan_with_weights_that_take_gradients():
    """The exit gate's shape: an outer ``lax.scan`` whose every step runs a
    head pass weighted by a sigmoid of the state; the gradients for the
    head, the state's weights and the gate's are those of the same loop
    over the checkpointed chunked form, and of the dense one."""
    n_tok, d, steps = 24, 16, 3
    x0, head, labels, _ = _operands(n_tok, d, jnp.float32, seed=5)
    mix = 0.2 * jax.random.normal(jax.random.PRNGKey(9), (d, d))
    gate = 0.5 * jax.random.normal(jax.random.PRNGKey(11), (d,))

    def loss(pass_fn, head, mix, gate):
        def body(carry, _):
            h, alive, total = carry
            h = jnp.tanh(h @ mix) + h
            lam = jax.nn.sigmoid(h @ gate)
            total = total + pass_fn(h, head, lam * alive)
            return (h, alive * (1.0 - lam), total), None

        (_, _, total), _ = lax.scan(
            body, (x0, jnp.ones((n_tok,)), jnp.zeros(())), None, steps)
        return total / n_tok

    forms = {
        "rule": lambda h, head, w: tfm._ce_weighted(h, head, labels, w, 4)[0],
        "checkpoint": lambda h, head, w: _old_chunked(h, head, labels, w, 4),
        "dense": lambda h, head, w: _dense(h, head, labels, w)[0],
    }
    grads = {name: jax.jit(jax.value_and_grad(
        lambda *a, fn=fn: loss(fn, *a), argnums=(0, 1, 2)))(head, mix, gate)
        for name, fn in forms.items()}
    for other in ("checkpoint", "dense"):
        np.testing.assert_allclose(grads["rule"][0], grads[other][0],
                                   rtol=1e-6)
        for g, r in zip(grads["rule"][1], grads[other][1]):
            assert _rel(g, r) < 2e-6, other


# -- the differentiated step ------------------------------------------------

def _mesh1():
    return make_mesh({"data": 1, "seq": 1, "model": 1}, jax.devices()[:1])


GLM_TINY = {
    "model_type": "glm4_moe_lite", "hidden_size": 32, "intermediate_size": 48,
    "moe_intermediate_size": 24, "num_attention_heads": 4,
    "num_key_value_heads": 4, "q_lora_rank": 16, "kv_lora_rank": 8,
    "qk_nope_head_dim": 12, "qk_rope_head_dim": 4, "v_head_dim": 16,
    "attention_bias": False, "rms_norm_eps": 1e-5, "norm_topk_prob": True,
    "num_experts_per_tok": 2, "rope_theta": 1000000, "rope_scaling": None,
    "partial_rotary_factor": 1, "routed_scaling_factor": 1.8,
    "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
    "n_routed_experts": 4, "n_shared_experts": 1, "router_width": 8,
    "experts_held": {"first": 2, "count": 4}, "first_k_dense_replace": 1,
    "num_hidden_layers": 2, "num_nextn_predict_layers": 1,
    "mtp_loss_weight": 0.3, "tie_word_embeddings": False,
    "vocab_size": VOCAB,
}
OURO_TINY = {
    "model_type": "ouro", "hidden_size": 32, "intermediate_size": 48,
    "hidden_act": "silu", "num_attention_heads": 4, "num_key_value_heads": 4,
    "head_dim": 8, "num_hidden_layers": 1,
    "layer_types": ["full_attention"], "total_ut_steps": 4,
    "exit_entropy_weight": 0.1, "rms_norm_eps": 1e-6, "rope_theta": 1000000,
    "rope_scaling": None, "tie_word_embeddings": False, "vocab_size": VOCAB,
}


def _vocab_products(jaxpr, times=1):
    """How often a ``dot_general`` with an axis of ``VOCAB`` entries runs in
    ``jaxpr``: one in a ``scan``'s body counts once a trip."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general" and any(
                VOCAB in v.aval.shape for v in (*eqn.invars, *eqn.outvars)):
            n += times
        inner = times * eqn.params["length"] \
            if eqn.primitive.name == "scan" else times
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _vocab_products(sub, inner)
    return n


def _step_jaxpr(arch, **kw):
    """The differentiated train step of ``arch`` on the singleton mesh over
    2 x 16 tokens, traced from shapes alone."""
    step, _ = tfm.make_train_step(_mesh1(), arch, lr=0.1,
                                  compute_dtype=jnp.float32, **kw)
    params = jax.tree.map(
        lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32),
        tfm.param_shapes(arch), is_leaf=lambda a: isinstance(a, tuple))
    rows = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    return jax.make_jaxpr(step)(params, rows, rows).jaxpr


@pytest.mark.parametrize("skip_last", [False, True], ids=["all", "skip_last"])
@pytest.mark.parametrize("masked", [False, True], ids=["whole", "masked"])
def test_loss_tail_chunked_is_the_unchunked_one(masked, skip_last):
    """``_ce_from_hidden`` with ``loss_chunks`` against itself without: the
    loss and the gradients in the hidden states and the head, with a row
    masked, with each row's last position left out (an MTP module's pass),
    and a chunk count that does not divide the tokens."""
    from jax.sharding import PartitionSpec as P

    b, t, d = 3, 7, 16
    x, head, labels, _ = _operands(b * t, d, jnp.float32, seed=7)
    x, labels = x.reshape(b, t, d), labels.reshape(b, t)
    mask = jnp.array([True, False, True]) if masked else None

    def loss(x, head, chunks):
        def local(x, head):
            return tfm._ce_from_hidden(x, head, labels, mask, 0.0, chunks,
                                       False, True, skip_last=skip_last)
        return tfm.shard_map(local, mesh=_mesh1(), in_specs=(P(), P()),
                             out_specs=P())(x, head)

    got = jax.value_and_grad(loss, (0, 1))(x, head, 4)
    want = jax.value_and_grad(loss, (0, 1))(x, head, None)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    for g, r in zip(got[1], want[1]):
        assert _rel(g, r) < 2e-6
    dx = np.asarray(got[1][0])
    assert dx[0].any() and (not masked or not dx[1].any())
    assert not skip_last or not dx[:, -1].any()


@pytest.mark.parametrize("name,passes,chunks", [
    ("gpt", 1, 2), ("gpt", 1, 8), ("glm", 2, 2), ("ouro", 4, 2),
    ("ouro", 4, None), ("gpt", 1, None)])
def test_differentiated_step_runs_three_vocabulary_products_a_head_pass(
        name, passes, chunks):
    """The whole differentiated step: logits, ``dlogits head^T`` and ``x^T
    dlogits`` once a chunk of every head pass (a GPT-shaped block's one, an
    MTP stack's two, a looped stack's four) and no second logits product;
    the checkpointed chunk this replaced counts four on the same counter."""
    arch = {"gpt": gpt_arch(1, 32, 4, 64, VOCAB),
            "glm": tfm.arch_from_config(GLM_TINY),
            "ouro": tfm.arch_from_config(OURO_TINY)}[name]
    jaxpr = _step_jaxpr(arch, loss_chunks=chunks)
    assert _vocab_products(jaxpr) == 3 * passes * (chunks or 1)
    # what the step unit's gauge says of this step: a looped stack's head
    # passes take the rule chunked or not, the others' when chunked
    def share(head_sharded):
        return tfm.step_choices(_mesh1(), arch, 2, 16, chunks, head_sharded)[
            "ce_grad_in_forward_share"]

    assert share(False) == float(name == "ouro" or bool(chunks))
    if name == "gpt":
        assert share(True) == 0.0
    else:               # as the step itself refuses
        with pytest.raises(ValueError, match="head_sharded"):
            share(True)
    assert ce_grad_in_forward(chunks, True, looped=True)

    x, head, labels, w = _operands(32, 16, jnp.float32)
    old = jax.make_jaxpr(jax.grad(_old_chunked, argnums=(0, 1)),
                         static_argnums=4)(x, head, labels, w, 2).jaxpr
    assert _vocab_products(old) == 4 * 2
    new = jax.make_jaxpr(jax.grad(
        lambda x, head: tfm._ce_weighted(x, head, labels, w, 2)[0],
        argnums=(0, 1)))(x, head).jaxpr
    assert _vocab_products(new) == 3 * 2


def test_eval_pass_is_the_old_chunked_loss_bit_for_bit():
    """``make_eval_loss`` differentiates nothing, so it runs the core's
    primal: the loss of the step's forward with the old chunk function in
    the core's place, bit for bit, masked rows and a chunk count that does
    not divide the tokens included."""
    from unittest import mock

    arch = gpt_arch(1, 32, 4, 64, VOCAB)
    params = init_params(np.random.default_rng(2), arch)
    rows = jax.random.randint(jax.random.PRNGKey(4), (4, 17), 0, VOCAB)
    mask = jnp.array([True, True, True, False])

    def loss():
        fn = tfm.make_eval_loss(_mesh1(), arch, masked=True, loss_chunks=3,
                                compute_dtype=jnp.float32)
        return np.asarray(fn(params, rows[:, :-1], rows[:, 1:], mask))

    calls = []

    def old_core(*a):
        calls.append(a[-1])
        return _old_chunked(*a), None

    new = loss()
    with mock.patch.object(tfm_head, "_ce_weighted", old_core):
        old = loss()
    assert calls == [3] and np.isfinite(new) and new == old


# -- the step unit ----------------------------------------------------------

@pytest.mark.parametrize("kw,share", [
    ({"loss_chunks": 4}, 1.0), ({}, 0.0),
    ({"loss_chunks": 4, "head_sharded": True}, 0.0)],
    ids=["chunked", "unchunked", "vocab_sharded"])
def test_step_unit_publishes_the_share_of_head_passes_on_the_rule(
        tmp_path, kw, share):
    """The gauge, set as the step is built: 1.0 where the chunked
    cross-entropy runs against a replicated head, 0.0 where the pass is
    unchunked or the head vocab-sharded (both leave the gradients to AD)."""
    from znicz_tpu.core import prng
    from znicz_tpu.core.backends import XLADevice
    from znicz_tpu.models import char_lm
    from znicz_tpu.observe import registry

    prng.seed_all(3)
    w = char_lm.build(max_epochs=1, seq_len=16, minibatch_size=8,
                      n_layers=1, d=16, heads=2,
                      data_dir=str(tmp_path / "corp"), **kw)
    assert w.step.ce_grad_in_forward_share is None
    w.initialize(device=XLADevice())
    w.run()
    assert np.isfinite(w.decision.metrics_history[-1]["metric_train"])
    assert w.step.ce_grad_in_forward_share == share
    fam = registry.REGISTRY.get("znicz_lm_ce_grad_in_forward_share")
    assert fam is not None and fam.labels(unit=w.step.name).get() == share
