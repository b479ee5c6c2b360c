"""What the ``glm4_moe_lite`` family brought to ``parallel/transformer.py``
(latent attention, a shared expert beside the routed ones, an untied head,
one multi-token-prediction module and its second loss term), at tiny widths
on the CPU against the benchmark's plain reference
(``benchmark/reference/glm4_moe_lite.py``): every kind of layer forward and
gradient, the whole cut model's first steps with and without the Pallas
kernels interpreted, the shares adding up to the uncut layer, latent
attention against a direct softmax, the second term's mask and weight, the
refusals by name, and the step unit's loss terms."""

import contextlib
import dataclasses
import json
import os
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

from reference import glm4_moe_lite as ref                 # noqa: E402

from znicz_tpu.core import prng                            # noqa: E402
from znicz_tpu.ops.pallas import attention as pattn        # noqa: E402
from znicz_tpu.parallel import moe, transformer as tfm     # noqa: E402
from znicz_tpu.parallel.arch import mechanisms_of_params   # noqa: E402
from znicz_tpu.parallel.blocks import (                    # noqa: E402
    _block_routed, _latent_qkv, _rows_rope)
from znicz_tpu.parallel.params import init_params          # noqa: E402
from znicz_tpu.parallel.mesh import make_mesh              # noqa: E402

TINY = {
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
    "num_hidden_layers": 3, "num_nextn_predict_layers": 1,
    "mtp_loss_weight": 0.3, "tie_word_embeddings": False, "vocab_size": 53,
    "hyper": {"lr": 0.05},
}
TRAFFIC = {"minibatch_size": 2, "seq_len": 16}


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


def _program_first_steps(cfg, seed, traffic=TRAFFIC, steps=3):
    """What the benchmark's builder reads off the timed step: losses, each
    leaf's first gradient as plain SGD applied it, each leaf's change."""
    arch, lr = _arch(cfg), cfg["hyper"]["lr"]
    step, _ = tfm.make_train_step(_mesh1(), arch, lr=lr, stats=True,
                                  compute_dtype=jnp.float32)
    p0 = ref.init_params(seed, cfg)
    b, t = traffic["minibatch_size"], traffic["seq_len"]
    params, losses, terms, grads = p0, [], [], None
    for s in range(steps):
        rows = ref.make_tokens(seed, cfg, t, s * b, (s + 1) * b)
        params, loss, stats = step(params, jnp.asarray(rows[:, :-1]),
                                   jnp.asarray(rows[:, 1:]))
        losses.append(float(loss))
        terms.append((float(stats.get("loss_main", loss)),
                      float(stats.get("loss_mtp", 0.0))))
        if s == 0:
            grads = jax.tree.map(lambda a, c: np.asarray(a - c) / lr, p0,
                                 params)
    deltas = jax.tree.map(lambda a, c: float(jnp.linalg.norm(a - c)), p0,
                          params)
    return losses, terms, _named(cfg, grads), _named(cfg, deltas)


def _check_gradients(grads, want, norm_rel=2e-3, diff_rel=5e-3):
    assert set(grads) == set(want["grad_norm"])
    for name, g in grads.items():
        assert np.linalg.norm(g) == pytest.approx(
            want["grad_norm"][name], rel=norm_rel, abs=2e-6), name
    for name, g in want["grad_first"].items():
        scale = max(np.linalg.norm(g), 1e-6)
        assert np.linalg.norm(grads[name] - g) / scale < diff_rel, name


@pytest.mark.parametrize("n_dense,mtp", [(1, 0), (0, 0), (0, 1)],
                         ids=["mla+swiglu", "mla+shared+experts",
                              "mla+shared+experts+mtp"])
def test_each_layer_kind_forward_and_gradient(n_dense, mtp):
    """A one-layer model of each feed-forward kind, then with the MTP
    module behind it: the step's first loss (the forward pass) and every
    leaf's first gradient are the plain reference's."""
    cfg = _cfg(num_hidden_layers=1, first_k_dense_replace=n_dense,
               num_nextn_predict_layers=mtp)
    want = ref.first_steps(7, cfg, TRAFFIC, 1, steps=1)
    losses, terms, grads, _ = _program_first_steps(cfg, 7, steps=1)
    assert losses[0] == pytest.approx(want["loss"][0], rel=2e-5)
    _check_gradients(grads, want)
    if not n_dense:
        # the selection bias steers and is never trained
        assert np.all(grads["B0.ebias"] == 0)
    if mtp:
        assert terms[0] == pytest.approx(
            (want["loss_main"][0], want["loss_mtp"][0]), rel=2e-5)
        assert np.all(grads["mtp.block.ebias"] == 0)
        # the module's lookup and the head's second pass reach the leaves
        # the main model owns
        assert "emb" in grads and "head" in grads


def test_cut_model_first_three_steps_follow_the_reference():
    """The benchmark's cut (a dense layer, sparse layers, the MTP module),
    tiny: three steps' losses, both terms, and every leaf's change."""
    cfg = _cfg()
    want = ref.first_steps(11, cfg, TRAFFIC, 1)
    losses, terms, _, deltas = _program_first_steps(cfg, 11)
    np.testing.assert_allclose(losses, want["loss"], rtol=5e-5)
    np.testing.assert_allclose([m for m, _ in terms], want["loss_main"],
                               rtol=5e-5)
    np.testing.assert_allclose([p for _, p in terms], want["loss_mtp"],
                               rtol=5e-5)
    assert set(deltas) == set(want["delta_norm"])
    for name, value in deltas.items():
        assert value == pytest.approx(want["delta_norm"][name], rel=5e-3,
                                      abs=1e-7), name


@pytest.mark.parametrize("form", ["rows", "blocked"])
def test_the_step_with_its_kernels_interpreted_follows_the_reference(form):
    """``engine.pallas_interpret`` puts the flash kernels (and, at widths
    they take, the grouped products) into the step: at a head of 64 and 128
    positions the whole-row form, and with that form refusing (as it does
    at the benchmark's head of 256) the key/value-blocked one; loss and
    every leaf's gradient stay the reference's."""
    from test_lfm2_arch import _pallas_interpret

    cfg = _cfg(hidden_size=64, qk_nope_head_dim=48, qk_rope_head_dim=16,
               v_head_dim=64, num_attention_heads=2, num_key_value_heads=2,
               num_hidden_layers=2)
    traffic = {"minibatch_size": 1, "seq_len": 128}
    want = ref.first_steps(5, cfg, traffic, 1, steps=1)
    refuse = mock.patch.object(pattn, "unsupported_reason",
                               lambda t, dh: "refused for the test")
    with _pallas_interpret(True), \
            (refuse if form == "blocked" else contextlib.nullcontext()):
        assert pattn.form_of(128, 64)[0] == form
        arch = _arch(cfg)
        text = str(jax.make_jaxpr(tfm.make_train_step(
            _mesh1(), arch, compute_dtype=jnp.float32)[0])(
                ref.init_params(5, cfg), jnp.zeros((1, 128), jnp.int32),
                jnp.zeros((1, 128), jnp.int32)))
        losses, _, grads, _ = _program_first_steps(cfg, 5, traffic, steps=1)
    names = {"rows": (pattn.FWD_KERNEL_NAME, pattn.BWD_KERNEL_NAME),
             "blocked": (pattn.KVB_FWD_KERNEL_NAME, pattn.KVB_DKV_KERNEL_NAME,
                         pattn.KVB_DQ_KERNEL_NAME)}
    for kind, kernels in names.items():
        for name in kernels:
            assert (name in text) == (kind == form), name
    assert losses[0] == pytest.approx(want["loss"][0], rel=2e-5)
    _check_gradients(grads, want, norm_rel=5e-3, diff_rel=1e-2)


def test_the_eight_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """8 experts split 8 x 1 (the cell's eight chips): the eight shares'
    routed parts, plus the shared expert, which every chip computes alike,
    counted ONCE, plus the residual stream, are the uncut reference's
    sparse feed-forward."""
    cfg = _cfg(num_hidden_layers=1, first_k_dense_replace=0,
               n_routed_experts=8, experts_held={"first": 0, "count": 8})
    dm = ref.dims(cfg)
    p = ref.init_leaf_group(3, cfg, "B0")
    v = jax.random.normal(jax.random.PRNGKey(3), (24, dm["d"]))
    ident = lambda a: a                                     # noqa: E731
    arch = _arch(cfg)
    with jax.default_matmul_precision("highest"):
        shared = ref._glu(v, p["sw1"], p["sw3"], p["sw2"], ident, ident)
        uncut = v + shared + ref._routed(p, v, dm, ident, ident)
        parts, pairs = [], 0.0
        for first in range(8):
            held = slice(first, first + 1)
            y, stats = moe.moe_routed_ffn(
                v, p["gate"], p["ebias"], p["ew1"][held], p["ew3"][held],
                p["ew2"][held], first=first, top_k=dm["top_k"],
                scale=dm["scale"])
            parts.append(y)
            pairs += float(stats["pairs_held"])
        # one chip's layer as the program computes it: its share of the
        # routed part beside the whole shared expert
        x3 = v[None]
        one = {**p, "ew1": p["ew1"][:1], "ew3": p["ew3"][:1],
               "ew2": p["ew2"][:1], "ln2_g": jnp.ones_like(p["ln2_g"])}
        share0, _, _ = _block_routed(
            x3, one, dataclasses.replace(arch, experts_held=1,
                                             norm="rms", eps=0.0), "t")
    np.testing.assert_allclose(v + shared + sum(parts), uncut, atol=5e-6)
    assert pairs == v.shape[0] * dm["top_k"]          # every pair, once
    rms = np.sqrt(np.mean(np.square(np.asarray(v)), -1, keepdims=True))
    vn = v / rms
    with jax.default_matmul_precision("highest"):
        want0 = v + ref._glu(vn, p["sw1"], p["sw3"], p["sw2"], ident,
                             ident) + moe.moe_routed_ffn(
            vn, p["gate"], p["ebias"], p["ew1"][:1], p["ew3"][:1],
            p["ew2"][:1], first=0, top_k=dm["top_k"], scale=dm["scale"])[0]
    np.testing.assert_allclose(share0[0], want0, atol=5e-6)


@pytest.mark.parametrize("interleaved", [True, False],
                         ids=["neighbour_pairs", "halves"])
def test_queries_rotated_as_whole_rows_are_the_cut_and_concatenated_ones(
        interleaved):
    """Where the flash kernels read the layer's layout, ``_latent_qkv``
    rotates the queries in place with the row kernel
    (``ops/pallas/rope.py``, interpreted here; the weight's columns
    permuted for neighbour pairs): q, k, v and the gradients to the input
    and to every leaf are those of cutting each head at ``nope`` and
    concatenating."""
    cfg = _cfg(num_hidden_layers=1, num_attention_heads=2,
               num_key_value_heads=2, qk_nope_head_dim=96,
               qk_rope_head_dim=32, v_head_dim=128,
               rope_interleave=interleaved)
    arch, dm = _arch(cfg), ref.dims(cfg)
    assert arch.rope_interleaved == interleaved
    p = ref.init_leaf_group(9, cfg, "B0")
    h = jax.random.normal(jax.random.PRNGKey(9), (2, 128, dm["d"]))
    cts = jax.random.normal(jax.random.PRNGKey(10), (3, 2, 128, 2, 128))
    cut = tfm._Run(2, 2)
    rows = tfm._Run(2, 2, use_flash=True, interpret=True)

    def loss(h, p, run):
        return sum((a * ct).sum() for a, ct in zip(
            _latent_qkv(h, p, arch, run), cts))

    with mock.patch.object(pattn, "unsupported_reason",
                           lambda t, dh: "refused for the test"), \
            jax.default_matmul_precision("highest"):
        assert _rows_rope(128, arch, rows)
        assert not _rows_rope(128, arch, cut)
        text = str(jax.make_jaxpr(lambda h: _latent_qkv(
            h, p, arch, rows))(h))
        got = _latent_qkv(h, p, arch, rows)
        want = _latent_qkv(h, p, arch, cut)
        g_got = jax.grad(loss, (0, 1))(h, p, rows)
        g_want = jax.grad(loss, (0, 1))(h, p, cut)
    assert "rope_tail" in text
    for a, w in zip(got, want):
        np.testing.assert_allclose(a, w, atol=2e-5)
    for a, w in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        np.testing.assert_allclose(a, w, atol=2e-4, rtol=2e-5)


def test_latent_attention_is_a_direct_softmax_with_one_shared_rotary_key():
    """``_latent_qkv`` + the attention core against ``softmax(q k^T /
    sqrt(nope + rope)) v`` written out head by head: the rotary part of the
    key is one vector a position, the same for every head, the pairs
    ``(2i, 2i + 1)`` turned by ``pos * theta^(-2i / rope)``."""
    cfg = _cfg(num_hidden_layers=1)
    arch, dm = _arch(cfg), ref.dims(cfg)
    p = ref.init_leaf_group(9, cfg, "B0")
    h = jax.random.normal(jax.random.PRNGKey(9), (2, 12, dm["d"]))
    nope, rope, heads = dm["nope"], dm["rope"], dm["heads"]
    with jax.default_matmul_precision("highest"):
        q, k, v = _latent_qkv(h, p, arch, tfm._Run(heads, heads))
        assert q.shape == k.shape == v.shape == (2, 12, heads, nope + rope)
        # one rotary key for all heads
        for head in range(1, heads):
            np.testing.assert_array_equal(k[:, :, head, nope:],
                                          k[:, :, 0, nope:])

        def turn(x, pos):           # (..., rope) at one position, in place
            out = np.zeros_like(x)
            for i in range(rope // 2):
                ang = pos * dm["theta"] ** (-2.0 * i / rope)
                a, b = x[..., 2 * i], x[..., 2 * i + 1]
                out[..., 2 * i] = a * np.cos(ang) - b * np.sin(ang)
                out[..., 2 * i + 1] = b * np.cos(ang) + a * np.sin(ang)
            return out

        rms = lambda a, g: a / np.sqrt(                     # noqa: E731
            (a * a).mean(-1, keepdims=True) + dm["eps"]) * g
        hn = np.asarray(h, np.float64)
        w = {name: np.asarray(leaf, np.float64) for name, leaf in p.items()}
        c_q = rms(hn @ w["wq_a"], w["q_a_g"])
        qf = (c_q @ w["wq_b"]).reshape(2, 12, heads, nope + rope)
        kv_a = hn @ w["wkv_a"]
        c_kv = rms(kv_a[..., :dm["kv_lora"]], w["kv_a_g"])
        kv = (c_kv @ w["wkv_b"]).reshape(2, 12, heads, nope + dm["vd"])
        want = np.zeros((2, 12, heads, dm["vd"]))
        for b in range(2):
            for head in range(heads):
                qs = np.stack([np.concatenate([
                    qf[b, t, head, :nope], turn(qf[b, t, head, nope:], t)])
                    for t in range(12)])
                ks = np.stack([np.concatenate([
                    kv[b, t, head, :nope],
                    turn(kv_a[b, t, dm["kv_lora"]:], t)])
                    for t in range(12)])
                s = qs @ ks.T / np.sqrt(nope + rope)
                s = np.where(np.tril(np.ones((12, 12), bool)), s, -np.inf)
                a = np.exp(s - s.max(-1, keepdims=True))
                a /= a.sum(-1, keepdims=True)
                want[b, :, head] = a @ kv[b, :, head, nope:]
        from znicz_tpu.ops import attention as att
        got = att.attention(jnp, q, k, v, causal=True)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_mtp_term_is_masked_at_the_last_position_and_weighted():
    """``loss = main + mtp_weight * mtp``; the module's term is the mean
    over the positions that have a second-next token, so the token that
    ends a row is its last label and nothing the module says at the last
    position counts."""
    cfg = _cfg()
    arch, mesh = _arch(cfg), _mesh1()
    params = ref.init_params(13, cfg)
    rows = ref.make_tokens(13, cfg, 16, 0, 2)
    tokens, labels = jnp.asarray(rows[:, :-1]), jnp.asarray(rows[:, 1:])

    def terms(a, labels=labels, params=params):
        step, _ = tfm.make_train_step(mesh, a, lr=0.0, stats=True,
                                      compute_dtype=jnp.float32)
        _, loss, stats = step(params, tokens, labels)
        return float(loss), float(stats["loss_main"]), \
            float(stats["loss_mtp"])

    loss, main, mtp = terms(arch)
    assert loss == pytest.approx(main + 0.3 * mtp, rel=1e-6)
    assert 0.5 * np.log(53) < mtp < 2 * np.log(53)
    heavy = dataclasses.replace(arch, mtp_weight=1.0)
    assert terms(heavy)[0] == pytest.approx(main + mtp, rel=1e-6)
    # without the module the loss is the main term alone
    plain = dataclasses.replace(arch, mtp=False)
    bare = {k: v for k, v in params.items() if k != "mtp"}
    step, _ = tfm.make_train_step(mesh, plain, lr=0.0,
                                  compute_dtype=jnp.float32)
    assert float(step(bare, tokens, labels)[1]) == pytest.approx(main,
                                                                 rel=1e-6)
    # the module at position i reads label i and is scored on label i + 1:
    # the FIRST label is read and never scored; the last is scored by the
    # module at the position before it and read by nobody who is scored
    moved = labels.at[:, 0].set((labels[:, 0] + 1) % 53)
    _, main2, mtp2 = terms(arch, moved)
    assert main2 != pytest.approx(main, rel=1e-6) and mtp2 != mtp
    by_hand = ref.first_steps(13, cfg, TRAFFIC, 1, steps=1)
    assert (main, mtp) == pytest.approx(
        (by_hand["loss_main"][0], by_hand["loss_mtp"][0]), rel=2e-5)
    # a module whose output is scored at the last position too would read
    # the row's first label as a second-next token: the count is t - 1
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 32))
    head = jax.random.normal(jax.random.PRNGKey(1), (32, 53))
    from jax.sharding import PartitionSpec as P
    from znicz_tpu.parallel.compat import shard_map

    def ce(skip):
        fn = shard_map(lambda x, l: tfm._ce_from_hidden(
            x, head, l, None, 0.0, None, False, True, skip_last=skip),
            mesh=mesh, in_specs=(P("data", "seq"), P("data", "seq")),
            out_specs=P())
        return fn
    logp = jax.nn.log_softmax(x @ head, -1)
    nll = -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]
    assert float(ce(True)(x, labels)) == pytest.approx(
        float(nll[:, :-1].mean()), rel=1e-5)
    assert float(ce(False)(x, labels)) == pytest.approx(float(nll.mean()),
                                                        rel=1e-5)


def test_published_configuration_is_read_as_the_issue_counted_it():
    with open(os.path.join(BENCH, "configs", "glm_4_7_flash.json")) as f:
        cfg = json.load(f)
    keys = cfg["builders"]["lm_train_keys"]["model_keys"]
    arch = tfm.arch_from_config({k: cfg[k] for k in keys})
    assert (arch.d, arch.heads, arch.kv_heads, arch.head_dim) == \
        (2048, 20, 20, 256)
    assert (arch.q_lora, arch.kv_lora, arch.nope_dim, arch.rope_dim) == \
        (768, 512, 192, 64)
    assert arch.mixers == ("latent",) * 5
    assert arch.ffns == ("glu",) + ("moe_routed",) * 4
    assert arch.kinds(5) == ("latent", "moe_routed")
    assert arch.routed_layers() == 5
    assert (arch.n_experts, arch.experts_held, arch.top_k) == (64, 8, 4)
    assert (arch.shared_ff, arch.moe_ff, arch.ff) == (1536, 1536, 10240)
    assert (arch.routed_scale, arch.mtp, arch.mtp_weight) == (1.8, True, 0.3)
    assert not arch.tied and arch.final_norm and arch.vocab == 19360
    shapes = tfm.param_shapes(arch)
    count = lambda tree: sum(int(np.prod(s)) for s in jax.tree.leaves(
        tree, is_leaf=lambda x: isinstance(x, tuple)))      # noqa: E731
    assert count(shapes) == 706_518_848
    mla = 2048 * 768 + 768 + 768 * 5120 + 2048 * 576 + 512 + \
        512 * 20 * 448 + 5120 * 2048
    assert mla == 21_759_232
    assert count(shapes["blocks"][1]) == mla + 4096 + 3 * 2048 * 1536 + \
        2048 * 64 + 64 + 8 * 3 * 2048 * 1536
    assert count(shapes["mtp"]) == count(shapes["blocks"][1]) + \
        4096 * 2048 + 3 * 2048
    # the reference makes the same leaves
    assert jax.tree.map(lambda a: tuple(a.shape), jax.eval_shape(
        lambda: ref.init_params(1, cfg))) == shapes
    # 23.5 TFLOP a step of two sequences: attention's causal half, both
    # head passes
    assert ref.train_flops_per_sample(cfg, 4096) == pytest.approx(
        11.757e12, rel=1e-3)
    # every number of the catalog's row is in the file under its key
    assert cfg["published"] == {"num_hidden_layers": 47,
                                "n_routed_experts": 64, "vocab_size": 154880}
    assert sorted(cfg["reduced"]) == sorted(cfg["published"])


def test_the_attention_form_follows_the_shape_alone():
    """The whole-row form wherever it ever accepted (``lfm2``'s shape:
    its program cannot move), the blocked form where only it accepts (this
    family's head of 256 from 2,048 positions on), dense attention with
    both refusals named otherwise."""
    assert pattn.form_of(4096, 64) == ("rows", None)
    assert pattn.form_of(2048, 128) == ("rows", None)
    assert pattn.form_of(4096, 256) == ("blocked", None)
    assert pattn.form_of(2048, 256) == ("blocked", None)
    assert pattn.form_of(8192, 64) == ("blocked", None)
    assert pattn.form_of(1024, 256) == ("rows", None)
    form, why = pattn.form_of(100, 64)
    assert form is None and "key/value-blocked" in why and "t=100" in why
    form, why = pattn.form_of(4096, 1024)
    assert form is None and "head_dim=1024" in why


# -- refusals by name ------------------------------

def test_a_third_model_type_is_refused_by_name():
    with pytest.raises(ValueError, match="deepseek_v3.*lfm2_moe, "
                                         "glm4_moe_lite"):
        tfm.arch_from_config({**TINY, "model_type": "deepseek_v3"})


@pytest.mark.parametrize("key,value,word", [
    ("v_head_dim", 8, "v_head_dim"), ("num_nextn_predict_layers", 2,
                                      "num_nextn_predict_layers"),
    ("n_group", 2, "n_group"), ("attention_bias", True, "attention_bias"),
    ("num_key_value_heads", 2, "num_key_value_heads"),
    ("partial_rotary_factor", 0.5, "partial_rotary_factor")])
def test_keys_the_stack_cannot_honour_are_refused_by_name(key, value, word):
    with pytest.raises(ValueError, match=word):
        _arch(_cfg(**{key: value}))


def test_the_new_kinds_refuse_a_sharded_mesh_by_name(cpu_devices):
    arch = _arch(_cfg())
    for axes in ({"data": 1, "seq": 1, "model": 2},
                 {"data": 1, "seq": 2, "model": 1}):
        with pytest.raises(ValueError, match="latent attention.*shared "
                                             "expert.*multi-token"):
            tfm.make_train_step(make_mesh(axes, jax.devices()[:2]), arch)


@pytest.mark.parametrize("over,word", [
    ({"num_nextn_predict_layers": 0, "first_k_dense_replace": 3},
     "latent attention"),
    ({"num_nextn_predict_layers": 0}, "shared expert"),
    ({}, "multi-token prediction")])
def test_serving_and_export_refuse_the_new_mechanisms_by_name(tmp_path, over,
                                                              word):
    from znicz_tpu.serve.kvcache import KVDecoder
    from znicz_tpu.utils.export import export_lm

    params = init_params(np.random.default_rng(1), _arch(_cfg(**over)))
    assert word in mechanisms_of_params(params)
    with pytest.raises(NotImplementedError, match=word):
        KVDecoder(params, heads=4)
    with pytest.raises(ValueError, match=word):
        export_lm(params, str(tmp_path / "m.npz"), heads=4)


# -- the step unit ------------------------------

def test_step_unit_trains_the_family_and_publishes_both_loss_terms(tmp_path):
    """``TransformerLMStep(arch=...)`` under the char-LM control graph:
    the Decision reads the total, the unit mirrors and the registry carry
    the two terms of the last training pass."""
    from test_lfm2_arch import _arch_workflow
    from znicz_tpu.core.backends import XLADevice
    from znicz_tpu.observe import registry

    model = {k: v for k, v in TINY.items() if k not in ("hyper",
                                                        "vocab_size")}
    prng.seed_all(5)
    w = _arch_workflow(model, str(tmp_path / "corp"), seq_len=16,
                       minibatch_size=8)
    w.initialize(device=XLADevice())
    w.run()
    step = w.step
    hist = w.decision.metrics_history
    assert np.isfinite(hist[-1]["metric_validation"])
    assert hist[-1]["metric_train"] < hist[0]["metric_validation"]
    assert step.arch.mtp and step.arch.vocab == w.loader.vocab_size
    main, mtp = step.loss_terms["main"], step.loss_terms["mtp"]
    assert 0 < main < 2 * np.log(step.arch.vocab) and 0 < mtp < 2 * np.log(
        step.arch.vocab)
    # the Decision's metric is the weighted total of the same pass
    assert hist[-1]["metric_train"] == pytest.approx(main + 0.3 * mtp,
                                                     rel=1e-4)
    for name, value in (("znicz_lm_loss_main", main),
                        ("znicz_lm_loss_mtp", mtp)):
        fam = registry.REGISTRY.get(name)
        assert fam is not None and fam.labels(unit=step.name).get() == value
    # three routed layers: two of the stack's and the module's
    assert 0 < step.moe_counters["pairs_held_per_step"] < 3 * 8 * 16 * 2
    with pytest.raises(ValueError, match="latent attention"):
        step.export_lm(str(tmp_path / "pkg.npz"))
    state = step.state_dict()
    step.load_state_dict(state)
    state["params"]["mtp"].pop("proj")
    with pytest.raises(ValueError, match="architecture"):
        step.load_state_dict(state)


def test_step_unit_publishes_the_direct_layout_share(tmp_path):
    """Heads of 128 through the key/value-blocked kernels (interpreted;
    the whole-row form refuses as it does at the benchmark's head of 256):
    every attention layer, the module's among them, reads the layer's own
    layout, and the unit says so."""
    from test_lfm2_arch import _arch_workflow, _pallas_interpret
    from znicz_tpu.core.backends import XLADevice
    from znicz_tpu.observe import registry

    model = {k: v for k, v in _cfg(
        num_attention_heads=2, num_key_value_heads=2, qk_nope_head_dim=96,
        qk_rope_head_dim=32, v_head_dim=128, num_hidden_layers=2).items()
        if k not in ("hyper", "vocab_size")}
    prng.seed_all(5)
    refuse = mock.patch.object(pattn, "unsupported_reason",
                               lambda t, dh: "refused for the test")
    with _pallas_interpret(True), refuse:
        assert pattn.direct_layout(128, 128)
        w = _arch_workflow(model, str(tmp_path / "corp"), max_epochs=1,
                           seq_len=128, minibatch_size=2)
        w.initialize(device=XLADevice())
        w.run()
    step = w.step
    assert np.isfinite(w.decision.metrics_history[-1]["metric_train"])
    assert step.attn_direct_layout_share == 1.0
    fam = registry.REGISTRY.get("znicz_lm_attn_direct_layout_share")
    assert fam is not None and fam.labels(unit=step.name).get() == 1.0
    # and the rows of the tile each blocked pass ran: the chooser's answer
    with refuse:
        want = pattn.kvb_block_rows(128, 128)
    assert want == {"fwd": 128, "dkv": 128, "dq": 128}
    assert step.attn_kvb_block_rows == want
    fam = registry.REGISTRY.get("znicz_lm_attn_kvb_block_rows")
    assert {name: fam.labels(**{"unit": step.name, "pass": name}).get()
            for name in want} == want

