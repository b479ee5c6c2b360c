"""What the ``KeyeVL2`` family brought to ``parallel/transformer.py``
(learned sparse attention: an indexer on every attention layer that picks
the keys a query attends to and is trained by its alignment loss; experts
routed by a softmax), at tiny widths on the CPU against the benchmark's
plain reference (``benchmark/reference/keye_vl2.py``): loss, both terms and
every gradient with a selection that bites, with and without the Pallas
kernels interpreted; where the alignment term's gradient goes and where it
does not; the threshold by counting against a sort; the eight shares adding
up to the uncut routed layer; multi-axis rotary positions on text; the
refusals by name; the step unit's counters."""

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

from reference import keye_vl2 as ref                      # noqa: E402

from znicz_tpu.ops.pallas import attention as pattn        # noqa: E402
from znicz_tpu.parallel import dsa, moe, transformer as tfm  # noqa: E402
from znicz_tpu.parallel.arch import mechanisms_of_params   # noqa: E402
from znicz_tpu.parallel.blocks import _rotate              # noqa: E402
from znicz_tpu.parallel.mesh import make_mesh              # noqa: E402

TINY = {
    "model_type": "KeyeVL2", "hidden_size": 32, "intermediate_size": 96,
    "moe_intermediate_size": 24, "hidden_act": "silu",
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "attention_bias": False, "num_hidden_layers": 2,
    "decoder_sparse_step": 1, "mlp_only_layers": [], "num_experts": 4,
    "router_width": 8, "experts_held": {"first": 2, "count": 4},
    "num_experts_per_tok": 2, "norm_topk_prob": True,
    "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 8},
    "rms_norm_eps": 1e-6, "rope_theta": 10000000,
    "rope_scaling": {"mrope_section": [2, 3, 3], "rope_type": "default",
                     "type": "default"},
    "sliding_window": None, "use_sliding_window": False,
    "tie_word_embeddings": False, "vocab_size": 53,
    "hyper": {"lr": 0.05},
}
TRAFFIC = {"minibatch_size": 2, "seq_len": 32}
INDEXER = ("wiq", "wik", "wiw", "ik_g", "ik_b")


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
    """What the benchmark's builder reads off the timed step: losses, the
    alignment term, each leaf's first gradient as plain SGD applied it,
    each leaf's change, the last step's counters."""
    arch, lr = _arch(cfg), cfg["hyper"]["lr"]
    step, _ = tfm.make_train_step(_mesh1(), arch, lr=lr, stats=True,
                                  loss_chunks=2, compute_dtype=jnp.float32)
    p0 = ref.init_params(seed, cfg)
    b, t = traffic["minibatch_size"], traffic["seq_len"]
    params, losses, index, grads = p0, [], [], None
    for s in range(steps):
        rows = ref.make_tokens(seed, cfg, t, s * b, (s + 1) * b)
        params, loss, stats = step(params, jnp.asarray(rows[:, :-1]),
                                   jnp.asarray(rows[:, 1:]))
        losses.append(float(loss))
        index.append(float(stats["loss_index"]))
        if s == 0:
            grads = jax.tree.map(lambda a, c: np.asarray(a - c) / lr, p0,
                                 params)
    deltas = jax.tree.map(lambda a, c: float(jnp.linalg.norm(a - c)), p0,
                          params)
    return losses, index, _named(cfg, grads), _named(cfg, deltas), stats


def _check_gradients(grads, want, norm_rel=2e-3, diff_rel=5e-3):
    assert set(grads) == set(want["grad_norm"])
    for name, g in grads.items():
        assert np.linalg.norm(g) == pytest.approx(
            want["grad_norm"][name], rel=norm_rel, abs=2e-6), name
    for name, g in want["grad_first"].items():
        scale = max(np.linalg.norm(g), 1e-6)
        assert np.linalg.norm(grads[name] - g) / scale < diff_rel, name


def test_first_three_steps_follow_the_reference_with_a_selection_that_bites():
    """Two layers, 32 positions, 8 keys a query (24 of 32 rows choose):
    three steps' losses, the alignment term, every leaf's first gradient
    and change, and the counters of the selection."""
    cfg = _cfg()
    want = ref.first_steps(11, cfg, TRAFFIC, 1)
    losses, index, grads, deltas, stats = _program_first_steps(cfg, 11)
    np.testing.assert_allclose(losses, want["loss"], rtol=5e-5)
    np.testing.assert_allclose(index, want["loss_index"], rtol=5e-5)
    assert want["loss_index"][0] > 0.01 * want["loss"][0]
    _check_gradients(grads, want)
    assert set(want["grad_first"]) >= {f"B1.{k}" for k in INDEXER}
    assert set(deltas) == set(want["delta_norm"])
    for name, value in deltas.items():
        assert value == pytest.approx(want["delta_norm"][name], rel=5e-3,
                                      abs=1e-7), name
    layers, b, t = 2, 2, 32
    pairs = ref.selected_pairs(t, 8)
    assert pairs == 8 * 9 // 2 + 24 * 8
    assert float(stats["dsa_selected"]) == layers * b * pairs
    assert float(stats["dsa_pairs"]) == layers * b * t * (t + 1) // 2
    assert float(stats["dsa_live_tiles"]) == float(stats["dsa_tiles"]) \
        == layers * b


@pytest.mark.parametrize("head_dim,index_dim,align_kernel", [
    (64, 8, False), (128, 8, True), (128, 64, True), (64, 16, False)])
def test_the_step_with_its_kernels_interpreted_follows_the_reference(
        head_dim, index_dim, align_kernel):
    """``engine.pallas_interpret`` puts the blocked flash kernels WITH the
    selection operand into the step (and no other attention kernel), at
    256 positions, two 128-row tiles a side: loss, the alignment term and
    every leaf's gradient stay the reference's.  At a head of 64 the
    alignment target is the ``jax.numpy`` form's (the kernel refuses the
    width), at 128 the kernel's, whose name then stands in the step; the
    index scores and their gradients are their two kernels' at any index
    width (the benchmark's 64 among them), beside either."""
    from test_lfm2_arch import _pallas_interpret
    from znicz_tpu.ops.pallas import dsa as pdsa

    rope = head_dim // 2
    cfg = _cfg(hidden_size=64, head_dim=head_dim, num_attention_heads=2,
               num_key_value_heads=1, num_hidden_layers=1,
               sa_config={**TINY["sa_config"], "topk": 48,
                          "indexer_head_dim": index_dim},
               rope_scaling={"mrope_section": [rope // 4, 3 * rope // 8,
                                               3 * rope // 8],
                             "rope_type": "default", "type": "default"})
    traffic = {"minibatch_size": 1, "seq_len": 256}
    want = ref.first_steps(5, cfg, traffic, 1, steps=1)
    with _pallas_interpret(True):
        arch = _arch(cfg)
        chose = tfm.step_choices(_mesh1(), arch, 1, 256)
        assert (chose["dsa_index_kernel_share"],
                chose["dsa_align_kernel_share"]) == (1.0, float(align_kernel))
        text = str(jax.make_jaxpr(tfm.make_train_step(
            _mesh1(), arch, compute_dtype=jnp.float32)[0])(
                ref.init_params(5, cfg), jnp.zeros((1, 256), jnp.int32),
                jnp.zeros((1, 256), jnp.int32)))
        losses, index, grads, _, stats = _program_first_steps(
            cfg, 5, traffic, steps=1)
    for name in pattn.KVB_SEL_KERNEL_NAMES.values():
        assert name in text
    for name in (pattn.FWD_KERNEL_NAME, pattn.KVB_FWD_KERNEL_NAME + '"',
                 pattn.KVB_DQ_KERNEL_NAME + '"'):
        assert name not in text
    assert (pdsa.ALIGN_KERNEL_NAME in text) == align_kernel
    for name in (pdsa.INDEX_SCORES_KERNEL_NAME, pdsa.INDEX_GRADS_KERNEL_NAME):
        assert name in text
    assert losses[0] == pytest.approx(want["loss"][0], rel=2e-5)
    assert index[0] == pytest.approx(want["loss_index"][0], rel=2e-5)
    _check_gradients(grads, want, norm_rel=5e-3, diff_rel=1e-2)
    assert float(stats["attn_flash"]) == 1.0
    # tiles of 256 rows at this length: one, and it is live
    assert float(stats["dsa_tiles"]) == 1.0


def test_the_alignment_term_trains_the_indexer_and_nothing_else():
    """Gradients of the two terms apart, through the program's own forward:
    the alignment term reaches the indexer's five leaves of every layer
    and no other leaf; the cross-entropy reaches every other leaf and none
    of the indexer's."""
    from jax.sharding import PartitionSpec as P
    from znicz_tpu.parallel.compat import shard_map

    cfg = _cfg()
    arch, mesh = _arch(cfg), _mesh1()
    run = tfm._run_of(mesh, arch)
    params = ref.init_params(3, cfg)
    rows = ref.make_tokens(3, cfg, 32, 0, 2)

    def both(ps, tok, lab):
        def terms(ps):
            loss, stats = tfm._forward_ce(ps, tok, lab, None, arch, run,
                                          jnp.float32)
            return loss - stats["loss_index"], stats["loss_index"]

        return (jax.grad(lambda ps: terms(ps)[0])(ps),
                jax.grad(lambda ps: terms(ps)[1])(ps))

    specs, rows_spec = tfm.param_specs(arch), P("data", "seq")
    g_ce, g_index = jax.jit(shard_map(
        both, mesh=mesh, in_specs=(specs, rows_spec, rows_spec),
        out_specs=(specs, specs)))(
            params, jnp.asarray(rows[:, :-1]), jnp.asarray(rows[:, 1:]))
    g_ce, g_index = _named(cfg, g_ce), _named(cfg, g_index)
    for name in g_ce:
        leaf = name.rsplit(".", 1)[-1]
        ce, index = (float(jnp.abs(g[name]).max()) for g in (g_ce, g_index))
        if leaf in INDEXER:
            assert ce == 0.0 and index > 0.0, name
        else:
            assert index == 0.0 and ce > 0.0, name


@pytest.mark.parametrize("t,top_k", [(64, 16), (512, 100), (96, 200)])
def test_the_threshold_by_counting_selects_what_a_sort_selects(t, top_k):
    """``dsa.index_select_align`` (blocks of queries, the row's threshold
    built two bits a pass) against the dense arithmetic with
    ``lax.top_k``: the same selection pair for pair, the same loss, the
    same three gradients; at 96 positions no row has 200 keys and every
    causal pair is selected."""
    b, h, kv, dh, hi, di = 2, 4, 2, 16, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(t), 5)
    qi = jax.random.normal(ks[0], (b, t, hi, di))
    ki = jax.random.normal(ks[1], (b, t, di))
    w = jax.random.normal(ks[2], (b, t, hi))
    q = jax.random.normal(ks[3], (b, t, h, dh))
    k = jax.random.normal(ks[4], (b, t, kv, dh))

    def dense(qi, ki, w):
        s = jnp.einsum("bqjd,bkd->bqjk", qi, ki)
        index = (jnp.maximum(s, 0) * w[..., None]).sum(2)
        causal = jnp.tril(jnp.ones((t, t), bool))
        _, top = jax.lax.top_k(jnp.where(causal, index, -jnp.inf),
                               min(top_k, t))
        sel = jax.vmap(jax.vmap(lambda r, i: r.at[i].set(True)))(
            jnp.zeros((b, t, t), bool), top) & causal
        a = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, h // kv, 2))
        a = jnp.where(sel[:, None], a / np.sqrt(dh), -jnp.inf)
        p = jax.lax.stop_gradient(jax.nn.softmax(a, -1).mean(1))
        logq = jax.nn.log_softmax(jnp.where(sel, index, -jnp.inf), -1)
        live = sel & (p > 0)
        kl = jnp.where(live, p * (jnp.log(jnp.where(live, p, 1.0)) -
                                  jnp.where(live, logq, 0.0)), 0.0)
        return kl.sum(-1).mean(), sel

    with jax.default_matmul_precision("highest"):
        sel, loss = dsa.index_select_align(qi, ki, w, q, k, top_k, "t")
        (want, want_sel), g_want = jax.value_and_grad(
            dense, (0, 1, 2), has_aux=True)(qi, ki, w)
        g_got = jax.grad(lambda *a: dsa.index_select_align(
            *a, q, k, top_k, "t")[1], (0, 1, 2))(qi, ki, w)
    assert sel.dtype == jnp.int8
    np.testing.assert_array_equal(sel != 0, want_sel)
    per_row = np.minimum(np.arange(t) + 1, top_k)
    np.testing.assert_array_equal((sel != 0).sum(-1),
                                  np.broadcast_to(per_row, (b, t)))
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    for got, g in zip(g_got, g_want):
        np.testing.assert_allclose(got, g, atol=1e-5 * float(
            jnp.abs(g).max()) + 1e-9)


def test_kth_largest_key_is_the_sorted_rows_kth():
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 64)) * 1e3
    x = x.at[:, :5].set(jnp.asarray([0.0, -5.0, 1e-30, -1e-30, 7.0]))
    keys = dsa.sortable_keys(x)
    assert int(keys.min()) > 0
    np.testing.assert_array_equal(jnp.argsort(keys, -1), jnp.argsort(x, -1))
    top = jnp.sort(keys, -1)[:, ::-1]
    for k in (1, 5, 16, 64):
        np.testing.assert_array_equal(dsa.kth_largest_key(keys, k),
                                      top[:, k - 1])
    # fewer than k keys above 0: the threshold is 0
    np.testing.assert_array_equal(dsa.kth_largest_key(keys, 65),
                                  np.zeros(8, np.uint32))


def test_the_eight_shares_are_the_uncut_routed_layer():
    """128 -> 8 experts split 8 x 1 (the cell's eight chips at one expert
    each): the eight shares' routed parts, from the program's layer with
    the softmax router, add up to the uncut reference's routed layer, and
    every (token, choice) pair is computed once."""
    cfg = _cfg(num_hidden_layers=1, num_experts=8,
               experts_held={"first": 0, "count": 8})
    dm = ref.dims(cfg)
    p = ref.init_leaf_group(3, cfg, "B0")
    v = jax.random.normal(jax.random.PRNGKey(3), (24, dm["d"]))
    ident = lambda a: a                                     # noqa: E731
    with jax.default_matmul_precision("highest"):
        uncut = ref.routed(p, v, dm, ident, ident)
        parts, pairs = [], 0.0
        for first in range(8):
            held = slice(first, first + 1)
            y, stats = moe.moe_routed_ffn(
                v, p["gate"], None, p["ew1"][held], p["ew3"][held],
                p["ew2"][held], first=first, top_k=dm["top_k"],
                score="softmax", norm_topk=True)
            parts.append(y)
            pairs += float(stats["pairs_held"])
            share = ref.routed(
                {**p, "ew1": p["ew1"][held], "ew3": p["ew3"][held],
                 "ew2": p["ew2"][held]}, v,
                {**dm, "first": first, "held": 1}, ident, ident)
            np.testing.assert_allclose(y, share, atol=5e-6)
    np.testing.assert_allclose(sum(parts), uncut, atol=5e-6)
    assert pairs == v.shape[0] * dm["top_k"]
    arch = _arch(cfg)
    assert (arch.score, arch.n_experts, arch.experts_held, arch.top_k,
            arch.expert_bias, arch.shared_ff) == ("softmax", 8, 8, 2, False,
                                                  0)


def test_three_equal_position_streams_are_plain_rotate_half():
    """The reference's M-RoPE with the three streams of a text token (all
    ``0 .. t-1``) over sections [16, 24, 24] is the program's ``_rotate``
    over the whole head; a stream of its own moves its section only."""
    t, heads, dh = 12, 3, 128
    x = jax.random.normal(jax.random.PRNGKey(1), (t, heads, dh))
    text = ref._text_positions(t, 3)
    got = ref.mrope(x, text, 1e7, [16, 24, 24])
    np.testing.assert_allclose(got, _rotate(x[None], 1e7)[0], atol=2e-6)
    np.testing.assert_allclose(got, ref.mrope(x, text[:1], 1e7), atol=0)
    moved = ref.mrope(x, np.stack([text[0], text[1] + 5, text[2]]), 1e7,
                      [16, 24, 24])
    same = np.isclose(moved, got, atol=1e-7).all((0, 1))
    frequency = np.arange(dh) % 64
    np.testing.assert_array_equal(same, ~((frequency >= 16) &
                                          (frequency < 40)))
    with pytest.raises(ValueError, match="sections"):
        ref.mrope(x, text, 1e7, [16, 24, 20])


@pytest.mark.parametrize("change,match", [
    ({"sliding_window": 4096}, "sliding_window"),
    ({"use_sliding_window": True}, "sliding_window"),
    ({"mlp_only_layers": [0]}, "mlp_only_layers"),
    ({"decoder_sparse_step": 2}, "decoder_sparse_step"),
    ({"rope_scaling": {"mrope_section": [2, 3, 2], "rope_type": "default"}},
     "mrope_section"),
    ({"rope_scaling": {"rope_type": "yarn", "factor": 4.0}}, "rope_scaling"),
    ({"attention_bias": True}, "attention_bias"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"sa_config": {**TINY["sa_config"], "indexer_num_kv_heads": 2}},
     "indexer_num_kv_heads"),
    ({"experts_held": {"first": 6, "count": 4}}, "experts_held"),
    ({"model_type": "KeyeVL3"}, "model_type"),
])
def test_keys_the_stack_cannot_honour_are_refused_by_name(change, match):
    with pytest.raises(ValueError, match=match):
        _arch(_cfg(**change))


def test_the_family_reads_into_the_arch_and_its_leaves():
    cfg = _cfg()
    arch = _arch(cfg)
    assert (arch.index_heads, arch.index_dim, arch.index_top_k) == (16, 8, 8)
    assert arch.qk_norm and arch.final_norm and not arch.tied
    assert arch.kv_heads == 2 and arch.rope_theta == 1e7
    assert set(arch.ffns) == {"moe_routed"} and arch.score == "softmax"
    assert "learned sparse attention (indexer)" in arch.mechanisms()
    shapes = tfm.param_shapes(arch)
    assert {k: shapes["blocks"][0][k] for k in INDEXER} == {
        "wiq": (32, 128), "wik": (32, 8), "wiw": (32, 16), "ik_g": (8,),
        "ik_b": (8,)}
    assert jax.tree.map(np.shape, ref.init_params(1, cfg)) == \
        jax.tree.map(tuple, shapes, is_leaf=lambda x: isinstance(x, tuple))
    assert "learned sparse attention (indexer)" in \
        mechanisms_of_params(ref.init_params(1, cfg))
    with pytest.raises(ValueError, match="index_top_k"):
        dataclasses.replace(arch, mtp=True)


def test_the_indexer_refuses_a_sharded_mesh_by_name(cpu_devices):
    two = make_mesh({"data": 1, "seq": 1, "model": 2}, jax.devices()[:2])
    with pytest.raises(ValueError, match="indexer"):
        tfm.make_train_step(two, _arch(_cfg()))


def test_the_unit_publishes_the_selections_counters(tmp_path):
    """``TransformerLMStep(arch=...)`` under the benchmark's control graph
    on the reference's seeded weights and rows: an epoch of three steps
    folds the indexers' counts and the alignment term into the pass's sums
    and publishes them once: the unit's mirror (``dsa_counters``) and the
    ``znicz_lm_dsa_*`` gauges; ``loss_terms`` stays an MTP stack's."""
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
    got = step.dsa_counters
    assert set(got) == {"selected_share", "live_tile_share", "index_loss",
                        "index_loss_share"}
    assert got["selected_share"] == pytest.approx(
        ref.selected_pairs(32, 8) / (32 * 33 / 2), rel=1e-6)
    assert got["live_tile_share"] == 1.0
    assert got["index_loss"] == pytest.approx(np.mean(want["loss_index"]),
                                              rel=2e-4)
    assert got["index_loss_share"] == pytest.approx(
        np.mean(want["loss_index"]) / np.mean(want["loss"]), rel=2e-4)
    assert w.decision.metrics_history[-1]["metric_train"] == pytest.approx(
        np.mean(want["loss"]), rel=2e-4)
    assert step.loss_terms == {} and step.loop_counters == {}
    assert step.moe_counters["pairs_held_per_step"] > 0
    for key, value in got.items():
        fam = registry.REGISTRY.get(f"znicz_lm_dsa_{key}")
        assert fam is not None and fam.labels(unit=step.name).get() == value
    # 32 positions are no block of queries: the jax.numpy form, and the
    # gauge says so
    assert step.dsa_align_kernel_share == step.dsa_index_kernel_share == 0.0
    for what in ("align", "index"):
        fam = registry.REGISTRY.get(f"znicz_lm_dsa_{what}_kernel_share")
        assert fam is not None and fam.labels(unit=step.name).get() == 0.0
    with pytest.raises(ValueError, match="indexer"):
        step.export_lm(str(tmp_path / "pkg.npz"))
    state = step.state_dict()
    step.load_state_dict(state)
    state["params"]["blocks"][0].pop("wik")
    with pytest.raises(ValueError, match="architecture"):
        step.load_state_dict(state)


def _built_step(head_dim, seq_len, interpret):
    """A one-layer step unit with an indexer, built (not run) at ``seq_len``
    positions with the step's kernels interpreted or not."""
    from builders import lm_train_keys
    from test_lfm2_arch import _pallas_interpret
    from znicz_tpu.core.backends import XLADevice

    rope = head_dim // 2
    cfg = {**_cfg(hidden_size=64, head_dim=head_dim, num_attention_heads=2,
                  num_key_value_heads=1, num_hidden_layers=1,
                  rope_scaling={"mrope_section": [rope // 4, 3 * rope // 8,
                                                  3 * rope // 8],
                                "rope_type": "default", "type": "default"}),
           "builders": {"lm_train_keys": {
               "model_keys": [k for k in TINY if k != "hyper"],
               "loss_chunks": 2}}}
    traffic = {"minibatch_size": 1, "seq_len": seq_len}
    rows = ref.make_tokens(17, cfg, seq_len, 0, 1)
    with _pallas_interpret(interpret):
        w = lm_train_keys.build_workflow(rows, cfg, traffic)
        w.step._params = ref.init_params(17, cfg)
        w.initialize(device=XLADevice())
    return w.step


@pytest.mark.parametrize("head_dim,seq_len,interpret,share", [
    (128, 256, True, 1.0),      # the kernel's shape, kernels interpreted
    (128, 256, False, 0.0),     # the same shape on this backend as it is
    (64, 256, True, 0.0),       # a head the kernel refuses
    (128, 96, True, 0.0),       # no whole blocks of queries
])
def test_the_unit_publishes_the_alignment_kernels_share(head_dim, seq_len,
                                                        interpret, share):
    """``znicz_lm_dsa_align_kernel_share`` and the unit's mirror, set as
    the step is built from what :func:`dsa.align_kernel_refusal` says of
    its shape: 1.0 where the kernel makes the target, 0.0 where the shape
    or the backend leaves it to the ``jax.numpy`` form."""
    from znicz_tpu.observe import registry

    step = _built_step(head_dim, seq_len, interpret)
    assert step.dsa_align_kernel_share == share
    fam = registry.REGISTRY.get("znicz_lm_dsa_align_kernel_share")
    assert fam is not None and fam.labels(unit=step.name).get() == share


@pytest.mark.parametrize("head_dim,seq_len,interpret,share", [
    (64, 256, True, 1.0),       # whole blocks of queries, kernels interpreted
    (64, 256, False, 0.0),      # the same shape on this backend as it is
    (128, 96, True, 0.0),       # no whole blocks of queries
])
def test_the_unit_publishes_the_index_kernels_share(head_dim, seq_len,
                                                    interpret, share):
    """``znicz_lm_dsa_index_kernel_share`` and the unit's mirror, set as the
    step is built from what :func:`dsa.index_kernel_refusal` says of its
    shape: 1.0 where the two kernels make the index scores and their
    gradients (whatever the attention's head, which is the alignment
    kernel's affair), 0.0 where the shape or the backend leaves them to the
    einsums."""
    from znicz_tpu.observe import registry

    step = _built_step(head_dim, seq_len, interpret)
    assert step.dsa_index_kernel_share == share
    fam = registry.REGISTRY.get("znicz_lm_dsa_index_kernel_share")
    assert fam is not None and fam.labels(unit=step.name).get() == share


def test_serving_refuses_the_indexer_by_name():
    from znicz_tpu.serve.kvcache import KVDecoder

    params = ref.init_params(1, _cfg())
    with pytest.raises(NotImplementedError, match="indexer"):
        KVDecoder(jax.tree.map(np.asarray, params), heads=4)
