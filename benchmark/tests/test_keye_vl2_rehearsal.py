"""CPU rehearsal of the ``keye_vl2_train_ep8_t16384`` cell: ``run.py`` end to
end over a tiny overlay of its configuration and traffic (every mechanism
kept: grouped-query attention with QK-norm, the indexer on every layer
with a selection that bites, the alignment term, softmax-routed experts of
which this share holds half, an untied head), the traced run's per-layer
metrics with the builder kind, the reader and the kernel count this cell
adds, the control that must come out as not correct (the reference in
fp8), a planted fault that must too, the refusal a program that cannot
read the family gives before the reference runs, the catalog's numbers in
the configuration file, and a compile-only rehearsal of the step at the
real widths for a v5e that is described and not attached, which reports
the compiled step's memory.
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

CELL = "keye_vl2_train_ep8_t16384"
CONFIG, TRAFFIC = "keye_vl_2_0_30b_a3b", "train_tokens_ep8_t16384"
TINY_KEYE = {
    "hidden_size": 64, "moe_intermediate_size": 32, "head_dim": 16,
    "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 61,
    "num_hidden_layers": 2, "num_experts": 4, "num_local_experts": 4,
    "router_width": 8, "experts_held": {"first": 0, "count": 4},
    "num_experts_per_tok": 2,
    "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 8},
    "rope_scaling": {"mrope_section": [2, 3, 3], "rope_type": "default",
                     "type": "default"},
    "hyper": {"lr": 0.05},
}
TINY_TOKENS = {"n_rows": 12, "minibatch_size": 2, "seq_len": 32,
               "k_steps": 2}
#: the catalog's row (``/opt/skills/guides/model-configs/
#: architectures.jsonl``, Keye-VL-2.0-30B-A3B): every number of its
#: ``config`` that the cut leaves as published
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 8,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False,
}


@pytest.fixture(scope="module")
def overlay(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("keye_overlay"))
    for kind, name, changes in (("configs", CONFIG, TINY_KEYE),
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


def test_the_cells_files_are_the_issues():
    roots = benchlib.Roots()
    bench = benchlib.benchmark_json(roots)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)
    assert len(cell["why"]) <= 200
    cfg, traffic = roots.data("configs", CONFIG), roots.data("traffic",
                                                             TRAFFIC)
    for key, value in PUBLISHED.items():
        assert cfg[key] == value, key
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "num_experts", "num_local_experts",
        "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 128,
                                "num_local_experts": 128,
                                "vocab_size": 151936}
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["num_local_experts"], cfg["vocab_size"]) == (4, 16, 16,
                                                             18992)
    assert cfg["vocab_size"] * 8 == 151936 and cfg["router_width"] == 128
    assert cfg["experts_held"] == {"first": 0, "count": 16}
    assert set(cfg["reduced"]) == set(cfg["how_reduced"])
    assert entry["source"] == cfg["source"] and len(entry["why"]) <= 200
    assert (traffic["builder"], traffic["generator"]) == ("lm_train_dsa",
                                                          "resident_rows")
    assert (traffic["n_rows"], traffic["minibatch_size"],
            traffic["seq_len"], traffic["k_steps"]) == (16, 1, 16384, 4)
    ref = roots.module("reference", cfg["reference"])
    rows = ref.make_tokens(5, cfg, 64, 0, 3)
    assert rows.shape == (3, 65) and rows.max() < 18992


def test_cell_runs_end_to_end_tiny(overlay):
    rc, result, outcome = _run(overlay, seed=2147483711)
    assert rc == 0 and result["correct"] is True, outcome["lines"]
    assert set(result["metrics"]) == {"train_samples_per_s", "setup_s"}
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(outcome["samples"]["readings"]) == {
        "loss_gap", "grad_norm_gap", "delta_norm_gap", "grad_diff_gap"}
    dsa = outcome["samples"]["dsa"]
    assert set(dsa) == {"selected_share", "live_tile_share", "index_loss",
                        "index_loss_share"}
    assert dsa["selected_share"] == pytest.approx(
        (8 * 9 / 2 + 24 * 8) / (32 * 33 / 2), rel=1e-6)
    assert 0.0 < dsa["index_loss_share"] < 1.0
    assert dsa["live_tile_share"] == 1.0
    assert any(ln.startswith("dsa (last class pass)")
               for ln in outcome["lines"])
    moe = outcome["samples"]["moe"]
    assert moe["pairs_held_per_step"] > 0 and "mtp_loss_share" not in moe


def test_traced_run_reports_every_metric_that_lists_the_cell(overlay):
    rc, result, outcome = _run(overlay, seed=13, seconds=2.0, trace=1)
    assert rc == 0 and result["correct"] is True, outcome["lines"]
    got = set(result["metrics"])
    # the device-trace readers find no TPU plane on the CPU; the program's
    # counters and spans are all there
    assert {"graph_ms_per_step", "train_step_rate_median",
            "dsa_selected_share", "dsa_live_tile_share",
            "dsa_index_loss_share", "moe_expert_load_max_over_mean",
            "moe_compact_share", "moe_gmm_tile_fill"} <= got
    bench = benchlib.benchmark_json(benchlib.Roots())
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [])}
    assert listed == {
        "attn_device_ms_per_step", "moe_route_device_ms_per_step",
        "moe_experts_device_ms_per_step", "ce_device_ms_per_step",
        "moe_expert_load_max_over_mean", "moe_compact_share",
        "moe_gmm_tile_fill", "moe_gmm_roofline",
        "dsa_index_device_ms_per_step", "dsa_select_device_ms_per_step",
        "dsa_align_device_ms_per_step", "dsa_selected_share",
        "dsa_live_tile_share", "dsa_index_loss_share",
        "flash_attn_dsa_roofline"}
    for name in listed:                 # each has its file and its reader
        spec = benchlib.Roots().data("metrics", name)
        benchlib.Roots().module("readers", spec["reader"])
    # nothing that was there was edited: the lists only grew, at their ends
    for m in bench["per_layer"] + bench["end_to_end"]:
        if CELL in m.get("workloads", []) and len(m["workloads"]) > 1:
            assert m["workloads"][-1] == CELL, m["name"]


def test_the_step_books_the_indexer_under_the_patterns_the_cell_lists():
    """The three new scopes are siblings of ``block<i>.attn`` by name, in
    every layer, forward and backward; each new pattern reads its own and
    the attention's pattern reads none of them."""
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

    for part in ("index", "select", "align"):
        metric = f"dsa_{part}_device_ms_per_step"
        assert matches(metric, f"block3.attn.{part}")
        assert not matches(metric, "block3.attn")
        assert not matches("attn_device_ms_per_step", f"block3.attn.{part}")
    cfg = {**benchlib.Roots().data("configs", CONFIG), **TINY_KEYE}
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
    want = {"embed", "ce", "update"}
    for i in range(2):
        want |= {f"block{i}.attn", f"block{i}.attn.index",
                 f"block{i}.attn.select", f"block{i}.attn.align",
                 f"block{i}.moe", f"block{i}.moe.route",
                 f"block{i}.moe.experts"}
    assert want <= seen, want - seen
    assert any(sc == "transpose(jvp(block1.attn.index))"
               for sc in scopes.values())
    bare = [n for n, sc in scopes.items() if not sc]
    assert len(bare) < 0.02 * len(scopes), bare


def test_kernel_counts_are_a_hand_count():
    """At 4 positions a query, top 2: rows select 1, 2, 2, 2 keys, 7 pairs;
    2 heads of 8: a product is 2 * 2 * 7 * 8 operations.  At the cell's
    size: 31,458,304 pairs a row of 134,225,920 causal ones (0.2344), four
    calls a step, 0.515 TFLOP a product; every kernel compute-bound even
    with the selection's 256 MiB counted."""
    roots = benchlib.Roots()
    kern = roots.module("kernels", "flash_attention_dsa")
    small = {"num_attention_heads": 2, "num_key_value_heads": 1,
             "head_dim": 8, "hidden_size": 16, "num_hidden_layers": 3,
             "sa_config": {"topk": 2}}
    calls = kern.calls_per_step(small, {"minibatch_size": 1, "seq_len": 4})
    assert kern.selected_pairs(4, 2) == 7
    product = 2.0 * 2 * 7 * 8
    assert [c["flops"] for c in calls] == [2 * product, 4 * product, product]
    assert all(c["count"] == 3 for c in calls)
    q_bytes, kv_bytes, row, sel = 2 * 4 * 2 * 8, 2 * 4 * 1 * 8, 4 * 2 * 4, 16
    assert [c["bytes"] for c in calls] == [
        2 * q_bytes + 2 * kv_bytes + row + sel,
        2 * q_bytes + 4 * kv_bytes + 2 * row + sel, q_bytes + sel]
    cfg, traffic = roots.data("configs", CONFIG), roots.data("traffic",
                                                             TRAFFIC)
    calls = kern.calls_per_step(cfg, traffic)
    from znicz_tpu.ops.pallas import attention as pattn
    assert [c["pattern"] for c in calls] == [
        pattn.KVB_SEL_KERNEL_NAMES[p] for p in ("fwd", "dkv", "dq")]
    assert pattn.kvb_block_rows(16384, 128, True) == {
        "fwd": 1024, "dkv": 1024, "dq": 1024}
    pairs = kern.selected_pairs(16384, 2048)
    assert pairs == 31_458_304
    assert pairs / (16384 * 16385 // 2) == pytest.approx(0.2344, abs=5e-5)
    product = 2.0 * 32 * pairs * 128
    assert [c["flops"] / product for c in calls] == [2, 4, 1]
    assert all(c["count"] == 4 for c in calls)
    least = sum(c["count"] * c["flops"] for c in calls) / 197e12
    assert least == pytest.approx(0.0366, rel=5e-3)
    assert all(c["flops"] / 197e12 > c["bytes"] / 819e9 for c in calls)
    ref = roots.module("reference", "keye_vl2")
    assert ref.selected_pairs(16384, 2048) == pairs
    # four layers and the head: 9.4 + 3.8 TFLOP of products a token passes,
    # 0.6 of indexer projections, 6.2 of selected attention, 1.6 of index
    assert ref.train_flops_per_sample(cfg, 16384) == pytest.approx(
        21.6e12, rel=0.02)
    # moe_gmm.py counts this configuration from its keys as the file stands
    gmm = roots.module("kernels", "moe_gmm").calls_per_step(
        {**cfg, "moe_pairs_held_per_step": 4 * 16384.0}, traffic)
    assert gmm[0]["count"] == 36 and gmm[0]["flops"] == pytest.approx(
        2.0 * 16384 * 2048 * 768)


def test_fp8_control_fails_a_limit(overlay):
    rc, result, outcome = _run(overlay, seed=5, control=True)
    assert rc == 0 and result["correct"] is True, outcome["lines"]
    ref = benchlib.Roots().module("reference", "keye_vl2")
    control = outcome["samples"]["control_readings"]
    assert any(control[k] > ref.LIMITS[k] for k in control), control


def test_a_planted_fault_comes_out_as_not_correct(overlay, monkeypatch):
    """The cell's own fault: an indexer that keeps half the keys the model
    keeps (``topk`` 4 for 8) is another selection, another attention and
    another alignment term."""
    from builders import lm_train_keys

    keys = lm_train_keys.arch_config
    monkeypatch.setattr(lm_train_keys, "arch_config", lambda cfg: {
        **keys(cfg), "sa_config": {**cfg["sa_config"], "topk": 4}})
    rc, result, outcome = _run(overlay, seed=11)
    assert rc == 0 and result["correct"] is False, outcome["lines"]
    ref = benchlib.Roots().module("reference", "keye_vl2")
    got = outcome["samples"]["readings"]
    assert sum(got[k] > ref.LIMITS[k] for k in got) >= 3, got


def test_a_program_that_cannot_read_the_family_is_refused_at_once(
        overlay, monkeypatch, capsys):
    """What the parent commit does with this cell: ``arch_from_config``
    refuses the ``model_type`` by name, and the run ends with exit code 1
    and no result line before the reference has run."""
    from znicz_tpu.parallel import transformer as tfm

    ref = benchlib.Roots().module("reference", "keye_vl2")
    monkeypatch.delitem(tfm._FAMILIES, "KeyeVL2")
    monkeypatch.setattr(ref, "first_steps", lambda *a, **k: pytest.fail(
        "the reference ran before the refusal"))
    rc, result, outcome = _run(overlay, seed=3)
    assert rc == 1 and result is None and outcome is None
    assert "KeyeVL2" in capsys.readouterr().err


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
    """The cell's step (four layers, 1 x 16,384 tokens, 18,992 ids)
    compiled for the described chip: each of the three blocked flash
    kernels WITH a selection four times and none without, the in-place row
    kernel for the rotary embedding, no ``(heads, t, t)`` array anywhere,
    and arguments plus temporaries that fit the chip.  That it compiles IS
    the check of its memory, and the compiler for a described chip counts
    what the chip's own does: both refused six layers with "Used 16.08G of
    15.75G hbm" (my chip run, PR 39); four count 14.0 GiB."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from znicz_tpu.ops.pallas import attention as pattn, rope as prope
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
    assert sum(math.prod(s.shape)
               for s in jax.tree.leaves(params)) == 465_391_104
    b, t = int(traffic["minibatch_size"]), int(traffic["seq_len"])
    tok = jax.ShapeDtypeStruct((b, t), jnp.int32,
                               sharding=NamedSharding(mesh, P("data", "seq")))
    mask = jax.ShapeDtypeStruct((b,), jnp.bool_,
                                sharding=NamedSharding(mesh, P("data")))
    compiled = step.lower(params, tok, tok, mask).compile()
    m = compiled.memory_analysis()
    print(f"compiled step for a described v5e: arguments "
          f"{m.argument_size_in_bytes / 2 ** 30:.3f} GiB (donated), "
          f"temporaries {m.temp_size_in_bytes / 2 ** 30:.3f} GiB")
    text = compiled.as_text()
    for name in pattn.KVB_SEL_KERNEL_NAMES.values():
        calls = [ln for ln in text.splitlines()
                 if "tpu_custom_call" in ln and name in ln]
        assert len(calls) == arch.n_layers, (name, len(calls))
    for name in (pattn.KVB_FWD_KERNEL_NAME, pattn.FWD_KERNEL_NAME):
        assert f'{name}"' not in text and f"{name}." not in text
    assert prope.KERNEL_NAME in text
    # one selection a layer, int8, shared by the heads; and no array as
    # large as (key/value heads, t, t) in any shape or dtype: the largest
    # are the selection itself and the routed layer's full pairs buffer,
    # t * t entries each
    assert f"s8[{b},{t},{t}]" in text
    largest = max(math.prod(int(n) for n in dims.split(","))
                  for dims in re.findall(r"[a-z]\w*\[([\d,]+)\]", text))
    assert largest <= t * t, largest
