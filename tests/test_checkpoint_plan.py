"""The checkpoint plan (``parallel/plan.py::checkpoint_plan``) held
to the compiler: the four benchmark cells whose layers are checkpointed by
``_loop_saves`` (``granite4h_micro_train_pp4_t8192``, whose state-space
stack keeps what a v5e has room for, ``ouro_train_pp8_t4096``, whose
looped stack keeps its own list, and ``nemotron3_nano_train_ep8_t8192``,
a stack of one-sub-layer layers with routed experts among them, and
``trinity_large_train_ep32_t8192``, window and full attention layers mixed)
compiled at their real widths for a TPU
v5e that is described and not attached, as ``benchmark/tests/
test_granite4h_rehearsal.py`` and ``test_ouro_rehearsal.py`` do, with the
plan a v5e's memory limit gives.

Each compile runs in a process of its own (``tests/v5e_step_compile.py``):
a worker that had loaded the TPU's library would disturb the profiler's
tests that run in it afterwards.  Skipped where no topology can be
described."""

import functools
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
GIB = 2 ** 30
HBM_USABLE = 15.75 * GIB          # what the runtime leaves of 16 GiB


@functools.lru_cache(maxsize=None)
def _compiled(config: str, traffic: str) -> dict:
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "TPU_LOG_DIR": "disabled"}
    env.pop("XLA_FLAGS", None)     # one CPU device is enough there
    done = subprocess.run(
        [sys.executable, os.path.join(HERE, "v5e_step_compile.py"), config,
         traffic, "15.75"], env=env, capture_output=True, text=True,
        timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def _compiled_or_skip(config: str, traffic: str) -> dict:
    """One compile a cell, whichever test asks first."""
    out = _compiled(config, traffic)
    if "skip" in out:
        pytest.skip(out["skip"])
    return out


@pytest.mark.parametrize("config,traffic,params,kept,tflop", [
    ("granite_4_0_h_micro", "train_tokens_pp4_t8192", 951_991_232,
     {"glu_wide": 10 * 2 * 8192 * 8192 * 2, "ssm_in": 9 * 8192 * 8512 * 2},
     (37.5, 38.6)),
    ("ouro_2_6b", "train_tokens_pp8_t4096", 509_661_185,
     {"glu_wide": 0}, (17.5, 18.7)),
    # a stack of one-sub-layer layers (M, E, *): the shared experts' wide
    # products count with the SwiGLUs', an E layer keeps its up-projections
    # and holds its experts' cast and gradients while its backward runs
    ("nemotron_3_nano_30b_a3b", "train_tokens_ep8_t8192", 986_254_848,
     {"glu_wide": 4 * 16384 * 3712 * 2, "ssm_in": 4 * 16384 * 10304 * 2},
     (26.0, 28.2)),
    # window and full attention layers mixed, a gated output, four norms a
    # layer, routed experts behind the fourth: checkpointed by policy (a
    # stack with window layers is one for long rows); the wide products (1
    # GiB) find no room beside the margin: 12.81 GiB compiled, 13.24 reckoned
    ("trinity_large_preview", "train_tokens_ep32_t8192", 1_604_388_096,
     {"glu_wide": 0}, (32.5, 33.9)),
])
def test_the_planned_step_fits_a_v5e_and_the_footprint_holds(
        config, traffic, params, kept, tflop):
    """The compiled step's arguments and temporaries against the plan's
    arithmetic: the footprint reckoned for today's list plus what the plan
    keeps never stands under the compiler's count by more than the margin
    (nor over it by more: a formula that counts double would refuse what
    fits), the whole stays under the rehearsals' line of 0.9 x 15.75 GiB,
    and the compiler's operation count says the kept products are not made
    again (47.3 TFLOP with today's list in the granite cell, 39.2 with the
    plan's and the scan in ``jax.numpy``, 38.1 with the scan in its kernels,
    whose operations the compiler does not count; a looped stack's count,
    loop bodies counted once, is the parent's)."""
    out = _compiled_or_skip(config, traffic)
    assert out["params"] == params and out["limit"] == int(HBM_USABLE)
    assert out["plan"] == kept
    live = out["argument_bytes"] + out["temp_bytes"]
    reckoned = out["footprint"] + sum(kept.values())
    print(f"{config}: compiled {live / GIB:.3f} GiB, reckoned "
          f"{reckoned / GIB:.3f} (footprint {out['footprint'] / GIB:.3f} + "
          f"kept {sum(kept.values()) / GIB:.3f}), "
          f"{out['flops'] / 1e12:.3f} TFLOP")
    assert live < 0.9 * HBM_USABLE, f"{live / GIB:.2f} GiB"
    assert abs(live - reckoned) <= out["margin"], (live, reckoned)
    assert reckoned + out["margin"] <= HBM_USABLE
    assert tflop[0] < out["flops"] / 1e12 < tflop[1]


def test_the_delta_rule_cell_compiles_for_a_v5e_and_holds_its_kernels():
    """The Solar-Open2 cell's step (three delta-rule linear-attention layers
    and one gated grouped-query layer, routed experts in each, 1 x 8,192
    tokens) compiled for the described chip, which refuses what does not
    fit its 15.75 GiB: 1,420,941,120 parameters; the convolution's two
    kernels once a linear layer and the delta rule's two once a linear layer
    (``kda_delta_fwd`` three times, not six: a checkpointed layer does not
    run the forward kernel again), the plain blocked flash kernels once for
    the one grouped-query layer; no float32 array with two chunk-length
    axes, beside a head's 128 channels or at all (the scores and the inverse
    live in the kernels' VMEM); 12.97 GiB of arguments and temporaries by
    ``memory_analysis()`` (5.29 + 7.68; 15.71 with the rule in ``jax.numpy``,
    where the compiler's own report of the bytes in use at the fullest said
    15.07, PR 52), which the plan's footprint (13.61) stands within
    its margin of, so the plan still keeps nothing beside the policy's list
    (0.14 GiB of room: the shared experts' wide products are 0.16); 36.2
    TFLOP outside the kernels."""
    from znicz_tpu.ops.pallas import attention as pattn

    out = _compiled_or_skip("solar_open2_250b",
                            "train_tokens_ep32_kda_t8192")
    assert out["params"] == 1_420_941_120 and out["tokens"] == 8192
    assert out["delta_rule_layers"] == 3 and out["window_layers"] == 0
    assert out["conv_kernels"] == {"ssm_conv_fwd": 3, "ssm_conv_bwd": 3}
    assert out["attn_kernels"] == {
        **{rf"{name}\b": 0 for name in pattn.KVB_SWA_KERNEL_NAMES.values()},
        **{rf"{name}\b": 1 for name in (
            pattn.KVB_FWD_KERNEL_NAME, pattn.KVB_DKV_KERNEL_NAME,
            pattn.KVB_DQ_KERNEL_NAME)}}
    assert out["delta_kernels"] == {"kda_delta_fwd": 3, "kda_delta_bwd": 3}
    assert out["chunk_channel_squares"] == []
    assert out["delta_chunk_squares"] == []
    assert out["plan"] == {"glu_wide": 0, "kda_in": 0}
    live = out["argument_bytes"] + out["temp_bytes"]
    print(f"solar_open2_250b: compiled {live / GIB:.3f} GiB, footprint "
          f"{out['footprint'] / GIB:.3f}, {out['flops'] / 1e12:.3f} TFLOP")
    assert live <= HBM_USABLE, f"{live / GIB:.2f} GiB"
    assert abs(live - out["footprint"]) <= out["margin"]
    assert 35.0 < out["flops"] / 1e12 < 37.5


def test_the_window_cell_holds_the_windowed_kernels_once_a_layer_a_pass():
    """The Trinity cell's compiled step (what a v5e would answer): each of
    the three windowed blocked kernels once a window layer (four: a
    checkpointed layer does not run its forward kernel again) and each of
    the three plain blocked kernels once, for the one full layer; 12.81 GiB
    of arguments and temporaries, under the 13-14 ISSUE 48 reckoned."""
    from znicz_tpu.ops.pallas import attention as pattn

    out = _compiled_or_skip("trinity_large_preview",
                            "train_tokens_ep32_t8192")
    assert out["window_layers"] == 4
    want = {rf"{name}\b": 4 for name in pattn.KVB_SWA_KERNEL_NAMES.values()}
    want.update({rf"{name}\b": 1 for name in (
        pattn.KVB_FWD_KERNEL_NAME, pattn.KVB_DKV_KERNEL_NAME,
        pattn.KVB_DQ_KERNEL_NAME)})
    assert out["attn_kernels"] == want
    live = out["argument_bytes"] + out["temp_bytes"]
    assert 12.5 * GIB < live < 13.1 * GIB, live / GIB


@pytest.mark.parametrize("config,traffic,layers", [
    ("granite_4_0_h_micro", "train_tokens_pp4_t8192", 9),
    ("nemotron_3_nano_30b_a3b", "train_tokens_ep8_t8192", 4),
])
def test_both_state_space_cells_hold_the_scan_kernels_and_no_chunk_square(
        config, traffic, layers):
    """The same compiled steps (what a v5e would answer: the scan's kernels
    wherever their shapes take them, ``ops/pallas/ssd.py``): every
    state-space layer holds the forward kernel once (a checkpointed layer
    does not run it again) and the backward kernel once, and no float32
    array with two chunk-length axes is left among the step's arrays."""
    from znicz_tpu.ops.pallas import ssd

    out = _compiled_or_skip(config, traffic)
    assert out["state_space_layers"] == layers
    assert out["scan_kernels"] == {ssd.FWD_KERNEL_NAME: layers,
                                   ssd.BWD_KERNEL_NAME: layers}
    assert out["chunk_squares"] == []


@pytest.mark.parametrize("config,traffic,layers,most_gib", [
    ("granite_4_0_h_micro", "train_tokens_pp4_t8192", 9, 12.95),
    # 12.38 GiB before the gate's kernels (PR 49), 12.68 with them
    ("nemotron_3_nano_30b_a3b", "train_tokens_ep8_t8192", 4, 12.75),
])
def test_both_state_space_cells_hold_the_convolution_kernels_and_no_wide_sum(
        config, traffic, layers, most_gib):
    """The same compiled steps hold the convolution's kernels
    (``ops/pallas/ssm_conv.py``) as they hold the scan's: the forward
    kernel once a state-space layer (its result is kept: a checkpointed
    layer does not run it again) and the backward kernel once; no
    instruction of the step writes a float32 array as long as a row and as
    wide as the convolution's channels (the ``jax.numpy`` form's sum, its
    padded operand, its shifted cotangents); and arguments plus temporaries
    stand over the 12.55 / 11.64 GiB the steps counted before the kernels
    (``PERF.md`` section 5, PR 46) by no more than the kept results, 0.598
    and 0.750 GiB."""
    from znicz_tpu.ops.pallas import ssm_conv

    out = _compiled_or_skip(config, traffic)
    assert out["conv_kernels"] == {ssm_conv.FWD_KERNEL_NAME: layers,
                                   ssm_conv.BWD_KERNEL_NAME: layers}
    assert out["conv_wide_f32"] == []
    live = out["argument_bytes"] + out["temp_bytes"]
    assert live <= most_gib * GIB, live / GIB


@pytest.mark.parametrize("config,traffic,kernels", [
    # one group: refused by shape, the parent's program
    ("granite_4_0_h_micro", "train_tokens_pp4_t8192", 0),
    ("nemotron_3_nano_30b_a3b", "train_tokens_ep8_t8192", 4),
])
def test_the_gate_kernels_stand_where_groups_are_several_and_keep_nothing(
        config, traffic, kernels):
    """The same compiled steps and the gate's kernels (``ops/pallas/
    ssm_gate.py``): in the Nemotron cell (eight groups) the forward kernel
    stands once a state-space layer and the backward kernel once, though
    NOTHING of the forward kernel's is kept (the output product stands
    inside their ``custom_vjp``, so no recomputation wants the gated rows:
    ``tests/test_ssm_gate_kernel.py`` counts the residuals), and no
    instruction of the step writes an array a (token, group) a row, the
    ``jax.numpy`` form's view; in the Granite cell (one group) neither
    kernel stands (the test above holds the steps' GiB).  The plan is the
    parent's in both (what the first test pins: ``glu_wide`` and ``ssm_in``
    kept with the bytes PR 47 counted), since its footprint counts nothing
    new."""
    from znicz_tpu.ops.pallas import ssm_gate

    out = _compiled_or_skip(config, traffic)
    assert out["gate_kernels"] == {ssm_gate.FWD_KERNEL_NAME: kernels,
                                   ssm_gate.BWD_KERNEL_NAME: kernels}
    assert out["group_rows"] == []


@pytest.mark.parametrize("config,tokens,footprint,kept", [
    ("granite_4_0_h_micro", 8192, 10_727_286_400,
     {"glu_wide": 2_684_354_560, "ssm_in": 1_255_145_472}),
    ("nemotron_3_nano_30b_a3b", 16384, 12_610_589_696,
     {"glu_wide": 486_539_264, "ssm_in": 1_350_565_888}),
])
def test_the_plan_counts_what_it_counted_before_the_gates_kernels(
        config, tokens, footprint, kept):
    """The arithmetic alone (no compile): the gate's kernels leave nothing
    kept, so ``step_footprint`` and the plan at a v5e's limit are PR 47's to
    the byte in both state-space cells; at the Granite cell's shape
    ``glu_wide`` and ``ssm_in`` stay on the kept list (0.09 GiB to spare: a
    kept gate result of 0.56 GiB would have cost one of them)."""
    from znicz_tpu.parallel import transformer as tfm
    from znicz_tpu.parallel.plan import (PLAN_MARGIN, checkpoint_plan,
                                         step_footprint)

    with open(os.path.join(os.path.dirname(HERE), "benchmark", "configs",
                           config + ".json")) as f:
        cfg = json.load(f)
    opts = cfg["builders"]["lm_train_keys"]
    arch = tfm.arch_from_config({k: cfg[k] for k in opts["model_keys"]})
    assert step_footprint(arch, tokens, 2, opts["loss_chunks"]) == footprint
    assert checkpoint_plan(arch, tokens, 2, int(HBM_USABLE),
                           opts["loss_chunks"]) == kept
    assert footprint + sum(kept.values()) + PLAN_MARGIN <= HBM_USABLE
