"""The state-space layer's gate and gated group norm kernels (``ops/pallas/
ssm_gate.py``, called from ``parallel/ssm.py::mixer``), interpreted on the
CPU: against the ``jax.numpy`` form (the closing lines of ``mixer``) under
``jax.grad`` in values and every gradient (the scan's result, ``z`` in the
projection's lanes, the gain, the output product's weight; through ``mixer``,
every leaf) at one group and at eight; a statistic taken over the wrong lanes
against the same tolerance; each refusal by name, the one-group sentence
among them; a refused shape's fallback and its one log line; what a
checkpointed layer keeps of the kernels (nothing); the gauge that says which
form a step's state-space layers got.  Both benchmark cells' steps compiled
at their real widths for a described TPU v5e are ``tests/
test_checkpoint_plan.py``'s."""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(HERE), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import nemotron_h as nemotron_ref            # noqa: E402

import test_nemotron_h_arch as nemotron                     # noqa: E402
from test_lfm2_arch import _pallas_interpret                # noqa: E402
from znicz_tpu.ops.pallas import ssm_gate as pgate          # noqa: E402
from znicz_tpu.parallel import plan, ssm, transformer as tfm  # noqa: E402
from znicz_tpu.parallel.mesh import make_mesh               # noqa: E402

EPS = 1e-5
#: the lanes behind ``z`` in the projection (``xBC``), cut down
BEHIND = 384


def _operands(seed, rows, t, inner, groups, d, dtype):
    r = np.random.default_rng(seed)
    y = r.normal(size=(rows, t, inner))
    proj = r.normal(size=(rows, t, inner + BEHIND))
    g = 1.0 + 0.2 * r.normal(size=(groups, inner // groups))
    w_out = r.normal(size=(inner, d)) / np.sqrt(inner)
    w = r.normal(size=(rows, t, d))
    return (*(jnp.asarray(v, dtype) for v in (y, proj, g, w_out)),
            jnp.asarray(w, jnp.float32))


def _plain(y, proj, g, w_out):
    """The ``jax.numpy`` form: ``mixer``'s closing lines."""
    b, t, inner = y.shape
    groups = g.shape[0]
    z = proj[..., :inner]
    gated = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    gated = gated.reshape(b * t * groups, inner // groups)
    gated = gated * lax.rsqrt((gated * gated).mean(-1, keepdims=True) + EPS)
    gated = gated.astype(y.dtype).reshape(b, t, groups, -1) * g
    return gated.reshape(b, t, inner) @ w_out


def _kernels(y, proj, g, w_out):
    return pgate.gate_out(y, proj, g.reshape(1, -1), w_out, 0, g.shape[0],
                          EPS, True)


def _loss(form, w):
    return lambda *ops: (form(*ops).astype(jnp.float32) * w).sum()


def _rel(got, want):
    got, want = (jnp.asarray(v, jnp.float32) for v in (got, want))
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


# -- (a) values and gradients -------------------------------------------------

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("inner,groups,t,rows", [
    (1024, 8, 48, 16),      # eight groups of one lane tile, three tiles
    (256, 2, 256, 256),     # one tile of two pieces of 128 rows
    (4096, 8, 32, 32),      # the Nemotron cell's groups: four lane tiles
    (512, 1, 64, 64),       # one group: the whole row, four lane tiles
    (2048, 1, 64, 64),      # one group of sixteen lane tiles
])
def test_the_kernels_are_the_numpy_form_in_values_and_gradients(
        inner, groups, t, rows, dtype):
    """``ssm_gate.gate_out`` (interpreted) against ``mixer``'s closing lines:
    the result and the gradient of a random functional of it in the scan's
    result, the projection (zero outside ``z``), the gain and the output
    product's weight.  In float32 to rounding (the sums differ in order);
    with bfloat16 operands ``dy``, ``dz`` and the weight's gradient within a
    rounding, and the gain's gradient as near as the ``jax.numpy`` form's
    16-bit sum over the tokens stands to the kernel's float32 one."""
    dtype = jnp.dtype(dtype)
    y, proj, g, w_out, w = _operands(7, 2, t, inner, groups, 128, dtype)
    assert pgate.unsupported_reason(t, inner, groups, 0,
                                    dtype.itemsize) is None
    assert pgate.tile_rows(t, inner // groups, 2) == rows
    with jax.default_matmul_precision("highest"):
        out, want_out = _kernels(y, proj, g, w_out), _plain(y, proj, g, w_out)
        got = jax.grad(_loss(_kernels, w), argnums=(0, 1, 2, 3))(
            y, proj, g, w_out)
        want = jax.grad(_loss(_plain, w), argnums=(0, 1, 2, 3))(
            y, proj, g, w_out)
    assert out.dtype == dtype and out.shape == want_out.shape
    assert [v.dtype for v in got] == [v.dtype for v in want]
    assert [v.shape for v in got] == [v.shape for v in want]
    assert not np.asarray(got[1][..., inner:]).any()
    names = ("y", "proj", "gain", "w_out")
    if dtype == jnp.float32:
        assert _rel(out, want_out) < 2e-6
        for name, v, v_want in zip(names, got, want):
            assert _rel(v, v_want) < 2e-6, (name, _rel(v, v_want))
        return
    assert _rel(out, want_out) < 2e-3
    for name, v, v_want in zip(names, got, want):
        most = 3e-2 if name == "gain" else 4e-3
        assert _rel(v, v_want) < most, (name, _rel(v, v_want))


def test_a_statistic_over_the_wrong_lanes_fails_the_same_tolerance(
        monkeypatch):
    """The tolerance above is tight enough: with the statistic's sum taken
    over all of a group's lane tiles but the last (a group's width off by a
    tile), the result leaves the ``jax.numpy`` form by far more than
    rounding."""
    whole = pgate._over_lanes
    monkeypatch.setattr(pgate, "_over_lanes",
                        lambda parts: whole(parts[:-1]))
    jax.clear_caches()
    y, proj, g, w_out, _ = _operands(7, 1, 32, 4096, 8, 128, jnp.float32)
    try:
        with jax.default_matmul_precision("highest"):
            out = _kernels(y, proj, g, w_out)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    with jax.default_matmul_precision("highest"):
        want = _plain(y, proj, g, w_out)
        assert _rel(_kernels(y, proj, g, w_out), want) < 2e-6
    assert _rel(out, want) > 5e-2


def _nemotron_cfg(**over):
    return nemotron._cfg(num_hidden_layers=1, hybrid_override_pattern="M",
                         **over)


def _mixer_loss(cfg, w):
    """The layer alone, ``ssm.mixer`` on a normed stream, as a functional
    of the stream and the layer's leaves."""
    arch = nemotron._arch(cfg)

    def loss(u, p):
        out, _ = ssm.mixer(u, p, arch.ssm_heads, arch.ssm_head_dim,
                           arch.ssm_state, arch.ssm_chunk, arch.eps, "blk",
                           arch.ssm_groups)
        return (out.astype(jnp.float32) * w).sum()
    return loss, arch


def _mixer_operands(cfg, t, seed=4):
    p = jax.tree.map(jnp.asarray,
                     nemotron_ref.init_params(3, cfg)["blocks"][0])
    p = {k: v for k, v in p.items() if k.startswith("ssm_")}
    r = np.random.default_rng(seed)
    u = jnp.asarray(r.normal(size=(2, t, p["ssm_in"].shape[0])), jnp.float32)
    return u, p, jnp.asarray(r.normal(size=u.shape), jnp.float32)


#: two groups of 128 lanes, kernels of all three pairs from 128 positions
WIDE = dict(mamba_num_heads=16, mamba_head_dim=16, ssm_state_size=128,
            n_groups=2, chunk_size=128)


def test_the_kernels_inside_the_mixer_give_its_gradients_to_every_leaf():
    """Through ``mixer`` with the three pairs of kernels interpreted against
    ``mixer`` in ``jax.numpy``: the layer's output and the gradients to the
    stream and to every leaf, the gain (which lies a group a row), the
    output product's weight and the input projection's (``z``'s lanes by
    the gate's kernel, ``xBC``'s by the convolution's) among them, in
    float32 at the tolerance the scan's kernels hold."""
    cfg = _nemotron_cfg(**WIDE)
    u, p, w = _mixer_operands(cfg, 128)
    loss, arch = _mixer_loss(cfg, w)
    inner = arch.ssm_heads * arch.ssm_head_dim
    assert p["ssm_g"].shape == (2, inner // 2)
    with jax.default_matmul_precision("highest"):
        with _pallas_interpret(True):
            assert ssm.gate_kernel_refusal(128, inner, 2, 0, 4, True) is None
            text = str(jax.make_jaxpr(loss)(u, p))
            got = jax.grad(loss, argnums=(0, 1))(u, p)
        want = jax.grad(loss, argnums=(0, 1))(u, p)
    assert pgate.FWD_KERNEL_NAME in text
    assert _rel(got[0], want[0]) < 5e-5
    assert set(got[1]) == set(want[1])
    for name in want[1]:
        assert _rel(got[1][name], want[1][name]) < 5e-5, name


def test_a_checkpointed_layer_keeps_nothing_of_the_gates_kernels():
    """What the backward pass keeps, counted: under the layer's policy
    (``plan._loop_saves``) the residuals of ``mixer`` with the gate's
    kernels are those of ``mixer`` with the gate in ``jax.numpy`` and no
    array more (the output product stands inside the kernels' ``custom_vjp``
    and its rule reads operands alone; the weight's gradient takes the gated
    rows the backward kernel writes again), and the forward kernel stands
    ONCE in the differentiated layer: it is not run again to recompute."""
    cfg = _nemotron_cfg(**WIDE)
    u, p, w = _mixer_operands(cfg, 256)
    loss, arch = _mixer_loss(cfg, w)
    kept = jax.checkpoint(loss, policy=plan._loop_saves)

    def residuals():
        from jax._src.ad_checkpoint import saved_residuals

        jax.clear_caches()
        return sorted((str(aval.shape), str(aval.dtype))
                      for aval, _ in saved_residuals(kept, u, p))

    with _pallas_interpret(True):
        with_kernels = residuals()
        text = str(jax.make_jaxpr(jax.grad(kept, argnums=(0, 1)))(u, p))
        whole = ssm.gate_kernel_refusal
        ssm.gate_kernel_refusal = lambda *a, **k: "refused by the test"
        try:
            without = residuals()
        finally:
            ssm.gate_kernel_refusal = whole
            jax.clear_caches()
    assert with_kernels == without
    assert text.count(f"name={pgate.FWD_KERNEL_NAME}") == 1
    assert text.count(f"name={pgate.BWD_KERNEL_NAME}") == 1


# -- (b) the one question -----------------------------------------------------

SHAPE = dict(t=8192, inner=4096, groups=8, start=0, itemsize=2,
             interpret=True)


@pytest.mark.parametrize("over,why", [
    ({"interpret": False}, "the backend is cpu and the step's kernels are "
                           "not interpreted"),
    ({"groups": 1}, "one group: the statistic over a whole row has no "
                    "group-wise view, and the compiled jax.numpy form "
                    "already stands at its traffic's least there"),
    ({"groups": 64}, "a group of 4096 / 64 entries is not whole tiles of "
                     "128 lanes"),
    ({"groups": 3}, "a group of 4096 / 3 entries is not whole tiles of 128 "
                    "lanes"),
    ({"start": 256}, "z starts at lane 256 of the projection, no multiple "
                     "of a group's 512 lanes"),
    ({"t": 8200}, "rows of 8200 positions are no multiple of 16"),
    ({"inner": 2 ** 20, "groups": 2, "itemsize": 4},
     "16 rows of a group of 524288 lanes do not fit the kernels' 32 MiB of "
     "VMEM seven times over"),
])
def test_each_refusal_by_name(over, why):
    """``ssm.gate_kernel_refusal``: None at the Nemotron cell's shape, and
    each reason in words where the platform, the one group (the Granite
    cell's) or the shape turns the kernels down."""
    assert ssm.gate_kernel_refusal(**SHAPE) is None
    assert ssm.gate_kernel_refusal(**{**SHAPE, "start": 4096}) is None
    got = ssm.gate_kernel_refusal(**{**SHAPE, **over})
    assert got is not None and why in got, got


def test_the_kernels_themselves_take_one_group_of_any_whole_lane_tiles():
    """The one-group refusal is ``ssm.py``'s, by what was measured
    (``PERF.md`` section 6, PR 49), not the kernels': they take the Granite
    cell's row of 4,096 lanes, in tiles cut to a MiB an operand."""
    assert pgate.unsupported_reason(8192, 4096, 1, 0, 2) is None
    assert pgate.tile_rows(8192, 4096, 2) == 128
    assert pgate.tile_rows(8192, 512, 2) == 1024
    assert pgate.tile_rows(48, 128, 2) == 16


def test_a_refused_gate_runs_the_numpy_form_and_says_why_once(caplog):
    """One group, with the kernels interpreted: ``mixer`` gives the
    ``jax.numpy`` form's very result, and one warning a shape."""
    cfg = _nemotron_cfg(**{**WIDE, "n_groups": 1})
    u, p, _ = _mixer_operands(cfg, 256)
    loss, _ = _mixer_loss(cfg, 1.0)
    whole = ssm.gate_kernel_refusal
    ssm._report_refusal.cache_clear()
    with caplog.at_level("INFO", logger="znicz_tpu.transformer"):
        with _pallas_interpret(True):
            got = loss(u, p)
            loss(u, p)
            # the same layer with the gate's kernels never asked
            ssm.gate_kernel_refusal = lambda *a, **k: "refused by the test"
            try:
                want = loss(u, p)
            finally:
                ssm.gate_kernel_refusal = whole
    assert float(got) == float(want)
    said = [r for r in caplog.records if "gate kernels refused" in r.message]
    assert [r.levelname for r in said] == ["WARNING", "WARNING"]
    assert "t=256 inner=256 groups=1 start=0" in said[0].message
    assert "one group: the statistic over a whole row" in said[0].message
    assert "refused by the test" in said[1].message


# -- (c) the gauge ------------------------------------------------------------

@pytest.mark.parametrize("groups,interpret,share", [
    (2, True, 1.0),         # two groups of a lane tile, kernels interpreted
    (2, False, 0.0),        # the same shape on this backend as it is
    (1, True, 0.0),         # one group
    (4, True, 0.0),         # groups of half a lane tile
])
def test_the_unit_publishes_the_gate_kernels_share(groups, interpret, share):
    """``znicz_lm_ssm_gate_kernel_share`` and the unit's mirror, set as the
    step is built from what :func:`ssm.gate_kernel_refusal` says of its
    shape: 1.0 where the kernels run the gate and the gated norm, 0.0 where
    the one group, the shape or the backend leaves them to the ``jax.numpy``
    form."""
    from builders import lm_train_keys
    from znicz_tpu.core.backends import XLADevice
    from znicz_tpu.observe import registry

    cfg = {**_nemotron_cfg(**{**WIDE, "n_groups": groups}),
           "builders": {"lm_train_keys": {
               "model_keys": [k for k in nemotron.TINY if k != "hyper"],
               "loss_chunks": 2}}}
    traffic = {"minibatch_size": 1, "seq_len": 256}
    rows = nemotron_ref.make_tokens(17, cfg, 256, 0, 1)
    with _pallas_interpret(interpret):
        w = lm_train_keys.build_workflow(rows, cfg, traffic)
        w.step._params = nemotron_ref.init_params(17, cfg)
        w.initialize(device=XLADevice())
    assert w.step.ssm_gate_kernel_share == share
    fam = registry.REGISTRY.get("znicz_lm_ssm_gate_kernel_share")
    assert fam is not None and fam.labels(unit=w.step.name).get() == share


def test_a_stack_without_state_space_layers_has_no_share():
    import test_lfm2_arch as lfm2

    arch = lfm2._arch(lfm2._cfg(["conv", "full_attention"], 1))
    mesh = make_mesh({"data": 1, "seq": 1, "model": 1}, jax.devices()[:1])
    assert tfm.step_choices(mesh, arch, 1, 16)["ssm_gate_kernel_share"] \
        is None
