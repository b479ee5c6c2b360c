"""The state-space layer's convolution kernels (``ops/pallas/ssm_conv.py``,
called from ``parallel/ssm.py::mixer``), interpreted on the CPU: against
``ssm._conv`` + ``silu`` under ``jax.grad`` in values and every gradient
(the projection's lanes, the taps, the bias; through ``mixer``, the input
projection's weight too); a halo dropped at a tile's edge against the same
tolerance; each refusal by name; a refused shape's fallback and its one log
line; the gauge that says which form a step's state-space layers got.  Both
benchmark cells' steps compiled at their real widths for a described TPU
v5e, with the kernels in them, are ``tests/test_checkpoint_plan.py``'s."""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(HERE), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import granitemoehybrid as granite_ref       # noqa: E402

import test_granitemoehybrid_arch as granite                # noqa: E402
from test_lfm2_arch import _pallas_interpret                # noqa: E402
from znicz_tpu.ops.pallas import ssm_conv as pconv          # noqa: E402
from znicz_tpu.parallel import ssm, transformer as tfm      # noqa: E402
from znicz_tpu.parallel.mesh import make_mesh               # noqa: E402

#: the lanes in front of the cut (``z``) and behind it (``dt``), cut down
START, BEHIND = 512, 64
#: both cells' channel widths: 4,352 = 17 x 256 (Granite), 6,144 = 12 x 512
WIDTHS = (4352, 6144)


def _operands(seed, rows, t, width, taps, dtype):
    r = np.random.default_rng(seed)
    proj = r.normal(size=(rows, t, START + width + BEHIND))
    k = r.normal(size=(taps, width)) / np.sqrt(taps)
    bias = 0.3 * r.normal(size=(width,))
    w = r.normal(size=(rows, t, width))
    return (jnp.asarray(proj, dtype), jnp.asarray(k, jnp.float32),
            jnp.asarray(bias, jnp.float32), jnp.asarray(w, jnp.float32))


def _plain(proj, k, bias):
    """The ``jax.numpy`` form of the layer's convolution, bias, ``silu``
    and cast on the cut."""
    cut = proj[..., START:START + k.shape[1]]
    return jax.nn.silu(ssm._conv(cut, k, bias)).astype(proj.dtype)


def _kernels(proj, k, bias):
    return pconv.conv(proj, jnp.concatenate([k, bias[None]], axis=0), START,
                      True)


def _loss(form, w):
    return lambda *ops: (form(*ops).astype(jnp.float32) * w).sum()


def _rel(got, want):
    got, want = (jnp.asarray(v, jnp.float32) for v in (got, want))
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


# -- (a) values and gradients -------------------------------------------------

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("taps", [2, 4])
@pytest.mark.parametrize("width,t,tile", [
    (WIDTHS[0], 64, (64, 256)),     # one tile a row: the zeros before it
    (WIDTHS[0], 48, (16, 256)),     # three tiles: a halo at a tile's edge
    (WIDTHS[1], 384, (128, 512)),   # three tiles of two pieces of 64 rows
])
def test_the_kernels_are_the_numpy_form_in_values_and_gradients(
        width, t, tile, taps, dtype):
    """``ssm_conv.conv`` (interpreted) against ``silu(_conv(cut) + bias)``
    cast: the result and the gradient of a random functional of it in the
    projection (zero outside the cut), the taps and the bias, at both
    cells' channel widths cut down in tokens.  In float32 to rounding (the
    sums differ in order); in bfloat16 the result one rounding of an entry
    apart at most in few entries, ``dv`` within a rounding, and the float32
    gradients of taps and bias as near as float32 sums over the tokens
    are."""
    dtype = jnp.dtype(dtype)
    proj, k, bias, w = _operands(7, 2, t, width, taps, dtype)
    assert pconv.unsupported_reason(t, START, width, taps) is None
    assert pconv.tiles(t, START, width) == tile
    y, want_y = _kernels(proj, k, bias), _plain(proj, k, bias)
    got = jax.grad(_loss(_kernels, w), argnums=(0, 1, 2))(proj, k, bias)
    want = jax.grad(_loss(_plain, w), argnums=(0, 1, 2))(proj, k, bias)
    assert y.dtype == dtype and y.shape == want_y.shape
    assert [g.dtype for g in got] == [g.dtype for g in want]
    outside = jnp.concatenate([got[0][..., :START],
                               got[0][..., START + width:]], axis=-1)
    assert not np.asarray(outside).any()
    if dtype == jnp.float32:
        np.testing.assert_allclose(y, want_y, rtol=2e-6, atol=2e-6)
        for name, g, g_want in zip(("v", "taps", "bias"), got, want):
            assert _rel(g, g_want) < 2e-6, (name, _rel(g, g_want))
        return
    apart = jnp.abs(y.astype(jnp.float32) - want_y.astype(jnp.float32))
    scale = float(jnp.abs(want_y.astype(jnp.float32)).max())
    assert float(apart.max()) <= 2.0 ** -7 * scale
    assert float((apart > 0).mean()) < 1e-3
    assert _rel(got[0], want[0]) < 2e-3
    for name, g, g_want in zip(("taps", "bias"), got[1:], want[1:]):
        assert _rel(g, g_want) < 2e-6, (name, _rel(g, g_want))


def test_a_halo_dropped_at_a_tiles_edge_fails_the_same_tolerance(monkeypatch):
    """The tolerance above is tight enough: with the rows in front of every
    tile read as zeros (the first tile's ARE zeros), the result leaves the
    ``jax.numpy`` form at each tile's first ``taps - 1`` rows by far more
    than rounding, and nowhere else."""
    monkeypatch.setattr(
        pconv, "_halo_of", lambda halo_ref, first, at:
        jnp.zeros(halo_ref.shape[1:2] + (pconv.LANES,), jnp.float32))
    jax.clear_caches()
    proj, k, bias, _ = _operands(7, 1, 48, WIDTHS[0], 4, jnp.float32)
    try:
        y = _kernels(proj, k, bias)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    apart = np.abs(np.asarray(y - _plain(proj, k, bias))).max(axis=(0, 2))
    edge = np.isin(np.arange(48) % 16, (0, 1, 2)) & (np.arange(48) >= 16)
    assert apart[edge].min() > 1e-2
    assert apart[~edge].max() < 2e-6


def _granite_cfg(**over):
    return granite._cfg(num_hidden_layers=1, layer_types=["mamba"], **over)


def _mixer_loss(cfg, w):
    """The layer alone, ``ssm.mixer`` on a normed stream, as a functional
    of the stream and the layer's leaves."""
    arch = granite._arch(cfg)

    def loss(u, p):
        out, _ = ssm.mixer(u, p, arch.ssm_heads, arch.ssm_head_dim,
                           arch.ssm_state, arch.ssm_chunk, arch.eps, "blk",
                           arch.ssm_groups)
        return (out.astype(jnp.float32) * w).sum()
    return loss, arch


def test_the_kernels_inside_the_mixer_give_its_gradients_to_every_leaf():
    """Through ``mixer`` with both pairs of kernels interpreted against
    ``mixer`` in ``jax.numpy``: the layer's output and the gradients to the
    stream and to every leaf, the input projection's weight (which the
    kernel path cuts into ``z | xBC`` and ``dt``), the taps and the bias
    among them, in float32 at the tolerance the scan's kernels hold."""
    cfg = _granite_cfg(mamba_n_heads=8, mamba_d_head=16, mamba_expand=4,
                       mamba_d_state=128, mamba_chunk_size=128)
    p = jax.tree.map(jnp.asarray,
                     granite_ref.init_params(3, cfg)["blocks"][0])
    p = {k: v for k, v in p.items() if k.startswith("ssm_")}
    r = np.random.default_rng(4)
    u = jnp.asarray(r.normal(size=(2, 256, p["ssm_in"].shape[0])),
                    jnp.float32)
    w = jnp.asarray(r.normal(size=u.shape), jnp.float32)
    loss, arch = _mixer_loss(cfg, w)
    inner = arch.ssm_heads * arch.ssm_head_dim
    with jax.default_matmul_precision("highest"):
        with _pallas_interpret(True):
            assert ssm.conv_kernel_refusal(
                256, inner, inner + 2 * arch.ssm_state, arch.conv_taps,
                True) is None
            text = str(jax.make_jaxpr(loss)(u, p))
            got = jax.grad(loss, argnums=(0, 1))(u, p)
        want = jax.grad(loss, argnums=(0, 1))(u, p)
    assert pconv.FWD_KERNEL_NAME in text
    assert _rel(got[0], want[0]) < 5e-5
    assert set(got[1]) == set(want[1])
    for name in want[1]:
        assert _rel(got[1][name], want[1][name]) < 5e-5, name


def test_a_delta_rule_layers_bias_free_cut_from_lane_zero_takes_the_kernels():
    """The delta-rule layer (``parallel/kda.py``) hands the kernels its whole
    ``q | k | v`` projection, cut from lane 0, with a bias row of zeros: the
    cell's shape (8,192 positions, 24,576 lanes, 4 taps) passes the one
    question with tiles of 1,024 x 512, and at a small shape the kernels
    (interpreted) give ``silu(conv(proj))`` and the gradients to the
    projection and the taps as ``ssm._conv`` does; through ``kda.mixer``,
    the layer's output and the gradients to the stream and to every leaf."""
    from znicz_tpu.parallel import kda
    from znicz_tpu.parallel.params import _kda_leaf_shapes

    assert ssm.conv_kernel_refusal(8192, 0, 24576, 4, True) is None
    assert pconv.tiles(8192, 0, 24576) == (1024, 512)
    r = np.random.default_rng(11)
    proj = jnp.asarray(r.normal(size=(2, 48, 384)), jnp.float32)
    k = jnp.asarray(r.normal(size=(4, 384)) / 2.0, jnp.float32)
    w = jnp.asarray(r.normal(size=proj.shape), jnp.float32)
    zero = jnp.zeros((384,), jnp.float32)

    def loss(interpret):
        return lambda a, b: (ssm._conv_silu(a, b, zero, 0, interpret) *
                             w).sum()

    got = jax.grad(loss(True), argnums=(0, 1))(proj, k)
    want = jax.grad(loss(None), argnums=(0, 1))(proj, k)
    np.testing.assert_allclose(ssm._conv_silu(proj, k, zero, 0, True),
                               ssm._conv_silu(proj, k, zero, 0, None),
                               rtol=2e-6, atol=2e-6)
    for g, g_want in zip(got, want):
        assert _rel(g, g_want) < 2e-6
    # the layer: 2 heads of 64 (q | k | v three whole lane tiles), rows of 64
    d, heads, width = 32, 2, 64
    p = {name: jnp.asarray(r.normal(size=shape) / np.sqrt(shape[0]),
                           jnp.float32)
         for name, shape in _kda_leaf_shapes(d, heads, width, width,
                                             4).items()}
    p["kda_dt_b"] = jnp.full((heads * width,), -3.0)
    u = jnp.asarray(r.normal(size=(2, 64, d)), jnp.float32)
    wo = jnp.asarray(r.normal(size=u.shape), jnp.float32)

    def layer(u_, p_):
        out, _ = kda.mixer(u_, p_, heads, width, 16, True, 1e-5, "blk.kda")
        return (out * wo).sum()

    with jax.default_matmul_precision("highest"):
        with _pallas_interpret(True):
            text = str(jax.make_jaxpr(layer)(u, p))
            got = jax.grad(layer, argnums=(0, 1))(u, p)
        want = jax.grad(layer, argnums=(0, 1))(u, p)
    assert pconv.FWD_KERNEL_NAME in text
    assert _rel(got[0], want[0]) < 5e-5
    for name in want[1]:
        assert _rel(got[1][name], want[1][name]) < 5e-5, name


# -- (b) the one question -----------------------------------------------------

SHAPE = dict(t=8192, start=4096, width=4352, taps=4, interpret=True)


@pytest.mark.parametrize("over,why", [
    ({"interpret": False}, "the backend is cpu and the step's kernels are "
                           "not interpreted"),
    ({"start": 4032}, "the cut of 4352 lanes behind 4032 is not whole tiles "
                      "of 128 lanes"),
    ({"width": 96}, "the cut of 96 lanes behind 4096 is not whole tiles of "
                    "128 lanes"),
    ({"t": 8200}, "rows of 8200 positions are no multiple of 16"),
    ({"t": 8}, "rows of 8 positions are no multiple of 16"),
    ({"taps": 18}, "18 taps reach further back than the 16 rows fetched in "
                   "front of a tile"),
])
def test_each_refusal_by_name(over, why):
    """``ssm.conv_kernel_refusal``: None at both cells' shapes, and each
    reason in words where the platform or the shape turns the kernels
    down."""
    assert ssm.conv_kernel_refusal(**SHAPE) is None
    assert ssm.conv_kernel_refusal(**{**SHAPE, "width": 6144}) is None
    got = ssm.conv_kernel_refusal(**{**SHAPE, **over})
    assert got is not None and why in got, got


def test_the_tiles_follow_the_shape():
    """A visit's tile: 1,024 rows where they divide the row, 256 lanes of
    the Granite cell's cut and 512 of the Nemotron cell's."""
    assert pconv.tiles(8192, 4096, 4352) == (1024, 256)
    assert pconv.tiles(8192, 4096, 6144) == (1024, 512)
    assert pconv.tiles(48, 256, 384) == (16, 128)


def test_a_refused_convolution_runs_the_numpy_form_and_says_why_once(caplog):
    """A cut the kernels refuse (96 channels behind 64 lanes), with the
    kernels interpreted: ``mixer`` gives the ``jax.numpy`` form's very
    result, and one warning a shape."""
    cfg = _granite_cfg()
    p = jax.tree.map(jnp.asarray,
                     granite_ref.init_params(3, cfg)["blocks"][0])
    u = jnp.asarray(np.random.default_rng(4).normal(
        size=(2, 32, p["ssm_in"].shape[0])), jnp.float32)
    loss, _ = _mixer_loss(cfg, 1.0)
    ssm._report_refusal.cache_clear()
    with caplog.at_level("INFO", logger="znicz_tpu.transformer"):
        with _pallas_interpret(True):
            got = loss(u, p)
            loss(u, p)
        want = loss(u, p)
    assert float(got) == float(want)
    said = [r for r in caplog.records
            if "convolution kernels refused" in r.message]
    assert [r.levelname for r in said] == ["WARNING", "INFO"]
    assert "t=32 start=64 width=96 taps=4" in said[0].message
    assert "is not whole tiles of 128 lanes" in said[0].message
    assert "the backend is cpu" in said[1].message


# -- (c) the gauge ------------------------------------------------------------

@pytest.mark.parametrize("state,interpret,share", [
    (128, True, 1.0),       # the kernels' shape, kernels interpreted
    (128, False, 0.0),      # the same shape on this backend as it is
    (16, True, 0.0),        # a cut that ends inside a lane tile
])
def test_the_unit_publishes_the_convolution_kernels_share(state, interpret,
                                                          share):
    """``znicz_lm_ssm_conv_kernel_share`` and the unit's mirror, set as the
    step is built from what :func:`ssm.conv_kernel_refusal` says of its
    shape: 1.0 where the kernels run the convolution, 0.0 where the shape
    or the backend leaves it to the ``jax.numpy`` form."""
    from builders import lm_train_keys
    from znicz_tpu.core.backends import XLADevice
    from znicz_tpu.observe import registry

    cfg = {**_granite_cfg(mamba_n_heads=8, mamba_d_head=16, mamba_expand=4,
                          mamba_d_state=state, mamba_chunk_size=128),
           "builders": {"lm_train_keys": {
               "model_keys": [k for k in granite.TINY if k != "hyper"],
               "loss_chunks": 2}}}
    traffic = {"minibatch_size": 1, "seq_len": 256}
    rows = granite_ref.make_tokens(17, cfg, 256, 0, 1)
    with _pallas_interpret(interpret):
        w = lm_train_keys.build_workflow(rows, cfg, traffic)
        w.step._params = granite_ref.init_params(17, cfg)
        w.initialize(device=XLADevice())
    assert w.step.ssm_conv_kernel_share == share
    fam = registry.REGISTRY.get("znicz_lm_ssm_conv_kernel_share")
    assert fam is not None and fam.labels(unit=w.step.name).get() == share


def test_a_stack_without_state_space_layers_has_no_share():
    import test_lfm2_arch as lfm2

    arch = lfm2._arch(lfm2._cfg(["conv", "full_attention"], 1))
    mesh = make_mesh({"data": 1, "seq": 1, "model": 1}, jax.devices()[:1])
    assert tfm.step_choices(mesh, arch, 1, 16)["ssm_conv_kernel_share"] \
        is None
