"""The gated short convolution's kernels (``ops/pallas/sconv.py``, called
from ``parallel/blocks.py::_block_sconv``), interpreted on the CPU: against
``blocks._sconv_gate`` under ``jax.grad`` in values and every gradient (the
projection's three cuts, the taps), and against the same arithmetic in
float32 for which of the two rounds less; a halo dropped at a tile's edge
against the same tolerance; each refusal by name; a refused layer's
fallback and its one log line; the gauge that says which form a step's
layers got.  The cell's step compiled at its real widths for a described
TPU v5e, with the kernels in it, is a scratch script's (``CHANGES.md``, PR
51)."""

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

from reference import lfm2_moe as ref                       # noqa: E402

import test_lfm2_arch as lfm2                               # noqa: E402
from test_lfm2_arch import _pallas_interpret                # noqa: E402
from znicz_tpu.core import prng                             # noqa: E402
from znicz_tpu.ops.pallas import sconv as psconv            # noqa: E402
from znicz_tpu.parallel import blocks, transformer as tfm   # noqa: E402


def _operands(seed, rows, t, d, taps, dtype):
    r = np.random.default_rng(seed)
    proj = r.normal(size=(rows, t, 3 * d))
    k = r.normal(size=(taps, d)) / np.sqrt(taps)
    w = r.normal(size=(rows, t, d))
    return (jnp.asarray(proj, dtype), jnp.asarray(k, jnp.float32),
            jnp.asarray(w, jnp.float32))


def _kernels(proj, k):
    return psconv.gate(proj, k, True)


def _loss(form, w):
    return lambda *ops: (form(*ops).astype(jnp.float32) * w).sum()


def _rel(got, want):
    got, want = (jnp.asarray(v, jnp.float32) for v in (got, want))
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def _cuts(dproj):
    return dict(zip("BCX", jnp.split(dproj, 3, axis=-1)))


# -- (a) values and gradients -------------------------------------------------

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("taps", [2, 3, 4])
@pytest.mark.parametrize("d,t,tile,bwd_rows", [
    (256, 64, (64, 256), 64),      # one tile a row: the zeros before it
    (640, 48, (16, 128), 16),      # three tiles of time, five blocks of lanes
    (1024, 384, (128, 512), 128),  # three tiles of two pieces, two blocks
])
def test_the_kernels_are_the_numpy_form_in_values_and_gradients(
        d, t, tile, bwd_rows, taps, dtype):
    """``sconv.gate`` (interpreted) against ``blocks._sconv_gate``: the
    result and the gradient of a random functional of it in each cut of the
    projection and in the taps, two rows of the batch.  In float32 to
    rounding (the sums differ in order).  In bfloat16 within the
    ``jax.numpy`` form's own roundings of ``z``, ``c``, ``dc`` and ``dz``,
    which the kernels do not make: against the same arithmetic in float32 on
    the same operands the kernels stand no further off than that form, in
    the result and in every gradient."""
    dtype = jnp.dtype(dtype)
    proj, k, w = _operands(7, 2, t, d, taps, dtype)
    assert psconv.unsupported_reason(t, d, taps, False) is None
    assert psconv.tiles(t, d, d) == tile
    assert psconv.bwd_rows(t, d, dtype.itemsize) == bwd_rows
    y, want_y = _kernels(proj, k), blocks._sconv_gate(proj, k)
    got = jax.grad(_loss(_kernels, w), argnums=(0, 1))(proj, k)
    want = jax.grad(_loss(blocks._sconv_gate, w), argnums=(0, 1))(proj, k)
    assert y.dtype == dtype and y.shape == want_y.shape == (2, t, d)
    assert [g.dtype for g in got] == [g.dtype for g in want]
    assert got[0].shape == proj.shape
    got, want = ({**_cuts(g[0]), "taps": g[1], "y": v}
                 for g, v in ((got, y), (want, want_y)))
    if dtype == jnp.float32:
        for name in want:
            assert _rel(got[name], want[name]) < 2e-6, name
        return
    for name in want:
        assert _rel(got[name], want[name]) < 8e-3, name
    up = proj.astype(jnp.float32)
    exact = jax.grad(_loss(blocks._sconv_gate, w), argnums=(0, 1))(up, k)
    exact = {**_cuts(exact[0]), "taps": exact[1],
             "y": blocks._sconv_gate(up, k)}
    for name in exact:
        assert _rel(got[name], exact[name]) <= \
            1.02 * _rel(want[name], exact[name]) + 1e-6, name
        # one rounding of the result at most: half a unit in the last place
        assert _rel(got[name], exact[name]) < 2.0 ** -8, name


def test_a_halo_dropped_at_a_tiles_edge_fails_the_same_tolerance(monkeypatch):
    """The tolerance above is tight enough: with the rows in front of every
    tile read as zeros (the first tile's ARE zeros), the result and ``dC``
    leave the ``jax.numpy`` form at each tile's first ``taps - 1`` rows by
    far more than rounding, and nowhere else."""
    monkeypatch.setattr(
        psconv, "_halo_of", lambda b_ref, x_ref, first, at_b, at_x:
        jnp.zeros((psconv.HALO, psconv.LANES), jnp.float32))
    jax.clear_caches()
    t, d, taps = 48, 640, 3
    proj, k, w = _operands(7, 1, t, d, taps, jnp.float32)
    try:
        y = _kernels(proj, k)
        dproj = jax.grad(_loss(_kernels, w))(proj, k)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    want_d = jax.grad(_loss(blocks._sconv_gate, w))(proj, k)
    edge = np.isin(np.arange(t) % 16, (0, 1)) & (np.arange(t) >= 16)
    for got, want in ((y, blocks._sconv_gate(proj, k)),
                      (_cuts(dproj)["C"], _cuts(want_d)["C"])):
        apart = np.abs(np.asarray(got - want)).max(axis=(0, 2))
        assert apart[edge].min() > 1e-2
        assert apart[~edge].max() < 2e-6


def test_the_cotangent_behind_a_tile_is_carried_over_its_edge(monkeypatch):
    """The backward kernel's other edge: ``dz`` of a tile's last ``taps -
    1`` rows reads ``dc`` of the tile behind it, which the visit before
    left in VMEM.  With a tile of 16 rows for the backward pass alone the
    gradients are those of one tile of 64."""
    proj, k, w = _operands(3, 2, 64, 256, 4, jnp.float32)
    whole = jax.grad(_loss(_kernels, w), argnums=(0, 1))(proj, k)
    monkeypatch.setattr(psconv, "_BWD_BLOCK_BYTES", 14 * 256 * 4 * 16)
    jax.clear_caches()
    try:
        assert psconv.bwd_rows(64, 256, 4) == 16
        tiled = jax.grad(_loss(_kernels, w), argnums=(0, 1))(proj, k)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    for a, b in zip(tiled, whole):
        assert _rel(a, b) < 2e-6


# -- (b) the one question -----------------------------------------------------

SHAPE = dict(t=4096, d=2048, taps=3, bias=False, interpret=True)


@pytest.mark.parametrize("over,why", [
    ({"interpret": False}, "the backend is cpu and the step's kernels are "
                           "not interpreted"),
    ({"d": 2000}, "the cuts of 2000 lanes are not whole tiles of 128 lanes"),
    ({"t": 4100}, "rows of 4100 positions are no multiple of 16"),
    ({"taps": 18}, "18 taps reach further back than the 16 rows fetched in "
                   "front of a tile"),
    ({"bias": True}, "the convolution has a bias, and the kernels add none"),
], ids=["backend", "lanes", "rows", "taps", "bias"])
def test_each_refusal_by_name(over, why):
    """``blocks.sconv_kernel_refusal``: None at the cell's shape, and each
    reason in words where the platform or the shape turns the kernels
    down."""
    assert blocks.sconv_kernel_refusal(**SHAPE) is None
    got = blocks.sconv_kernel_refusal(**{**SHAPE, **over})
    assert got is not None and why in got, got


def test_the_tiles_follow_the_shape():
    """The cell's: a forward visit of 1,024 rows x 512 lanes of each cut
    (``ssm_conv.tiles``), a backward visit of 256 whole rows."""
    assert psconv.tiles(4096, 2048, 2048) == (1024, 512)
    assert psconv.bwd_rows(4096, 2048, 2) == 256
    assert psconv.bwd_rows(4096, 2048, 4) == 128
    assert psconv.bwd_rows(48, 128, 2) == 16


def _layer(hidden, bias):
    cfg = lfm2._cfg(["conv"], 1, hidden_size=hidden)
    arch = lfm2._arch(cfg)
    p = {k: jnp.asarray(v) for k, v in
         ref.init_params(3, cfg)["blocks"][0].items()
         if k in ("ln1_g", "w_in", "conv_k", "w_out")}
    r = np.random.default_rng(4)
    if bias:
        p["conv_b"] = jnp.asarray(0.3 * r.normal(size=(hidden,)), jnp.float32)
    x = jnp.asarray(r.normal(size=(2, 32, hidden)), jnp.float32)
    return arch, p, x


@pytest.mark.parametrize("hidden,bias,shape,why", [
    (32, False, "t=32 d=32 taps=3 bias=False",
     "are not whole tiles of 128 lanes"),
    (128, True, "t=32 d=128 taps=3 bias=True", "has a bias"),
], ids=["lanes", "bias"])
def test_a_refused_layer_runs_the_numpy_form_and_says_why_once(
        caplog, hidden, bias, shape, why):
    """A layer the kernels refuse, with the kernels interpreted:
    ``_block_sconv`` gives the ``jax.numpy`` form's very result (with the
    bias where the layer has one), and one warning a shape."""
    arch, p, x = _layer(hidden, bias)
    on, off = (blocks._Run(1, 1, interpret=flag) for flag in (True, False))
    blocks._report_sconv_refusal.cache_clear()
    with caplog.at_level("INFO", logger="znicz_tpu.transformer"):
        got = blocks._block_sconv(x, p, arch, on)
        blocks._block_sconv(x, p, arch, on)
        want = blocks._block_sconv(x, p, arch, off)
    np.testing.assert_array_equal(got, want)
    u = blocks._norm(x, p, "ln1", arch)
    plain = x + blocks._sconv_gate(u @ p["w_in"], p["conv_k"],
                                   p.get("conv_b")) @ p["w_out"]
    np.testing.assert_array_equal(got, plain)
    if bias:
        assert _rel(got - x, blocks._sconv_gate(
            u @ p["w_in"], p["conv_k"]) @ p["w_out"]) > 1e-2
    said = [r for r in caplog.records
            if "gated short convolution kernels refused" in r.message]
    assert [r.levelname for r in said] == ["WARNING", "INFO"]
    assert shape in said[0].message and why in said[0].message
    assert "the backend is cpu" in said[1].message


def test_a_layer_the_kernels_take_runs_them():
    """At whole lane tiles and rows of whole halo tiles, interpreted, the
    layer's trace holds both kernels and its gradients are the ``jax.numpy``
    layer's to every leaf."""
    arch, p, x = _layer(128, False)
    w = jnp.asarray(np.random.default_rng(5).normal(size=x.shape),
                    jnp.float32)

    def loss(run):
        return lambda x, p: (blocks._block_sconv(x, p, arch, run) * w).sum()
    on, off = (blocks._Run(1, 1, interpret=flag) for flag in (True, False))
    with jax.default_matmul_precision("highest"):
        text = str(jax.make_jaxpr(jax.grad(loss(on), argnums=(0, 1)))(x, p))
        got = jax.grad(loss(on), argnums=(0, 1))(x, p)
        want = jax.grad(loss(off), argnums=(0, 1))(x, p)
    assert psconv.FWD_KERNEL_NAME in text and psconv.BWD_KERNEL_NAME in text
    assert psconv.FWD_KERNEL_NAME not in str(jax.make_jaxpr(loss(off))(x, p))
    assert _rel(got[0], want[0]) < 5e-6
    assert set(got[1]) == set(want[1])
    for name in want[1]:
        assert _rel(got[1][name], want[1][name]) < 5e-6, name


# -- (c) the gauge ------------------------------------------------------------

@pytest.mark.parametrize("hidden,interpret,share", [
    (128, True, 1.0),       # the kernels' shape, kernels interpreted
    (128, False, 0.0),      # the same shape on this backend as it is
    (32, True, 0.0),        # cuts that end inside a lane tile
])
def test_the_unit_publishes_the_kernels_share(tmp_path, hidden, interpret,
                                              share):
    """``znicz_lm_sconv_kernel_share`` and the unit's mirror, set as the
    step is built from what :func:`blocks.sconv_kernel_refusal` says of its
    shape: 1.0 where the kernels run the gates and taps, 0.0 where the
    shape or the backend leaves them to the ``jax.numpy`` form."""
    from znicz_tpu.core.backends import XLADevice
    from znicz_tpu.observe import registry

    cfg = lfm2._cfg(["conv", "conv"], 1, hidden_size=hidden)
    model = {k: v for k, v in cfg.items()
             if k not in ("router_width", "hyper", "vocab_size")}
    prng.seed_all(5)
    with _pallas_interpret(interpret):
        w = lfm2._arch_workflow(
            {**model, "num_experts": cfg["router_width"]},
            str(tmp_path / "corp"), max_epochs=1, seq_len=16,
            minibatch_size=2)
        w.initialize(device=XLADevice())
    assert w.step.sconv_kernel_share == share
    fam = registry.REGISTRY.get("znicz_lm_sconv_kernel_share")
    assert fam is not None and fam.labels(unit=w.step.name).get() == share


def test_a_stack_without_the_layer_has_no_share():
    arch = lfm2._arch(lfm2._cfg(["full_attention"], 1))
    assert tfm.step_choices(lfm2._mesh1(), arch, 1, 16)[
        "sconv_kernel_share"] is None
