"""Native C++ loader-core tests (SURVEY.md §3.2 PRNG row + §4.1
fill_minibatch): build-on-first-use, gather parity with numpy, xorshift
stream sanity, shuffle permutation validity."""

import numpy as np
import pytest

from znicz_tpu import native

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="no C++ toolchain")


def test_gather_rows_matches_numpy():
    rng = np.random.default_rng(0)
    src = rng.normal(size=(500, 37)).astype(np.float32)
    idx = np.concatenate([rng.integers(0, 500, 90),
                          np.full(10, -1)]).astype(np.int64)
    dst = np.empty((100, 37), np.float32)
    native.gather_rows(src, idx, dst)
    ref = np.zeros_like(dst)
    ref[:90] = src[idx[:90]]
    np.testing.assert_array_equal(dst, ref)


def test_gather_rows_multi_dim_and_threads():
    rng = np.random.default_rng(1)
    src = rng.normal(size=(256, 8, 8, 3)).astype(np.float32)
    idx = rng.integers(0, 256, 128).astype(np.int64)
    d1 = np.empty((128, 8, 8, 3), np.float32)
    d8 = np.empty_like(d1)
    native.gather_rows(src, idx, d1, n_threads=1)
    native.gather_rows(src, idx, d8, n_threads=8)
    np.testing.assert_array_equal(d1, src[idx])
    np.testing.assert_array_equal(d8, d1)


def test_xorshift_stream():
    gen = native.XorShift128P(42)
    u = gen.uniform(100_000)
    assert 0.0 <= u.min() and u.max() < 1.0
    assert abs(u.mean() - 0.5) < 0.01
    # deterministic per seed, advancing state
    gen2 = native.XorShift128P(42)
    np.testing.assert_array_equal(gen2.uniform(100_000), u)
    assert not np.array_equal(gen.uniform(8), gen.uniform(8))
    assert not np.array_equal(native.XorShift128P(43).uniform(100),
                              native.XorShift128P(42).uniform(100))


def test_native_shuffle_is_permutation():
    gen = native.XorShift128P(7)
    idx = np.arange(1000, dtype=np.int64)
    gen.shuffle(idx)
    assert not np.array_equal(idx, np.arange(1000))
    np.testing.assert_array_equal(np.sort(idx), np.arange(1000))


def test_loader_uses_native_gather():
    """FullBatchLoader minibatches are identical with/without the native
    path (bit-identical contract)."""
    from znicz_tpu.core import prng
    from znicz_tpu.loader.synthetic import SyntheticClassifierLoader

    def serve(force_numpy):
        prng.seed_all(5)
        loader = SyntheticClassifierLoader(
            None, n_classes=4, sample_shape=(9,), n_train=100, n_valid=40,
            minibatch_size=32)
        loader.initialize(device=None)
        if force_numpy:
            # strided view breaks contiguity -> numpy fallback
            loader.original_data.mem = np.asfortranarray(
                loader.original_data.mem)
        outs = []
        for _ in range(6):
            loader.run()
            outs.append(loader.minibatch_data.mem.copy())
        return outs

    a = serve(False)
    b = serve(True)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


# -- native inference runtime (libVeles/libZnicz rebuild) --------------------

def _export_trained(build, tmp_path, name, **kw):
    import os

    from znicz_tpu.core import prng
    from znicz_tpu.core.backends import XLADevice
    from znicz_tpu.utils.export import export_forward

    prng.seed_all(7)
    w = build(**kw)
    w.initialize(device=XLADevice())
    w.run()
    return export_forward(w, os.path.join(str(tmp_path), name))


def test_native_infer_fc_matches_python(tmp_path):
    """The C++ runtime loads a forward package standalone (ZIP + NPY +
    manifest all parsed natively) and reproduces the Python
    ExportedForward on an FC+softmax model."""
    from znicz_tpu.models import wine
    from znicz_tpu.native.infer import NativeForward, available
    from znicz_tpu.utils.export import ExportedForward

    if not available():
        pytest.skip("no native compiler/zlib")
    path = _export_trained(wine.build, tmp_path, "wine.npz", max_epochs=2,
                           n_train=60, n_valid=30, minibatch_size=10)
    py = ExportedForward(path)
    cc = NativeForward(path)
    x = np.random.default_rng(0).normal(size=(16, 13)).astype(np.float32)
    np.testing.assert_allclose(cc(x), np.asarray(py(x)).reshape(16, -1),
                               rtol=2e-4, atol=2e-5)
    # softmax rows normalize
    np.testing.assert_allclose(cc(x).sum(axis=1), 1.0, rtol=1e-5)


def test_native_infer_conv_stack_matches_python(tmp_path):
    """conv_relu -> max_pooling (default window stride) -> conv_relu ->
    max_pooling -> all2all_relu -> softmax, end to end vs Python."""
    from znicz_tpu.models import mnist_conv
    from znicz_tpu.native.infer import NativeForward, available
    from znicz_tpu.utils.export import ExportedForward

    if not available():
        pytest.skip("no native compiler/zlib")
    path = _export_trained(mnist_conv.build, tmp_path, "conv.npz",
                           max_epochs=1, n_train=200, n_valid=50,
                           minibatch_size=50)
    py = ExportedForward(path)
    cc = NativeForward(path)
    x = np.random.default_rng(1).normal(
        size=(8,) + py.input_shape).astype(np.float32)
    np.testing.assert_allclose(cc(x), np.asarray(py(x)).reshape(8, -1),
                               rtol=2e-3, atol=2e-4)


def test_native_infer_rejects_unsupported_layer(tmp_path):
    """A package with a layer outside the v1 forward set fails to LOAD
    with the type named — never a silent wrong answer."""
    import json
    import os

    from znicz_tpu.native.infer import NativeForward, available

    if not available():
        pytest.skip("no native compiler/zlib")
    meta = {"format": "znicz_tpu.forward", "version": 1, "name": "bad",
            "ema": False, "input_shape": [4, 4, 2],
            "arch": [{"type": "deconv", "config": {"n_kernels": 2,
                                                   "kx": 3, "ky": 3}}]}
    path = os.path.join(str(tmp_path), "bad.npz")
    with open(path, "wb") as f:
        np.savez_compressed(f, __arch__=np.array(json.dumps(meta)))
    with pytest.raises(ValueError, match="deconv"):
        NativeForward(path)


def _raw_pkg(tmp_path, name, arch, arrays, input_shape=(4, 4, 2)):
    import json
    import os

    meta = {"format": "znicz_tpu.forward", "version": 1, "name": "t",
            "ema": False, "input_shape": list(input_shape), "arch": arch}
    path = os.path.join(str(tmp_path), name)
    with open(path, "wb") as f:
        np.savez_compressed(f, __arch__=np.array(json.dumps(meta)),
                            **arrays)
    return path


def test_native_infer_pooling_default_geometry(tmp_path):
    """A bare {"type": "max_pooling"} config means kx=ky=2 with stride =
    window (the Pooling units' Python defaults) — must load and match the
    oracle, not divide by zero."""
    from znicz_tpu.native.infer import NativeForward, available
    from znicz_tpu.ops import pooling as pool_ops

    if not available():
        pytest.skip("no native compiler/zlib")
    p = _raw_pkg(tmp_path, "pool.npz",
                 [{"type": "max_pooling", "config": {}}], {}, (5, 5, 3))
    x = np.random.default_rng(3).normal(size=(2, 5, 5, 3)).astype(
        np.float32)
    ref, _ = pool_ops.max_forward(np, x, 2, 2, 2, 2)
    np.testing.assert_allclose(NativeForward(p)(x), ref.reshape(2, -1),
                               rtol=1e-6)


def test_native_infer_weights_transposed(tmp_path):
    """weights_transposed fc layers (stored (out, in), applied as W.T —
    All2All.xla_apply_linear) are honored by a load-time transpose."""
    from znicz_tpu.native.infer import NativeForward, available

    if not available():
        pytest.skip("no native compiler/zlib")
    rng = np.random.default_rng(4)
    w_t = rng.normal(size=(6, 32)).astype(np.float32)   # (out, in)
    p = _raw_pkg(tmp_path, "wt.npz",
                 [{"type": "all2all",
                   "config": {"output_sample_shape": 6,
                              "weights_transposed": True}}],
                 {"0.weights": w_t}, (4, 4, 2))
    x = rng.normal(size=(3, 4, 4, 2)).astype(np.float32)
    ref = x.reshape(3, -1) @ w_t.T
    np.testing.assert_allclose(NativeForward(p)(x), ref, rtol=1e-5,
                               atol=1e-6)


def test_native_infer_malformed_packages_fail_closed(tmp_path):
    """Structurally broken packages fail at LOAD with a named reason —
    never UB, never a silent wrong answer."""
    from znicz_tpu.native.infer import NativeForward, available

    if not available():
        pytest.skip("no native compiler/zlib")
    cases = [
        # fc without weights
        ([{"type": "all2all", "config": {"output_sample_shape": 4}}], {}),
        # arch entry without a type key
        ([{"config": {}}], {}),
        # conv weights disagreeing with declared geometry
        ([{"type": "conv", "config": {"n_kernels": 4, "kx": 3, "ky": 3}}],
         {"0.weights": np.zeros((5, 5, 2, 4), np.float32)}),
        # fc weight rows != input features
        ([{"type": "all2all", "config": {"output_sample_shape": 4}}],
         {"0.weights": np.zeros((7, 4), np.float32)}),
    ]
    for i, (arch, arrays) in enumerate(cases):
        p = _raw_pkg(tmp_path, f"bad{i}.npz", arch, arrays)
        with pytest.raises(ValueError):
            NativeForward(p)


def test_native_infer_closed_handle_raises(tmp_path):
    from znicz_tpu.native.infer import NativeForward, available

    if not available():
        pytest.skip("no native compiler/zlib")
    rng = np.random.default_rng(5)
    p = _raw_pkg(tmp_path, "ok.npz",
                 [{"type": "all2all", "config": {"output_sample_shape": 3}}],
                 {"0.weights": rng.normal(size=(32, 3)).astype(np.float32)})
    nf = NativeForward(p)
    nf(np.zeros((1, 4, 4, 2), np.float32))
    nf.close()
    with pytest.raises(RuntimeError, match="closed"):
        nf(np.zeros((1, 4, 4, 2), np.float32))
