"""Tier-2 tests for the SpamFilter and YaleFaces sample families plus
direct tier-1 coverage of the text bag-of-words loader (the reference's
research samples pin seeded metrics the same way — SURVEY.md §5)."""

import os

import numpy as np

from znicz_tpu.core import prng
from znicz_tpu.core.backends import XLADevice
from znicz_tpu.core.workflow import Workflow
from znicz_tpu.loader import text as text_mod
from znicz_tpu.models import spam, yale_faces


# ---------------------------------------------------------------------------
# text loader, directly
# ---------------------------------------------------------------------------

def test_corpus_round_trip(tmp_path):
    path = str(tmp_path / "c.txt")
    with open(path, "w", encoding="utf-8") as f:
        f.write("1\tbuy gold buy now\n\n0\thello old friend\n")
    docs, labels = text_mod.read_corpus(path)
    assert docs == [["buy", "gold", "buy", "now"],
                    ["hello", "old", "friend"]]
    assert labels.tolist() == [1, 0]


def test_vocabulary_order_and_vectorize():
    docs = [["b", "a", "b", "c"], ["a", "c", "c", "d"]]
    # counts: b=2 a=2 c=3 d=1 -> order: c(3), a(2), b(2) [alpha tie], d(1)
    vocab = text_mod.build_vocabulary(docs, vocab_size=3)
    assert vocab == {"c": 0, "a": 1, "b": 2}
    mat = text_mod.vectorize([["d", "c", "c", "a"]], vocab)
    np.testing.assert_allclose(
        mat, np.log1p([[2.0, 1.0, 0.0]]), rtol=1e-6)   # d is OOV: dropped


def test_synthesized_corpus_is_deterministic_and_separable(tmp_path):
    d1, d2 = str(tmp_path / "a"), str(tmp_path / "b")
    text_mod.synthesize_text_corpus(d1, n_train=100, n_test=40)
    text_mod.synthesize_text_corpus(d2, n_train=100, n_test=40)
    for name in text_mod.FILES.values():
        with open(os.path.join(d1, name), encoding="utf-8") as f1, \
                open(os.path.join(d2, name), encoding="utf-8") as f2:
            assert f1.read() == f2.read()
    docs, labels = text_mod.read_corpus(os.path.join(d1, "train.txt"))
    assert sorted(set(labels.tolist())) == [0, 1]
    # nearest-class-mean over raw counts separates the two classes
    vocab = text_mod.build_vocabulary(docs, 300)
    mat = text_mod.vectorize(docs, vocab)
    means = np.stack([mat[labels == c].mean(0) for c in (0, 1)])
    pred = np.argmin(((mat[:, None] - means[None]) ** 2).sum(-1), axis=1)
    assert (pred == labels).mean() > 0.95


def test_torn_corpus_is_regenerated(tmp_path):
    """A synthesis interrupted between the train and test writes must be
    detected and repaired, not served with an empty VALID split."""
    d = str(tmp_path / "torn")
    text_mod.synthesize_text_corpus(d, n_train=50, n_test=20)
    os.remove(os.path.join(d, text_mod.FILES["test"]))
    loader = text_mod.TextBagOfWordsLoader(
        Workflow(name="torn"), data_dir=d, minibatch_size=10)
    loader._ensure_files()
    assert os.path.exists(os.path.join(d, text_mod.FILES["test"]))


def test_image_tree_regeneration_contract(tmp_path):
    from znicz_tpu.loader import image as image_mod

    d = str(tmp_path / "tree")
    image_mod.ensure_image_tree(d, n_classes=3, n_per_class=2,
                                size=(8, 8))
    vfile = os.path.join(d, ".synth_version")
    assert open(vfile).read().strip() == image_mod.SYNTH_VERSION
    # stale marker -> rebuilt; fresh marker -> untouched
    mtime = os.path.getmtime(vfile)
    image_mod.ensure_image_tree(d, n_classes=3, n_per_class=2,
                                size=(8, 8))
    assert os.path.getmtime(vfile) == mtime
    with open(vfile, "w") as f:
        f.write("0-stale")
    image_mod.ensure_image_tree(d, n_classes=3, n_per_class=2,
                                size=(8, 8))
    assert open(vfile).read().strip() == image_mod.SYNTH_VERSION
    # markerless non-empty tree = user data: never touched
    user = str(tmp_path / "user")
    os.makedirs(os.path.join(user, "class_a"))
    with open(os.path.join(user, "class_a", "x.txt"), "w") as f:
        f.write("sentinel")
    image_mod.ensure_image_tree(user)
    assert os.listdir(user) == ["class_a"]


def test_text_loader_serves_and_restores(tmp_path):
    d = str(tmp_path / "corpus")
    text_mod.synthesize_text_corpus(d, n_train=80, n_test=20)
    prng.seed_all(5)
    w = Workflow(name="t")
    loader = text_mod.TextBagOfWordsLoader(
        w, data_dir=d, vocab_size=64, minibatch_size=20)
    loader.initialize(device=XLADevice())
    assert loader.class_lengths == [0, 20, 80]
    assert len(loader.vocab) == 64
    assert loader.original_data.shape == (100, 64)
    loader.run()
    assert loader.minibatch_data.mem.shape == (20, 64)
    served = loader.original_data.mem.copy()

    # state round-trip into a fresh loader over the same files
    state = loader.state_dict()
    prng.seed_all(99)                      # restore must not depend on prng
    loader2 = text_mod.TextBagOfWordsLoader(
        Workflow(name="t2"), data_dir=d, vocab_size=64, minibatch_size=20)
    loader2.initialize(device=XLADevice())
    loader2.load_state_dict(state)
    assert loader2.vocab == loader.vocab
    np.testing.assert_allclose(loader2.original_data.mem, served,
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# sample workflows, pinned (tier-2)
# ---------------------------------------------------------------------------

def _train(build, seed=31, **kw):
    prng.seed_all(seed)
    w = build(**kw)
    w.initialize(device=XLADevice())
    w.run()
    assert bool(w.decision.complete)
    return w


def test_spam_sample():
    w = _train(spam.build, max_epochs=5)
    hist = w.decision.metrics_history
    assert [int(h["metric_validation"]) for h in hist] == \
        [86, 0, 0, 0, 0], hist
    assert int(hist[0]["metric_train"]) == 28, hist
    assert w.loader.class_lengths == [0, 200, 600]
    assert len(w.loader.vocab) == 256


def test_yale_faces_sample():
    w = _train(yale_faces.build, max_epochs=5)
    hist = w.decision.metrics_history
    assert [int(h["metric_validation"]) for h in hist] == \
        [72, 6, 0, 0, 0], hist
    assert [int(h["metric_train"]) for h in hist][:2] == [139, 8], hist
    assert w.loader.n_classes == 15
    assert w.loader.class_lengths == [0, 75, 225]
