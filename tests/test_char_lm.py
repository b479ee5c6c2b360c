"""Char-LM workflow tests: the sequence loader's serving contract and
the transformer step as a workflow citizen (epochs, VALID passes,
Decision stopping, snapshot roundtrip) — the beyond-parity model family
riding the reference's control graph."""

import pytest

# full SPMD training runs on the virtual 8-device CPU mesh take
# minutes per file; tier-1 (-m 'not slow') must fit its 870 s
# budget, so these ride the registered slow lane
pytestmark = pytest.mark.slow

import numpy as np

from znicz_tpu.core import prng
from znicz_tpu.core.backends import XLADevice
from znicz_tpu.loader.base import TEST, TRAIN, VALID
from znicz_tpu.models import char_lm


def test_char_sequence_loader_contract(tmp_path):
    """Windows are next-char pairs from the right streams, classes carve
    the corpus deterministically, epochs reshuffle order not content."""
    from znicz_tpu.loader.sequence import CharSequenceLoader

    prng.seed_all(3)
    loader = CharSequenceLoader(None, data_dir=str(tmp_path / "corp"),
                                seq_len=16, minibatch_size=8,
                                valid_fraction=0.2)
    loader.initialize(device=None)
    assert loader.vocab_size > 5
    assert all(loader.class_lengths[c] > 0 for c in (TEST, VALID, TRAIN))
    seen_classes = []
    checked = 0
    for _ in range(100_000):
        loader.run()
        cls = int(loader.minibatch_class)
        if cls not in seen_classes:
            seen_classes.append(cls)
            # verify the first minibatch of each class pass in depth:
            # labels are data shifted by one within the SAME stream window
            data = loader.minibatch_data.mem
            labels = loader.minibatch_labels.mem
            stream = loader._streams[cls]
            for row in range(loader.minibatch_size):
                gi = loader.minibatch_indices.mem[row]
                off = int(loader._starts[gi])
                np.testing.assert_array_equal(data[row],
                                              stream[off:off + 16])
                np.testing.assert_array_equal(labels[row],
                                              stream[off + 1:off + 17])
                checked += 1
        if loader.epoch_number >= 1:
            break
    assert seen_classes == [TEST, VALID, TRAIN]   # reference class order
    assert checked >= 3


def test_char_lm_trains_and_stops(tmp_path):
    """Seeded run: validation CE per char collapses from ln(vocab) and
    the Decision's max_epochs stop fires."""
    prng.seed_all(11)
    w = char_lm.build(max_epochs=4, seq_len=32, minibatch_size=16,
                      n_layers=2, d=32, heads=2,
                      data_dir=str(tmp_path / "corp"))
    w.initialize(device=XLADevice())
    w.run()
    h = w.decision.metrics_history
    assert len(h) == 4
    assert bool(w.decision.complete)
    first, last = h[0]["metric_validation"], h[-1]["metric_validation"]
    # epoch-1 VALID runs before any training: near-random CE, at least
    # ln(vocab) (the uniform-predictor floor)
    assert first > np.log(w.loader.vocab_size) - 0.2
    assert last < 0.5 * first, h                            # learned
    assert np.isfinite(last)


def test_char_lm_snapshot_roundtrip(tmp_path):
    """Params survive a snapshot/restore: the restored workflow's eval
    loss equals the original's (state_dict/load_state_dict contract)."""
    import jax

    prng.seed_all(11)
    w = char_lm.build(max_epochs=2, seq_len=32, minibatch_size=16,
                      data_dir=str(tmp_path / "corp"))
    w.initialize(device=XLADevice())
    w.run()
    state = w.step.state_dict()

    prng.seed_all(99)    # different init — restore must overwrite it
    w2 = char_lm.build(max_epochs=2, seq_len=32, minibatch_size=16,
                       data_dir=str(tmp_path / "corp"))
    w2.initialize(device=XLADevice())
    w2.step.load_state_dict(state)
    tokens = jax.numpy.asarray(
        np.arange(16 * 32, dtype=np.int32).reshape(16, 32)
        % w.loader.vocab_size)
    labels = jax.numpy.roll(tokens, -1, axis=1)
    mask = jax.numpy.ones(16, bool)
    a = float(jax.device_get(w.step._eval(w.step._params, tokens, labels,
                                          mask)))
    b = float(jax.device_get(w2.step._eval(w2.step._params, tokens,
                                           labels, mask)))
    assert abs(a - b) < 1e-5, (a, b)


def test_char_lm_sharded_mesh(tmp_path):
    """The LM step trains under a real dp x sp x tp mesh (params sharded
    by param_specs, minibatches placed P('data','seq'))."""
    from znicz_tpu.parallel.mesh import make_mesh

    prng.seed_all(11)
    w = char_lm.build(max_epochs=2, seq_len=32, minibatch_size=16,
                      n_layers=2, d=32, heads=4,
                      mesh=make_mesh({"data": 2, "seq": 2, "model": 2}),
                      data_dir=str(tmp_path / "corp"))
    w.initialize(device=XLADevice())
    w.run()
    h = w.decision.metrics_history
    assert h[-1]["metric_validation"] < h[0]["metric_validation"], h


def test_char_lm_snapshotter_resume_bit_exact(tmp_path):
    """Full-machinery resume: run 4 epochs with the Snapshotter side
    chain, then rebuild fresh, restore_state from the epoch-2 snapshot,
    continue — the continued run's metric history matches the unbroken
    run's tail (the framework-wide bit-exact-resume contract, now
    covering state_dict-only forwards)."""
    from znicz_tpu.snapshotter import restore_state

    snap_dir = str(tmp_path / "snaps")
    corp = str(tmp_path / "corp")

    def fresh(max_epochs, with_snap):
        prng.seed_all(11)
        return char_lm.build(
            max_epochs=max_epochs, seq_len=32, minibatch_size=16,
            data_dir=corp,
            snapshotter_config={"prefix": "lm", "directory": snap_dir,
                                "only_improved": False, "keep_all": True}
            if with_snap else None)

    w = fresh(4, True)
    w.initialize(device=XLADevice())
    w.run()
    full_hist = w.decision.metrics_history

    w2 = fresh(4, False)
    w2.initialize(device=XLADevice())
    meta = restore_state(w2, str(tmp_path / "snaps" / "lm_2.npz"))
    assert meta["loader"]["epoch_number"] == 2
    w2.run()
    resumed = w2.decision.metrics_history
    # history restored up to epoch 2, then continued identically
    for a, b in zip(full_hist, resumed):
        assert a["epoch"] == b["epoch"]
        np.testing.assert_allclose(a["metric_validation"],
                                   b["metric_validation"], rtol=1e-5)
    assert len(resumed) == len(full_hist)


def test_char_lm_loss_chunks_trains(tmp_path):
    """The chunked-CE lever is reachable from the model zoo: same
    workflow, loss_chunks=4, CE per char still collapses (the chunk
    count only changes summation order)."""
    prng.seed_all(11)
    w = char_lm.build(max_epochs=3, seq_len=32, minibatch_size=16,
                      n_layers=2, d=32, heads=2,
                      data_dir=str(tmp_path / "corp"), loss_chunks=4)
    w.initialize(device=XLADevice())
    w.run()
    h = w.decision.metrics_history
    assert h[-1]["metric_validation"] < \
        0.6 * np.log(w.loader.vocab_size)


def test_char_lm_moe_trains(tmp_path):
    """MoE FFN + aux + top-2 routing reachable from the model zoo: the
    char-LM workflow trains with 4 experts and the CE still collapses."""
    prng.seed_all(11)
    w = char_lm.build(max_epochs=3, seq_len=32, minibatch_size=16,
                      n_layers=2, d=32, heads=2,
                      data_dir=str(tmp_path / "corp"), n_experts=4,
                      moe_aux_weight=0.01, moe_top_k=2)
    w.initialize(device=XLADevice())
    w.run()
    h = w.decision.metrics_history
    assert h[-1]["metric_validation"] < \
        0.7 * np.log(w.loader.vocab_size)
