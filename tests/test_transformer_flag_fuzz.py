"""Seeded composition fuzz over the transformer step's flag surface:
every MATH-PRESERVING flag (loss_chunks, head_sharded, donate,
shard_update) must leave the training trajectory unchanged vs the plain
step in ANY combination on ANY mesh — pairwise parity is pinned
elsewhere; this catches interaction bugs between the execution-strategy
switches.  Model-CHANGING flags (n_experts/top_k/aux) are fuzzed for
mesh invariance instead (tp1 == tp2 for the same config)."""

import pytest

# full SPMD training runs on the virtual 8-device CPU mesh take
# minutes per file; tier-1 (-m 'not slow') must fit its 870 s
# budget, so these ride the registered slow lane
pytestmark = pytest.mark.slow

import numpy as np

import jax

from znicz_tpu.core import prng
from znicz_tpu.parallel.mesh import make_mesh
from znicz_tpu.parallel import transformer as tfm
from znicz_tpu.parallel.params import init_params

MESHES = (
    {"data": 2, "seq": 2, "model": 2},
    {"data": 4, "seq": 1, "model": 2},
    {"data": 2, "seq": 1, "model": 1},
    {"data": 1, "seq": 2, "model": 4},
)


def _run(mesh, masked, tokens, labels, mask, n_steps=3, **kw):
    n_layers, d, heads, ff, vocab = 2, 32, 4, 64, 16
    prng.seed_all(41)
    params = init_params(prng.get(), n_layers, d, heads, ff, vocab,
                         n_experts=kw.get("n_experts"))
    step, _ = tfm.make_train_step(mesh, n_layers, d, heads, ff, vocab,
                                  lr=0.2, masked=masked, **kw)
    args = (tokens, labels, mask) if masked else (tokens, labels)
    run = []
    for _ in range(n_steps):
        params, loss = step(params, *args)
        run.append(float(loss))
    return run, jax.device_get(jax.tree.leaves(params))


def test_math_preserving_flag_combinations(cpu_devices):
    rng = np.random.default_rng(99)
    tokens = rng.integers(0, 16, (4, 16)).astype(np.int32)
    labels = ((tokens + 1) % 16).astype(np.int32)
    mask = np.array([True, True, True, False])

    baselines = {}   # (mesh_axes, masked) -> (losses, params); the
                     # baseline is flag-independent so duplicates memoize
    for trial in range(6):
        mesh_axes = MESHES[int(rng.integers(len(MESHES)))]
        masked = bool(rng.integers(2))
        flags = {
            "loss_chunks": [None, 2, 3, 5][int(rng.integers(4))],
            "head_sharded": bool(rng.integers(2)),
            "donate": False,   # donation forbids plain-python rebinds
                               # of the SAME host params; covered by
                               # test_donate_matches_baseline
            "shard_update": bool(rng.integers(2)),
        }
        mesh = make_mesh(mesh_axes)
        key = (tuple(sorted(mesh_axes.items())), masked)
        if key not in baselines:
            baselines[key] = _run(mesh, masked, tokens, labels, mask)
        base, base_p = baselines[key]
        got, got_p = _run(mesh, masked, tokens, labels, mask, **flags)
        np.testing.assert_allclose(
            got, base, rtol=2e-4, atol=2e-5,
            err_msg=f"trial {trial}: {mesh_axes} masked={masked} {flags}")
        for a, b in zip(got_p, base_p):
            np.testing.assert_allclose(
                a, b, rtol=3e-4, atol=3e-5,
                err_msg=f"trial {trial}: {mesh_axes} {flags}")


def test_model_changing_flags_mesh_invariant(cpu_devices):
    rng = np.random.default_rng(7)
    tokens = rng.integers(0, 16, (4, 16)).astype(np.int32)
    labels = ((tokens + 1) % 16).astype(np.int32)
    mask = np.array([True, True, False, False])

    for trial in range(3):
        flags = {
            "n_experts": int(rng.choice([2, 4])),
            "moe_top_k": int(rng.integers(1, 3)),
            "moe_aux_weight": float(rng.choice([0.0, 0.01])),
            "loss_chunks": [None, 4][int(rng.integers(2))],
            "head_sharded": bool(rng.integers(2)),
        }
        masked = bool(rng.integers(2))
        a, _ = _run(make_mesh({"data": 2, "seq": 2, "model": 1}),
                    masked, tokens, labels, mask, **flags)
        b, _ = _run(make_mesh({"data": 2, "seq": 2, "model": 2}),
                    masked, tokens, labels, mask, **flags)
        np.testing.assert_allclose(
            b, a, rtol=2e-4, atol=2e-5,
            err_msg=f"trial {trial}: masked={masked} {flags}")


def test_quantized_collectives_gate(cpu_devices):
    """ISSUE 18 gate over the quantized transformer path: mode=off is
    BIT-IDENTICAL to a step that never saw the config for random
    math-preserving flag combos on random meshes; int8 and bf16 (same
    explicit-psum semantics, different codec noise) track each other
    tightly; and on model=1 meshes the quantized trajectory matches the
    single-device FULL-BATCH run — the true-batch-mean pin the exact
    path's AD-transposed reduction does not satisfy (see
    make_train_step's reduction-semantics note)."""
    rng = np.random.default_rng(18)
    tokens = rng.integers(0, 16, (4, 16)).astype(np.int32)
    labels = ((tokens + 1) % 16).astype(np.int32)
    mask = np.array([True, True, True, False])

    for trial in range(3):
        mesh_axes = MESHES[int(rng.integers(len(MESHES)))]
        masked = bool(rng.integers(2))
        flags = {
            "loss_chunks": [None, 2][int(rng.integers(2))],
            "head_sharded": bool(rng.integers(2)),
            "shard_update": bool(rng.integers(2)),
        }
        mesh = make_mesh(mesh_axes)
        base, base_p = _run(mesh, masked, tokens, labels, mask, **flags)
        off, off_p = _run(mesh, masked, tokens, labels, mask,
                          quantized_collectives={"mode": "off"}, **flags)
        assert off == base, (trial, mesh_axes, masked, flags)
        for a, b in zip(off_p, base_p):
            np.testing.assert_array_equal(
                a, b, err_msg=f"trial {trial}: {mesh_axes} {flags}")

    # single-device full-batch reference: what a true batch-mean
    # gradient trajectory must reproduce regardless of the data/seq
    # split (the transformer codec path carries no EF residual, so the
    # int8 band is codec noise alone)
    ref, _ = _run(make_mesh({"data": 1, "seq": 1, "model": 1}),
                  False, tokens, labels, mask)
    for mesh_axes in ({"data": 2, "seq": 1, "model": 1},
                      {"data": 2, "seq": 2, "model": 1}):
        mesh = make_mesh(mesh_axes)
        runs = {}
        for mode in ("bf16", "int8"):
            runs[mode], _ = _run(
                mesh, False, tokens, labels, mask,
                quantized_collectives={"mode": mode, "chunk": 128})
        np.testing.assert_allclose(runs["int8"], runs["bf16"],
                                   rtol=0.05, err_msg=str(mesh_axes))
        np.testing.assert_allclose(runs["bf16"], ref, rtol=5e-3,
                                   err_msg=str(mesh_axes))
        np.testing.assert_allclose(runs["int8"], ref, rtol=0.05,
                                   err_msg=str(mesh_axes))
