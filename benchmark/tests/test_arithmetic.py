"""The estimator, the generator and the comparison, pinned on the CPU."""

import collections

import numpy as np
import pytest

import benchlib
from generators import open_loop_quantiles as gen

#: a serving mix at the lengths a chip run would use (the shipped directory
#: has none yet: PERF.md, Open questions)
CHAT = {"rate_rps": 3.0,
        "prompt_tokens": {"median": 192, "p95": 1024, "lo": 16, "hi": 1536,
                          "round_to": 16},
        "output_tokens": {"median": 32, "p95": 80, "lo": 8, "hi": 96,
                          "round_to": 1}}


def test_whole_window_rate_sees_one_stalled_sub_window():
    walls = [1.0] * 24
    walls[7] = 1.6                      # one host stall of 0.6 s
    est = benchlib.window_rates(walls, 4096, chips=1)
    # the end-to-end rate is all the samples over all the time: 2.4 % low
    assert est["rate_window"] == pytest.approx(4096 * 24 / 24.6)
    # the per-layer median rate is what the step sustains unstalled
    assert est["rate_median"] == pytest.approx(4096.0)
    assert est["slowest"][0] == (7, 1.6)
    assert est["n_windows"] == 24


def test_window_rates_per_chip_and_empty():
    est = benchlib.window_rates([2.0, 2.0], 1024, chips=4)
    assert est["rate_window"] == est["rate_median"] == pytest.approx(128.0)
    with pytest.raises(ValueError):
        benchlib.window_rates([], 1, 1)


def test_percentile_matches_numpy():
    xs = np.random.default_rng(0).normal(size=257)
    for q in (50, 85, 95, 99):
        assert benchlib.percentile(xs, q) == pytest.approx(
            float(np.percentile(xs, q)))


@pytest.mark.parametrize("seconds", [20.0, 45.0])
def test_quantile_mix_same_multiset_for_every_seed(seconds):
    runs = [gen.generate(CHAT, seed, seconds, vocab=50257)
            for seed in (1, 2, 3000000019)]
    key = lambda r: (len(r["prompt"]), r["max_new"])     # noqa: E731
    counts = [len(r) for r in runs]
    assert len(set(counts)) == 1 and counts[0] == gen.count(CHAT, seconds)
    # the same prompt lengths and the same output lengths, as multisets
    for field in (lambda r: len(r["prompt"]), lambda r: r["max_new"]):
        bags = [collections.Counter(field(r) for r in run) for run in runs]
        assert bags[0] == bags[1] == bags[2]
    # but another order, another pairing, other arrivals
    assert [key(r) for r in runs[0]] != [key(r) for r in runs[1]]
    assert [r["due_s"] for r in runs[0]] != [r["due_s"] for r in runs[1]]
    # and the same seed gives the same inputs
    again = gen.generate(CHAT, 1, seconds, vocab=50257)
    assert all((a["prompt"] == b["prompt"]).all() and a["due_s"] == b["due_s"]
               for a, b in zip(runs[0], again))


def test_quantile_mix_shape():
    prompts, outputs = gen.multiset(CHAT, 90)
    assert prompts.min() >= 16 and prompts.max() <= 1536
    assert (prompts % 16 == 0).all()
    assert outputs.min() >= 8 and outputs.max() <= 96
    assert 176 <= np.median(prompts) <= 208          # median 192, paged
    assert 30 <= np.median(outputs) <= 34
    due = [r["due_s"] for r in gen.generate(CHAT, 5, 45.0, 50257)]
    assert due == sorted(due) and 0 <= due[0] and due[-1] < 45.0


def test_worst_leaf_gap_uses_the_median_leaf_for_tiny_gradients():
    ref = {"a": 1.0, "b": 2.0, "c": 1e-9}
    prog = {"a": 1.01, "b": 2.0, "c": 3e-9}           # c is all but zero
    gap, leaf = benchlib.worst_leaf_gap(prog, ref)
    assert leaf == "a" and gap == pytest.approx(0.01)
    with pytest.raises(benchlib.BenchmarkError):
        benchlib.worst_leaf_gap({"a": 1.0}, ref)


def test_judge_prints_each_number_beside_its_limit_and_fails_nan():
    ok, lines = benchlib.judge({"x": (0.5, "d"), "y": (float("nan"), "d")},
                               {"x": 1.0, "y": 1.0})
    assert not ok and "limit 1" in lines[0] and lines[1].endswith("[d]")
    assert "FAILED" in lines[1] and "ok" in lines[0]


def test_unknown_device_kind_is_an_error():
    roots = benchlib.Roots()
    assert benchlib.peaks_for("TPU v5 lite", roots)["bf16_flops"] == 197e12
    with pytest.raises(benchlib.BenchmarkError):
        benchlib.peaks_for("TPU v9", roots)
