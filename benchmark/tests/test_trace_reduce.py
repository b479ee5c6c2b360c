"""The trace-to-metrics reduction: its interval arithmetic on hand-made
events, and its numbers pinned on a small trace recorded on a v5e
(``tests/fixture_v5e.xplane.pb``: six passes of a small jitted matmul loop
under ``bench.pass`` / ``bench.read`` annotations, 80 KB)."""

import os

import pytest

import trace_reduce as tr

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixture_v5e.xplane.pb")


def test_union_counts_overlap_and_nesting_once():
    assert tr.union_length([(0, 10), (5, 12), (20, 30), (22, 25)]) == 22
    assert tr.union_length([]) == 0
    assert tr.union_length([(3, 3)]) == 0


def test_gaps_are_the_complement_inside_the_window():
    busy = [(2, 4), (3, 6), (8, 9)]
    assert tr.gaps_of(busy, 0, 10) == [(0, 2), (6, 8), (9, 10)]
    assert tr.gaps_of(busy, 3, 8) == [(6, 8)]
    assert tr.gaps_of([], 1, 2) == [(1, 2)]


def test_self_time_gives_children_their_own_and_sums_to_the_union():
    # a while of 100 ns holding two fusions of 30 and 20, then a copy
    events = [(0, 100, "while.3"), (10, 40, "fusion.7"),
              (50, 70, "fusion.9"), (120, 150, "copy.1")]
    out = tr.self_times(events)
    assert out == {"while": pytest.approx(50e-9),
                   "fusion": pytest.approx(50e-9),
                   "copy": pytest.approx(30e-9)}
    assert sum(out.values()) == pytest.approx(
        tr.union_length([(s, e) for s, e, _ in events]) / 1e9)


def test_stable_names_drop_compile_numbering():
    assert tr.stable_name("%fusion.123") == "fusion"
    assert tr.stable_name("convert.181.remat") == "convert.remat"
    assert tr.stable_name("all-reduce.4") == "all-reduce"


def _fake_trace(devices, host):
    t = tr.Trace.__new__(tr.Trace)
    t.devices, t.host = devices, host
    t.device_names = sorted(devices)
    return t


def test_idle_gaps_go_to_the_innermost_overlapping_host_span():
    ops = [(0, 100, "fusion.1", "fusion"), (400, 500, "fusion.2", "fusion"),
           (520, 600, "fusion.3", "fusion"), (900, 1000, "psum.4",
                                              "all-reduce")]
    host = [(90, 410, "np.asarray(jax.Array)"), (50, 950, "bench.pass"),
            (600, 905, "PjitFunction(step)")]
    t = _fake_trace({"/device:TPU:0": ops}, host)
    gaps = dict(t.idle_gaps(min_gap_ns=10))
    # 100..400 lies inside both spans: the shorter (inner) one wins;
    # 500..520 only bench.pass covers; 600..900 PjitFunction
    assert gaps == {"np.asarray(jax.Array)": pytest.approx(300e-9),
                    "bench.pass": pytest.approx(20e-9),
                    "PjitFunction(step)": pytest.approx(300e-9)}
    assert t.busy_s() == pytest.approx(380e-9)
    assert t.matching_s(["fusion"]) == (pytest.approx(280e-9), 3)
    # a collective is found by its opcode: JAX names a psum's all-reduce
    # "psum"
    assert t.collective_s() == (pytest.approx(100e-9), 1)
    assert t.matching_s(["all-reduce"]) == (0.0, 0)


def test_hlo_lines_split_into_name_and_opcode():
    assert tr.split_hlo(
        "%psum.3 = f32[11,11,3,96]{3,2,1,0:T(4,128)} all-reduce(f32[11,11,"
        "3,96]{3,2,1,0:T(4,128)} %fusion.9), replica_groups={}") == \
        ("psum.3", "all-reduce")
    assert tr.split_hlo(
        "%copy-start = (s32[2048]{0:T(1024)S(1)}, s32[2048]{0:T(1024)}, "
        "u32[]{:S(2)}) copy-start(s32[2048]{0:T(1024)} %labels.1)") == \
        ("copy-start", "copy-start")
    assert tr.split_hlo("%add.1 = s32[] add(s32[] %a, s32[] %b)") == \
        ("add.1", "add")
    # a consumer names the kernel only among its operands
    name, op = tr.split_hlo(
        "%fusion.5 = bf16[4,2048]{1,0} fusion(bf16[4,2048]{1,0} "
        "%jvp_flash_attention_fwd_.3), kind=kLoop")
    assert (name, op) == ("fusion.5", "fusion")
    assert tr.split_hlo("bench.pass") == ("bench.pass", "")


@pytest.mark.skipif(not os.path.exists(FIXTURE), reason="fixture not recorded")
def test_recorded_v5e_trace_reduces_to_pinned_numbers():
    t = tr.Trace(FIXTURE)
    assert t.device_names == ["/device:TPU:0"]
    pinned = PINNED
    assert t.busy_s() == pytest.approx(pinned["busy_s"], rel=1e-9)
    ops = dict(t.op_self_times())
    assert sum(ops.values()) == pytest.approx(t.busy_s(), rel=1e-6)
    for name, seconds in pinned["ops"].items():
        assert ops[name] == pytest.approx(seconds, rel=1e-9)
    gaps = dict(t.idle_gaps())
    for name, seconds in pinned["gaps"].items():
        assert gaps[name] == pytest.approx(seconds, rel=1e-9)
    lo, hi = t.span()
    assert sum(gaps.values()) <= (hi - lo) / 1e9 - t.busy_s() + 1e-9
    # 30 fusions (6 passes of a 4-trip loop body and a tail), no collective
    assert t.matching_s(["fusion"]) == (pytest.approx(0.000301537), 30)
    assert t.collective_s() == (0.0, 0)
    assert t.breakdown()["device_ops"][0][0] == "convolution_multiply_fusion"


#: read once from the fixture with this file's own code and then frozen, so
#: a later change to the reduction shows as a changed number
PINNED = {
    "busy_s": 0.00036045,
    "ops": {"convolution_multiply_fusion": 0.000277453, "copy": 3.3302e-05,
            "copy-done": 2.4139e-05, "tanh_add_fusion": 2.4084e-05,
            "while": 4.52e-07},
    "gaps": {"bench.pass": 0.068378862, "bench.read": 0.045113788,
             "(no host span)": 0.00011299},
}
