"""Tier-3 core-framework tests (SURVEY.md §5): config, gates, unit graph,
memory mapping, prng determinism — the rebuild of veles/tests/ core tests."""

import pickle

import numpy as np

from znicz_tpu.core import prng
from znicz_tpu.core.backends import NumpyDevice, XLADevice
from znicz_tpu.core.config import Config, Tune, fix_config, root, walk_tunes
from znicz_tpu.core.memory import Array, roundup
from znicz_tpu.core.mutable import Bool
from znicz_tpu.core.plumbing import Repeater
from znicz_tpu.core.units import TrivialUnit, Unit
from znicz_tpu.core.workflow import Workflow


# -- config -----------------------------------------------------------------

def test_config_tree_autovivify_and_update():
    cfg = Config("test")
    cfg.loader.minibatch_size = 60
    assert cfg.loader.minibatch_size == 60
    cfg.update({"decision": {"max_epochs": 3}, "lr": 0.01})
    assert cfg.decision.max_epochs == 3 and cfg.lr == 0.01
    assert "loader" in cfg and "missing" not in cfg
    assert not cfg.empty_subtree
    assert cfg.as_dict()["decision"] == {"max_epochs": 3}


def test_config_tune_fix_and_walk():
    cfg = Config("test")
    cfg.gd.learning_rate = Tune(0.01, 0.001, 0.1)
    cfg.gd.momentum = 0.9
    tunes = dict(walk_tunes(cfg))
    assert list(tunes) == ["gd.learning_rate"]
    fix_config(cfg)
    assert cfg.gd.learning_rate == 0.01


def test_root_defaults_exist():
    assert root.common.engine.get("backend") in ("auto", "tpu", "numpy")


# -- mutable gates ----------------------------------------------------------

def test_bool_assignment_and_composites():
    complete = Bool(False)
    improved = Bool(True)
    gate = ~complete & improved
    assert bool(gate)
    complete <<= True
    assert not bool(gate)  # composite re-evaluates operands live
    blocked = complete | Bool(False)
    assert bool(blocked)


# -- memory -----------------------------------------------------------------

def test_roundup():
    assert roundup(5, 4) == 8 and roundup(8, 4) == 8


def test_array_map_semantics_numpy_device():
    arr = Array(np.arange(6, dtype=np.float32).reshape(2, 3))
    arr.initialize(NumpyDevice())
    assert arr.map_read()[1, 2] == 5.0
    arr.map_write()[0, 0] = 42.0
    assert arr.mem[0, 0] == 42.0


def test_array_device_roundtrip():
    dev = XLADevice()  # CPU jax device under the test platform
    arr = Array(np.ones((4, 4), dtype=np.float32))
    arr.initialize(dev)
    dv = arr.devmem
    assert dv.shape == (4, 4)
    # simulate a compiled-step output replacing the buffer
    arr.set_devmem(dv * 3.0)
    assert arr.map_read()[0, 0] == 3.0
    # host write flows back on next devmem access
    arr.map_write()[0, 0] = 7.0
    assert float(arr.devmem[0, 0]) == 7.0


def test_array_pickle_drops_device():
    dev = XLADevice()
    arr = Array(np.full((2, 2), 5.0, np.float32))
    arr.initialize(dev)
    arr.set_devmem(arr.devmem + 1)
    restored = pickle.loads(pickle.dumps(arr))
    assert restored.mem[0, 0] == 6.0 and restored.device is None


# -- prng -------------------------------------------------------------------

def test_prng_determinism_and_state():
    gen = prng.get("t1")
    gen.seed(123)
    a = gen.uniform(-1, 1, (5,))
    state = gen.state_dict()
    b = gen.uniform(-1, 1, (5,))
    gen.load_state_dict(state)
    b2 = gen.uniform(-1, 1, (5,))
    np.testing.assert_array_equal(b, b2)
    gen.seed(123)
    np.testing.assert_array_equal(a, gen.uniform(-1, 1, (5,)))


def test_prng_keys_deterministic():
    gen = prng.get("t2")
    gen.seed(7)
    k1 = gen.key()
    gen.seed(7)
    k2 = gen.key()
    assert (np.asarray(k1) == np.asarray(k2)).all()


# -- unit graph -------------------------------------------------------------

class Recorder(Unit):
    """Appends its name to a shared trace on each run."""

    def __init__(self, workflow, trace, name):
        super().__init__(workflow, name=name)
        self.trace = trace

    def run(self):
        self.trace.append(self.name)


def test_control_chain_and_all_links_join():
    wf = Workflow(name="wf")
    trace = []
    a = Recorder(wf, trace, "a")
    b = Recorder(wf, trace, "b")
    c = Recorder(wf, trace, "c")  # fires only after BOTH a and b
    a.link_from(wf.start_point)
    b.link_from(wf.start_point)
    c.link_from(a)
    c.link_from(b)
    wf.end_point.link_from(c)
    wf.initialize(device=None)
    wf.run()
    assert trace == ["a", "b", "c"]
    assert wf.end_point.reached


def test_gate_skip_propagates_without_running():
    wf = Workflow(name="wf")
    trace = []
    a = Recorder(wf, trace, "a")
    b = Recorder(wf, trace, "b")
    a.link_from(wf.start_point)
    b.link_from(a)
    wf.end_point.link_from(b)
    a.gate_skip <<= True
    wf.initialize(device=None)
    wf.run()
    assert trace == ["b"]  # a skipped but signal propagated


def test_gate_block_stops_propagation():
    wf = Workflow(name="wf")
    trace = []
    a = Recorder(wf, trace, "a")
    a.link_from(wf.start_point)
    wf.end_point.link_from(a)
    a.gate_block <<= True
    wf.initialize(device=None)
    wf.run()
    assert trace == [] and not wf.end_point.reached


def test_repeater_loop_with_decision_gate():
    """The reference's training-loop shape: Repeater -> work -> decision,
    loop back to Repeater until `complete` flips, then end_point opens."""
    wf = Workflow(name="wf")
    trace = []

    class Decision(Unit):
        def __init__(self, workflow):
            super().__init__(workflow, name="decision")
            self.complete = Bool(False)
            self.n = 0

        def run(self):
            self.n += 1
            if self.n >= 3:
                self.complete <<= True

    rep = Repeater(wf)
    work = Recorder(wf, trace, "work")
    dec = Decision(wf)
    rep.link_from(wf.start_point)
    work.link_from(rep)
    dec.link_from(work)
    rep.link_from(dec)           # loop back-edge
    rep.gate_block = dec.complete
    wf.end_point.link_from(dec)
    wf.end_point.gate_block = ~dec.complete
    wf.initialize(device=None)
    wf.run()
    assert trace == ["work"] * 3
    assert wf.end_point.reached


def test_link_attrs_aliasing_two_way():
    wf = Workflow(name="wf")
    a = TrivialUnit(wf, name="a")
    b = TrivialUnit(wf, name="b")
    a.output = Array(np.zeros(3, np.float32))
    b.link_attrs(a, ("input", "output"))
    assert b.input is a.output
    a.output = Array(np.ones(3, np.float32))
    assert b.input is a.output  # live alias, not a snapshot
    b.input = Array(np.full(3, 2.0, np.float32))
    assert a.output.mem[0] == 2.0  # two-way write-back


def test_timing_table():
    wf = Workflow(name="wf")
    trace = []
    a = Recorder(wf, trace, "a")
    a.link_from(wf.start_point)
    wf.end_point.link_from(a)
    wf.initialize(device=None)
    wf.run()
    table = wf.timing_table()
    assert "a" in table and "runs" in table


def test_metrics_jsonl_sink(tmp_path):
    """root.common.metrics_file streams one JSON object per epoch
    (SURVEY §6.5 machine-readable metrics)."""
    import json

    from znicz_tpu.core import prng
    from znicz_tpu.core.backends import XLADevice
    from znicz_tpu.core.config import root
    from znicz_tpu.models import wine

    path = tmp_path / "metrics.jsonl"
    root.common.metrics_file = str(path)
    try:
        prng.seed_all(3)
        w = wine.build(max_epochs=3, n_train=60, n_valid=30,
                       minibatch_size=10)
        w.initialize(device=XLADevice())
        w.run()
    finally:
        del root.common.metrics_file
    lines = [json.loads(line) for line in path.read_text().splitlines()]
    assert [rec["epoch"] for rec in lines] == [1, 2, 3]
    assert all("metric_validation" in rec and rec["workflow"] == "Wine"
               for rec in lines), lines
