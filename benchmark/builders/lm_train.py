"""Builder kind ``lm_train``: ``TransformerLMStep`` in its unit graph
(Repeater -> loader -> step -> DecisionMSE, the wiring of
``znicz_tpu/models/char_lm.py``), driven by ``Launcher``.

The benchmark supplies, from ``--seed`` through the configuration's
reference module: the weights, made on the device in one jitted call and
handed to the step before ``xla_init`` (no host draw, no transfer), and the
token rows, served by a ``Loader`` of the benchmark's own
(``CharSequenceLoader`` derives its vocabulary from a character corpus; a
token loader inside the program is listed in PERF.md for a later PR).
"""

from __future__ import annotations

import numpy as np

from benchlib import BenchmarkError
from builders.train_common import (TrainCell, TrainTap, apply_engine,
                                   attach_tap, restore_engine)


def _token_loader_class():
    from znicz_tpu.loader.base import TRAIN, Loader

    class SeededTokenLoader(Loader):
        """Serves ``(tokens, next-token labels)`` rows made from the
        seed; the vocabulary is the configuration's."""

        def __init__(self, workflow=None, rows=None, vocab_size: int = 0,
                     **kwargs) -> None:
            super().__init__(workflow, **kwargs)
            self._rows = np.asarray(rows, np.int32)
            self.vocab_size = int(vocab_size)
            self.seq_len = self._rows.shape[1] - 1

        def load_data(self) -> None:
            self.class_lengths[TRAIN] = len(self._rows)

        def create_minibatch_data(self) -> None:
            shape = (self.max_minibatch_size, self.seq_len)
            self.minibatch_data.reset(shape=shape, dtype=np.int32)
            self.minibatch_labels.reset(shape=shape, dtype=np.int32)

        def fill_minibatch(self) -> None:
            idx = np.asarray(self.minibatch_indices.mem)
            rows = self._rows[np.maximum(idx, 0)]
            rows[idx < 0] = 0
            self.minibatch_data.map_write()[...] = rows[:, :-1]
            self.minibatch_labels.map_write()[...] = rows[:, 1:]

    return SeededTokenLoader


def build_workflow(rows, cfg: dict, traffic: dict, mesh=None):
    """The char-LM control graph with the seeded token loader."""
    from znicz_tpu.core.plumbing import Repeater
    from znicz_tpu.units.decision import DecisionMSE
    from znicz_tpu.units.lm import TransformerLMStep
    from znicz_tpu.units.nn_units import NNWorkflow

    opts = cfg["builders"]["lm_train"]
    w = NNWorkflow(name="BenchLM")
    w.repeater = Repeater(w)
    w.loader = _token_loader_class()(
        w, rows=rows, vocab_size=int(cfg["vocab_size"]),
        minibatch_size=int(traffic["minibatch_size"]), shuffle_limit=0)
    step = w.step = TransformerLMStep(
        w, loader=w.loader, n_layers=int(cfg["n_layer"]),
        d=int(cfg["n_embd"]), heads=int(cfg["n_head"]),
        ff=int(cfg["n_inner"]), lr=float(cfg["hyper"]["lr"]), mesh=mesh,
        loss_chunks=opts.get("loss_chunks"))
    dec = w.decision = DecisionMSE(w, max_epochs=10 ** 9,
                                   fail_iterations=10 ** 9)
    w.forwards, w.gds = [step], []
    w.repeater.link_from(w.start_point)
    w.loader.link_from(w.repeater)
    step.link_from(w.loader)
    dec.link_from(step)
    w.repeater.link_from(dec)
    w.end_point.link_from(dec)
    w.end_point.gate_block = ~dec.complete
    dec.link_attrs(w.loader, "minibatch_class", "last_minibatch",
                   "class_lengths", "epoch_number")
    dec.link_attrs(step, "minibatch_mse", "minibatch_size")
    return w


class Cell(TrainCell):
    def __init__(self, ctx) -> None:
        self.ctx = ctx
        opts = ctx.config["builders"]["lm_train"]
        # the depth this builder runs (a cut of depth is listed under the
        # configuration's ``reduced``); the reference follows the same
        self.cfg = {**ctx.config,
                    "n_layer": opts.get("n_layer", ctx.config["n_layer"])}
        self.traffic = ctx.traffic
        self.ref = ctx.roots.module("reference", self.cfg["reference"])
        self.lr = float(self.cfg["hyper"]["lr"])

    def read_loss(self) -> float:
        return float(self.w.step.minibatch_mse)

    def _norms(self) -> dict:
        """Norm of (seeded leaf - the step's leaf), group by group, so
        only one group of seeded leaves is alive beside the program."""
        import jax
        import jax.numpy as jnp

        norm = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))
        params = self.w.step._params
        out = {}
        for group in ("emb", "head"):
            w0 = self.ref.init_leaf_group(self.ctx.seed, self.cfg, group)
            out[group] = float(norm(w0, params[group]))
        for li, blk in enumerate(params["blocks"]):
            w0 = self.ref.init_leaf_group(self.ctx.seed, self.cfg, li)
            for k, leaf in blk.items():
                out[f"B{li}.{k}"] = float(norm(w0[k], leaf))
        return out

    def grad_norms(self) -> dict:
        # plain SGD: g = (w0 - w1) / lr
        return {k: v / self.lr for k, v in self._norms().items()}

    def delta_norms(self) -> dict:
        return self._norms()

    def run(self) -> dict:
        from znicz_tpu.core import prng
        from znicz_tpu.core.backends import TPUDevice, XLADevice
        from znicz_tpu.launcher import Launcher

        ctx, cfg, traffic, ref = self.ctx, self.cfg, self.traffic, self.ref
        if ctx.chips != 1:
            raise BenchmarkError("lm_train cells run on one chip")
        batch, t = int(traffic["minibatch_size"]), int(traffic["seq_len"])
        ctx.roots.module("generators", traffic["generator"]).plan(traffic,
                                                                  batch)
        ref_readings = self.reference_first_steps(1)

        prev_engine = apply_engine({**cfg.get("engine", {}),
                                    **traffic.get("engine", {})})
        try:
            prng.seed_all(ctx.seed & 0x7FFFFFFF)
            rows = ref.make_tokens(ctx.seed, cfg, t, 0,
                                   int(traffic["n_rows"]))
            on_tpu = ctx.devices[0].platform == "tpu"
            device = TPUDevice() if on_tpu else XLADevice(ctx.devices[0])
            launcher = Launcher(device=device)
            self.w, _ = launcher.load(build_workflow, rows=rows, cfg=cfg,
                                      traffic=traffic)
            self.w.step._params = ref.init_params(ctx.seed, cfg)
            tap = TrainTap(ctx, self, k=int(traffic["k_steps"]))
            attach_tap(self.w, tap)
            launcher.main()
        finally:
            restore_engine(prev_engine)
        return self.outcome(tap, ref_readings, batch, 1,
                            ref.train_flops_per_sample(cfg, t))


def run(ctx) -> dict:
    return Cell(ctx).run()
