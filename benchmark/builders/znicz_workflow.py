"""Builder kind ``znicz_workflow``: a source-paper workflow file
(``znicz_tpu/models/<name>.py``) through ``Launcher.load`` +
``Launcher.main``, exactly as ``python -m znicz_tpu <model>.py`` runs it,
with the dataset resident on the device and index-fed
(``FusedTrainStep``).

What the benchmark supplies, all from ``--seed`` and all made by the
configuration's reference module, so the reference can make them again:
the weights (set into the forward units before ``initialize``, which keeps
what it finds), the images (a ``FullBatchLoader`` of the benchmark's own,
registered under ``bench_seeded_rows``) and the step's dropout key (set
when the tap initializes, after the step).
"""

from __future__ import annotations

import importlib

import numpy as np

from benchlib import BenchmarkError
from builders.train_common import (TrainCell, TrainTap, apply_engine,
                                   attach_tap, restore_engine)

_LOADER_NAME = "bench_seeded_rows"


def _register_loader() -> None:
    from znicz_tpu.loader.base import TRAIN, get_loader, register_loader
    from znicz_tpu.loader.fullbatch import FullBatchLoader

    try:
        get_loader(_LOADER_NAME)
        return
    except (KeyError, ValueError):
        pass

    @register_loader(_LOADER_NAME)
    class SeededRowsLoader(FullBatchLoader):
        """Serves the rows the reference module makes from the seed."""

        def __init__(self, workflow=None, n_train: int = 0, source=None,
                     **kwargs) -> None:
            for unused in ("n_classes", "sample_shape", "n_valid", "spread",
                           "noise"):
                kwargs.pop(unused, None)
            super().__init__(workflow, **kwargs)
            self.n_train = int(n_train)
            self._source = source      # {"ref": module, "seed", "cfg"}

        def load_data(self) -> None:
            src = self._source
            data, labels = src["ref"].make_rows(src["seed"], src["cfg"], 0,
                                                self.n_train)
            self.original_data.mem = data
            self.original_labels.mem = labels
            self.class_lengths[TRAIN] = self.n_train


class Cell(TrainCell):
    def __init__(self, ctx) -> None:
        self.ctx = ctx
        self.cfg, self.traffic = ctx.config, ctx.traffic
        self.ref = ctx.roots.module("reference", self.cfg["reference"])
        self.chips = ctx.chips
        self.per_chip = int(self.cfg["minibatch_size"])
        self.batch = self.per_chip * self.chips
        self._total, self._seen = 0.0, 0

    # -- what the tap calls --------------------------------------------------
    def read_loss(self) -> float:
        st = self.w.step
        st.flush_metrics()
        total, seen = float(st.loss), int(st.minibatch_size)
        if seen > self._seen:
            value = (total - self._total) / (seen - self._seen)
        else:                      # a new class pass: its mean so far
            value = total / max(seen, 1)
        self._total, self._seen = total, seen
        return value

    def _norms(self, fn, keep: dict | None = None) -> dict:
        import jax.numpy as jnp

        out = {}
        for j, i in enumerate(self.ref.PARAM_LAYERS):
            leaf = self.w.step._params[i]
            for k in ("w", "b"):
                w0 = self.weights0[j][k]
                value = fn(leaf, k, jnp.asarray(w0))
                out[f"L{i}.{k}"] = float(jnp.sqrt(jnp.sum(jnp.square(value))))
                if keep is not None:
                    keep[f"L{i}.{k}"] = np.asarray(value)
        return out

    @staticmethod
    def _shaped(a, like):
        """A flat-sharded (ZeRO-1, padded) leaf back in its own shape."""
        return a.reshape(-1)[:like.size].reshape(like.shape).astype(like.dtype)

    def grad_norms(self) -> dict:
        # momentum SGD from rest: v1 = lr * (g / batch + wd * w0)
        h = self.cfg["hyper"]
        lr = float(h["lr"])
        wd = {"w": float(h["weights_decay"]),
              "b": float(h["weights_decay_bias"])}
        self.grad_first: dict = {}
        return self._norms(lambda leaf, k, w0: self._shaped(
            leaf["v" + k], w0) / lr - wd[k] * w0, keep=self.grad_first)

    def delta_norms(self) -> dict:
        return self._norms(lambda leaf, k, w0: self._shaped(leaf[k], w0) - w0)

    # -- the run -------------------------------------------------------------
    def _check_hyper(self) -> None:
        h = self.cfg["hyper"]
        want = {"lr": h["lr"], "wd": h["weights_decay"],
                "mom": h["momentum"], "lr_b": h["lr"],
                "wd_b": h["weights_decay_bias"], "mom_b": h["momentum"],
                "l1": 0.0}
        got = self.w.step.hyper_params()
        for i in self.ref.PARAM_LAYERS:
            for key, value in want.items():
                if not np.isclose(got[i][key], value, rtol=1e-6):
                    raise BenchmarkError(
                        f"layer {i} runs {key}={got[i][key]}, the "
                        f"configuration file states {value}")

    def run(self) -> dict:
        from znicz_tpu.core import prng
        from znicz_tpu.core.backends import TPUDevice, XLADevice
        from znicz_tpu.launcher import Launcher
        from znicz_tpu.parallel.mesh import data_parallel_mesh

        ctx, cfg, traffic, ref = self.ctx, self.cfg, self.traffic, self.ref
        ctx.roots.module("generators", traffic["generator"]).plan(
            traffic, self.batch)
        ref_readings = self.reference_first_steps(self.chips)

        _register_loader()
        self.weights0 = ref.make_weights(ctx.seed, cfg)
        prev_engine = apply_engine({**cfg.get("engine", {}),
                                    **traffic.get("engine", {})})
        try:
            prng.seed_all(ctx.seed & 0x7FFFFFFF)
            devices = ctx.devices[:self.chips]
            on_tpu = devices[0].platform == "tpu"
            device = TPUDevice() if on_tpu and self.chips == 1 else \
                XLADevice(devices[0])
            mesh = data_parallel_mesh(self.chips, devices) \
                if self.chips > 1 else None
            module = importlib.import_module(cfg["workflow"]["module"])
            launcher = Launcher(device=device)
            kwargs = dict(cfg["workflow"]["kwargs"])
            kwargs["minibatch_size"] = self.batch
            self.w, _ = launcher.load(
                getattr(module, cfg["workflow"]["builder"]), mesh=mesh,
                max_epochs=10 ** 9, n_train=int(traffic["n_train"]),
                loader_name=_LOADER_NAME,
                loader_config={"shuffle_limit": 0, "source": {
                    "ref": ref, "seed": ctx.seed, "cfg": cfg}}, **kwargs)
            w = self.w
            # the clock ends the window: the decision unit would stop the
            # run after 100 epochs without a better training error
            w.decision.fail_iterations = 10 ** 9
            if traffic.get("shard_update"):
                w.step.shard_update = True          # ZeRO-1 momenta
            for j, i in enumerate(ref.PARAM_LAYERS):
                w.forwards[i].weights.mem = self.weights0[j]["w"].copy()
                w.forwards[i].bias.mem = self.weights0[j]["b"].copy()

            def seed_step_key() -> None:
                st = w.step
                st._key = st._put(np.asarray(ref.dropout_key(ctx.seed)))
                self._check_hyper()
                if st._dataset_dev is None:
                    raise BenchmarkError(
                        "the step did not pin the dataset on the device; "
                        "the cell measures the index-fed path")

            tap = TrainTap(ctx, self, k=int(traffic["k_steps"]))
            attach_tap(w, tap, on_initialize=seed_step_key)
            launcher.main()
        finally:
            restore_engine(prev_engine)
        tap.readings["grad_first"] = self.grad_first
        return self.outcome(tap, ref_readings, self.batch, self.chips,
                            ref.train_flops_per_sample(cfg))


def run(ctx) -> dict:
    return Cell(ctx).run()
