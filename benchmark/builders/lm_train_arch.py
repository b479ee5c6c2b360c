"""Builder kind ``lm_train_arch``: ``TransformerLMStep`` built from a model's
own configuration keys (``layer_types``, ``num_experts``, ... and this
chip's ``experts_held``), in the unit graph of ``znicz_tpu/models/
char_lm.py`` (Repeater -> loader -> step -> DecisionMSE), driven by
``Launcher``: the path ``python -m znicz_tpu <workflow file>`` takes.

As ``lm_train`` does, the benchmark supplies from ``--seed``, through the
configuration's reference module, the weights (made on the device in one
jitted call and handed to the step before ``xla_init``) and the token rows
(``lm_train``'s seeded loader).  What differs: the step leaves its loss on
the device, so the tap reads ``step.last_loss`` after its own fence; the
small leaves' first gradients are kept whole for ``grad_diff_gap``; and the
routed layers' counters go to the readers (``samples["moe"]``, and the
pairs a step into ``config_as_run`` for ``kernels/moe_gmm.py``).

A program that cannot build a step from such keys (every commit before
ISSUE 28) is refused at once, before the reference runs.
"""

from __future__ import annotations

import numpy as np

from benchlib import BenchmarkError
from builders.lm_train import _token_loader_class
from builders.train_common import (TrainCell, TrainTap, apply_engine,
                                   attach_tap, restore_engine)

#: the configuration keys that are the model's own; the rest of the file
#: (deployment, what was reduced or assumed, the builder's options) is not
MODEL_KEYS = (
    "model_type", "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_attention_heads", "num_key_value_heads", "layer_types",
    "num_hidden_layers", "num_dense_layers", "num_experts_per_tok",
    "conv_L_cache", "conv_bias", "norm_eps", "norm_topk_prob",
    "rope_parameters", "routed_scaling_factor", "use_expert_bias",
    "vocab_size")


def arch_config(cfg: dict) -> dict:
    """The mapping the step is built from: the model's keys as run, the
    router at its published width, and the experts this chip holds."""
    return {**{k: cfg[k] for k in MODEL_KEYS},
            "num_experts": int(cfg["router_width"]),
            "experts_held": dict(cfg["experts_held"])}


def build_workflow(rows, cfg: dict, traffic: dict, mesh=None):
    """The char-LM control graph with the seeded token loader."""
    from znicz_tpu.core.plumbing import Repeater
    from znicz_tpu.units.decision import DecisionMSE
    from znicz_tpu.units.lm import TransformerLMStep
    from znicz_tpu.units.nn_units import NNWorkflow

    opts = cfg["builders"]["lm_train_arch"]
    w = NNWorkflow(name="BenchLM")
    w.repeater = Repeater(w)
    w.loader = _token_loader_class()(
        w, rows=rows, vocab_size=int(cfg["vocab_size"]),
        minibatch_size=int(traffic["minibatch_size"]), shuffle_limit=0)
    step = w.step = TransformerLMStep(
        w, loader=w.loader, arch=arch_config(cfg),
        lr=float(cfg["hyper"]["lr"]), mesh=mesh,
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
        self.cfg, self.traffic = ctx.config, ctx.traffic
        self.ref = ctx.roots.module("reference", self.cfg["reference"])
        self.lr = float(self.cfg["hyper"]["lr"])
        self.grad_first: dict = {}

    def read_loss(self) -> float:
        # the newest step's loss, fetched here and not by the step
        return float(self.w.step.last_loss)

    def _norms(self, keep: dict | None = None) -> dict:
        """Norm of (seeded leaf - the step's leaf), group by group, so
        only one group of seeded leaves is alive beside the program;
        ``keep`` takes the small leaves' differences whole."""
        import jax
        import jax.numpy as jnp

        norm = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))
        params = self.w.step._params
        small = set(self.ref.KEEP)
        out = {}

        def one(name, w0, w1, leaf):
            out[name] = float(norm(w0, w1))
            if keep is not None and (leaf in small or name == "norm_g"):
                keep[name] = np.asarray(w0 - w1)

        for group in ("emb", "norm_g"):
            one(group, self.ref.init_leaf_group(self.ctx.seed, self.cfg,
                                                group), params[group], "")
        for li, blk in enumerate(params["blocks"]):
            w0 = self.ref.init_leaf_group(self.ctx.seed, self.cfg, li)
            if set(w0) != set(blk):
                raise BenchmarkError(
                    f"layer {li}: the step holds {sorted(blk)}, the "
                    f"reference makes {sorted(w0)}")
            for k, leaf in blk.items():
                one(f"B{li}.{k}", w0[k], leaf, k)
            del w0
        return out

    def grad_norms(self) -> dict:
        # plain SGD: g = (w0 - w1) / lr
        kept: dict = {}
        norms = {k: v / self.lr for k, v in self._norms(kept).items()}
        self.grad_first = {k: v / np.float32(self.lr)
                           for k, v in kept.items()}
        return norms

    def delta_norms(self) -> dict:
        return self._norms()

    def run(self) -> dict:
        from znicz_tpu.parallel import transformer as tfm

        if not hasattr(tfm, "arch_from_config"):
            raise BenchmarkError(
                "this program builds no train step from a model's "
                "configuration keys (parallel/transformer.py has no "
                "arch_from_config): the cell cannot run on it")
        from znicz_tpu.core import prng
        from znicz_tpu.core.backends import TPUDevice, XLADevice
        from znicz_tpu.launcher import Launcher

        ctx, cfg, traffic, ref = self.ctx, self.cfg, self.traffic, self.ref
        if ctx.chips != 1:
            raise BenchmarkError("lm_train_arch cells run on one chip")
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
            # a traced run must hold one whole epoch's counters
            epoch = int(traffic["n_rows"]) // batch
            k = int(traffic["k_steps"])
            tap = TrainTap(ctx, self, k=k,
                           trace_from=max(2, -(-epoch // k)))
            attach_tap(self.w, tap)
            launcher.main()
        finally:
            restore_engine(prev_engine)
        tap.readings["grad_first"] = self.grad_first
        out = self.outcome(tap, ref_readings, batch, 1,
                           ref.train_flops_per_sample(cfg, t))
        moe = dict(self.w.step.moe_counters)
        if moe:
            out["lines"].append(
                f"moe: {moe['pairs_held_per_step']:.1f} pairs a step to the "
                f"held experts, fullest held expert "
                f"{moe['load_max_over_mean']:.3f} x the mean (last class "
                f"pass)")
            out["samples"]["moe"] = moe
            out["samples"]["config_as_run"] = {
                **cfg, "moe_pairs_held_per_step": moe["pairs_held_per_step"]}
        return out


def run(ctx) -> dict:
    return Cell(ctx).run()
