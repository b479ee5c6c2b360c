"""Builder kind ``lm_train_keys``: what ``lm_train_arch`` does (the char-LM
unit graph with ``TransformerLMStep(arch=...)`` under ``Launcher``, seeded
weights and token rows from the configuration's reference module, the loss
read off the device after the tap's own fence, the small leaves' first
gradients kept whole), for ANY family the program's ``arch_from_config``
reads, from data alone:

- the keys handed to the step are those the configuration lists itself
  (``builders.lm_train_keys.model_keys``; nothing here names a family's
  key), and
- the parameter groups whose norms are compared are those the reference
  lists (``leaf_groups(cfg)``: ``{group: path in the step's pytree}``; a
  group is one array or a nested dict of them, ``init_leaf_group`` gives
  the seeded leaves in the same shape), so a head of its own or a module
  behind the stack needs no line here.

The program's refusal of the keys (``arch_from_config``: an unknown
``model_type``, a key it cannot honour) becomes the run's refusal before
the reference runs: a commit that cannot build the model exits 1 in
seconds.  Beside the routed layers' counters (``samples["moe"]``) it
records, for a stack with a second loss term, the weighted term's share of
the loss over the last class pass (``mtp_loss_share``).
"""

from __future__ import annotations

import numpy as np

from benchlib import BenchmarkError
from builders import lm_train_arch
from builders.lm_train import _token_loader_class
from builders.train_common import (TrainTap, apply_engine, attach_tap,
                                   restore_engine)

KIND = "lm_train_keys"


def arch_config(cfg: dict) -> dict:
    """The mapping the step is built from: the configuration's own list of
    the model's keys, as run."""
    return {k: cfg[k] for k in cfg["builders"][KIND]["model_keys"]}


def build_workflow(rows, cfg: dict, traffic: dict, mesh=None):
    """The char-LM control graph with the seeded token loader."""
    from znicz_tpu.core.plumbing import Repeater
    from znicz_tpu.units.decision import DecisionMSE
    from znicz_tpu.units.lm import TransformerLMStep
    from znicz_tpu.units.nn_units import NNWorkflow

    w = NNWorkflow(name="BenchLM")
    w.repeater = Repeater(w)
    w.loader = _token_loader_class()(
        w, rows=rows, vocab_size=int(cfg["vocab_size"]),
        minibatch_size=int(traffic["minibatch_size"]), shuffle_limit=0)
    step = w.step = TransformerLMStep(
        w, loader=w.loader, arch=arch_config(cfg),
        lr=float(cfg["hyper"]["lr"]), mesh=mesh,
        loss_chunks=cfg["builders"][KIND].get("loss_chunks"))
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


def flat_leaves(tree, prefix: str) -> dict:
    """``{dotted name: leaf}`` of an array or a nested dict of them."""
    if not isinstance(tree, dict):
        return {prefix: tree}
    out = {}
    for k, v in tree.items():
        out.update(flat_leaves(v, f"{prefix}.{k}"))
    return out


class Cell(lm_train_arch.Cell):
    def _norms(self, keep: dict | None = None) -> dict:
        """Norm of (seeded leaf - the step's leaf), group by group of the
        reference's ``leaf_groups``, so only one group of seeded leaves is
        alive beside the program; ``keep`` takes the small leaves'
        differences whole."""
        import jax
        import jax.numpy as jnp

        norm = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))
        small = set(self.ref.KEEP)
        out = {}
        for group, path in self.ref.leaf_groups(self.cfg).items():
            mine = self.w.step._params
            for key in path:
                mine = mine[key]
            mine = flat_leaves(mine, group)
            seeded = flat_leaves(self.ref.init_leaf_group(
                self.ctx.seed, self.cfg, group), group)
            if set(seeded) != set(mine):
                raise BenchmarkError(
                    f"group {group}: the step holds {sorted(mine)}, the "
                    f"reference makes {sorted(seeded)}")
            for name, w0 in seeded.items():
                out[name] = float(norm(w0, mine[name]))
                if keep is not None and name.rsplit(".", 1)[-1] in small:
                    keep[name] = np.asarray(w0 - mine[name])
            del seeded
        return out

    def run(self) -> dict:
        from znicz_tpu.parallel import transformer as tfm

        ctx, cfg, traffic, ref = self.ctx, self.cfg, self.traffic, self.ref
        try:
            arch = tfm.arch_from_config(arch_config(cfg))
        except (AttributeError, KeyError, TypeError, ValueError) as exc:
            raise BenchmarkError(
                f"this program builds no train step from configuration "
                f"{cfg['name']}'s keys: {exc!r}") from exc
        from znicz_tpu.core import prng
        from znicz_tpu.core.backends import TPUDevice, XLADevice
        from znicz_tpu.launcher import Launcher

        if ctx.chips != 1:
            raise BenchmarkError(f"{KIND} cells run on one chip")
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
        step = self.w.step
        moe = dict(step.moe_counters)
        if moe:
            out["lines"].append(
                f"moe: {moe['pairs_held_per_step']:.1f} pairs a step to the "
                f"held experts, fullest held expert "
                f"{moe['load_max_over_mean']:.3f} x the mean (last class "
                f"pass)")
            out["samples"]["config_as_run"] = {
                **cfg, "moe_pairs_held_per_step": moe["pairs_held_per_step"]}
        terms = dict(getattr(step, "loss_terms", None) or {})
        if terms:
            second = arch.mtp_weight * terms["mtp"]
            moe["mtp_loss_share"] = second / (terms["main"] + second)
            first = {k: [round(float(v), 5) for v in ref_readings[k]]
                     for k in ("loss_main", "loss_mtp")}
            out["lines"].append(
                f"loss terms (last class pass): main {terms['main']:.5f}, "
                f"mtp {terms['mtp']:.5f} x {arch.mtp_weight:g}; the "
                f"reference's first steps: main {first['loss_main']}, mtp "
                f"{first['loss_mtp']}")
        if moe:
            out["samples"]["moe"] = moe
        return out


def run(ctx) -> dict:
    return Cell(ctx).run()
