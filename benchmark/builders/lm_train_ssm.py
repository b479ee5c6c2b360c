"""Builder kind ``lm_train_ssm``: ``lm_train_keys`` for a stack with
state-space layers.  The model is built from the configuration's
``builders.lm_train_keys`` section exactly as that kind builds it (the
program's refusal of the keys is the run's refusal, exit code 1 before the
reference runs); what this kind adds is the step unit's ``ssm_counters`` of
the last whole class pass (the mean decay a position ``exp(dt A)`` and the
RMS of the state behind a row's last position) copied into
``samples["ssm"]`` for reader ``ssm_counter``, and, in the log, the same two
readings of the FIRST step (the pass's device-side sums after one step)
beside the reference's for that step, the one pair made from the same row.
A program whose unit has no such counters records none.
"""

from __future__ import annotations

from builders import lm_train_keys

KIND = "lm_train_ssm"


class Cell(lm_train_keys.Cell):
    first_ssm: dict | None = None

    def read_loss(self) -> float:
        loss = super().read_loss()
        if self.first_ssm is None:
            # after the first step the pass's sums are that step's alone
            import jax

            acc = getattr(self.w.step, "_acc", None) or {}
            layers = float(jax.device_get(acc.get("ssm_layers", 0.0)))
            self.first_ssm = {
                "decay_mean": float(acc["ssm_decay"]) / layers,
                "final_state_rms": float(acc["ssm_state_rms"]) / layers} \
                if layers else {}
        return loss

    def reference_first_steps(self, chips: int) -> dict:
        readings = super().reference_first_steps(chips)
        self.ref_ssm = (readings.get("ssm") or [{}])[0]
        return readings

    def outcome(self, tap, ref_readings, batch, chips, flops_per_sample):
        if self.ctx.control:
            # the control is a second reference run, 9 GB at this size: it
            # does not fit beside the program's 3.8 GB of masters, which
            # nothing reads any more (benchmark/limits.py alone asks for it)
            import gc

            self.w.step._params = None
            gc.collect()
        return super().outcome(tap, ref_readings, batch, chips,
                               flops_per_sample)

    def run(self) -> dict:
        import gc

        gc.collect()        # an earlier seed's workflow, where one process
        out = super().run()  # runs several (benchmark/limits.py)
        ssm = dict(getattr(self.w.step, "ssm_counters", None) or {})
        if ssm:
            out["samples"]["ssm"] = ssm
            out["lines"].append(
                f"ssm (last class pass): mean decay a position "
                f"{ssm['decay_mean']:.6f}, RMS of the last state "
                f"{ssm['final_state_rms']:.6f}")
        if self.first_ssm:
            out["samples"]["ssm_first_step"] = {"program": self.first_ssm,
                                                "reference": self.ref_ssm}
            out["lines"].append(
                f"ssm (first step): program {self.first_ssm}, reference "
                f"{self.ref_ssm}")
        return out


def run(ctx) -> dict:
    return Cell(ctx).run()
