"""Builder kind ``lm_train_loop``: ``lm_train_keys`` for a looped stack.
The model is built from the configuration's ``builders.lm_train_keys``
section exactly as that kind builds it (the program's refusal of the keys
is the run's refusal, exit code 1 before the reference runs); what this
kind adds is the step unit's ``loop_counters`` of the last whole class pass
(the mean exit step, the exit distribution's mean entropy, each loop step's
own cross-entropy) copied into ``samples["loop"]`` for reader
``loop_counter``.  A program whose unit has no such counters records none.
"""

from __future__ import annotations

from builders import lm_train_keys

KIND = "lm_train_loop"


class Cell(lm_train_keys.Cell):
    def run(self) -> dict:
        out = super().run()
        loop = dict(getattr(self.w.step, "loop_counters", None) or {})
        if loop:
            out["samples"]["loop"] = loop
            by_step = [round(loop[k], 5) for k in sorted(loop)
                       if k.startswith("loss_step")]
            out["lines"].append(
                f"loop (last class pass): mean exit step "
                f"{loop['exit_step_mean']:.4f}, mean entropy "
                f"{loop['exit_entropy']:.4f} nats, cross-entropy by loop "
                f"step {by_step}")
        return out


def run(ctx) -> dict:
    return Cell(ctx).run()
