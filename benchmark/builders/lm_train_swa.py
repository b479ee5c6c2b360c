"""Builder kind ``lm_train_swa``: ``lm_train_keys`` for a stack whose
attention layers have a window on their scores.  The model is built from the
configuration's ``builders.lm_train_keys`` section exactly as that kind
builds it (the program's refusal of the keys is the run's refusal, exit code
1 before the reference runs); what this kind adds is the step unit's
``attn_counters`` of the last whole class pass (the window layers a step and
the share of the causal triangle's tiles that the blocked kernels' visit
tables list under the window) copied into ``samples["attn"]`` for reader
``attn_counter``, and, as ``lm_train_ssm`` does, the program's masters
released before a control run.  A program whose unit has no such counters
records none.
"""

from __future__ import annotations

from builders import lm_train_keys

KIND = "lm_train_swa"


class Cell(lm_train_keys.Cell):
    def outcome(self, tap, ref_readings, batch, chips, flops_per_sample):
        if self.ctx.control:
            # the control is a second reference run, 6.4 GB of float32
            # weights at this size: it does not fit beside the program's 6.4
            # GB of masters, which nothing reads any more
            # (benchmark/limits.py alone asks for it)
            import gc

            self.w.step._params = None
            gc.collect()
        return super().outcome(tap, ref_readings, batch, chips,
                               flops_per_sample)

    def run(self) -> dict:
        import gc

        gc.collect()        # an earlier seed's workflow, where one process
        out = super().run()  # runs several (benchmark/limits.py)
        attn = dict(getattr(self.w.step, "attn_counters", None) or {})
        if attn:
            out["samples"]["attn"] = attn
            out["lines"].append(
                f"attn (last class pass): {attn['window_layers']:g} window "
                f"layers a step, their kernels' tables list "
                f"{attn['window_tile_share']:.4f} of the causal triangle's "
                f"tiles")
        return out


def run(ctx) -> dict:
    return Cell(ctx).run()
