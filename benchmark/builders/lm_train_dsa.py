"""Builder kind ``lm_train_dsa``: ``lm_train_keys`` for a stack whose
attention layers carry an indexer (learned sparse attention).  The model is
built from the configuration's ``builders.lm_train_keys`` section exactly as
that kind builds it (the program's refusal of the keys is the run's refusal,
exit code 1 before the reference runs); what this kind adds is the step
unit's ``dsa_counters`` of the last whole class pass (the selected share of
the causal pairs, the share of the visited tiles that hold a selected pair,
the alignment term and its share of the loss) copied into
``samples["dsa"]`` for reader ``dsa_counter``.  A program whose unit has no
such counters records none.
"""

from __future__ import annotations

from builders import lm_train_keys

KIND = "lm_train_dsa"


class Cell(lm_train_keys.Cell):
    def run(self) -> dict:
        out = super().run()
        dsa = dict(getattr(self.w.step, "dsa_counters", None) or {})
        if dsa:
            out["samples"]["dsa"] = dsa
            out["lines"].append(
                f"dsa (last class pass): selected "
                f"{dsa['selected_share']:.6f} of the causal pairs, "
                f"{dsa['live_tile_share']:.4f} of the visited tiles hold a "
                f"selected pair, alignment term {dsa['index_loss']:.5f} "
                f"({dsa['index_loss_share']:.4f} of the loss)")
        return out


def run(ctx) -> dict:
    return Cell(ctx).run()
