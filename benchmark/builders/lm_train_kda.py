"""Builder kind ``lm_train_kda``: ``lm_train_swa`` for a stack with
delta-rule linear-attention layers.  The model is built from the
configuration's ``builders.lm_train_keys`` section exactly as that kind
builds it (the program's refusal of the keys is the run's refusal, exit code
1 before the reference runs) and the program's masters are released before a
control run, as there; what this kind adds is the step unit's
``kda_counters`` of the last whole class pass (the mean decay a position and
key channel ``exp(g)``, the mean ``beta``, the RMS of the state behind a
row's last position) copied into ``samples["kda"]`` for reader
``kda_counter``, and the same readings of the FIRST step (the pass's
device-side sums after one step) beside the reference's for that step, the
one pair made from the same row: the log has both, and the relative gap of
the two last-state RMSs is one more number compared, ``kda_state_gap``,
against the reference file's limit of that name (a carry that is dropped or
shortened moves it; it joins ``samples["readings"]`` and, of a control run,
``samples["control_readings"]``, so ``benchmark/limits.py`` prints it with
the rest).  A program whose unit has no such counters records none, and the
gap is then not compared.
"""

from __future__ import annotations

from builders import lm_train_swa

KIND = "lm_train_kda"
GAP = "kda_state_gap"


def _state_gap(got: dict, want: dict) -> float | None:
    """The relative gap of two first steps' last-state RMSs, None where
    either side has none."""
    a, b = (got or {}).get("final_state_rms"), \
        (want or {}).get("final_state_rms")
    return None if a is None or not b else abs(a / b - 1.0)


class _Recording:
    """The reference module, remembering what ``first_steps`` last gave: the
    control's linear-attention readings, which ``TrainCell.outcome`` drops."""

    def __init__(self, ref) -> None:
        self._ref, self.last = ref, None

    def __getattr__(self, name):
        return getattr(self._ref, name)

    def first_steps(self, *args, **kwargs):
        self.last = self._ref.first_steps(*args, **kwargs)
        return self.last


class Cell(lm_train_swa.Cell):
    first_kda: dict | None = None
    ref_kda: dict | None = None

    def read_loss(self) -> float:
        loss = super().read_loss()
        if self.first_kda is None:
            # after the first step the pass's sums are that step's alone
            import jax

            acc = jax.device_get(getattr(self.w.step, "_acc", None) or {})
            layers = float(acc.get("kda_layers", 0.0))
            self.first_kda = {
                "decay_mean": float(acc["kda_decay"]) / layers,
                "beta_mean": float(acc["kda_beta"]) / layers,
                "final_state_rms": float(acc["kda_state_rms"]) / layers} \
                if layers else {}
        return loss

    def reference_first_steps(self, chips: int) -> dict:
        readings = super().reference_first_steps(chips)
        self.ref_kda = (readings.get("kda") or [{}])[0]
        return readings

    def outcome(self, tap, ref_readings, batch, chips, flops_per_sample):
        self.ref = recording = _Recording(self.ref)
        try:
            out = super().outcome(tap, ref_readings, batch, chips,
                                  flops_per_sample)
        finally:
            self.ref = recording._ref
        gap = _state_gap(self.first_kda, self.ref_kda)
        if gap is None:
            return out
        limit = self.ref.LIMITS[GAP]
        good = gap <= limit                           # NaN fails
        out["correct"] = out["correct"] and good
        out["lines"].append(
            f"check {GAP}: {gap:.6g} (limit {limit:g}) "
            f"{'ok' if good else 'FAILED'} [first step's last-state RMS "
            f"{self.first_kda['final_state_rms']:.6g} vs "
            f"{self.ref_kda['final_state_rms']:.6g}]")
        out["samples"]["readings"][GAP] = gap
        if recording.last is not None:
            control = _state_gap((recording.last.get("kda") or [{}])[0],
                                 self.ref_kda)
            if control is not None:
                out["samples"]["control_readings"][GAP] = control
        return out

    def run(self) -> dict:
        out = super().run()
        kda = dict(getattr(self.w.step, "kda_counters", None) or {})
        if kda:
            out["samples"]["kda"] = kda
            out["lines"].append(
                f"kda (last class pass): mean decay a position and channel "
                f"{kda['decay_mean']:.6f}, mean beta {kda['beta_mean']:.6f}, "
                f"RMS of the last state {kda['final_state_rms']:.6f}")
        if self.first_kda:
            out["samples"]["kda_first_step"] = {"program": self.first_kda,
                                                "reference": self.ref_kda}
            out["lines"].append(
                f"kda (first step): program {self.first_kda}, reference "
                f"{self.ref_kda}")
        return out


def run(ctx) -> dict:
    return Cell(ctx).run()
