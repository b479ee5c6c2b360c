"""Kohonen SOM units — rebuild of veles.znicz kohonen.py :: KohonenBase,
KohonenForward, KohonenTrainer (+ the sample's decision logic).

Unsupervised winner-take-all with Gaussian neighborhood decay; no gradient
pair (SURVEY.md §3.1).  ``KohonenTrainer`` owns the ``(sy*sx, n_input)``
weights and performs the batched update (znicz_tpu.ops.kohonen);
``KohonenForward`` emits winner indices (and hit counts) using the shared
weights.  ``KohonenDecision`` stops on max_epochs or when the epoch weight
delta stabilizes.
"""

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from znicz_tpu.core import prng
from znicz_tpu.core.memory import Array
from znicz_tpu.core.accelerated_units import AcceleratedUnit
from znicz_tpu.ops import kohonen as k_ops
from znicz_tpu.units.decision import DecisionBase


def _som_batch_step(x, w, coords, alpha, radius, bs, *, pallas: bool,
                    interpret: bool):
    """THE one SOM batch-update rule — shared by the per-minibatch jit
    and the epoch scan so the two modes cannot drift."""
    if pallas:
        from znicz_tpu.ops.pallas import som_step
        new_w, idx = som_step(x, w, coords, alpha, radius, bs,
                              interpret=interpret)
        return new_w, idx.astype(jnp.int32)
    mask = jnp.arange(x.shape[0]) < bs
    new_w, idx = k_ops.update(jnp, x, w, coords, alpha, radius, mask)
    return new_w, idx.astype(jnp.int32)


_som_batch_step_jit = jax.jit(_som_batch_step,
                              static_argnames=("pallas", "interpret"))


@jax.jit
def _winners_jit(x, w):
    """Winner indices per sample — module-level (ISSUE 7 satellite):
    the previous per-``xla_init`` ``jax.jit(lambda ...)`` gave every
    KohonenForward build a fresh empty trace cache, so repeated builds
    in one process (supervised restarts, warm-up-then-time benches,
    forge reloads) re-traced and re-looked-up a program jit already
    had.  One module-level jitted function memoizes per (shape, dtype)
    for the life of the process — the same fix ``_epoch_scan`` records
    for the scan path — and the persistent compilation cache
    (znicz_tpu.compilecache) carries the compile across processes."""
    return k_ops.winners(jnp, x, w).astype(jnp.int32)


@partial(jax.jit, static_argnames=("pallas", "interpret"))
def _epoch_scan(dataset, w, coords, idxs, ms, alpha, radius, *,
                pallas: bool, interpret: bool):
    """One compiled class pass over the pinned dataset PLUS the decision
    metric ``|ΔW|/|W|`` in the same dispatch — the per-epoch host round
    trip is then a single scalar fetch.  Module-level (not a per-workflow
    closure) so jit's in-process cache carries across workflow builds:
    a warm-up build genuinely warms the timed build (the closure version
    re-traced per build, and on hardware the re-trace + persistent-cache
    reload dominated the whole measured SOM run)."""
    def body(wc, inp):
        idx, m = inp
        new_w, _ = _som_batch_step(dataset[idx], wc, coords, alpha,
                                   radius, m.sum(), pallas=pallas,
                                   interpret=interpret)
        return new_w, None

    new_w, _ = jax.lax.scan(body, w, (idxs, ms))
    delta = jnp.abs(new_w - w).sum() / jnp.maximum(
        jnp.abs(w).sum(), 1e-12)
    return new_w, delta


class KohonenBase(AcceleratedUnit):
    """Shared geometry (reference: kohonen.py :: KohonenBase)."""

    def __init__(self, workflow=None, shape=(8, 8), **kwargs) -> None:
        super().__init__(workflow, **kwargs)
        self.sy, self.sx = int(shape[0]), int(shape[1])
        self.input = Array()
        self.weights = Array()

    @property
    def n_neurons(self) -> int:
        return self.sy * self.sx

    def _flat_input(self, mem):
        return mem.reshape(mem.shape[0], -1)


class KohonenTrainer(KohonenBase):
    """Reference: kohonen.py :: KohonenTrainer.

    ``gradient_decay``/``radius_decay``: per-epoch multiplicative decay of
    the learning rate and neighborhood radius (reference semantics of the
    time-decaying schedules)."""

    def __init__(self, workflow=None, shape=(8, 8), alpha: float = 0.5,
                 alpha_min: float = 0.01, gradient_decay: float = 0.95,
                 radius: float = None, radius_min: float = 0.5,
                 radius_decay: float = 0.95, **kwargs) -> None:
        super().__init__(workflow, shape=shape, **kwargs)
        self.alpha0 = float(alpha)
        self.alpha_min = float(alpha_min)
        self.gradient_decay = float(gradient_decay)
        self.radius0 = float(radius if radius is not None
                             else max(self.sy, self.sx) / 2.0)
        self.radius_min = float(radius_min)
        self.radius_decay = float(radius_decay)
        self.epoch_number = 0            # data-linked from the loader
        self.epoch_ended = False         # data-linked from the loader
        self.winners = Array()
        self._coords_np = None
        #: optional loader reference enabling epoch-scan mode: ONE
        #: compiled lax.scan dispatch per class pass over the HBM-pinned
        #: dataset, instead of one dispatch per minibatch (the same
        #: design as FusedTrainStep epoch scanning; per-minibatch
        #: dispatch latency dominates SOM steps).  Resolved from
        #: ``root.common.engine.scan_epoch`` at xla_init when None.
        self.loader = None
        self.scan_epoch = None
        self._scan_fn = None
        self._dataset_dev = None
        self._coords_dev = None
        self._scan_in_flight = False  # current class pass scan-dispatched
        #: device scalar |ΔW|/|W| of the last scan-dispatched pass —
        #: KohonenDecision fetches it (ONE d2h fence per epoch) instead
        #: of reading full weights twice
        self.scan_delta_dev = None
        #: weights as of the START of the current epoch (consumed by
        #: KohonenDecision's |ΔW| metric on the PER-MINIBATCH path —
        #: its own capture point runs after this unit, which would miss
        #: the first minibatch's movement).  Scan mode never populates
        #: it: the delta rides the dispatch as ``scan_delta_dev``
        self.epoch_start_weights = None
        self._snap_epoch = None

    @property
    def _schedule_epoch(self) -> int:
        """The epoch the CURRENT minibatch belongs to.  The loader
        increments ``epoch_number`` while serving the last minibatch of
        an epoch (before this unit runs on it), so the raw counter would
        decay the schedule one minibatch early each epoch."""
        e = int(self.epoch_number)
        if bool(getattr(self, "epoch_ended", False)):
            e = max(e - 1, 0)
        return e

    # current schedule values (read by tests/plotters)
    @property
    def alpha(self) -> float:
        return max(self.alpha0 * self.gradient_decay ** self._schedule_epoch,
                   self.alpha_min)

    @property
    def radius(self) -> float:
        return max(self.radius0 * self.radius_decay ** self._schedule_epoch,
                   self.radius_min)

    def _common_init(self, **kwargs) -> None:
        dim = int(np.prod(self.input.shape[1:]))
        if not self.weights:
            self.weights.mem = prng.get().normal(
                0.0, 0.1, (self.n_neurons, dim))
        if not self.winners or len(self.winners) != self.input.shape[0]:
            self.winners.reset(shape=(self.input.shape[0],), dtype=np.int32)
        self._coords_np = np.asarray(k_ops.grid_coords(np, self.sy, self.sx))
        self.init_array(self.input, self.weights, self.winners)

    def _maybe_snapshot_epoch_start(self) -> None:
        e = self._schedule_epoch
        if self._snap_epoch != e:
            self.epoch_start_weights = np.asarray(
                self.weights.map_read()).copy()
            self._snap_epoch = e

    def numpy_run(self) -> None:
        self._maybe_snapshot_epoch_start()
        x = self._flat_input(self.input.mem)
        mask = self._mask(x.shape[0])
        new_w, idx = k_ops.update(np, x, self.weights.mem, self._coords_np,
                                  self.alpha, self.radius, mask)
        self.weights.map_invalidate()
        self.weights.mem = new_w
        self.winners.map_invalidate()
        self.winners.mem = idx.astype(np.int32)

    def _mask(self, n):
        bs = self.current_batch_size(self.input)
        if bs >= n:
            return None
        return (np.arange(n) < bs)

    def xla_init(self) -> None:
        from znicz_tpu.core.config import root

        coords = jnp.asarray(self._coords_np)
        self._coords_dev = coords
        # pallas=True selects the fused distance+argmin+update kernel:
        # weights read and written once per batch step
        self._use_pallas = bool(root.common.engine.get("pallas", False))
        self._interp = bool(root.common.engine.get("pallas_interpret",
                                                   False))
        self._xla_fn = partial(_som_batch_step_jit,
                               pallas=self._use_pallas,
                               interpret=self._interp)
        self._maybe_enable_scan()

    def _maybe_enable_scan(self) -> None:
        """Pin the loader's full-batch dataset on device and compile the
        per-class-pass scan (one dispatch per pass; class-plan padding
        sits at the tail, so the per-step ``bs`` mask stays valid)."""
        from znicz_tpu.core.config import root

        if self.scan_epoch is None:
            self.scan_epoch = bool(root.common.engine.get("scan_epoch",
                                                          False))
        loader = self.loader
        data_arr = getattr(loader, "original_data", None)
        if not self.scan_epoch or loader is None or not data_arr:
            return
        if getattr(loader, "augmenting", False):
            # per-serve augmentation is data-dependent: the pinned-scan
            # shortcut would silently train on the raw uncropped dataset
            # (same guard as FusedTrainStep._pin_dataset)
            return
        data = np.asarray(data_arr.mem, np.float32)
        data = data.reshape(data.shape[0], -1)
        limit = int(root.common.engine.get(
            "dataset_on_device_max_bytes", 1 << 30))
        if data.nbytes > limit:
            return
        self._dataset_dev = jnp.asarray(data)
        self._scan_fn = partial(_epoch_scan, pallas=self._use_pallas,
                                interpret=self._interp)
        loader.capture_class_plan = True
        # NOTE: the loader keeps filling minibatch_data — KohonenForward
        # (winner maps / hits plotters) and the mid-pass-resume fallback
        # below read it; SOM minibatches are small, so the per-step host
        # fill is not the bottleneck the scan removes (dispatch latency)

    def xla_run(self) -> None:
        if self._scan_fn is not None and \
                (int(self.loader.minibatch_offset) == 0 or
                 self._scan_in_flight):
            # epoch-scan mode: dispatch the WHOLE class pass at its first
            # minibatch; later minibatches of the pass are no-ops (the
            # control loop still walks them — the loader serves cheaply).
            # ``winners`` is not updated per minibatch here; winner maps
            # come from KohonenForward as in the demo graph.
            if int(self.loader.minibatch_offset) == 0:
                from znicz_tpu.loader.base import plan_device_arrays
                idxs, ms = plan_device_arrays(self.loader.class_plan())
                self.weights.unmap()
                new_w, delta = self._scan_fn(
                    self._dataset_dev, self.weights.devmem,
                    self._coords_dev, idxs, ms, self.alpha, self.radius)
                self.weights.set_devmem(new_w)
                self.scan_delta_dev = delta      # fetched by the decision
                self._scan_in_flight = True
            if self.loader.last_minibatch:
                self._scan_in_flight = False
            return
        # per-minibatch path: also the fallback for a class pass entered
        # MID-WAY (restored loader state after resume — same defense as
        # FusedTrainStep.run)
        self._maybe_snapshot_epoch_start()
        self.input.unmap()
        self.weights.unmap()
        x = self.input.devmem
        new_w, idx = self._xla_fn(
            x.reshape(x.shape[0], -1), self.weights.devmem,
            self._coords_dev, self.alpha, self.radius,
            self.current_batch_size(self.input))
        self.weights.set_devmem(new_w)
        self.winners.set_devmem(idx)


class KohonenForward(KohonenBase):
    """Reference: kohonen.py :: KohonenForward — winner index per sample
    (+ hit counts for the SOM plotters); weights linked from the trainer."""

    def __init__(self, workflow=None, shape=(8, 8), compute_hits: bool = True,
                 **kwargs) -> None:
        super().__init__(workflow, shape=shape, **kwargs)
        self.output = Array()
        self.compute_hits = compute_hits
        self.hits = None

    def _common_init(self, **kwargs) -> None:
        if not self.output or len(self.output) != self.input.shape[0]:
            self.output.reset(shape=(self.input.shape[0],), dtype=np.int32)
        if self.compute_hits and self.hits is None:
            self.hits = np.zeros(self.n_neurons, np.int64)
        self.init_array(self.input, self.weights, self.output)

    def numpy_run(self) -> None:
        x = self._flat_input(self.input.mem)
        idx = k_ops.winners(np, x, self.weights.mem)
        self.output.map_invalidate()
        self.output.mem = idx.astype(np.int32)
        if self.compute_hits:
            bs = self.current_batch_size(self.input)
            self.hits += np.bincount(idx[:bs], minlength=self.n_neurons)

    def xla_init(self) -> None:
        self._xla_fn = _winners_jit

    def xla_run(self) -> None:
        self.input.unmap()
        self.weights.unmap()
        x = self.input.devmem
        idx = self._xla_fn(x.reshape(x.shape[0], -1), self.weights.devmem)
        self.output.set_devmem(idx)
        if self.compute_hits:
            bs = self.current_batch_size(self.input)
            self.hits += np.bincount(np.asarray(idx)[:bs],
                                     minlength=self.n_neurons)


class KohonenDecision(DecisionBase):
    """Epoch bookkeeping for SOM training: metric is the epoch's weight
    movement ``|ΔW|/|W|``; stops on max_epochs or when movement falls
    below ``min_delta`` (reference sample's stop logic)."""

    def __init__(self, workflow=None, min_delta: float = 1e-4,
                 **kwargs) -> None:
        super().__init__(workflow, **kwargs)
        self.min_delta = float(min_delta)
        self.trainer = None
        self._epoch_start_w = None
        self.weights_delta = 0.0

    def accumulate(self, cls: int) -> None:
        if getattr(self.trainer, "scan_delta_dev", None) is not None:
            return            # metric rides the scan dispatch on device
        if self._epoch_start_w is None:
            pre = getattr(self.trainer, "epoch_start_weights", None)
            self._epoch_start_w = pre.copy() if pre is not None \
                else self.trainer.weights.map_read().copy()

    def finalize_class(self, cls: int) -> float:
        delta_dev = getattr(self.trainer, "scan_delta_dev", None)
        if delta_dev is not None:
            # scan mode: ONE scalar d2h is the whole per-epoch fence
            self.weights_delta = float(jax.device_get(delta_dev))
            self.trainer.scan_delta_dev = None
            return self.weights_delta
        w = self.trainer.weights.map_read()
        denom = max(float(np.abs(self._epoch_start_w).sum()), 1e-12)
        self.weights_delta = float(
            np.abs(w - self._epoch_start_w).sum()) / denom
        return self.weights_delta

    def reset_epoch(self) -> None:
        self._epoch_start_w = None

    def run(self) -> None:
        super().run()
        if bool(self.epoch_ended) and self.weights_delta < self.min_delta:
            self.complete.set(True)

    def on_epoch_logged(self) -> None:
        self.info(f"epoch {int(self.epoch_number)}: weights delta "
                  f"{self.weights_delta:.6f}")
