"""FusedTrainStep — the traced-segment compiler (SURVEY.md §8 design
stance).

Takes the accelerated segment of an NN workflow (forwards -> evaluator ->
gradient updates) and compiles it into ONE pure XLA program:

    (params, hyper, x, labels/targets, mask) -> (params', metrics)

``shard_map``-ped over a device mesh: the batch shards over the ``data``
axis, params are replicated, gradient sums ride ``lax.psum`` over ICI —
this is the rebuild of both (a) the reference's per-unit kernel-enqueue hot
loop and (b) its entire ZeroMQ master-slave protocol (§4.2), which
dissolves into the collective.

The backward pass is ``jax.value_and_grad`` of the composed forward +
evaluator loss: per-unit hand-written backward paths (units/gd.py) remain
the eager/tier-1 semantics; the equivalence of the two is pinned by
tests/test_units_fc.py::test_gd_matches_autograd and
tests/test_parallel.py (fused-vs-eager parity).

Per-layer hyperparameters (lr, weight decay, momentum) are traced scalars
read from the gradient units — LR schedule units mutate them without
triggering recompilation.  They live on device (``_hyper_device``) and are
re-uploaded only when a schedule actually changes a value; the per-step RNG
key likewise lives on device and is split inside the compiled step, so the
hot loop ships no host scalars at all.

Mixed precision: when the device reports a bfloat16 ``compute_dtype``
(an XLADevice on a TPU), activations and matmul/conv inputs run bf16 while
master params, gradient accumulation, loss and the SGD update stay f32 —
the standard MXU recipe.  On CPU (tests) compute stays f32, so tier-1/2
numerics are unchanged.

``train_steps`` scans K minibatches inside one compiled program — the
TPU-native answer to per-step dispatch latency: where the reference's hot
loop enqueues kernels per minibatch, ours compiles the whole minibatch
loop and dispatches once.

In the control graph, FusedTrainStep is one Unit replacing the whole
segment: Repeater -> Loader -> FusedTrainStep -> Decision -> Repeater;
Loader/Decision/Snapshotter stay host-side exactly like the reference.
"""

from __future__ import annotations

import math
import re
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from znicz_tpu.parallel.compat import quantized_psum, shard_map
# hoisted out of the program-build path (_apply_update used to import it
# per trace); the modules are jax-only, so the import is always safe here
from znicz_tpu.parallel import qcomm, zero

from znicz_tpu.core import prng
from znicz_tpu.core.config import root
from znicz_tpu.core.units import Unit
from znicz_tpu.loader.base import TRAIN
from znicz_tpu.observe import probe as _probe
from znicz_tpu.observe.anatomy import StepCadence
from znicz_tpu.observe.trace import TRACER as _TRACER
from znicz_tpu.ops import sgd
from znicz_tpu.resilience.faults import poison_hook
from znicz_tpu.units.all2all import All2All, All2AllSoftmax
from znicz_tpu.units.conv import Conv
from znicz_tpu.units.deconv import Deconv
from znicz_tpu.units.dropout import DropoutForward
from znicz_tpu.units.evaluator import EvaluatorMSE, EvaluatorSoftmax
from znicz_tpu.units.normalization import LRNormalizerForward
from znicz_tpu.units.pooling import Pooling


#: forward-unit class -> scope group; anything else (activations,
#: cutters) is "act"
_SCOPE_GROUPS = ((Conv, "conv"), (Deconv, "conv"), (All2All, "fc"),
                 (LRNormalizerForward, "norm"), (Pooling, "pool"),
                 (DropoutForward, "dropout"))


def unit_scope(index: int, unit) -> str:
    """``<group>.<index>_<unit name>``: the named scope of one forward
    unit in the compiled step.  The index makes it unique per layer
    (unit names default to the class name); characters that would break
    an operation's ``op_name`` path are replaced."""
    group = next((g for cls, g in _SCOPE_GROUPS if isinstance(unit, cls)),
                 "act")
    return f"{group}.{index:02d}_" + re.sub(r"[^A-Za-z0-9_.\-]", "_",
                                            str(unit.name))


def pinned_row_shape(n_values: int) -> tuple:
    """The shape one sample takes in the array :meth:`FusedTrainStep.
    _pin_dataset` pins: its values flat, and where that costs at most an
    eighth more, padded to whole (8, 128) tiles and split into lanes,
    ``(k, 128)`` with ``k`` a multiple of 8.

    A TPU lays an array out by what pads least, not row-major: an NHWC
    array with a batch that is a multiple of 128 lies batch-minor
    (``f32[2048,227,227,3]{0,2,3,1}``), and so does ``[N, H*W*C]``, so
    gathering rows of either relays the whole array out first, in every
    step.  ``[N, k, 128]`` pads nothing either way and stays row-major:
    the gather then reads its rows and nothing else (v5e compiles, PR
    26; PERF.md section 6)."""
    pad = -n_values % (8 * 128)
    if pad * 8 > n_values:
        return (n_values,)
    return ((n_values + pad) // 128, 128)


def full_batch_arrays(loader, mse: bool):
    """The ONE place that decides whether a loader exposes a static
    full-batch dataset: returns ``(data_arr, labels_arr, None)`` or
    ``(None, None, reason)``.  Shared by the HBM dataset pinning
    (:meth:`FusedTrainStep._pin_dataset`) and the vmapped population
    evaluator (utils/genetics) so the loader contract lives in one
    function."""
    if loader is None:
        return None, None, "no loader"
    data_arr = getattr(loader, "original_data", None)
    if not data_arr:
        return None, None, "loader exposes no original_data"
    if getattr(loader, "augmenting", False):
        # augmenting loaders serve data-dependent minibatches
        # (mirror/crop per serve) — a static array stack would
        # silently skip the augmentation
        return None, None, "augmenting loader"
    labels_arr = getattr(
        loader, "original_targets" if mse else "original_labels", None)
    if not labels_arr:
        return None, None, "loader exposes no labels/targets array"
    return data_arr, labels_arr, None


class FusedTrainStep(Unit):
    """One-unit replacement for the accelerated segment of the graph."""

    #: optimizer registry: adamw state lives in extra leaf entries
    #: (sw/sb second moments, t step count) snapshotted via
    #: extra_state_arrays/load_extra_state
    OPTIMIZERS = ("sgd", "adam")
    ADAM_DEFAULTS = {"beta1": 0.9, "beta2": 0.999, "eps": 1e-8}

    def __init__(self, workflow=None, forwards=None, evaluator=None,
                 gds=None, loader=None, mesh: Optional[Mesh] = None,
                 donate: bool = True, defer_metrics: bool = True,
                 scan_epoch: Optional[bool] = None,
                 optimizer: str = "sgd",
                 optimizer_config: Optional[dict] = None,
                 shard_update: bool = False,
                 shard_params: bool = False,
                 clip_norm: Optional[float] = None,
                 accumulate_steps: int = 1,
                 ema_decay: Optional[float] = None,
                 quantized_collectives: Optional[dict] = None,
                 **kwargs) -> None:
        super().__init__(workflow, **kwargs)
        #: quantized-collective codec config (ISSUE 18, EQuARX-style):
        #: ``{"mode": "off|bf16|int8", "chunk": N, "error_feedback":
        #: bool}`` — the gradient psum and (under shard_params) the
        #: regather chain ship int8/bf16 payloads; error feedback
        #: carries the quantization error into the next step's grads in
        #: persistent rw/rb residual leaves.  ``None`` defers to
        #: ``root.common.engine.quantized_collectives``; mode=off (or no
        #: config at all) compiles today's exact programs bit for bit.
        self.quantized_collectives = quantized_collectives
        if ema_decay is not None and not 0.0 < ema_decay < 1.0:
            raise ValueError(f"ema_decay must be in (0, 1), got "
                             f"{ema_decay}")
        #: exponential moving average of the params (Polyak averaging,
        #: beyond-reference): ew/eb leaves updated at every optimizer
        #: apply, read back via ema_params(), snapshotted with the
        #: optimizer state.  None = off.
        self.ema_decay = ema_decay
        if optimizer not in self.OPTIMIZERS:
            raise ValueError(f"unknown optimizer {optimizer!r}; "
                             f"registered: {self.OPTIMIZERS}")
        if accumulate_steps < 1:
            raise ValueError(f"accumulate_steps must be >= 1, got "
                             f"{accumulate_steps}")
        #: gradient accumulation: apply the optimizer every N train
        #: minibatches on the summed gradients — effective batch N x
        #: minibatch without the activation memory of a bigger batch.
        #: Per-minibatch metrics still publish every run; clipping (and
        #: the adam step count) applies per EFFECTIVE batch.
        self.accumulate_steps = int(accumulate_steps)
        #: ZeRO-grade persistent PARAMETER sharding (ISSUE 15): w/b live
        #: flat-sharded over ``data`` BETWEEN steps exactly like the
        #: optimizer state, full weights materialize on demand through a
        #: per-leaf all-gather chain (zero.gather_chain) for each
        #: forward/backward, and the post-update regather disappears —
        #: each replica keeps only its updated slice.  Per-chip
        #: persistent state (params + momenta + adam moments + EMA)
        #: scales 1/n with the dp mesh; numerics stay bit-identical to
        #: the replicated update (the gather is exact data movement and
        #: the shard update is elementwise on the same values).  Implies
        #: ``shard_update``.
        self.shard_params = bool(shard_params)
        #: ZeRO-style cross-replica sharding of the weight update (Xu et
        #: al. 2020, arXiv:2004.13336): gradients reduce-scatter over the
        #: ``data`` axis, each replica updates only its 1/n shard of the
        #: params with its 1/n shard of the OPTIMIZER STATE (momenta live
        #: sharded — the memory win), and updated params all-gather back.
        #: Numerically equivalent to the replicated update.
        self.shard_update = bool(shard_update) or self.shard_params
        #: global-norm gradient clipping (None = off): the batch-mean
        #: gradient across ALL layers is rescaled to at most this L2
        #: norm before the optimizer applies it (standard global clip)
        self.clip_norm = clip_norm
        #: "sgd" (reference semantics: momentum folded into the gd units'
        #: gradient buffers) or "adam" (AdamW, beyond-reference; lr and
        #: weight decay still come from the gd units' hyperparams, so LR
        #: schedule units keep working)
        self.optimizer = optimizer
        self.optimizer_config = {**self.ADAM_DEFAULTS,
                                 **(optimizer_config or {})}
        #: optional storage dtype for the SGD momentum buffers
        #: (``optimizer_config={"state_dtype": "bfloat16"}``): the update
        #: math stays f32 (cast in, cast out), only the persistent
        #: velocity lives narrow — at large batch the f32 w+v HBM traffic
        #: of the update rivals the matmul time, and halving the velocity
        #: bytes is the remaining lever (docs/TUNING.md).  Snapshots
        #: always store f32 (bf16->f32 is exact), so resume is bit-exact
        #: and portable across the flag.
        sd = self.optimizer_config.pop("state_dtype", None)
        self.state_dtype = jnp.dtype(sd) if sd is not None else None
        if self.state_dtype is not None and optimizer != "sgd":
            raise ValueError(
                "state_dtype applies to the SGD momentum buffers only "
                "(adam moments need f32 second-moment accumulation)")
        #: dispatch one compiled lax.scan per CLASS PASS instead of one
        #: program per minibatch (requires the pinned dataset; same
        #: "virtual minibatch" Decision accounting as defer_metrics).
        #: Hyperparams are read once per pass, so per-MINIBATCH LR
        #: schedules (LearningRateAdjust by_epoch=False) collapse to
        #: per-pass granularity in this mode; per-epoch schedules are
        #: unaffected.  None -> root.common.engine.scan_epoch (False)
        self.scan_epoch = scan_epoch
        self.forwards = list(forwards or [])
        self.evaluator = evaluator
        #: gradient units in FORWARD order (gds[i] pairs forwards[i]);
        #: suppliers of per-layer hyperparams + momentum buffers
        self.gds = list(gds or [])
        self.loader = loader
        self.mesh = mesh
        self.donate = donate
        #: keep per-minibatch metric sums ON DEVICE and sync to host once
        #: per class pass (at ``loader.last_minibatch``) — the hot loop
        #: then never blocks on host scalars between steps.  The Decision
        #: sees one aggregated "virtual minibatch" per class pass with
        #: identical epoch totals.  ``False`` restores per-minibatch sync.
        self.defer_metrics = defer_metrics
        #: forward/backward compute dtype (resolved from the device at
        #: initialize; bf16 on TPU, f32 elsewhere); params stay f32
        self.compute_dtype = None
        self._params = None
        self._key = None          # device-resident PRNG key, split per step
        self._train_fn = None
        self._eval_fn = None
        self._dataset_dev = None  # HBM-pinned (data, labels) full batch
        self._sample_shape = ()   # of one sample of the pinned data
        self._train_fn_idx = None
        self._eval_fn_idx = None
        self._scan_idx_fns = {}   # "train"/"eval" -> class-pass scan fn
        self._scan_in_flight = False  # current class pass was scan-dispatched
        self._scan_fn = None      # lazily-built K-step lax.scan variant
        self._grad_fn = None      # accumulation: grads-only half-step
        self._grad_fn_idx = None
        self._apply_fn = None     # accumulation: deferred optimizer apply
        self._grad_acc = None     # device-side summed grads
        self._bs_acc = None       # device-side summed sample count
        self._acc_count = 0       # minibatches since last apply
        self._hyper_cache = None  # (signature, device pytree)
        self._zero_gather_nbytes = 0   # bytes gathered per dispatch
        self._zero_gather_counter = None   # cached registry child
        self._gather_via_psum = False  # resolved from config at build
        self._codec = None        # resolved qcomm.Codec (None = exact)
        self._ef = False          # error-feedback residuals active?
        self._qcomm_grad_bytes = None    # (wire, exact) per train step
        self._qcomm_gather_bytes = None  # (wire, exact) per dispatch
        self._qcomm_grad_counters = None
        self._qcomm_gather_counters = None
        #: wall between consecutive dispatches ->
        #: znicz_anatomy_step_seconds{plane="fused"} (the fleet
        #: watchtower's straggler rule reads it), and what the stall
        #: watch looks at; always on
        self._cadence = StepCadence("fused")
        self._acc = None          # device-side metric sums (deferred mode)
        self._conf_seen = None    # confusion sums already folded this pass
        self._nt_valid = None     # nearest-target recovery proven valid?
        # metrics the Decision links to (mirrors the evaluator's attrs)
        self.n_err = 0
        self.mse = 0.0
        self.loss = 0.0
        #: host mirror of the summed sample count behind the current
        #: n_err/mse values; the Decision's ``minibatch_size`` link points
        #: here in fused workflows
        self.minibatch_size = 0

    # -- parameter pytree ---------------------------------------------------
    #: leaf keys holding optimizer state (sharded under shard_update)
    OPT_STATE_KEYS = ("vw", "vb", "sw", "sb")

    def _put(self, value, spec=P()):
        """THE device placement convention: ``value`` (array or pytree)
        onto this step's mesh under ``spec`` (a PartitionSpec or a
        matching pytree of them).  The input pipeline's stager
        (:meth:`make_stager`) shares this, so a pre-staged batch lands
        with exactly the layout the compiled step expects."""
        from jax.sharding import NamedSharding
        shardings = jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), spec,
            is_leaf=lambda s: isinstance(s, P))
        return jax.device_put(value, shardings)

    def _flat_shard_put(self, host_arr):
        """Flatten + pad an optimizer-state array and place it sharded
        over the ``data`` axis (ZeRO layout).  Dtype-preserving: callers
        own the storage dtype (f32 snapshots/adam moments; state_dtype
        momenta arrive pre-narrowed from put_state)."""
        n = self.mesh.shape["data"]
        flat = np.asarray(host_arr).reshape(-1)
        flat = np.pad(flat, (0, (-len(flat)) % n))
        return self._put(flat, P("data"))

    def _leaf_sharded(self, k: str) -> bool:
        """Does leaf key ``k`` live flat-sharded over ``data``?  THE one
        layout decision shared by gather_params/param_specs/
        extra_state_arrays/load_extra_state/sync_to_units."""
        if k in self.OPT_STATE_KEYS:
            return self.shard_update
        if k in ("w", "b", "ew", "eb"):
            return self.shard_params
        if k in ("rw", "rb"):
            # error-feedback residuals: rank-LOCAL (n, *param_shape)
            # slabs sharded on axis 0 — each replica carries only its
            # own quantization error (extra_state_arrays/load_extra_
            # state special-case these: the slab snapshots as-is, not
            # through the flat reassembly)
            return True
        return False            # t (scalar step count)

    def gather_params(self):
        """Build the params pytree from the unit Arrays: w/b replicated
        over the mesh (the sharding the step outputs, so the jit
        signature is stable from the first call); optimizer-state leaves
        flat-sharded over ``data`` when ``shard_update``; w/b (and the
        EMA mirrors) flat-sharded too when ``shard_params``."""
        put = lambda a: self._put(np.asarray(a))  # noqa: E731
        put_v = self._flat_shard_put if self.shard_update else put
        put_w = self._flat_shard_put if self.shard_params else put

        def put_state(a):
            # momentum buffers live in state_dtype (unit Arrays / snapshots
            # keep f32; the narrow copy exists only inside the step)
            if self.state_dtype is not None:
                a = np.asarray(a).astype(self.state_dtype)
            return put_v(a)

        params = []
        for fwd, gd in zip(self.forwards, self.gds):
            leaf = {k: put_w(arr.map_read())
                    for k, arr in fwd.param_arrays().items()}
            if "w" in leaf:
                leaf["vw"] = put_state(
                    np.zeros_like(fwd.weights.map_read())
                    if not gd.gradient_weights
                    else gd.gradient_weights.map_read())
            if "b" in leaf:
                leaf["vb"] = put_state(
                    np.zeros_like(fwd.bias.map_read())
                    if not gd.gradient_bias
                    else gd.gradient_bias.map_read())
            if self.optimizer == "adam":
                # vw/vb double as first moments; second moments + step
                # count are step-level state (restored from snapshots via
                # load_extra_state AFTER this rebuild)
                if "w" in leaf:
                    leaf["sw"] = put_v(
                        np.zeros_like(fwd.weights.map_read()))
                if "b" in leaf:
                    leaf["sb"] = put_v(np.zeros_like(fwd.bias.map_read()))
                leaf["t"] = put(np.float32(0.0))
            if self.ema_decay is not None:
                # EMA mirrors share the layout of the params they track
                # (flat-sharded under shard_params)
                if "w" in leaf:
                    leaf["ew"] = put_w(fwd.weights.map_read())
                if "b" in leaf:
                    leaf["eb"] = put_w(fwd.bias.map_read())
            if self._ef:
                # error-feedback residuals (one param-shaped slab per
                # replica, zero at build): quantization error of step t
                # rides into step t+1's gradient — persistent optimizer-
                # adjacent state, snapshotted via extra_state_arrays
                n = self.mesh.shape["data"]
                for k in ("w", "b"):
                    if k in leaf:
                        leaf["r" + k] = self._put(
                            np.zeros((n,) + self._param_shape(
                                len(params), k), np.float32), P("data"))
            params.append(leaf)
        return params

    def ema_params(self):
        """Host copies of the Polyak-averaged weights: a list of
        {"w": ..., "b": ...} dicts in unit order (export/eval view),
        fetched in ONE batched ``jax.device_get`` and reassembled from
        flat shards when ``shard_params``."""
        if self.ema_decay is None:
            raise RuntimeError("ema_decay is not enabled on this step")
        dev = {f"{i}.{k}": leaf[k]
               for i, leaf in enumerate(self._params)
               for k in ("ew", "eb") if k in leaf}
        host = jax.device_get(dev) if dev else {}
        out = [{} for _ in self._params]
        for key, val in host.items():
            i, k = key.split(".", 1)
            i = int(i)
            if self._leaf_sharded(k):
                val = self._unshard_host(val, self._param_shape(i, k))
            out[i]["w" if k == "ew" else "b"] = np.asarray(val)
        return out

    def param_specs(self):
        """Per-leaf PartitionSpecs matching gather_params' placement."""
        return [{k: (P("data") if self._leaf_sharded(k) else P())
                 for k in leaf} for leaf in self._params]

    def _res_specs(self):
        """out_specs for ``_local_grads``' residual-update return: the
        rw/rb slab layout under error feedback, ``None`` (an empty
        pytree — zero extra outputs) otherwise."""
        if not self._ef:
            return None
        return [{k: P("data") for k in ("rw", "rb") if k in leaf}
                for leaf in self._params]

    def _unshard_host(self, flat_host, like_shape):
        """Flat zero-padded HOST array (the device_get of a sharded
        leaf) -> host array of the original parameter shape.  Callers
        own the D2H transfer — the snapshot path batches the whole tree
        into one ``jax.device_get`` before reassembling."""
        size = int(np.prod(like_shape))
        return np.asarray(flat_host).reshape(-1)[:size].reshape(like_shape)

    def hyper_params(self):
        """Per-layer hyperparams as host floats (traced scalars)."""
        return [
            {"lr": float(gd.learning_rate), "wd": float(gd.weights_decay),
             "l1": float(gd.l1_vs_l2), "mom": float(gd.gradient_moment),
             "lr_b": float(gd.learning_rate_bias),
             "wd_b": float(gd.weights_decay_bias),
             "mom_b": float(gd.gradient_moment_bias)}
            for gd in self.gds
        ]

    def _hyper_device(self):
        """Device-resident hyperparam pytree, re-uploaded only when an LR
        schedule actually changed a value — the per-step rebuild shipped
        ~20 host scalars per minibatch (VERDICT r2 weak #1)."""
        host = self.hyper_params()
        sig = tuple(tuple(sorted(h.items())) for h in host)
        if self._hyper_cache is None or self._hyper_cache[0] != sig:
            dev = self._put(jax.tree.map(np.float32, host))
            self._hyper_cache = (sig, dev)
        return self._hyper_cache[1]

    def _param_shape(self, i: int, key: str):
        fwd = self.forwards[i]
        return (fwd.weights if key.endswith("w") else fwd.bias).shape

    def _account_zero_memory(self) -> None:
        """Per-chip persistent-state byte accounting into the
        ``znicz_zero_*`` registry families: params (w/b) vs
        optimizer/EMA state, sharded leaves counted at their 1/n slice
        (padding included — the flat arrays are padded to a multiple of
        n, so the per-chip figure carries the real padding epsilon).
        Also fixes the static per-dispatch gathered-bytes figure for the
        shard_params chain and caches its counter child."""
        n = self.mesh.shape["data"]
        param_b = opt_b = gather_b = 0
        for leaf in self._params:
            for k, v in leaf.items():
                nb = int(np.prod(v.shape)) * v.dtype.itemsize
                per_chip = nb // n if self._leaf_sharded(k) else nb
                if k in ("w", "b"):
                    param_b += per_chip
                    if self.shard_params:
                        gather_b += nb
                else:
                    opt_b += per_chip
        self._zero_gather_nbytes = gather_b
        _probe.zero_memory(self.name, param_b, opt_b)
        self._zero_gather_counter = _probe.zero_gather_counter(self.name)
        self._account_qcomm()

    def _account_qcomm(self) -> None:
        """Static per-dispatch wire/exact byte figures for the quantized
        collectives (same build-time convention as
        ``_zero_gather_nbytes``), plus the compression-ratio gauges and
        cached counter children.  Exact bytes follow each collective's
        native accounting: full f32 grads per train step for the psum,
        the padded-flat f32 leaf (= ``znicz_zero_gathered_bytes_total``'s
        figure) per dispatch for the shard_params regather."""
        if self._codec is None:
            return
        n = self.mesh.shape["data"]
        grad_wire = grad_exact = zg_wire = zg_exact = 0
        for i, leaf in enumerate(self._params):
            for k in ("w", "b"):
                if k not in leaf:
                    continue
                size = int(np.prod(self._param_shape(i, k)))
                grad_wire += qcomm.wire_nbytes(self._codec, size)
                grad_exact += qcomm.exact_nbytes(size)
                if self.shard_params:
                    padded = size + (-size) % n
                    zg_wire += n * qcomm.wire_nbytes(self._codec,
                                                     padded // n)
                    zg_exact += qcomm.exact_nbytes(padded)
        self._qcomm_grad_bytes = (grad_wire, grad_exact)
        self._qcomm_grad_counters = _probe.qcomm_counters(
            self.name, "grad_psum")
        _probe.qcomm_ratio(self.name, "grad_psum", grad_wire, grad_exact)
        if self.shard_params:
            self._qcomm_gather_bytes = (zg_wire, zg_exact)
            self._qcomm_gather_counters = _probe.qcomm_counters(
                self.name, "zero_gather")
            _probe.qcomm_ratio(self.name, "zero_gather", zg_wire,
                               zg_exact)

    def _note_gathered(self, n_steps: int = 1) -> None:
        """Count ``n_steps`` dispatches' worth of on-demand all-gather
        traffic (every dispatch under shard_params — train, eval, or
        each scanned minibatch — regathers the full w/b set once)."""
        if not _probe.enabled():
            return
        if self._zero_gather_nbytes:
            self._zero_gather_counter.inc(
                float(self._zero_gather_nbytes) * n_steps)
        if self._qcomm_gather_bytes:
            wire, exact = self._qcomm_gather_bytes
            c_wire, c_exact = self._qcomm_gather_counters
            c_wire.inc(float(wire) * n_steps)
            c_exact.inc(float(exact) * n_steps)

    def _note_qcomm_grads(self, n_steps: int = 1) -> None:
        """Count ``n_steps`` TRAIN dispatches' worth of quantized
        gradient-psum traffic (eval dispatches compute no grads, so the
        caller — not ``_finish_run`` — gates on the minibatch class)."""
        if self._qcomm_grad_bytes and _probe.enabled():
            wire, exact = self._qcomm_grad_bytes
            c_wire, c_exact = self._qcomm_grad_counters
            c_wire.inc(float(wire) * n_steps)
            c_exact.inc(float(exact) * n_steps)

    def _publish_residual_norm(self) -> None:
        """Global L2 norm of the error-feedback residual tree into the
        ``znicz_qcomm_residual_norm`` gauge (class-pass cadence — one
        small device reduction + scalar fetch, never per minibatch)."""
        if not self._ef or not _probe.enabled():
            return
        total = jnp.zeros((), jnp.float32)
        for leaf in self._params:
            for k in ("rw", "rb"):
                if k in leaf:
                    r = leaf[k].astype(jnp.float32)
                    total = total + jnp.vdot(r, r)
        _probe.qcomm_residual_norm(self.name,
                                   float(jnp.sqrt(total)))

    def extra_state_arrays(self) -> dict:
        """Optimizer state that has no unit Array home (adam second
        moments + step count, EMA mirrors) -> host arrays for the
        snapshotter, always in the PARAM shape (snapshots stay
        layout-independent: a sharded run restores into a replicated one
        and vice versa).  The whole tree comes down in ONE
        ``jax.device_get`` call — one blocking transfer per snapshot,
        not one per optimizer-state leaf (snapshot stalls must not scale
        with layer count)."""
        out = {}
        if self._params is None:
            return out
        keys = []
        if self.optimizer == "adam":
            keys += ["sw", "sb", "t"]
        if self.ema_decay is not None:
            keys += ["ew", "eb"]
        if self._ef:
            keys += ["rw", "rb"]
        dev = {f"{i}.{k}": leaf[k]
               for i, leaf in enumerate(self._params)
               for k in keys if k in leaf}
        host = jax.device_get(dev) if dev else {}
        for key, val in host.items():
            i, k = key.split(".", 1)
            if k in ("rw", "rb"):
                # error-feedback residuals are genuinely per-rank state:
                # the (n, *param_shape) slab snapshots AS-IS (same mesh
                # resumes bit-exact; load_extra_state folds the rank sum
                # — the only quantity the EF correction depends on —
                # when the world size changed)
                out[key] = np.asarray(val)
                continue
            if self._leaf_sharded(k):
                val = self._unshard_host(val, self._param_shape(int(i), k))
            out[key] = np.asarray(val)
        return out

    def load_extra_state(self, arrays: dict) -> None:
        """Restore extra_state_arrays output into the (already rebuilt)
        device params — call after gather_params on resume.  Arrays
        arrive in the PARAM shape and land in whatever layout THIS step
        uses (the cross-layout resume contract)."""
        for key, val in arrays.items():
            i, k = key.split(".", 1)
            if k in ("rw", "rb"):
                if not self._ef:
                    # quantized -> exact cross-layout restore: the
                    # residual has no home (and no effect) here — drop
                    # it rather than corrupt the leaf layout
                    continue
                n = self.mesh.shape["data"]
                val = np.asarray(val, np.float32)
                if val.shape[0] != n:
                    # cross-world restore: only the rank SUM of the
                    # residuals is meaningful (Σr is the total deferred
                    # quantization error) — fold it onto rank 0
                    folded = np.zeros((n,) + val.shape[1:], np.float32)
                    folded[0] = val.sum(axis=0)
                    val = folded
                self._params[int(i)][k] = self._put(val, P("data"))
            elif self._leaf_sharded(k):
                self._params[int(i)][k] = self._flat_shard_put(val)
            else:
                self._params[int(i)][k] = self._put(np.asarray(val))

    def sync_to_units(self) -> None:
        """Write the device params back into the unit Arrays (snapshot /
        inspection path; the hot loop never does this).  Replicated
        leaves hand their device buffer over zero-copy (set_devmem);
        flat-sharded leaves come down in ONE batched ``jax.device_get``
        and reassemble to the param shape host-side."""
        fetch = {f"{i}.{k}": leaf[k]
                 for i, leaf in enumerate(self._params)
                 for k in ("w", "b", "vw", "vb")
                 if k in leaf and self._leaf_sharded(k)}
        host = jax.device_get(fetch) if fetch else {}

        def put_host(arr, flat, shape):
            arr.map_invalidate()
            arr.mem = np.asarray(self._unshard_host(flat, shape),
                                 dtype=np.float32)

        for i, (fwd, gd, leaf) in enumerate(
                zip(self.forwards, self.gds, self._params)):
            if "w" in leaf:
                if self.shard_params:
                    put_host(fwd.weights, host[f"{i}.w"],
                             fwd.weights.shape)
                else:
                    fwd.weights.set_devmem(leaf["w"])
            if "b" in leaf:
                if self.shard_params:
                    put_host(fwd.bias, host[f"{i}.b"], fwd.bias.shape)
                else:
                    fwd.bias.set_devmem(leaf["b"])
            if not self.shard_update:
                # unit buffers are f32 (astype is a no-op without
                # state_dtype; exact widening with it)
                if "w" in leaf:
                    gd.gradient_weights.set_devmem(
                        leaf["vw"].astype(jnp.float32))
                if "b" in leaf:
                    gd.gradient_bias.set_devmem(
                        leaf["vb"].astype(jnp.float32))
                continue
            # sharded momenta: reassemble to the param shape host-side
            # (the batched fetch above; f32 widening is exact)
            if "w" in leaf:
                put_host(gd.gradient_weights, host[f"{i}.vw"],
                         fwd.weights.shape)
            if "b" in leaf:
                put_host(gd.gradient_bias, host[f"{i}.vb"],
                         fwd.bias.shape)

    # -- forward / loss composition -----------------------------------------
    def _forward_chain(self, params, x, train: bool, rng=None):
        """Compose the forwards; returns pre-softmax logits when the last
        layer is All2AllSoftmax (loss uses log_softmax directly).

        ``rng`` is a per-step key; each NEEDS_RNG unit (dropout, stochastic
        pooling) gets a per-unit fold so masks are independent across units
        and steps.

        Activations and param inputs are cast to ``compute_dtype`` (bf16 on
        TPU) — AD then casts cotangents back, so gradients accumulate into
        the f32 master params."""
        cdt = self.compute_dtype or jnp.float32
        x = x.astype(cdt)
        last = len(self.forwards) - 1
        logits_tail = isinstance(self.forwards[last], All2AllSoftmax) and \
            isinstance(self.evaluator, EvaluatorSoftmax)
        for i, (fwd, p) in enumerate(zip(self.forwards, params)):
            # one named scope per forward unit (metadata only): the
            # profiler's operations carry it, the backward pass as
            # transpose(jvp(<scope>)) -- docs/OBSERVABILITY.md
            with _probe.scope(unit_scope(i, fwd)):
                pc = {k: (v.astype(cdt) if k in ("w", "b") else v)
                      for k, v in p.items()}
                unit_rng = None
                if getattr(fwd, "NEEDS_RNG", False) and rng is not None:
                    unit_rng = jax.random.fold_in(rng, i)
                if i == last and logits_tail:
                    x = fwd.xla_apply_linear(pc, x)
                else:
                    x = fwd.xla_apply(pc, x, rng=unit_rng, train=train)
        return x, logits_tail

    def _nt_recovery_valid(self) -> bool:
        """Fused nearest-target n_err is emitted only when the label-
        recovery assumption is PROVEN at trace time: every stored target
        must be the exact prototype row of its label (noisy targets
        would silently recover wrong labels — the eager evaluator, which
        has real label plumbing, stays correct for those).  Cached after
        the first check."""
        if self._nt_valid is not None:
            return self._nt_valid
        self._nt_valid = False
        ev = self.evaluator
        loader = self.loader
        if isinstance(ev, EvaluatorMSE) and ev._classifies and \
                loader is not None:
            targets = getattr(loader, "original_targets", None)
            labels = getattr(loader, "original_labels", None)
            if targets and labels:
                protos = ev.class_targets.map_read()
                lab = np.asarray(labels.mem)
                self._nt_valid = bool(
                    np.array_equal(np.asarray(targets.mem),
                                   protos[lab]))
        return self._nt_valid

    @_probe.scoped("loss")
    def _loss_and_metrics(self, out, logits_tail, labels, mask):
        """Masked loss-sum + metric sums over the local shard (f32
        regardless of the forward's compute dtype)."""
        out = out.astype(jnp.float32)
        fmask = mask.astype(out.dtype)
        if isinstance(self.evaluator, EvaluatorSoftmax):
            if logits_tail:
                logp = jax.nn.log_softmax(out, axis=1)
            else:
                logp = jnp.log(jnp.clip(out, 1e-30, None))
            n = out.shape[0]
            picked = logp[jnp.arange(n), labels]
            # per-class weights (evaluator contract): the CE term of each
            # sample is scaled by its TRUE class's weight, so AD yields
            # err_output rows scaled exactly like the eager evaluator's
            cw = getattr(self.evaluator, "class_weights", None)
            wrow = fmask if cw is None else \
                fmask * jnp.asarray(cw, out.dtype)[labels]
            loss = -(picked * wrow).sum()
            pred = out.argmax(axis=1)
            n_err = ((pred != labels) & mask).sum()
            metrics = {"loss": loss, "n_err": n_err}
            if getattr(self.evaluator, "compute_confusion_matrix", False):
                # (pred, label) count matrix as f32 sums — exact up to
                # 2^24 samples per class pass, far above any epoch here;
                # orientation matches the eager evaluator's
                # np.add.at(confusion, (max_idx, labels), 1)
                c = out.shape[1]
                pred_oh = jax.nn.one_hot(pred, c, dtype=jnp.float32) * \
                    fmask[:, None]
                lab_oh = jax.nn.one_hot(labels, c, dtype=jnp.float32)
                metrics["confusion"] = pred_oh.T @ lab_oh
            return loss, metrics
        if isinstance(self.evaluator, EvaluatorMSE):
            n = out.shape[0]
            diff = (out.reshape(n, -1) -
                    labels.reshape(n, -1)) * fmask[:, None]
            loss = 0.5 * (diff * diff).sum()
            mse_sum = (diff * diff).mean(axis=1).sum()
            metrics = {"loss": loss, "mse_sum": mse_sum}
            if self._nt_recovery_valid():
                # nearest-target classification without label plumbing:
                # the init-time check proved targets are exact prototype
                # rows, so the integer label is recoverable as the
                # nearest prototype of the TARGET; n_err then counts
                # outputs nearest a different prototype (the eager
                # evaluator's count).  Prototypes are a small static
                # table baked in at trace time.
                protos = jnp.asarray(
                    self.evaluator.class_targets.map_read(), out.dtype)
                pred = EvaluatorMSE.nearest_prototype(jnp, out, protos)
                lab = EvaluatorMSE.nearest_prototype(
                    jnp, labels.reshape(n, -1).astype(out.dtype), protos)
                metrics["n_err"] = ((pred != lab) & mask).sum()
            return loss, metrics
        raise TypeError(f"unsupported evaluator {type(self.evaluator)}")

    # -- compiled step bodies ------------------------------------------------
    def _local_train(self, params, key, hyper, x, labels, mask):
        """One step: ``(params, key, ...) -> (params', key', metrics)``.
        The key is split ON DEVICE — the host never mints per-step keys.
        Gradient computation is shared with the accumulation half-step
        (_local_grads); the optimizer application with the deferred apply
        (_apply_update)."""
        key, grads, metrics, new_res = self._local_grads(params, key, x,
                                                         labels, mask)
        if new_res is not None:
            # fold the stepped error-feedback residuals into the params
            # carry BEFORE the apply (_apply_update's dict(leaf) copy
            # passes them through to the output pytree)
            params = [{**leaf, **nr}
                      for leaf, nr in zip(params, new_res)]
        new_params = self._apply_update(params, grads, hyper,
                                        metrics["bs"])
        return new_params, key, metrics

    @_probe.scoped("update")
    def _apply_update(self, params, grads, hyper, bs):
        """Apply one optimizer step for summed gradients ``grads`` over
        ``bs`` total samples — shared by the per-minibatch step and the
        gradient-accumulation apply."""
        if self.clip_norm is not None:
            # clip the batch-mean gradient's GLOBAL norm across layers;
            # scaling grad_sum by the same factor is equivalent and keeps
            # the downstream /bs convention untouched
            sq = sum(jnp.sum(jnp.square(g / bs))
                     for leaf in grads for g in leaf.values())
            gnorm = jnp.sqrt(sq)
            scale = jnp.minimum(1.0, self.clip_norm /
                                jnp.maximum(gnorm, 1e-12))
            grads = [{k: v * scale for k, v in leaf.items()}
                     for leaf in grads]
        # SGD backend: XLA-fused by default; the Pallas single-HBM-pass
        # kernel when root.common.engine.pallas is set (SURVEY.md §3.2
        # "fused SGD-update" kernel parity deliverable)
        use_pallas = bool(root.common.engine.get("pallas", False))
        interp = bool(root.common.engine.get("pallas_interpret", False))
        cfg = self.optimizer_config
        if use_pallas:
            from znicz_tpu.ops.pallas import (fused_adam_update,
                                              fused_sgd_update)

            def upd(w, g, v, lr, wd, l1, mom, bsz):
                return fused_sgd_update(w, g, v, lr, wd, l1, mom,
                                        bsz.astype(jnp.float32),
                                        interpret=interp)

            def adam_upd(w, g, m, s, t_new, lr, wd, bsz):
                return fused_adam_update(
                    w, g, m, s, t_new, lr, wd, cfg["beta1"],
                    cfg["beta2"], cfg["eps"], bsz.astype(jnp.float32),
                    interpret=interp)
        else:
            from znicz_tpu.ops import adam

            def upd(w, g, v, lr, wd, l1, mom, bsz):
                return sgd.update(jnp, w, g, v, lr, wd, l1, mom, bsz)

            def adam_upd(w, g, m, s, t_new, lr, wd, bsz):
                return adam.update(jnp, w, g, m, s, t_new, lr, wd,
                                   cfg["beta1"], cfg["beta2"],
                                   cfg["eps"], bsz)

        # narrow momenta (state_dtype) need no handling here: both
        # backends preserve the velocity's storage dtype themselves —
        # ops.sgd.update widens for the math and returns vel narrow; the
        # Pallas kernel casts in-tile (single HBM pass preserved)

        if self.shard_update:
            n_data = self.mesh.shape["data"]   # static: pad math below
            rank = jax.lax.axis_index("data")
            sp = self.shard_params

            def my_slice(w):
                return zero.pad_slice(w, rank, n_data)

            def regather(w_shard, like):
                return zero.psum_regather(w_shard, rank, n_data, "data",
                                          like)

            def apply(leaf, grad, h, wk, vk, sk, lr_k, wd_k, new, t_new):
                # the grads arrive ALREADY globally summed: the vma
                # system requires cotangents of unvaried (replicated)
                # primals to be unvaried, so AD inserts the cross-replica
                # psum itself.  Each replica therefore just slices its
                # shard — the sharding win is the ZeRO-1 one (optimizer
                # state + update compute at 1/n), not grad bandwidth
                g = my_slice(grad[wk])
                # under shard_params the leaf already IS the flat shard
                w_sh = leaf[wk] if sp else my_slice(leaf[wk])
                if self.optimizer == "adam":
                    w_sh, new[vk], new[sk] = adam_upd(
                        w_sh, g, leaf[vk], leaf[sk], t_new, h[lr_k],
                        h[wd_k], bs)
                else:
                    mom_k = "mom" if wk == "w" else "mom_b"
                    w_sh, new[vk] = upd(w_sh, g, leaf[vk], h[lr_k],
                                        h[wd_k], h["l1"], h[mom_k], bs)
                # shard_params: the updated slice IS the persistent
                # layout — the post-update regather disappears entirely
                # (the next forward regathers on demand instead)
                new[wk] = w_sh if sp else regather(w_sh, leaf[wk])
        else:
            apply = None

        new_params = []
        for leaf, grad, h in zip(params, grads, hyper):
            new = dict(leaf)
            t_new = leaf["t"] + 1.0 if self.optimizer == "adam" else None
            if apply is not None:
                if "w" in leaf:
                    apply(leaf, grad, h, "w", "vw", "sw", "lr", "wd",
                          new, t_new)
                if "b" in leaf:
                    apply(leaf, grad, h, "b", "vb", "sb", "lr_b", "wd_b",
                          new, t_new)
                if t_new is not None:
                    new["t"] = t_new
            elif self.optimizer == "adam":
                if "w" in leaf:
                    new["w"], new["vw"], new["sw"] = adam_upd(
                        leaf["w"], grad["w"], leaf["vw"], leaf["sw"],
                        t_new, h["lr"], h["wd"], bs)
                if "b" in leaf:
                    new["b"], new["vb"], new["sb"] = adam_upd(
                        leaf["b"], grad["b"], leaf["vb"], leaf["sb"],
                        t_new, h["lr_b"], h["wd_b"], bs)
                new["t"] = t_new
            else:
                if "w" in leaf:
                    new["w"], new["vw"] = upd(
                        leaf["w"], grad["w"], leaf["vw"], h["lr"], h["wd"],
                        h["l1"], h["mom"], bs)
                if "b" in leaf:
                    new["b"], new["vb"] = upd(
                        leaf["b"], grad["b"], leaf["vb"], h["lr_b"],
                        h["wd_b"], h["l1"], h["mom_b"], bs)
            if self.ema_decay is not None:
                d = jnp.float32(self.ema_decay)
                if "ew" in leaf:
                    new["ew"] = d * leaf["ew"] + (1.0 - d) * new["w"]
                if "eb" in leaf:
                    new["eb"] = d * leaf["eb"] + (1.0 - d) * new["b"]
            new_params.append(new)
        return new_params

    def _gather_full(self, leaves):
        """``shard_params`` materialization: full w/b arrays from the
        flat shards via the per-leaf all-gather chain
        (:func:`zero.gather_chain`), dispatched in consumption order
        ahead of the forward so XLA's async collectives overlap leaf
        i+1's gather with leaf i's compute.  Non-w/b keys pass through;
        a no-op without ``shard_params``."""
        if not self.shard_params:
            return leaves
        n = self.mesh.shape["data"]
        rank = jax.lax.axis_index("data")
        shards, likes, sites = [], [], []
        for i, leaf in enumerate(leaves):
            for k in ("w", "b"):
                if k in leaf:
                    shards.append(leaf[k])
                    likes.append(jax.ShapeDtypeStruct(
                        self._param_shape(i, k), leaf[k].dtype))
                    sites.append((i, k))
        with _probe.scope("zero_gather"):
            full = zero.gather_chain(shards, likes, rank, n, "data",
                                     via_psum=self._gather_via_psum,
                                     codec=self._codec)
        out = [dict(leaf) for leaf in leaves]
        for (i, k), v in zip(sites, full):
            out[i][k] = v
        return out

    def _local_grads(self, params, key, x, labels, mask):
        """Gradient-accumulation half-step: summed grads + metrics, NO
        update (the apply happens every ``accumulate_steps`` runs)."""
        key, sub = jax.random.split(key)
        rng = jax.random.fold_in(sub, jax.lax.axis_index("data"))
        trainable = [{k: v for k, v in leaf.items() if k in ("w", "b")}
                     for leaf in params]
        # shard_params: materialize full weights OUTSIDE the
        # differentiated function — grads land param-shaped and reduce
        # through the SAME explicit psum as every other mode (AD through
        # the gather would transpose to a reduce-scatter, changing the
        # reduction path and with it the bit-exact parity with the
        # replicated/shard_update paths); the update slices them
        trainable = self._gather_full(trainable)

        def loss_fn(ps):
            out, logits_tail = self._forward_chain(ps, x, train=True,
                                                   rng=rng)
            loss, metrics = self._loss_and_metrics(
                out, logits_tail, labels, mask)
            metrics = jax.lax.psum(metrics, "data")
            # LOCAL loss on purpose: the cross-device reduction happens
            # on the GRADS below.  Differentiating through a psum'd loss
            # depends on the psum transpose convention (it flips with
            # the replication checker, see parallel/compat.py) and never
            # yields replicated params on >1 device — the explicit grad
            # psum is correct under either convention.
            return loss, metrics

        (_, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(trainable)
        # the grad reduction rides the quantized-psum seam: exact
        # lax.psum when no codec (bit-identical program), int8/bf16
        # payload + error-feedback residuals otherwise.  metrics/bs
        # psums above/below stay exact always — telemetry and the
        # Decision's sample accounting must never quantize.
        residuals = None
        if self._ef:
            # local residual view: the (1, *shape) slab's single row
            residuals = [{k: params[i]["r" + k][0] for k in g}
                         for i, g in enumerate(grads)]
        with _probe.scope("grad_reduce"):
            grads, res_out = quantized_psum(grads, "data", self._codec,
                                            residuals)
        new_res = None if res_out is None else \
            [{"r" + k: v[None] for k, v in leaf.items()}
             for leaf in res_out]
        metrics["bs"] = jax.lax.psum(mask.sum(), "data")
        return key, grads, metrics, new_res

    @_probe.scoped("gather_batch")
    def _gather_batch(self, data, labels, idx):
        """The index gather from the dataset pinned on the device: whole
        rows as :meth:`_pin_dataset` laid them out, back in the sample's
        shape (and rid of the rows' padding) once gathered."""
        n, shape = idx.shape[0], self._sample_shape
        rows = data[idx].reshape(n, -1)[:, :math.prod(shape)]
        return rows.reshape(n, *shape), labels[idx]

    def _local_grads_idx(self, params, key, data, labels, idx, mask):
        return self._local_grads(
            params, key, *self._gather_batch(data, labels, idx), mask)

    def _local_apply(self, params, hyper, grads, bs):
        return self._apply_update(params, grads, hyper, bs)

    def _local_eval(self, params, x, labels, mask):
        params = self._gather_full(params)
        out, logits_tail = self._forward_chain(params, x, train=False)
        _, metrics = self._loss_and_metrics(out, logits_tail, labels, mask)
        metrics = jax.lax.psum(metrics, "data")
        metrics["bs"] = jax.lax.psum(mask.sum(), "data")
        return metrics

    # index-fed variants: the dataset lives on HBM (see initialize); the
    # host ships ~4 bytes/sample of indices per step instead of the
    # minibatch itself (reference: FullBatchLoader's ``on_device`` option)
    def _local_train_idx(self, params, key, hyper, data, labels, idx, mask):
        return self._local_train(
            params, key, hyper, *self._gather_batch(data, labels, idx),
            mask)

    def _local_eval_idx(self, params, data, labels, idx, mask):
        return self._local_eval(
            params, *self._gather_batch(data, labels, idx), mask)

    # -- lifecycle ----------------------------------------------------------
    def initialize(self, device=None, **kwargs) -> None:
        # the step subsumes the segment units: they are not in the control
        # graph, so initialize them here (weights allocated + filled) before
        # gathering the params pytree
        with _probe.setup_phase("init_params"):
            for unit in (*self.forwards, self.evaluator, *self.gds):
                if unit is not None and not unit.initialized:
                    unit.initialize(device=device, **kwargs)
                    unit.initialized = True
        # compile-latency plane (ISSUE 7): the program builds below are
        # the training path's cold compiles — route them through the
        # persistent cache so a restarted process (or a second host on
        # a shared cache dir) pays trace cost only
        from znicz_tpu import compilecache
        compilecache.ensure()
        if self.optimizer == "adam":
            # the adam branch reads lr/wd only; a configured L1 mix would
            # be silently dropped — refuse like the fused=False guard
            bad = [gd.name for gd in self.gds
                   if float(getattr(gd, "l1_vs_l2", 0.0)) != 0.0]
            if bad:
                raise ValueError(
                    f"l1_vs_l2 is SGD-only (adam applies decoupled L2 "
                    f"weight decay); set it to 0 on: {bad}")
        if self.mesh is None:
            # local_devices: under a jax.distributed join, devices()[0]
            # belongs to process 0 — a default mesh must be addressable
            # from THIS rank (the elastic fleet's standalone-SPMD path)
            self.mesh = Mesh(np.array(jax.local_devices()[:1]), ("data",))
            if jax.local_device_count() > 1:
                # there is no CLI flag for a mesh: say what a bare
                # `python -m znicz_tpu model.py` leaves unused
                self.info(f"no mesh given: using 1 of "
                          f"{jax.local_device_count()} local devices "
                          f"(pass mesh= to the workflow to use more)")
        n_data = self.mesh.shape["data"]
        if self.loader is not None and \
                self.loader.max_minibatch_size % n_data != 0:
            raise ValueError(
                f"minibatch {self.loader.max_minibatch_size} not divisible "
                f"by data-mesh size {n_data}")
        if self.compute_dtype is None:
            self.compute_dtype = getattr(device, "compute_dtype", None) or \
                jnp.float32
        # shard_params regather flavor: payload-proportional all_gather
        # by default; engine.zero_gather_via_psum opts into the
        # provably-replicating psum fallback (parallel/compat.py shim
        # notes — the checker cannot infer replication through the
        # all_gather, so a caller re-enabling check_vma needs this)
        self._gather_via_psum = bool(root.common.engine.get(
            "zero_gather_via_psum", False))
        # quantized collectives (ISSUE 18): resolve BEFORE gather_params
        # — the error-feedback residual leaves must exist in the pytree
        # the specs and programs are built from
        self._codec = qcomm.resolve(self.quantized_collectives)
        self._ef = self._codec is not None and self._codec.error_feedback
        with _probe.setup_phase("place"):
            self._params = _probe.placed(self.gather_params())
            self._key = self._put(prng.get().key())
        self._account_zero_memory()
        rep, sh = P(), P("data")
        pspecs = self.param_specs()
        train = shard_map(self._local_train, mesh=self.mesh,
                          in_specs=(pspecs, rep, rep, sh, sh, sh),
                          out_specs=(pspecs, rep, rep))
        evalf = shard_map(self._local_eval, mesh=self.mesh,
                          in_specs=(pspecs, sh, sh, sh),
                          out_specs=rep)
        donate = (0, 1) if self.donate else ()
        self._train_fn = jax.jit(train, donate_argnums=donate)
        self._eval_fn = jax.jit(evalf)
        if self.accumulate_steps > 1:
            gradf = shard_map(self._local_grads, mesh=self.mesh,
                              in_specs=(pspecs, rep, sh, sh, sh),
                              out_specs=(rep, rep, rep,
                                         self._res_specs()))
            applyf = shard_map(self._local_apply, mesh=self.mesh,
                               in_specs=(pspecs, rep, rep, rep),
                               out_specs=pspecs)
            self._grad_fn = jax.jit(gradf)
            self._apply_fn = jax.jit(
                applyf, donate_argnums=(0,) if self.donate else ())
        with _probe.setup_phase("place"):
            self._pin_dataset()
            _probe.placed(self._dataset_dev)
        if self._scan_idx_fns:
            # VERDICT r5 item 6: in epoch-scan mode hyperparams are read
            # once per class pass, so a per-MINIBATCH LR schedule would
            # silently coarsen to per-pass granularity — refuse instead
            # of changing training dynamics quietly
            from znicz_tpu.units.lr_adjust import LearningRateAdjust
            gd_ids = {id(gd) for gd in self.gds}
            offenders = [
                u.name for u in (self.workflow.units if self.workflow
                                 else [])
                if isinstance(u, LearningRateAdjust) and not u.by_epoch
                and any(id(gd) in gd_ids for gd, _, _ in u._gd_units)]
            if offenders:
                raise ValueError(
                    f"scan_epoch compiles a whole class pass into one "
                    f"dispatch reading hyperparams once, so the "
                    f"per-minibatch (by_epoch=False) LearningRateAdjust "
                    f"unit(s) {offenders} would silently coarsen to "
                    f"per-pass schedules; use by_epoch=True or disable "
                    f"scan_epoch")
        # telemetry plane: wrap every compiled program so its FIRST call
        # (the trace+compile+run cold path) lands in the
        # znicz_compile_seconds histogram with a compile.cold span —
        # the ROADMAP compile-latency item's baseline — then donate the
        # wrappers to the recompile probe, which polls the REAL
        # compile-cache sizes through them, so an unexpected mid-run
        # recompile lands as a counter increment plus an instant event
        # on the step timeline.  Keyed per INSTANCE (two live steps keep
        # separate watches; the probe holds weakrefs, so a dropped step
        # reaps its own entry) while the metric label stays the class
        # name.
        label = type(self).__name__
        for attr in ("_train_fn", "_eval_fn", "_grad_fn", "_apply_fn",
                     "_train_fn_idx", "_eval_fn_idx", "_grad_fn_idx",
                     "_scan_fn"):
            fn = getattr(self, attr, None)
            if fn is not None:
                setattr(self, attr, _probe.time_compiles(label, fn))
        self._scan_idx_fns = {k: _probe.time_compiles(label, fn)
                              for k, fn in self._scan_idx_fns.items()}
        fns = [getattr(self, n, None) for n in
               ("_train_fn", "_eval_fn", "_grad_fn", "_apply_fn",
                "_train_fn_idx", "_eval_fn_idx", "_grad_fn_idx",
                "_scan_fn")] + \
            list(self._scan_idx_fns.values())
        _probe.watch_compiles(f"{type(self).__name__}-{id(self):x}",
                              *(f for f in fns if f is not None),
                              label=label)
        self.initialized = True

    def _pin_dataset(self) -> None:
        """Place a full-batch dataset on HBM so the hot loop ships only
        minibatch INDICES — per-step host->device data transfer (the
        dominant cost for image workflows) disappears.  Gated on size
        (``root.common.engine.dataset_on_device_max_bytes``, default 1
        GiB, against the bytes of the pinned data array) and on the
        loader exposing ``original_data``.

        The data is pinned in the form the step consumes, so that the
        step's program reads and writes nothing of the dataset's size
        but the rows it gathers: in ``compute_dtype`` (the step's own
        cast, which the compiler otherwise hoists above the gather and
        applies to the whole array in every step) and in rows of
        :func:`pinned_row_shape`.  Both are done on the device, once.
        Labels and MSE targets stay as they are."""
        self._dataset_dev = None
        self._train_fn_idx = self._eval_fn_idx = None
        loader = self.loader
        data_arr, labels_arr, _why = full_batch_arrays(
            loader, mse=isinstance(self.evaluator, EvaluatorMSE))
        if data_arr is None:
            return
        limit = int(root.common.engine.get(
            "dataset_on_device_max_bytes", 1 << 30))
        host = np.asarray(data_arr.mem, np.float32)
        cdt = jnp.dtype(self.compute_dtype)
        n, n_values = len(host), math.prod(host.shape[1:])
        row = pinned_row_shape(n_values)
        width = math.prod(row)
        if n * width * cdt.itemsize > limit:
            return
        self._sample_shape = host.shape[1:]
        data = self._put(host.reshape(n, n_values))
        if (data.dtype, data.shape[1:]) != (cdt, row):
            def as_pinned(a):
                a = jnp.pad(a.astype(cdt), ((0, 0), (0, width - n_values)))
                return a.reshape(n, *row)
            staged, data = data, jax.jit(as_pinned)(data)
            staged.delete()
        self._dataset_dev = (data, self._put(np.asarray(labels_arr.mem)))
        self.info(f"pinned the dataset on the device: {n} rows of "
                  f"{n_values} values as {data.dtype}{list(data.shape)}, "
                  f"{data.nbytes} bytes")
        rep, sh = P(), P("data")
        pspecs = self.param_specs()
        train = shard_map(self._local_train_idx, mesh=self.mesh,
                          in_specs=(pspecs, rep, rep, rep, rep, sh, sh),
                          out_specs=(pspecs, rep, rep))
        evalf = shard_map(self._local_eval_idx, mesh=self.mesh,
                          in_specs=(pspecs, rep, rep, sh, sh),
                          out_specs=rep)
        donate = (0, 1) if self.donate else ()
        self._train_fn_idx = jax.jit(train, donate_argnums=donate)
        self._eval_fn_idx = jax.jit(evalf)
        if self.accumulate_steps > 1:
            gradf = shard_map(self._local_grads_idx, mesh=self.mesh,
                              in_specs=(pspecs, rep, rep, rep, sh, sh),
                              out_specs=(rep, rep, rep,
                                         self._res_specs()))
            self._grad_fn_idx = jax.jit(gradf)
        # the loader now only needs to serve indices — its per-step host
        # gather + device upload of the minibatch would be dead work
        loader.serve_indices_only = True
        if self.scan_epoch is None:
            self.scan_epoch = bool(root.common.engine.get("scan_epoch",
                                                          False))
        if self.scan_epoch and self.accumulate_steps > 1:
            raise ValueError("accumulate_steps > 1 is a per-minibatch "
                             "mode; disable scan_epoch to use it")
        if self.scan_epoch:
            self._build_scan_idx_fns()

    def _build_scan_idx_fns(self) -> None:
        """Class-pass scan programs over the index plan: ONE dispatch per
        class pass (train or eval) — per-minibatch host dispatch latency
        leaves the hot loop entirely."""
        def local_train_many(params, key, hyper, data, labels, idxs, ms):
            def body(carry, inp):
                p, k = carry
                idx, m = inp
                p, k, metrics = self._local_train(
                    p, k, hyper, *self._gather_batch(data, labels, idx), m)
                return (p, k), metrics
            (params, key), mets = jax.lax.scan(
                body, (params, key), (idxs, ms))
            return params, key, jax.tree.map(lambda a: a.sum(0), mets)

        def local_eval_many(params, data, labels, idxs, ms):
            def body(_, inp):
                idx, m = inp
                return None, self._local_eval(
                    params, *self._gather_batch(data, labels, idx), m)
            _, mets = jax.lax.scan(body, None, (idxs, ms))
            return jax.tree.map(lambda a: a.sum(0), mets)

        rep = P()
        shs = P(None, "data")
        pspecs = self.param_specs()
        donate = (0, 1) if self.donate else ()
        self._scan_idx_fns["train"] = jax.jit(shard_map(
            local_train_many, mesh=self.mesh,
            in_specs=(pspecs, rep, rep, rep, rep, shs, shs),
            out_specs=(pspecs, rep, rep)), donate_argnums=donate)
        self._scan_idx_fns["eval"] = jax.jit(shard_map(
            local_eval_many, mesh=self.mesh,
            in_specs=(pspecs, rep, rep, shs, shs),
            out_specs=rep))
        # plan capture costs an int64 matrix per class pass — only pay it
        # when this mode actually consumes it
        self.loader.capture_class_plan = True

    def _build_scan_fn(self):
        """K-step variant: ``lax.scan`` over stacked minibatches inside the
        same shard_map'd program — one dispatch per K steps."""
        def local_many(params, key, hyper, xs, ys, ms):
            def body(carry, inp):
                p, k = carry
                p, k, metrics = self._local_train(p, k, hyper, *inp)
                return (p, k), metrics
            (params, key), mets = jax.lax.scan(
                body, (params, key), (xs, ys, ms))
            return params, key, jax.tree.map(lambda a: a.sum(0), mets)

        rep = P()
        sh = P(None, "data")  # (step, batch, ...): batch axis sharded
        pspecs = self.param_specs()
        fn = shard_map(local_many, mesh=self.mesh,
                       in_specs=(pspecs, rep, rep, sh, sh, sh),
                       out_specs=(pspecs, rep, rep))
        donate = (0, 1) if self.donate else ()
        self._scan_fn = _probe.time_compiles(
            type(self).__name__, jax.jit(fn, donate_argnums=donate))

    def train_steps(self, xs, ys, masks):
        """Run ``xs.shape[0]`` training minibatches in ONE dispatch and
        return the summed metric pytree (device-resident).  ``xs/ys/masks``
        carry a leading step axis over per-step minibatches — the input
        pipeline stages them on device, the compiled program loops.  This
        is the hot path for ms-scale steps, where per-step host dispatch
        latency would otherwise dominate."""
        if self.accumulate_steps > 1:
            raise ValueError("train_steps (K-step scan) applies the "
                             "optimizer per minibatch; accumulate_steps "
                             "> 1 requires the per-minibatch run() path")
        if self._scan_fn is None:
            self._build_scan_fn()
        self._params, self._key, metrics = self._scan_fn(
            self._params, self._key, self._hyper_device(), xs, ys, masks)
        self._note_gathered(int(xs.shape[0]))
        self._note_qcomm_grads(int(xs.shape[0]))
        return metrics

    # -- input-pipeline staging ---------------------------------------------
    def make_stager(self):
        """Producer-side staging callable for the input pipeline
        (znicz_tpu.pipeline): issues the NEXT batch's ``device_put`` with
        this step's input shardings while the current step is still
        executing, so the H2D transfer hides under device compute.
        Signature: ``stage(record, arrays) -> (staged_dict, nbytes)``.

        Ring-slot safety: ``arrays`` come from the loader's rotating
        fill_batch buffers, handed off through
        :func:`~znicz_tpu.pipeline.prefetcher.ring_safe_stager` (copy on
        the aliasing CPU backend, H2D fence on accelerators)."""
        from znicz_tpu.pipeline.prefetcher import ring_safe_stager

        sh = P("data")
        # ONE tuple put: batch, labels/targets and mask ride a single
        # staging call
        safe_put = ring_safe_stager(
            lambda x, y, m: self._put((x, y, m), (sh, sh, sh)))

        def stage(rec, arrays):
            if self._scan_idx_fns:
                # epoch-scan feeding dispatches whole class passes from
                # the captured plan — per-minibatch staging would be
                # dead device buffers (the pipeline still overlaps the
                # shuffle/plan work)
                return None, 0
            mask = rec["indices"] >= 0
            if self._dataset_dev is not None:
                # index-fed mode: only the indices + mask ride H2D (both
                # freshly built per record — no ring slot to protect)
                idx = np.maximum(rec["indices"], 0).astype(np.int32)
                idx_d, mask_d = self._put((idx, mask), (sh, sh))
                return ({"idx": idx_d, "mask": mask_d},
                        idx.nbytes + mask.nbytes)
            x = arrays["data"]
            y = arrays["targets" if isinstance(self.evaluator, EvaluatorMSE)
                       else "labels"]
            x_d, y_d, mask_d = safe_put(x, y, mask)
            return ({"x": x_d, "y": y_d, "mask": mask_d},
                    x.nbytes + y.nbytes + mask.nbytes)

        return stage

    # -- per-minibatch control callback -------------------------------------
    def run(self) -> None:
        loader = self.loader
        # pipelined feeding: the batch (or its indices) was device_put by
        # the prefetch worker with this step's shardings — consume the
        # staged arrays instead of re-shipping the host copies
        staged = loader.take_staged() \
            if getattr(loader, "pipeline", None) is not None else None
        if self._dataset_dev is not None and self._scan_idx_fns and \
                (int(loader.minibatch_offset) == 0 or
                 self._scan_in_flight):
            self._run_scanned_class(loader)
            return
        # (a class pass entered MID-WAY — restored loader state — falls
        # through to the per-minibatch path for the remainder; _acc is
        # NOT a valid in-flight marker because that path sets it too)
        with _TRACER.timed("train.dispatch") as span:
            metrics = self._dispatch(loader, staged)
        # the batch-size sum is an output of the step that no later step
        # takes by donation: the stall watch asks it is_ready()
        self._cadence.tick(span.t0, metrics["bs"])
        self._finish_run(loader, metrics)

    def _dispatch(self, loader, staged):
        """Hand one minibatch to its compiled program (train, grads
        half-step or eval; index-fed or not) and return the metrics
        pytree, still on the device."""
        mask = staged["mask"] if staged is not None else \
            loader.minibatch_indices.mem >= 0
        accumulate = self.accumulate_steps > 1
        if self._dataset_dev is not None:
            # index-fed hot path: dataset already on HBM
            idx = staged["idx"] if staged is not None else \
                np.maximum(loader.minibatch_indices.mem, 0).astype(
                    np.int32)
            data, labels_all = self._dataset_dev
            if int(loader.minibatch_class) != TRAIN:
                return self._eval_fn_idx(self._params, data, labels_all,
                                         idx, mask)
            if accumulate:
                self._key, grads, metrics, new_res = self._grad_fn_idx(
                    self._params, self._key, data, labels_all, idx, mask)
                self._fold_residuals(new_res)
                self._accumulate(grads, metrics, loader)
            else:
                self._params, self._key, metrics = self._train_fn_idx(
                    self._params, self._key, self._hyper_device(),
                    data, labels_all, idx, mask)
            self._note_qcomm_grads()
            return metrics
        if staged is not None:
            x, labels = staged["x"], staged["y"]
        elif isinstance(self.evaluator, EvaluatorMSE):
            x = loader.minibatch_data.mem
            labels = loader.minibatch_targets.mem
        else:
            x = loader.minibatch_data.mem
            labels = loader.minibatch_labels.mem
        if int(loader.minibatch_class) != TRAIN:
            return self._eval_fn(self._params, x, labels, mask)
        if accumulate:
            self._key, grads, metrics, new_res = self._grad_fn(
                self._params, self._key, x, labels, mask)
            self._fold_residuals(new_res)
            self._accumulate(grads, metrics, loader)
        else:
            self._params, self._key, metrics = self._train_fn(
                self._params, self._key, self._hyper_device(),
                x, labels, mask)
        self._note_qcomm_grads()
        return metrics

    def _fold_residuals(self, new_res) -> None:
        """Persist the residual updates returned by a ``_grad_fn``
        half-step into the params pytree (the full-step path folds them
        inside the compiled program; the accumulation path returns them
        because the apply is deferred)."""
        if new_res is not None:
            for leaf, nr in zip(self._params, new_res):
                leaf.update(nr)

    def _accumulate(self, grads, metrics, loader) -> None:
        """Fold one half-step's summed grads into the device accumulator;
        apply the optimizer every ``accumulate_steps`` train minibatches
        and at the END of a train pass (a ragged tail must not leak into
        the next epoch's first effective batch)."""
        bs = metrics["bs"]
        if self._grad_acc is None:
            self._grad_acc = grads
            self._bs_acc = bs
        else:
            self._grad_acc = jax.tree.map(jnp.add, self._grad_acc, grads)
            self._bs_acc = self._bs_acc + bs
        self._acc_count += 1
        if self._acc_count >= self.accumulate_steps or \
                loader.last_minibatch:
            self._params = self._apply_fn(
                self._params, self._hyper_device(), self._grad_acc,
                self._bs_acc)
            self._grad_acc = None
            self._bs_acc = None
            self._acc_count = 0

    def _run_scanned_class(self, loader) -> None:
        """Epoch-scan mode: the FIRST minibatch of a class pass dispatches
        the whole pass as one scanned program; the control loop keeps
        iterating (the loader serves indices cheaply) and the summed
        metrics land at the last minibatch — the same "virtual minibatch"
        the Decision already sees in deferred mode."""
        if int(loader.minibatch_offset) == 0:
            from znicz_tpu.loader.base import plan_device_arrays
            idxs, ms = plan_device_arrays(loader.class_plan())
            data, labels = self._dataset_dev
            with _TRACER.timed("train.dispatch") as span:
                if int(loader.minibatch_class) == TRAIN:
                    self._params, self._key, metrics = \
                        self._scan_idx_fns["train"](
                            self._params, self._key, self._hyper_device(),
                            data, labels, idxs, ms)
                    self._note_qcomm_grads(int(idxs.shape[0]))
                else:
                    metrics = self._scan_idx_fns["eval"](
                        self._params, data, labels, idxs, ms)
            self._cadence.tick(span.t0, metrics["bs"])
            self._note_gathered(int(idxs.shape[0]))
            self._acc = metrics
            self._scan_in_flight = True
        if loader.last_minibatch:
            self._read_metrics(self._acc, cumulative=True)
            self._acc = None
            self._conf_seen = None
            self._scan_in_flight = False
            self._publish_residual_norm()
        else:
            self.n_err = 0
            self.mse = 0.0
            self.loss = 0.0
            self.minibatch_size = 0

    def _finish_run(self, loader, metrics) -> None:
        # one dispatch (train, grads half-step, or eval) = one on-demand
        # full-weight regather under shard_params
        self._note_gathered()
        if loader.last_minibatch:
            self._publish_residual_norm()
        # chaos hook (site "step.params"): NaN-poisons the param pytree —
        # the observable effect of NaN gradients — so health-guard and
        # rollback paths are exercised against the real fused step
        self._params = poison_hook("step.params", self._params)
        if not self.defer_metrics:
            self._read_metrics(metrics)
            return
        # deferred mode: fold into the device-side accumulator (async tiny
        # adds, no host sync) and only fetch at the end of the class pass
        self._acc = metrics if self._acc is None else \
            jax.tree.map(jnp.add, self._acc, metrics)
        if loader.last_minibatch:
            self._read_metrics(self._acc, cumulative=True)
            self._acc = None
            self._conf_seen = None
        else:
            # non-final minibatches contribute zero to the Decision's
            # accumulators; the class-pass totals land in one shot above
            self.n_err = 0
            self.mse = 0.0
            self.loss = 0.0
            self.minibatch_size = 0

    def _read_metrics(self, sums, cumulative: bool = False) -> None:
        """THE blocking metric read: fetch the device-side sums and
        publish them.  The ``train.metrics_read`` span is where the host
        waits for the device to drain (once a class pass in deferred
        mode), so the profiler can put the chip's idle gap there down to
        it."""
        with _TRACER.span("train.metrics_read"):
            self._publish(jax.device_get(sums), cumulative=cumulative)

    def _publish(self, sums, cumulative: bool = False) -> None:
        """Write (host) metric sums into the attrs the Decision reads.

        ``cumulative=True`` marks sums that cover the class pass SO FAR
        (the deferred/scan accumulator) rather than one minibatch — the
        confusion matrix folds only the delta since the last publish, so
        a mid-pass ``flush_metrics`` never double-counts."""
        bs = float(sums["bs"])
        self.minibatch_size = int(bs)
        # chaos hook (site "step.loss"): NaN into the published loss
        self.loss = poison_hook("step.loss", float(sums["loss"]))
        if "n_err" in sums:
            self.n_err = int(sums["n_err"])
        if "mse_sum" in sums:
            self.mse = float(sums["mse_sum"]) / max(bs, 1.0)
        if "confusion" in sums and \
                getattr(self.evaluator, "confusion_matrix", None) is not None:
            # accumulate like the eager evaluator; the Decision copies and
            # zeroes the matrix at each class-pass end (finalize_class)
            conf = np.rint(np.asarray(sums["confusion"])).astype(np.int64)
            if cumulative:
                delta = conf if self._conf_seen is None else \
                    conf - self._conf_seen
                self._conf_seen = conf
            else:
                delta = conf
            self.evaluator.confusion_matrix += delta

    def flush_metrics(self) -> None:
        """Sync pending deferred sums into the host mirrors (probe/debug
        path; the training loop flushes itself per class).  ``_acc`` is NOT
        reset — the class pass keeps accumulating, so a mid-pass flush never
        truncates the Decision's epoch accounting."""
        if self._acc is not None:
            self._read_metrics(self._acc, cumulative=True)

    def stop(self) -> None:
        self._cadence.close()
        if self._params is not None:
            self.sync_to_units()
