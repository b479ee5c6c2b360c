"""Transformer language-model step unit — wires the SPMD transformer
stack (znicz_tpu.parallel.transformer: sharded blocks, ring/flash
attention, mixed precision) into the unit graph with the same control
contract as FusedTrainStep: Repeater -> Loader -> step -> Decision.

Beyond-parity: the reference predates transformers (SURVEY.md §3.4 row
"SP/CP: NO — pre-transformer framework"); this unit is what makes the
beyond-parity stack a *workflow citizen* — epochs, validation passes,
Decision stopping, snapshot/resume — instead of a standalone demo.

XLA-only by design (like ``optimizer="adam"`` is fused-only): a numpy
transformer oracle would re-implement the whole stack for no oracle
value — parity for the math is pinned in test_transformer_spmd.py
against autograd.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from znicz_tpu.core import prng
from znicz_tpu.core.accelerated_units import AcceleratedUnit
from znicz_tpu.loader.base import TRAIN
from znicz_tpu.observe.anatomy import StepCadence
from znicz_tpu.observe.trace import TRACER


def _term_gauges() -> dict:
    """The registry's gauge of each named term a step reports in the
    loss's own convention (``parallel/transformer.py``: the stats whose
    names start ``loss_`` or ``loop_``), by the term's name; a looped
    stack's ``loop_loss_step<r>`` share one family labelled by ``step``."""
    from znicz_tpu.observe import registry

    tail = ", mean over the last training pass's steps"
    return {
        "loss_main": registry.gauge(
            "znicz_lm_loss_main",
            "next-token cross-entropy of the main stack" + tail, ("unit",)),
        "loss_mtp": registry.gauge(
            "znicz_lm_loss_mtp",
            "second-next-token cross-entropy of the multi-token-prediction "
            "module, unweighted" + tail, ("unit",)),
        "loop_exit_step_mean": registry.gauge(
            "znicz_lm_loop_exit_step_mean",
            "mean over tokens of sum_r r * p_r, the loop step a token is "
            "expected to leave a looped stack at (1 .. loop steps)" + tail,
            ("unit",)),
        "loop_exit_entropy": registry.gauge(
            "znicz_lm_loop_exit_entropy",
            "mean over tokens of the exit distribution's entropy in nats "
            "(at most ln of the loop steps)" + tail, ("unit",)),
        "loop_loss_step": registry.gauge(
            "znicz_lm_loop_loss_step",
            "next-token cross-entropy of one loop step's output alone"
            + tail, ("unit", "step")),
    }


def _choice_gauges() -> tuple:
    """``(key, gauge, label)`` of each constant of a step as it is built
    (``parallel/transformer.py::step_choices``, by its key, which is the
    unit's attribute too): a value that is a dict sets one child a name
    under ``label``.  The next selection is one row here."""
    from znicz_tpu.observe import registry

    return (
        ("ce_grad_in_forward_share", registry.gauge(
            "znicz_lm_ce_grad_in_forward_share",
            "head passes of the train step that made their gradients in "
            "the pass that made their logits (chunked cross-entropy "
            "against a replicated head: three products a pass) over all "
            "its head passes (an unchunked or a vocab-sharded one leaves "
            "them to AD)",
            ("unit",)), None),
        ("attn_kvb_block_rows", registry.gauge(
            "znicz_lm_attn_kvb_block_rows",
            "rows of the (square) tile each pass of the key/value-blocked "
            "flash kernels runs for the step's attention shape (forward, "
            "dk/dv, dq), 0 where its attention layers run the whole-row "
            "form, no flash kernel, or there are none",
            ("unit", "pass")), "pass"),
        ("checkpoint_kept_bytes", registry.gauge(
            "znicz_lm_checkpoint_kept_bytes",
            "bytes all the step's checkpointed layers keep of an optional "
            "kind of activation for the backward pass (the SwiGLU's two "
            "wide products, a state-space layer's input projection), 0 for "
            "a kind the device's memory refused",
            ("unit", "name")), "name"),
        ("dsa_align_kernel_share", registry.gauge(
            "znicz_lm_dsa_align_kernel_share",
            "layers with an indexer whose alignment target (the heads' mean "
            "attention probabilities over the selection) the Pallas kernel "
            "dsa_align_target makes over the layers with an indexer (the "
            "rest: blocked jax.numpy, the heads' scores through HBM)",
            ("unit",)), None),
        ("dsa_index_kernel_share", registry.gauge(
            "znicz_lm_dsa_index_kernel_share",
            "layers with an indexer whose index scores and their gradients "
            "to the index queries, keys and weights the Pallas kernels "
            "dsa_index_scores and dsa_index_grads make over the layers with "
            "an indexer (the rest: blocked jax.numpy einsums, the index "
            "heads' scores through HBM)",
            ("unit",)), None),
        ("moe_gmm_kernel_share", registry.gauge(
            "znicz_lm_moe_gmm_kernel_share",
            "routed expert layers whose grouped products the Pallas kernels "
            "moe_gmm_rows, moe_gmm_rows_t and moe_gmm_weights make over the "
            "routed expert layers (the rest: lax.ragged_dot, which the "
            "shape or the platform left them to)",
            ("unit",)), None),
        ("ssm_scan_kernel_share", registry.gauge(
            "znicz_lm_ssm_scan_kernel_share",
            "state-space layers whose chunked scan the Pallas kernels "
            "ssd_scan_fwd and ssd_scan_bwd run, a chunk's decay and score "
            "matrices in VMEM, over the state-space layers (the rest: the "
            "jax.numpy form, which the shape or the platform left them to)",
            ("unit",)), None),
        ("ssm_conv_kernel_share", registry.gauge(
            "znicz_lm_ssm_conv_kernel_share",
            "state-space layers whose causal convolution, bias and silu the "
            "Pallas kernels ssm_conv_fwd and ssm_conv_bwd run on the input "
            "projection's own lanes, the float32 sum in VMEM, over the "
            "state-space layers (the rest: the jax.numpy form, which the "
            "shape or the platform left them to)",
            ("unit",)), None),
        ("ssm_gate_kernel_share", registry.gauge(
            "znicz_lm_ssm_gate_kernel_share",
            "state-space layers whose gate, gated group norm and output "
            "product the Pallas kernels ssm_gate_fwd and ssm_gate_bwd run, "
            "a group's statistic taken in VMEM, over the state-space layers "
            "(the rest: the jax.numpy form, which one group, the shape or "
            "the platform left them to)",
            ("unit",)), None),
        ("kda_conv_kernel_share", registry.gauge(
            "znicz_lm_kda_conv_kernel_share",
            "delta-rule linear-attention layers whose three causal "
            "convolutions and silu the Pallas kernels ssm_conv_fwd and "
            "ssm_conv_bwd run on the q | k | v projection's own lanes over "
            "the delta-rule layers (the rest: the jax.numpy form, which the "
            "shape or the platform left them to)",
            ("unit",)), None),
        ("kda_delta_kernel_share", registry.gauge(
            "znicz_lm_kda_delta_kernel_share",
            "delta-rule linear-attention layers whose chunked rule (scores "
            "under the decays, unit-triangular inverse, carry and outputs) "
            "the Pallas kernels kda_delta_fwd and kda_delta_bwd run, a chunk "
            "of a block of heads in VMEM, over the delta-rule layers (the "
            "rest: the jax.numpy form, which the shape or the platform left "
            "them to)",
            ("unit",)), None),
        ("sconv_kernel_share", registry.gauge(
            "znicz_lm_sconv_kernel_share",
            "gated short convolutions whose gates and taps the Pallas "
            "kernels sconv_gate_fwd and sconv_gate_bwd run on the input "
            "projection's own lanes, the float32 sum in VMEM and the "
            "projection's cotangent written whole, over the gated short "
            "convolutions (the rest: the jax.numpy form, which the shape "
            "or the platform left them to)",
            ("unit",)), None),
    )


def _fold_pass(acc, loss, mask, stats):
    """One minibatch into the class pass's device-side sums: the loss
    weighted by the rows that count (the Decision's own weighting), the
    rows, the steps, and whatever counters the step gave."""
    import jax.numpy as jnp

    rows = mask.sum().astype(jnp.float32)
    new = {"loss_rows": loss * rows, "rows": rows,
           "steps": jnp.ones((), jnp.float32), **stats}
    if acc is None:
        return new
    return {k: acc[k] + v for k, v in new.items()}


class TransformerLMStep(AcceleratedUnit):
    """One train-or-eval step per served (tokens, labels) minibatch.

    ``arch`` is the architecture as one mapping of the model's own keys
    (``model_type`` and that family's: ``layer_types``,
    ``num_dense_layers``, ``num_experts``, ... or ``q_lora_rank``,
    ``n_shared_experts``, ``num_nextn_predict_layers``, ... or
    ``total_ut_steps``, ... or ``sa_config``, ``num_experts``, ... or
    ``mamba_n_heads``, ``embedding_multiplier``, ... and
    ``experts_held``, this chip's share:
    ``{"first", "count"}``; see
    ``parallel.arch.arch_from_config``); the vocabulary is the
    loader's.  Without it the unit builds the GPT-shaped block from
    ``n_layers``, ``d``, ``heads``, ``ff`` (and ``n_experts``).

    Mirrors the fused step's donation/dispatch discipline: params live
    on the device and are donated to each step, and no step waits for its
    loss.  The loss stays on the device, is folded into a device-side sum
    and fetched once a class pass, at ``loader.last_minibatch`` (span
    ``lm.loss_read``), with the accounting of ``FusedTrainStep``'s
    deferred mode: ``minibatch_mse`` (mean CE loss per token — the
    DecisionMSE contract: a lower-is-better per-sample metric) and
    ``minibatch_size`` then carry the whole pass, and on every earlier
    minibatch ``minibatch_size`` is 0 and ``minibatch_mse`` is the newest
    step's loss as a device array, not fetched (``float()`` of it
    waits for that step).
    """

    def __init__(self, workflow=None, loader=None, n_layers: int = 2,
                 d: int = 32, heads: int = 2, ff: Optional[int] = None,
                 lr: float = 0.1, mesh=None, arch=None,
                 loss_chunks: Optional[int] = None,
                 head_sharded: bool = False,
                 n_experts: Optional[int] = None,
                 moe_aux_weight: float = 0.0,
                 moe_top_k: int = 1,
                 moe_zloss_weight: float = 0.0, **kwargs) -> None:
        super().__init__(workflow, **kwargs)
        self.loader = loader
        #: the model's own keys, or None for the GPT-shaped block below
        self.arch_config = dict(arch) if arch is not None else None
        if arch is not None and n_experts is not None:
            raise ValueError("arch= carries its own experts; n_experts "
                             "belongs to the GPT-shaped block")
        self.n_layers = int(n_layers)
        self.d = int(d)
        self.heads = int(heads)
        self.ff = int(ff) if ff is not None else 4 * self.d
        self.lr = float(lr)
        self.mesh = mesh
        #: CE loss chunk count — set when vocab ≫ d so the (tokens,
        #: vocab) logits never materialize (docs/TUNING.md)
        self.loss_chunks = loss_chunks
        #: vocab-shard the LM head over the mesh's model axis (Megatron
        #: parallel cross-entropy; vocab must divide by tp)
        self.head_sharded = head_sharded
        #: MoE FFN blocks: expert count (sharded over the model axis),
        #: load-balance aux weight (training loss only), and routing k
        self.n_experts = n_experts
        self.moe_aux_weight = float(moe_aux_weight)
        self.moe_top_k = int(moe_top_k)
        self.moe_zloss_weight = float(moe_zloss_weight)
        if n_experts is None and (self.moe_aux_weight != 0.0 or
                                  self.moe_zloss_weight != 0.0 or
                                  self.moe_top_k != 1):
            raise ValueError(
                "moe_aux_weight/moe_zloss_weight/moe_top_k have no "
                "effect without "
                "n_experts — a dense model would train silently")
        #: wall between consecutive dispatches ->
        #: znicz_anatomy_step_seconds{plane="transformer"}, and what the
        #: stall watch looks at; always on
        self._cadence = StepCadence("transformer")
        self.vocab_size: Optional[int] = None
        # decision links (DecisionMSE contract)
        self.minibatch_mse = 0.0
        self.minibatch_size = 0
        #: the newest dispatch's loss, on the device (never fetched here)
        self.last_loss = None
        #: class-pass sums on the device: loss * rows, rows, steps and the
        #: routed layers' counters; None between passes
        self._acc = None
        #: the last finished pass's routed-expert counters (host floats):
        #: pairs routed to held experts a step, the fullest held
        #: expert's load over the mean, the compact buffer's and the row
        #: tiles' shares and, over the held pairs, the share of the experts'
        #: hidden entries a squared ReLU zeroed (0 for gated experts)
        self.moe_counters: dict = {}
        #: the last finished training pass's mean loss terms of a stack
        #: with an MTP module (``last_loss`` and the Decision's metric are
        #: their weighted total): ``{"main", "mtp"}``, unweighted
        self.loss_terms: dict = {}
        #: the last finished training pass's means of a looped stack:
        #: ``exit_step_mean`` (of ``sum_r r * p_r``), ``exit_entropy`` (of
        #: ``H(p)``, nats) and ``loss_step<r>``, each loop step's own
        #: cross-entropy
        self.loop_counters: dict = {}
        #: the last finished training pass's readings of a stack whose
        #: attention layers carry an indexer (learned sparse attention):
        #: ``selected_share`` (selected over causal (query, key) pairs),
        #: ``live_tile_share`` (of the tiles the blocked forward kernel
        #: visits, those that hold a selected pair), ``index_loss`` (the
        #: alignment term, summed over the layers) and
        #: ``index_loss_share`` (its share of the pass's loss)
        self.dsa_counters: dict = {}
        #: the last finished training pass's readings of a stack with
        #: state-space layers (``parallel/ssm.py``), means over its steps
        #: and layers: ``decay_mean`` (of ``exp(dt A)`` over positions and
        #: heads) and ``final_state_rms`` (RMS of the state behind a row's
        #: last position); neither depends on the chunk the scan runs in
        self.ssm_counters: dict = {}
        #: the last finished training pass's readings of a stack with
        #: delta-rule linear-attention layers (``parallel/kda.py``), means
        #: over its steps and layers: ``decay_mean`` (of ``exp(g)`` over
        #: positions, heads and key channels), ``beta_mean``,
        #: ``final_state_rms`` (RMS of the state behind a row's last
        #: position) and ``layers`` (such layers a step); none depends on
        #: the chunk the rule runs in
        self.kda_counters: dict = {}
        #: of the last finished pass's attention layers that ran a flash
        #: kernel, the share whose kernels read the layer's layout
        #: (``ops/pallas/attention.py::direct_layout``); None without one
        self.attn_direct_layout_share: Optional[float] = None
        #: the last finished pass's readings of a stack whose attention
        #: layers have a window on their scores: ``window_layers`` (such
        #: layers a step) and ``window_tile_share`` (tiles the blocked flash
        #: kernels' visit tables list under the window over those the causal
        #: triangle's list, all three passes; 1.0: the window ran as a mask
        #: alone); empty without one
        self.attn_counters: dict = {}
        #: the constants of the step as it is built, each under its key of
        #: ``parallel/transformer.py::step_choices`` (which documents them)
        #: and gauged by :func:`_choice_gauges`; empty or None until then.
        #: ``{pass: rows}`` of the blocked flash kernels' tiles
        self.attn_kvb_block_rows: dict = {}
        #: of the layers with an indexer, the share whose alignment target
        #: and whose index scores the Pallas kernels make; None without one
        self.dsa_align_kernel_share: Optional[float] = None
        self.dsa_index_kernel_share: Optional[float] = None
        #: of the routed expert layers, the share whose grouped products
        #: the Pallas kernels make; None without one
        self.moe_gmm_kernel_share: Optional[float] = None
        #: of the state-space layers, the share whose scan, whose
        #: convolution, and whose gate and gated norm, the Pallas kernels
        #: run; None without one
        self.ssm_scan_kernel_share: Optional[float] = None
        self.ssm_conv_kernel_share: Optional[float] = None
        self.ssm_gate_kernel_share: Optional[float] = None
        #: of the delta-rule layers, the share whose convolutions the
        #: state-space layer's Pallas kernels run, and whose rule its own;
        #: None without one
        self.kda_conv_kernel_share: Optional[float] = None
        self.kda_delta_kernel_share: Optional[float] = None
        #: of the gated short convolutions, the share whose gates and taps
        #: the Pallas kernels run; None without one
        self.sconv_kernel_share: Optional[float] = None
        #: ``{name: bytes}`` the checkpointed layers keep beside their
        #: policy's own list (``parallel/plan.py::checkpoint_plan``)
        self.checkpoint_kept_bytes: dict = {}
        #: of the head passes, the share that make their gradients where
        #: they make their logits (``parallel/head.py::ce_grad_in_forward``)
        self.ce_grad_in_forward_share: Optional[float] = None
        self.arch = None
        self._params = None
        self._step = None
        self._eval = None
        self._fold = None

    # -- lifecycle ----------------------------------------------------------
    def numpy_init(self) -> None:
        raise NotImplementedError(
            "TransformerLMStep is XLA-only (run with -d tpu/auto); the "
            "transformer stack has no numpy oracle by design")

    def xla_init(self) -> None:
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from znicz_tpu.parallel import transformer as tfm
        from znicz_tpu.parallel.mesh import make_mesh
        from znicz_tpu.parallel.params import init_params

        if self.loader is None:
            raise ValueError("TransformerLMStep needs loader=")
        self.vocab_size = int(self.loader.vocab_size)
        if self.mesh is None:
            self.mesh = make_mesh({"data": 1, "seq": 1, "model": 1},
                                  jax.local_devices())
            if jax.local_device_count() > 1:
                self.info(f"no mesh given: using 1 of "
                          f"{jax.local_device_count()} local devices "
                          f"(pass mesh= to the workflow to use more)")
        from znicz_tpu.observe import probe

        self.arch = self._resolve_arch()
        if self._params is None:
            with probe.setup_phase("init_params"):
                self._params = init_params(prng.get(), self.arch)
        with probe.setup_phase("place"):
            self._params = probe.placed(self._place_params(self._params))
        # masked=True: the loader's padded tail rows (base.py static-shape
        # policy) contribute neither loss nor gradients.  donate=True: the
        # step consumes the params it is handed (xla_run rebinds them)
        self._step, _ = tfm.make_train_step(
            self.mesh, self.arch, lr=self.lr, masked=True, donate=True,
            loss_chunks=self.loss_chunks, head_sharded=self.head_sharded,
            moe_aux_weight=self.moe_aux_weight,
            moe_zloss_weight=self.moe_zloss_weight, stats=True)
        self._eval = tfm.make_eval_loss(
            self.mesh, self.arch, masked=True, loss_chunks=self.loss_chunks,
            head_sharded=self.head_sharded)
        self._publish_choices(tfm.step_choices(
            self.mesh, self.arch, int(self.loader.max_minibatch_size),
            int(self.loader.minibatch_data.shape[1]), self.loss_chunks,
            self.head_sharded))
        self._fold = jax.jit(_fold_pass)
        # cold-compile timing, and the shapes probe.scope_map() lowers
        # the programs from again (as FusedTrainStep's programs)
        label = type(self).__name__
        self._step = probe.time_compiles(label, self._step)
        self._eval = probe.time_compiles(label, self._eval)
        #: minibatch placement: batch over data, time over seq
        self._batch_sharding = NamedSharding(self.mesh, P("data", "seq"))
        self._mask_sharding = NamedSharding(self.mesh, P("data"))
        #: reused mask row — the hot loop allocates nothing per step
        self._arange = np.arange(self.loader.max_minibatch_size)
        from znicz_tpu.pipeline.prefetcher import ring_safe_stager

        self._put = ring_safe_stager(lambda t, l, m: jax.device_put(
            (t, l, m), (self._batch_sharding, self._batch_sharding,
                        self._mask_sharding)))

    def _resolve_arch(self):
        """The ``Arch`` this unit runs, at the loader's vocabulary."""
        from znicz_tpu.parallel.arch import arch_from_config, gpt_arch

        if self.arch_config is not None:
            return arch_from_config(self.arch_config, self.vocab_size)
        return gpt_arch(self.n_layers, self.d, self.heads, self.ff,
                        self.vocab_size, self.n_experts, self.moe_top_k)

    def _stage_batch(self, tokens, labels, count: int):
        """ONE fused ``device_put``: tokens, labels and the padding mask
        ride a single staged tuple transfer instead of three separate
        H2D trips (shared by xla_run and the input-pipeline stager).
        Detached or fenced (``ring_safe_stager``): no step waits for its
        loss any more, so the loader refills its host arrays while
        earlier steps are still queued."""
        return self._put(tokens, labels, self._arange < count)

    def make_stager(self):
        """Producer-side staging for the input pipeline
        (znicz_tpu.pipeline): the worker issues the next batch's fused
        tuple put while the current step computes; ring-slot handoff via
        the shared ring_safe_stager (copy on the aliasing CPU backend,
        H2D fence on accelerators)."""
        def stage(rec, arrays):
            tokens, labels = arrays["data"], arrays["labels"]
            staged = self._stage_batch(tokens, labels, rec["size"])
            nbytes = tokens.nbytes + labels.nbytes + \
                self._arange.size  # one byte per bool mask element
            return {"lm": staged}, nbytes
        return stage

    def _place_params(self, params):
        """Mesh placement by param_specs — the ONE layout used by init
        and restore alike."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from znicz_tpu.parallel.params import param_specs

        specs = param_specs(self.arch, self.head_sharded)
        return jax.device_put(
            params, jax.tree.map(
                lambda s: NamedSharding(self.mesh, s), specs,
                is_leaf=lambda x: isinstance(x, P)))

    def stop(self) -> None:
        self._cadence.close()

    # -- compute ------------------------------------------------------------
    def numpy_run(self) -> None:
        self.numpy_init()

    def xla_run(self) -> None:
        import jax

        loader = self.loader
        count = int(loader.minibatch_size)
        staged = loader.take_staged() \
            if getattr(loader, "pipeline", None) is not None else None
        if staged is not None:
            # pipelined feeding: the prefetch worker already issued the
            # fused tuple put, overlapped with the previous step
            tokens, labels, mask = staged["lm"]
        else:
            with TRACER.span("lm.stage"):
                tokens, labels, mask = self._stage_batch(
                    loader.minibatch_data.mem, loader.minibatch_labels.mem,
                    count)
        with TRACER.timed("lm.dispatch") as span:
            if int(self.loader.minibatch_class) == TRAIN:
                self._params, loss, stats = self._step(
                    self._params, tokens, labels, mask)
            else:
                loss, stats = self._eval(self._params, tokens, labels,
                                         mask), {}
            # no wait: the loss joins the pass's sums on the device (one
            # tiny program behind the step) and work stays queued
            self._acc = self._fold(self._acc, loss, mask, stats)
        # the loss is the step's newest output that the next step does
        # not take by donation: the stall watch asks it is_ready()
        self._cadence.tick(span.t0, loss)
        self.last_loss = loss
        if not loader.last_minibatch:
            # minibatches before the last contribute zero to the
            # Decision's accumulators (FusedTrainStep's deferred mode);
            # the newest loss stays reachable, unfetched
            self.minibatch_mse = loss
            self.minibatch_size = 0
            return
        # the one blocking read of the class pass: its totals land here
        with TRACER.span("lm.loss_read"):
            sums = jax.device_get(self._acc)
        self._acc = None
        rows, steps = float(sums["rows"]), max(float(sums["steps"]), 1.0)
        self.minibatch_mse = float(sums["loss_rows"]) / max(rows, 1.0)
        self.minibatch_size = int(rows)
        if "pairs_held" in sums:
            self._publish_moe(float(sums["pairs_held"]) / steps,
                              float(sums["load_max_over_mean"]) / steps,
                              float(sums["compact"]) / steps,
                              float(sums["tile_fill"]) / steps,
                              float(sums["pairs_held"]),
                              float(sums.get("act_zero", 0.0)) / steps)
        self._publish_terms(sums, steps)
        if "dsa_pairs" in sums:
            self._publish_dsa(sums, steps, self.minibatch_mse)
        if sums.get("ssm_layers"):
            self._publish_ssm(sums)
        if sums.get("kda_layers"):
            self._publish_kda(sums, steps)
        if "attn_flash" in sums:
            self._publish_attn_layout(float(sums["attn_direct"]) /
                                      float(sums["attn_flash"]))
        if "attn_window" in sums:
            self._publish_attn_window(
                float(sums["attn_window"]) / steps,
                float(sums["attn_window_tiles"]) /
                float(sums["attn_causal_tiles"]))

    def _publish_choices(self, choices: dict) -> None:
        """The constants of the step as it is built (``step_choices``):
        each the unit's mirror under its key and, unless None (a stack
        without what it describes), the process registry."""
        for key, gauge, label in _choice_gauges():
            value = choices[key]
            setattr(self, key, value)
            if value is None:
                continue
            if label is None:
                gauge.labels(unit=self.name).set(value)
                continue
            for name, one in value.items():
                gauge.labels(**{"unit": self.name, label: name}).set(one)

    def _publish_attn_layout(self, share: float) -> None:
        """Of the attention layers that ran a flash kernel, the share whose
        kernels read the layer's own layout (a constant of the traced
        step): the unit's mirror and the process registry."""
        from znicz_tpu.observe import registry

        self.attn_direct_layout_share = share
        registry.gauge(
            "znicz_lm_attn_direct_layout_share",
            "attention layers whose flash kernels read the layer's (batch, "
            "t, heads x head_dim) layout over the attention layers that ran "
            "a flash kernel (the rest fold their operands head-major)",
            ("unit",)).labels(unit=self.name).set(share)

    def _publish_attn_window(self, layers: float, tile_share: float) -> None:
        """A finished pass's readings of the window layers (constants of
        the traced step): the unit's mirror and the process registry."""
        from znicz_tpu.observe import registry

        self.attn_counters = {"window_layers": layers,
                              "window_tile_share": tile_share}
        registry.gauge(
            "znicz_lm_attn_window_layers",
            "attention layers of a step whose scores have a window (query i "
            "sees key j iff 0 <= i - j < window), last class pass",
            ("unit",)).labels(unit=self.name).set(layers)
        registry.gauge(
            "znicz_lm_attn_window_tile_share",
            "tiles the key/value-blocked flash kernels' visit tables list "
            "under the window over the tiles the causal triangle's tables "
            "list, the three passes of every window layer of the last class "
            "pass (1.0: the window ran as a mask alone, on dense scores or "
            "in tiles that are all visited)",
            ("unit",)).labels(unit=self.name).set(tile_share)

    def _publish_dsa(self, sums: dict, steps: float, loss: float) -> None:
        """A finished pass's readings of the indexers' selections and of
        the alignment term: the unit's mirror and the process registry."""
        from znicz_tpu.observe import registry

        index_loss = float(sums["loss_index"]) / steps
        self.dsa_counters = {
            "selected_share":
                float(sums["dsa_selected"]) / float(sums["dsa_pairs"]),
            "live_tile_share":
                float(sums["dsa_live_tiles"]) / float(sums["dsa_tiles"]),
            "index_loss": index_loss,
            "index_loss_share": index_loss / loss if loss else 0.0}
        # each family by its literal name: tools/check_metric_catalogue.py
        # reads declarations off the source
        gauges = {
            "selected_share": registry.gauge(
                "znicz_lm_dsa_selected_share",
                "(query, key) pairs the indexers selected over the causal "
                "pairs, all attention layers of the last class pass",
                ("unit",)),
            "live_tile_share": registry.gauge(
                "znicz_lm_dsa_live_tile_share",
                "tiles of the blocked forward kernel's visit table that "
                "hold at least one selected pair over the tiles visited, "
                "all attention layers of the last class pass", ("unit",)),
            "index_loss": registry.gauge(
                "znicz_lm_dsa_index_loss",
                "the indexers' alignment term (KL from the heads' mean "
                "attention probabilities to the softmax of the index "
                "scores over the selection), summed over the layers, mean "
                "over the last class pass's steps", ("unit",)),
            "index_loss_share": registry.gauge(
                "znicz_lm_dsa_index_loss_share",
                "the alignment term over the whole loss (cross-entropy "
                "plus the term), last class pass", ("unit",))}
        for key, value in self.dsa_counters.items():
            gauges[key].labels(unit=self.name).set(value)

    def _publish_ssm(self, sums: dict) -> None:
        """A finished training pass's readings of the state-space layers
        (each summed over the pass's steps and layers, as their count is):
        the unit's mirror and the process registry."""
        from znicz_tpu.observe import registry

        layers = float(sums["ssm_layers"])
        self.ssm_counters = {
            "decay_mean": float(sums["ssm_decay"]) / layers,
            "final_state_rms": float(sums["ssm_state_rms"]) / layers}
        registry.gauge(
            "znicz_lm_ssm_decay_mean",
            "mean over positions, heads, state-space layers and the last "
            "training pass's steps of the state's decay a position, "
            "exp(dt A) (1: the state is kept whole; 0: dropped)",
            ("unit",)).labels(unit=self.name).set(
                self.ssm_counters["decay_mean"])
        registry.gauge(
            "znicz_lm_ssm_final_state_rms",
            "RMS of a state-space layer's state behind a row's last "
            "position, mean over rows, layers and the last training "
            "pass's steps (a carry that is dropped or shortened moves it)",
            ("unit",)).labels(unit=self.name).set(
                self.ssm_counters["final_state_rms"])

    def _publish_kda(self, sums: dict, steps: float) -> None:
        """A finished training pass's readings of the delta-rule layers
        (each summed over the pass's steps and layers, as their count is):
        the unit's mirror and the process registry."""
        from znicz_tpu.observe import registry

        layers = float(sums["kda_layers"])
        self.kda_counters = {
            "decay_mean": float(sums["kda_decay"]) / layers,
            "beta_mean": float(sums["kda_beta"]) / layers,
            "final_state_rms": float(sums["kda_state_rms"]) / layers,
            "layers": layers / steps}
        # each family by its literal name: tools/check_metric_catalogue.py
        # reads declarations off the source
        gauges = {
            "decay_mean": registry.gauge(
                "znicz_lm_kda_decay_mean",
                "mean over positions, heads, key channels, delta-rule "
                "layers and the last training pass's steps of the state's "
                "decay a position, exp(g) (1: a channel is kept whole; 0: "
                "dropped)", ("unit",)),
            "beta_mean": registry.gauge(
                "znicz_lm_kda_beta_mean",
                "mean over positions, heads, delta-rule layers and the last "
                "training pass's steps of the delta rule's step beta (in "
                "(0, 2) with negative eigenvalues allowed, else (0, 1))",
                ("unit",)),
            "final_state_rms": registry.gauge(
                "znicz_lm_kda_final_state_rms",
                "RMS of a delta-rule layer's state behind a row's last "
                "position, mean over rows, layers and the last training "
                "pass's steps (a carry that is dropped or shortened moves "
                "it)", ("unit",)),
            "layers": registry.gauge(
                "znicz_lm_kda_layers",
                "delta-rule linear-attention layers a step runs, last "
                "training pass", ("unit",))}
        for key, value in self.kda_counters.items():
            gauges[key].labels(unit=self.name).set(value)

    def _publish_terms(self, sums: dict, steps: float) -> None:
        """A finished training pass's named terms, each the mean over its
        steps: the loss's terms of a stack with an MTP module into
        ``loss_terms``, a looped stack's means into ``loop_counters``, all
        into the process registry (:func:`_term_gauges`)."""
        gauges = _term_gauges()
        loss, loop = {}, {}
        for name, total in sums.items():
            value, labels = float(total) / steps, {"unit": self.name}
            if name in ("loss_main", "loss_mtp"):
                loss[name.removeprefix("loss_")] = value
            elif name.startswith("loop_"):
                loop[name.removeprefix("loop_")] = value
                if name.startswith("loop_loss_step"):
                    labels["step"] = name.removeprefix("loop_loss_step")
                    name = "loop_loss_step"
            else:
                continue
            gauges[name].labels(**labels).set(value)
        if loss or loop:
            self.loss_terms, self.loop_counters = loss, loop

    def _publish_moe(self, pairs_a_step: float, load_ratio: float,
                     compact_share: float, tile_fill: float,
                     pairs: float, act_zero_share: float) -> None:
        """A finished pass's routed-expert counters: the unit's mirror
        and the process registry (docs/OBSERVABILITY.md)."""
        from znicz_tpu.observe import registry

        self.moe_counters = {"pairs_held_per_step": pairs_a_step,
                             "load_max_over_mean": load_ratio,
                             "compact_share": compact_share,
                             "tile_fill": tile_fill,
                             "act_zero_share": act_zero_share}
        registry.counter(
            "znicz_lm_moe_pairs_held_total",
            "(token, choice) pairs routed to experts this chip holds",
            ("unit",)).labels(unit=self.name).inc(pairs)
        registry.gauge(
            "znicz_lm_moe_load_max_over_mean",
            "fullest held expert's pairs over the held experts' mean, "
            "averaged over the routed layers and the last class pass",
            ("unit",)).labels(unit=self.name).set(load_ratio)
        registry.gauge(
            "znicz_lm_moe_compact_share",
            "share of the last class pass's routed layer-steps whose "
            "held pairs fitted the compact pairs buffer (the rest took "
            "the full one; none dropped a pair)",
            ("unit",)).labels(unit=self.name).set(compact_share)
        registry.gauge(
            "znicz_lm_moe_tile_fill",
            "held pairs over the row-slots the routed layers' grouped "
            "products visited (a row tile two experts share is visited "
            "twice), averaged over the layers and the last class pass",
            ("unit",)).labels(unit=self.name).set(tile_fill)
        registry.gauge(
            "znicz_lm_moe_act_zero_share",
            "over the pairs routed to held experts, the share of the "
            "experts' hidden entries that a squared ReLU zeroed (0 for "
            "gated experts), averaged over the routed layers and the last "
            "class pass",
            ("unit",)).labels(unit=self.name).set(act_zero_share)

    # -- serving handoff (ISSUE 10) -----------------------------------------
    def export_lm(self, path: str,
                  draft_layers: int | None = None) -> str:
        """Package the trained params as a generative serving artifact
        (``utils/export.py::export_lm``): weights + architecture +
        the loader's charmap, bootable by ``python -m znicz_tpu
        generate`` into the KV-cache decode plane.  The SAME params
        that trained serve — the unified train/serve contract the serve
        plane is built on.

        ``draft_layers=k`` also ships a layer-truncated DRAFT model
        (first k blocks + the shared embedding/head) for speculative
        decoding (ISSUE 12) — the zero-extra-training proposer whose
        logits track the target's."""
        import jax

        from znicz_tpu.utils.export import export_lm

        if self._params is None:
            raise ValueError("export_lm needs an initialized workflow "
                             "(params live on device after xla_init)")
        if self.n_experts:
            raise ValueError("export_lm cannot package an MoE stack "
                             "(KV-cache decode serves dense FFN only)")
        extra = self.arch.mechanisms() if self.arch is not None else []
        if extra:
            raise ValueError(
                f"export_lm cannot package {', '.join(extra)}: serve/ "
                f"decodes the GPT-shaped block only (no convolution state "
                f"beside keys and values, no experts when decoding)")
        params = jax.tree.map(lambda a: np.array(jax.device_get(a)),
                              self._params)
        draft = None
        if draft_layers:
            from znicz_tpu.serve.paged import truncate_draft
            draft = truncate_draft(params, draft_layers)
        charmap = list(getattr(self.loader, "vocab", []) or []) or None
        wf = getattr(self, "workflow", None)
        return export_lm(params, path, heads=self.heads, charmap=charmap,
                         name=getattr(wf, "name", None) or "char_lm",
                         draft_params=draft)

    # -- snapshot support ---------------------------------------------------
    def state_dict(self) -> dict:
        import jax

        if self._params is None:
            return {}
        # a copy: on the CPU device_get hands back a view of the device
        # buffer, which the next step is donated and overwrites
        return {"params": jax.tree.map(
            lambda a: np.array(jax.device_get(a)), self._params)}

    def _check_gpt_snapshot(self, params, restored_vocab: int) -> None:
        if len(params["blocks"]) != self.n_layers or \
                int(params["emb"].shape[1]) != self.d or \
                tuple(params["head"].shape) != (self.d, restored_vocab):
            raise ValueError(
                f"snapshot params (d={params['emb'].shape[1]}, "
                f"{len(params['blocks'])} blocks) do not match this "
                f"workflow (d={self.d}, {self.n_layers} blocks)")
        # the FFN flavor is architecture too: a dense snapshot cannot
        # restore into an MoE workflow (or vice versa), and the expert
        # count must match — the params pytree would otherwise win
        # silently over the configured architecture
        blk0 = params["blocks"][0]
        snap_experts = int(blk0["ew1"].shape[0]) if "ew1" in blk0 else None
        if snap_experts != (self.n_experts or None):
            raise ValueError(
                f"snapshot FFN flavor (n_experts={snap_experts}) does "
                f"not match this workflow (n_experts={self.n_experts})")

    def load_state_dict(self, state: dict) -> None:
        if "params" not in state:
            return
        params = state["params"]
        # architecture validation — the generic snapshot restore checks
        # tree STRUCTURE; shape semantics are this unit's contract:
        restored_vocab = int(params["emb"].shape[0])
        if self.arch_config is not None:
            # every leaf's shape follows from the architecture
            import jax

            from znicz_tpu.parallel.arch import arch_from_config
            from znicz_tpu.parallel.params import param_shapes

            want = param_shapes(arch_from_config(
                self.arch_config, restored_vocab))
            if jax.tree.map(lambda a: tuple(np.shape(a)), params) != want:
                raise ValueError(
                    "snapshot params do not match this workflow's "
                    "architecture (leaf names or shapes differ)")
        else:
            self._check_gpt_snapshot(params, restored_vocab)
        # vocab must match what the loader SERVES NOW — after a restore
        # the loader has adopted the snapshot vocab (CharSequenceLoader
        # snapshots it), so a mismatch means a genuinely different corpus
        live_vocab = int(self.loader.vocab_size) \
            if self.loader is not None else self.vocab_size
        if live_vocab and restored_vocab != live_vocab:
            raise ValueError(
                f"snapshot params carry vocab {restored_vocab} but the "
                f"loader serves vocab {live_vocab} — the corpus does not "
                f"match the snapshot")
        self.vocab_size = restored_vocab
        if self._step is not None:
            # already initialized: only re-place the arrays onto the
            # mesh — the compiled step/eval stay valid
            params = self._place_params(params)
        self._params = params
