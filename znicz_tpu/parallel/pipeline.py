"""Pipeline parallelism over the ``pipe`` mesh axis — GPipe-style
microbatch rotation expressed as one SPMD program (TPU-native extension;
SURVEY.md §3.4 PP row).

All devices run the same traced loop; device ``s`` applies stage ``s``'s
params (stacked stage weights sharded over the pipe axis, leading dim).
Each tick every device hands its activation to the next stage via one
``lax.ppermute`` (neighbor ICI traffic); stage 0 feeds microbatch ``t``,
stage ``S-1`` collects finished microbatch ``t - (S-1)``.  The bubble is
the standard ``S-1`` ticks.

Exactness pin: tests/test_parallel_axes.py::test_pipeline_matches_sequential.

``make_pipeline_step`` is the ``(data, pipe, expert)`` demonstration over it:
GPipe microbatching over ``pipe`` with an expert-parallel MoE block a stage
(``__graft_entry__.py``'s third dry-run leg).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from znicz_tpu.parallel.arch import _default_compute_dtype
from znicz_tpu.parallel.compat import shard_map
from znicz_tpu.parallel.moe import moe_ffn


def pipeline_apply(stage_fn, stage_params_local, xs, n_stages: int,
                   axis_name: str = "pipe"):
    """Run ``n_micro`` microbatches through the stage pipeline.

    - ``stage_fn(params, x) -> y``: one stage's compute; every stage must
      map shape ``(mb, d) -> (mb, d)`` (homogeneous-stage pipeline);
    - ``stage_params_local``: this device's stage params pytree (the
      caller shards a stage-stacked pytree over the pipe axis);
    - ``xs``: ``(n_micro, mb, d)`` microbatches (replicated);
    - ``n_stages``: static pipe-axis size (mesh.shape[axis_name]).
    Returns ``(n_micro, mb, d)``, replicated via the final psum.
    """
    stage = lax.axis_index(axis_name)
    n_micro = xs.shape[0]
    perm_fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def tick(act, t):
        # stage 0 ingests microbatch t (clipped; ticks past the feed window
        # only drain the pipe)
        feed = lax.dynamic_index_in_dim(
            xs, jnp.clip(t, 0, n_micro - 1), axis=0, keepdims=False)
        act = jnp.where(stage == 0, feed, act)
        y = stage_fn(stage_params_local, act)
        # the last stage emits the finished microbatch; others emit zeros
        done = jnp.where(stage == n_stages - 1, y, jnp.zeros_like(y))
        # rotate activations one stage forward (wraparound into stage 0 is
        # overwritten by the next feed)
        return lax.ppermute(y, axis_name, perm_fwd), done

    from znicz_tpu.parallel.mesh import varying
    # initial carry inherits xs's varying axes (e.g. data) and is cast
    # varying over the pipe axis the loop rotates on (scan vma rule)
    act0 = varying(xs[0] * 0.0, axis_name)
    _, emitted = lax.scan(tick, act0,
                          jnp.arange(n_micro + n_stages - 1))
    # microbatch t finishes at tick t + (S-1); gather in feed order, then
    # replicate off the last stage
    outs = emitted[jnp.arange(n_micro) + (n_stages - 1)]
    return lax.psum(outs, axis_name)


# -- dp x pipe x expert configuration ---------------------------------------
def init_moe_pipeline_params(gen, n_stages: int, d: int, ff: int,
                             n_experts: int):
    """Stage-stacked MoE-block params (leading dim = pipe stage)."""
    def w(shape, scale=None):
        scale = scale or 1.0 / np.sqrt(shape[-2])
        return gen.normal(0.0, scale, shape).astype(np.float32)

    return {
        "gate": w((n_stages, d, n_experts)),
        "w1": w((n_stages, n_experts, d, ff)),
        "b1": np.zeros((n_stages, n_experts, ff), np.float32),
        "w2": w((n_stages, n_experts, ff, d)),
        "b2": np.zeros((n_stages, n_experts, d), np.float32),
    }


def moe_pipeline_specs():
    return {k: P("pipe", "expert") if k != "gate" else P("pipe")
            for k in ("gate", "w1", "b1", "w2", "b2")}


def make_pipeline_step(mesh: Mesh, n_experts: int, lr: float = 0.05,
                       compute_dtype=None):
    """-> jitted ``step(params, xs, ys) -> (params, loss)`` on a
    ``(data, pipe, expert)`` mesh: each pipe stage is an expert-parallel
    MoE residual block; xs ``(n_micro, mb, d)`` microbatches (data-sharded
    on mb), ys same shape (regression targets — keeps the demo loss
    self-contained).  Feature/ff sizes flow from the params pytree.
    Mixed precision follows the same recipe as make_train_step: bf16
    compute on accelerators, f32 masters/updates, f32 loss."""
    n_stages = mesh.shape["pipe"]
    ep = mesh.shape["expert"]
    if n_experts % ep:
        raise ValueError(f"expert-axis size {ep} must divide "
                         f"n_experts={n_experts}")
    specs = moe_pipeline_specs()
    cdt = _default_compute_dtype(compute_dtype)

    def stage_fn(p, x):
        y, _ = moe_ffn(x, p["gate"][0], p["w1"][0], p["b1"][0],
                       p["w2"][0], p["b2"][0], jax.nn.gelu, "expert")
        return x + y

    def local_step(params, xs, ys):
        def loss_fn(ps):
            ps = jax.tree.map(lambda w: w.astype(cdt), ps)
            out = pipeline_apply(
                lambda _unused, x: stage_fn(ps, x), None,
                xs.astype(cdt), n_stages, "pipe")
            diff = out.astype(jnp.float32) - ys
            return lax.psum((diff * diff).mean(), "data")

        loss, grads = jax.value_and_grad(loss_fn)(params)
        n_data = lax.psum(1, "data")
        new_params = jax.tree.map(
            lambda w, g: w - lr * g / n_data, params, grads)
        return new_params, loss / n_data

    step = shard_map(
        local_step, mesh=mesh,
        in_specs=(specs, P(None, "data"), P(None, "data")),
        out_specs=(specs, P()))
    return jax.jit(step), specs
