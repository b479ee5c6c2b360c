"""``shard_map`` with the replication checker off — one import site for
every user.

The static replication checker (``check_vma``) cannot infer replication
through this codebase's psum-composed update functions, and rejects
out_specs that are in fact correct (the documented escape hatch in the
error message itself is to disable the check).  The real correctness
guard is the test suite's numeric parity coverage:
sharded-vs-replicated equality, mesh-size invariance, and the
snapshot/resume bit-exactness pins all fail loudly if a P() output ever
stops being replicated.

Callers may still pass ``check_vma=`` explicitly; an explicit keyword
overrides the relaxed default.

This module also hosts :func:`quantized_psum` — the ONE opt-in seam
through which the explicit gradient psums (parallel/step.py's fused
step, parallel/transformer.py's sharded step) pick up the quantized
collective codec (parallel/qcomm.py): ``codec=None`` emits a verbatim
``jax.lax.psum``, so the exact path's program is bit-identical to a
build that never imported the codec.
"""

from __future__ import annotations

import functools

import jax

shard_map = functools.partial(jax.shard_map, check_vma=False)


def quantized_psum(tree, axis_name, codec=None, residuals=None):
    """``lax.psum(tree, axis_name)`` with an opt-in quantized wire
    format: -> ``(summed_tree, new_residual_tree)``.

    ``codec=None`` (mode=off) is the EXACT path — one verbatim
    ``jax.lax.psum`` over the tree, ``residuals`` handed back untouched
    — so flipping the flag off reproduces today's program bit for bit.
    With a :class:`~znicz_tpu.parallel.qcomm.Codec`, the tree reduces
    through qcomm.psum_tree (int8/bf16 payload on the wire, f32 local
    sum) and ``residuals`` carries the error-feedback state: pass the
    previous step's residual tree (same structure as ``tree``) and
    persist the returned one."""
    if codec is None:
        return jax.lax.psum(tree, axis_name), residuals
    from znicz_tpu.parallel import qcomm
    return qcomm.psum_tree(tree, axis_name, codec, residuals)


__all__ = ["shard_map", "quantized_psum"]
