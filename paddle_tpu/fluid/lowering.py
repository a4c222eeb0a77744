"""Block -> XLA lowering.

This module replaces the reference's entire runtime dispatch path:
``Executor::Run`` walking ops one-by-one (paddle/framework/executor.cc:77,
per-op loop at :116-138), ``OperatorWithKernel::Run`` kernel selection
(paddle/framework/operator.cc:459,485) and the data-transform glue
(data_transform.cc).  Instead of interpreting the block per step, we trace
every op's JAX emitter once into a single function and hand the whole block to
XLA — one fused TPU executable per (program, shapes) signature; ops dissolve
into the XLA graph, so there is no per-op launch overhead, no intermediate
HBM round-trips XLA doesn't choose, and collectives/sharding compose with the
math under one SPMD partitioner.

Gradient ops (``*_grad``) without a custom emitter are lowered generically via
``jax.vjp`` over the forward emitter (see core/registry.py for why this is
sound, and fast where XLA's CSE merges the recomputed forward with the
forward op's: it does for XLA ops, it does NOT for custom calls, so an op
whose forward is a Pallas kernel brings a grad emitter of its own, as
``fused_attention`` does).

RNG: each random op carries a build-time ``__rng_salt__`` attr; its key is
``fold_in(step_key, salt)``.  Grad ops inherit the salt, so a vjp-recomputed
dropout mask is bit-identical to the forward one — the property the reference
gets by saving the mask tensor (dropout_op.cc) we get by key determinism.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

from .core.desc import OpDesc, ProgramDesc
from .core.lod import SeqArray
from .core.registry import (EmitCtx, GRAD_SUFFIX, base_op_type, get_op_info,
                            has_op, is_grad_op_type)

__all__ = ["run_block_ops", "build_step_fn", "HOST_OPS"]

# ops executed host-side by the Executor, never traced
HOST_OPS = {"save", "load", "save_combine", "load_combine"}
# pure marker ops (wired by the executor's feed/fetch handling)
MARKER_OPS = {"feed", "fetch"}


def _op_rng(op: OpDesc, idx: int, step_key):
    salt = op.attr("__rng_salt__", None)
    return jax.random.fold_in(step_key, salt if salt is not None else idx)


def _gather_inputs(op: OpDesc, env: Dict[str, Any]) -> Dict[str, list]:
    ins: Dict[str, list] = {}
    for slot, names in op.inputs.items():
        vals = []
        for n in names:
            if not n:
                continue
            if n not in env:
                raise KeyError(
                    f"op {op.type}: input {slot}={n!r} not materialized; "
                    f"known vars: {sorted(env)[:20]}...")
            vals.append(env[n])
        if vals:
            ins[slot] = vals
    return ins


def _scatter_outputs(op: OpDesc, outs: Dict[str, list], env: Dict[str, Any]):
    for slot, names in op.outputs.items():
        vals = outs.get(slot, [])
        for n, v in zip(names, vals):
            if n:
                env[n] = v


def _emit_generic_grad(ctx: EmitCtx, op: OpDesc, ins: Dict[str, list]):
    """Lower a ``*_grad`` op by vjp over the forward emitter.

    The reference hand-writes every grad kernel (REGISTER_OP pairs each op
    with its grad, op_registry.h:148); here the adjoint is derived.  Forward
    input slots come through under their original names; cotangents under
    ``<OutSlot>@GRAD``; requested gradients go out under ``<InSlot>@GRAD``.
    Missing cotangent slots are treated as zero by exclusion from the vjp
    output selection.
    """
    base = base_op_type(op.type)
    info = get_op_info(base)
    primals = {s: v for s, v in ins.items() if not s.endswith(GRAD_SUFFIX)}
    cotangents = {s[: -len(GRAD_SUFFIX)]: v for s, v in ins.items()
                  if s.endswith(GRAD_SUFFIX)}

    # reconstruct the forward op's slot->var-name map: control-flow emitters
    # (while/recurrent/conditional_block) read input NAMES off the desc to
    # seed their sub-block environments
    fwd_inputs = {s: names for s, names in op.inputs.items()
                  if not s.endswith(GRAD_SUFFIX)}
    fwd_op = OpDesc(base, fwd_inputs, {}, dict(op.attrs))
    grad_slot_order = sorted(cotangents)

    def fwd_selected(p):
        fctx = EmitCtx(fwd_op, rng=ctx.rng, lower_block=ctx.lower_block,
                       mode=ctx.mode, noted=ctx.noted)
        outs = info.emit(fctx, p)
        sel = []
        for slot in grad_slot_order:
            for v in outs.get(slot, []):
                sel.append(v.data if isinstance(v, SeqArray) else v)
        return sel

    primals_out, vjp_fn = jax.vjp(fwd_selected, primals)
    cts = []
    for v, o in zip(
            (v for slot in grad_slot_order for v in cotangents[slot]),
            primals_out):
        c = v.data if isinstance(v, SeqArray) else v
        # mixed precision (bf16 activations, f32 master weights) can hand
        # back an upcast cotangent; vjp transpose rules require the
        # forward output's dtype exactly
        if hasattr(c, "dtype") and c.dtype != o.dtype:
            c = c.astype(o.dtype)
        cts.append(c)
    grads = vjp_fn(cts)[0]

    out: Dict[str, list] = {}
    for slot, names in op.outputs.items():
        assert slot.endswith(GRAD_SUFFIX), (op.type, slot)
        fwd_slot = slot[: -len(GRAD_SUFFIX)]
        gvals = grads.get(fwd_slot, [])
        fixed = []
        for primal, g in zip(primals.get(fwd_slot, []), gvals):
            fixed.append(_fix_grad(primal, g))
        out[slot] = fixed
    return out


def _fix_grad(primal, g):
    """Clean up vjp artifacts: float0 tangents for int primals -> zeros;
    SeqArray grads inherit the primal's lengths."""
    if isinstance(primal, SeqArray):
        gd = g.data if isinstance(g, SeqArray) else g
        gd = _fix_grad(primal.data, gd)
        return SeqArray(gd, primal.lengths)
    if g is None or (hasattr(g, "dtype") and g.dtype == jax.dtypes.float0):
        return jnp.zeros_like(primal)
    return g


def run_block_ops(desc: ProgramDesc, block_idx: int, env: Dict[str, Any],
                  step_key, mode: str = "train",
                  noted: Optional[list] = None) -> Dict[str, Any]:
    """Trace every op of a block into the caller's env (the in-trace analog of
    the executor loop at executor.cc:116-138).  ``noted`` receives what
    emitters say of their lowering (``EmitCtx.note``)."""
    block = desc.block(block_idx)

    def lower_sub(idx: int, sub_env: Dict[str, Any]) -> Dict[str, Any]:
        return run_block_ops(desc, idx, sub_env, step_key, mode, noted)

    for idx, op in enumerate(block.ops):
        if op.type in MARKER_OPS or op.type in HOST_OPS:
            continue
        ins = _gather_inputs(op, env)
        ctx = EmitCtx(op, rng=_op_rng(op, idx, step_key),
                      lower_block=lower_sub, mode=mode, noted=noted)
        if has_op(op.type):
            outs = get_op_info(op.type).emit(ctx, ins)
        elif is_grad_op_type(op.type) and has_op(base_op_type(op.type)):
            outs = _emit_generic_grad(ctx, op, ins)
        else:
            raise KeyError(f"no emitter for op type {op.type!r}")
        _scatter_outputs(op, outs, env)
    return env


def build_step_fn(desc: ProgramDesc, block_idx: int,
                  feed_names: Sequence[str], state_in: Sequence[str],
                  state_out: Sequence[str], fetch_names: Sequence[str],
                  mode: str = "train") -> Callable:
    """Build the pure function for one executor step:

        (feeds, state, rng_bits) -> (fetches, new_state)

    jit-compiled by the Executor; `state` carries every persistable the block
    reads (parameters, accumulators, LR) and `new_state` returns EVERY state
    entry (updated or passed through) so the state dict can be buffer-donated:
    unchanged entries alias their donated inputs for free, and the scope is
    always left holding live buffers.  This is the functional replacement for
    in-place Scope mutation (scope.h:38).

    ``rng_bits`` is an int32[2] (seed, step) from which the step key is
    derived *inside* the computation — no host-side key splitting per step.

    ``step.noted`` lists what the emitters said of their lowering
    (``EmitCtx.note``: ``(what, args)``), filled as the step is traced.
    """
    feed_names = tuple(feed_names)
    state_in = tuple(state_in)
    state_out = tuple(dict.fromkeys(tuple(state_in) + tuple(state_out)))
    fetch_names = tuple(fetch_names)

    def step(feeds: Dict[str, Any], state: Dict[str, Any], rng_bits):
        step_key = jax.random.fold_in(jax.random.key(rng_bits[0]), rng_bits[1])
        env: Dict[str, Any] = {}
        env.update(state)
        env.update(feeds)
        env = run_block_ops(desc, block_idx, env, step_key, mode,
                            step.noted)
        fetches = [env[n] for n in fetch_names]
        new_state = {n: env[n] for n in state_out if n in env}
        return fetches, new_state

    # the executable's name in a profile (``jit_train_step``): by mode, so
    # that a trace says which step ran and a refactor renames nothing
    step.__name__ = f"{mode}_step"
    step.noted = []
    return step
