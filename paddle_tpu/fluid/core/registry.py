"""Op registry: static registration of op *semantics* as JAX emitters.

TPU-native analog of the reference's OpRegistry/OpInfo machinery
(paddle/framework/op_registry.h:62, REGISTER_OP at :148,
REGISTER_OP_CPU_KERNEL/REGISTER_OP_CUDA_KERNEL at :180-196).  The key design
shift: where the reference registers one hand-written kernel per (op, place,
dtype, layout) and dispatches at runtime (operator.cc:459 -> :485
GetExpectedKernelType), here each op registers ONE pure JAX emitter.  The
executor traces every emitter in a block into a single jaxpr and hands the
whole block to XLA, which does the per-backend lowering, fusion, and layout
assignment that the reference implements by hand (operators/math/*,
data_transform.cc).

Gradients: the reference pairs each op with a hand-written grad op
(REGISTER_OP registers both; grad_op_desc_maker.h emits the grad OpDesc).  We
keep the *desc-level* contract — ``append_backward`` emits real ``*_grad`` ops
into the program — but the default grad emitter derives the math with
``jax.vjp`` over the forward emitter, recomputing the forward inside the grad
op.  XLA CSE/fusion dedupes the recompute inside one compiled block WHERE THE
FORWARD IS XLA OPS, so this costs ~nothing at runtime there while keeping
every op differentiable by construction (no per-op grad kernels to
hand-maintain).  CSE does not merge custom calls: an op whose forward is a
Pallas (Mosaic) kernel runs that kernel a second time inside its generic
grad op (the trace of a Transformer step showed two ``flash_fwd`` calls an
attention op).  Such an op registers a grad emitter of its own
(``<op>_grad``, which preempts the generic one) and, for what that emitter
needs beyond the forward's inputs and the cotangents, a ``grad_maker``:
``fused_attention`` (ops/nn_ops.py) hands its grad op the forward's ``Out``
and row statistics.
"""

from __future__ import annotations

import inspect
from typing import Any, Callable, Dict, List, Optional, Sequence

__all__ = ["OpInfo", "EmitCtx", "register", "primitive", "get_op_info",
           "has_op", "registered_ops", "GRAD_SUFFIX", "grad_var_name",
           "is_grad_op_type", "base_op_type"]

GRAD_SUFFIX = "@GRAD"


def grad_var_name(name: str) -> str:
    return name + GRAD_SUFFIX


def is_grad_op_type(op_type: str) -> bool:
    return op_type.endswith("_grad")


def base_op_type(grad_op_type: str) -> str:
    assert grad_op_type.endswith("_grad")
    return grad_op_type[: -len("_grad")]


class EmitCtx:
    """Per-op emission context handed to every emitter.

    Carries the op's attributes, a derived RNG key (functional analog of the
    reference's per-device curand generators in platform/device_context.h), and
    a hook for lowering sub-blocks (control-flow ops -- the analog of the
    executor recursion in while_op.cc / recurrent_op.cc).
    """

    __slots__ = ("op", "attrs", "rng", "lower_block", "mode", "noted")

    def __init__(self, op, rng=None, lower_block=None, mode="train",
                 noted=None):
        self.op = op
        self.attrs = op.attrs
        self.rng = rng
        self.lower_block = lower_block  # callable(block_idx, env) -> env
        self.mode = mode                # "train" | "infer"
        self.noted = noted              # the step's list of (what, args)

    def attr(self, name: str, default: Any = None) -> Any:
        return self.attrs.get(name, default)

    def note(self, what: str, **args) -> None:
        """Say how this op was lowered, where that was a choice: a tracer
        instant ``lowering/<what>`` (once a trace, nothing a step; under
        the executor's ``executor/compile`` span) and an entry in the
        list of the step being built (``build_step_fn(...).noted``)."""
        from ...observability.tracing import tracer

        tracer().instant(f"lowering/{what}", cat="lowering", **args)
        if self.noted is not None:
            self.noted.append((what, args))


class OpInfo:
    """Registered semantics for one op type."""

    __slots__ = ("type", "emit", "no_grad", "grad_maker", "stop_grad_slots",
                 "doc")

    def __init__(self, type: str, emit: Callable, no_grad: bool = False,
                 grad_maker: Optional[Callable] = None,
                 stop_grad_slots: Sequence[str] = (), doc: str = ""):
        self.type = type
        self.emit = emit                      # (ctx, ins: dict[str, list]) -> dict[str, list]
        self.no_grad = no_grad
        # (op, block) -> {slot: [Variable]}: forward-side inputs of the
        # grad op beyond the forward's own inputs (its outputs, say); may
        # add an output slot to the forward op it is given
        self.grad_maker = grad_maker
        self.stop_grad_slots = tuple(stop_grad_slots)
        self.doc = doc


_REGISTRY: Dict[str, OpInfo] = {}


def register(op_info: OpInfo) -> OpInfo:
    if op_info.type in _REGISTRY:
        raise ValueError(f"op {op_info.type!r} already registered")
    _REGISTRY[op_info.type] = op_info
    return op_info


def get_op_info(op_type: str) -> OpInfo:
    try:
        return _REGISTRY[op_type]
    except KeyError:
        raise KeyError(
            f"op {op_type!r} is not registered; known ops: "
            f"{sorted(_REGISTRY)[:40]}...") from None


def has_op(op_type: str) -> bool:
    return op_type in _REGISTRY


def registered_ops() -> List[str]:
    return sorted(_REGISTRY)


def _parse_slot(spec: str):
    """Slot spec mini-language: "X" required single, "Bias?" optional single,
    "X*" variadic list.  An optional OUTPUT is left unwritten when the
    emitter returns None for it."""
    if spec.endswith("*"):
        return spec[:-1], "list"
    if spec.endswith("?"):
        return spec[:-1], "optional"
    return spec, "single"


def primitive(op_type: str, inputs: Sequence[str] = ("X",),
              outputs: Sequence[str] = ("Out",), no_grad: bool = False,
              stop_grad_slots: Sequence[str] = (), seq_transparent: bool = False,
              grad_maker: Optional[Callable] = None):
    """Decorator: register a function of (ctx, *input_slots) -> output value(s)
    as an op emitter.

    The wrapped function receives one positional arg per input slot (a single
    array, None for missing optionals, or a list for variadic slots) and must
    return one value per output slot (tuple if multiple).  This is the analog
    of REGISTER_OP_*_KERNEL, minus the per-device/dtype explosion.

    ``seq_transparent=True``: if any input is a SeqArray (padded sequence
    batch), the kernel sees only its ``.data`` and outputs are re-wrapped with
    the first input's lengths — how elementwise/activation ops inherit LoD in
    the reference (they copy lod from input to output).
    """
    in_specs = [_parse_slot(s) for s in inputs]
    out_specs = [_parse_slot(s) for s in outputs]

    def deco(fn):
        def emit(ctx: EmitCtx, ins: Dict[str, list]) -> Dict[str, list]:
            from .lod import SeqArray

            args = []
            lengths = None
            for name, kind in in_specs:
                vals = ins.get(name, [])
                if seq_transparent:
                    unwrapped = []
                    for v in vals:
                        if isinstance(v, SeqArray):
                            if lengths is None:
                                lengths = v.lengths
                            unwrapped.append(v.data)
                        else:
                            unwrapped.append(v)
                    vals = unwrapped
                if kind == "list":
                    args.append(list(vals))
                elif kind == "optional":
                    args.append(vals[0] if vals else None)
                else:
                    if not vals:
                        raise ValueError(
                            f"op {op_type}: missing required input slot {name}")
                    args.append(vals[0])
            result = fn(ctx, *args)
            if len(out_specs) == 1:
                result = (result,)
            elif not isinstance(result, tuple):
                raise ValueError(f"op {op_type}: expected tuple of "
                                 f"{len(out_specs)} outputs")
            out = {}
            for (slot, kind), val in zip(out_specs, result):
                if val is None and kind == "optional":
                    continue
                vals = list(val) if isinstance(val, list) else [val]
                if lengths is not None:
                    vals = [SeqArray(v, lengths)
                            if not isinstance(v, SeqArray) else v for v in vals]
                out[slot] = vals
            return out

        info = OpInfo(type=op_type, emit=emit, no_grad=no_grad,
                      stop_grad_slots=stop_grad_slots,
                      grad_maker=grad_maker,
                      doc=inspect.getdoc(fn) or "")
        register(info)
        return fn

    return deco
