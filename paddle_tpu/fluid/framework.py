"""User-facing graph-building API: Variable / Operator / Block / Program.

Python mirror of the IR, the analog of the reference's
python/paddle/v2/fluid/framework.py (Variable:126, Operator:361, Block:632,
Program:826, Parameter:987, default programs :1045,1056).  Differences driven
by the TPU/XLA design:

* Shape/dtype inference does not call per-op C++ InferShape; it abstractly
  evaluates the op's JAX emitter with ``jax.eval_shape`` — one inference rule
  per op for free, always consistent with the actual lowering.
* Variables may carry a ``lod_level`` (sequence axis); at runtime those lower
  to SeqArray (padded data + lengths) rather than offset-encoded LoD.
* Parameters may carry a sharding annotation (a PartitionSpec-like tuple) —
  the TPU-native replacement for the reference's per-layer device attributes
  (ParallelNeuralNetwork) and pserver block splits.
"""

from __future__ import annotations

import contextlib
from typing import Dict, List, Optional, Sequence

import numpy as np

from . import unique_name
from .core import registry as _registry
from .core.desc import BlockDesc, OpDesc, ProgramDesc, VarDesc
from .core.lod import SeqArray
from .core.registry import EmitCtx, get_op_info
from .core.types import VarType, canonical_dtype

__all__ = [
    "Variable", "Parameter", "Operator", "Block", "Program",
    "default_main_program", "default_startup_program", "program_guard",
    "switch_main_program", "switch_startup_program", "grad_var_name",
]

grad_var_name = _registry.grad_var_name

# Dummy extents used for abstract shape inference in place of dynamic dims.
_DUMMY_BATCH = 13
_DUMMY_TIME = 11

# Ops we skip build-time shape inference for (control flow & IO — their
# emitters need a live block lowerer or runtime-only context).
_NO_INFER_OPS = {"feed", "fetch", "while", "conditional_block", "print",
                 "save", "load", "save_combine", "load_combine"}

# Ops that consume RNG.  Each instance gets a __rng_salt__ attr at build
# time, unique WITHIN ITS PROGRAM; the *_grad op copies the attr, so the
# vjp-recomputed forward (lowering.py) derives the IDENTICAL key — the
# property the reference gets by saving dropout masks (dropout_op.cc), we
# get by key determinism.  The salt counter lives on the Program, NOT in
# a module global: a process-global counter made identically-seeded
# builds depend on every program built before them (different salts ->
# different random init -> different tokens), which is both a
# reproducibility hole and the cross-module test-order flake the PR 12
# note records — and it would poison a content-addressed executable
# cache, since two identical builds would never share a fingerprint.
_RANDOM_OPS = {"dropout", "uniform_random", "gaussian_random",
               "truncated_gaussian_random", "nce", "sampling_id",
               "fused_attention"}


class Variable:
    """A named, typed slot in a Block — mirror of framework.py:126 backed by a
    VarDesc instead of a C++ desc."""

    def __init__(self, block: "Block", name: str,
                 type: str = VarType.DENSE_TENSOR, dtype="float32",
                 shape: Optional[Sequence[int]] = None, lod_level: int = 0,
                 persistable: bool = False, stop_gradient: bool = False):
        self.block = block
        desc = block.desc.vars.get(name)
        if desc is None:
            desc = VarDesc(name=name, type=type, dtype=canonical_dtype(dtype),
                           shape=list(shape) if shape is not None else None,
                           lod_level=lod_level, persistable=persistable,
                           stop_gradient=stop_gradient)
            block.desc.add_var(desc)
        self.desc = desc
        self.op: Optional[Operator] = None  # producer, set by append_op

    # -- desc accessors -----------------------------------------------------
    @property
    def name(self) -> str:
        return self.desc.name

    @property
    def shape(self):
        return tuple(self.desc.shape) if self.desc.shape is not None else None

    @property
    def dtype(self) -> str:
        return self.desc.dtype

    @property
    def lod_level(self) -> int:
        return self.desc.lod_level

    @property
    def type(self) -> str:
        return self.desc.type

    @property
    def persistable(self) -> bool:
        return self.desc.persistable

    @persistable.setter
    def persistable(self, v: bool):
        self.desc.persistable = bool(v)

    @property
    def stop_gradient(self) -> bool:
        return self.desc.stop_gradient

    @stop_gradient.setter
    def stop_gradient(self, v: bool):
        self.desc.stop_gradient = bool(v)

    @property
    def grad_name(self) -> str:
        return grad_var_name(self.name)

    def abstract_value(self):
        """ShapeDtypeStruct (or SeqArray thereof) standing in for this var
        during eval_shape-based inference."""
        return abstract_from_meta(self.shape, self.dtype, self.lod_level,
                                  name=self.name)

    def set_sharding(self, sharding: Optional[Sequence[Optional[str]]]):
        """Mutate the desc-level sharding annotation.  Goes through the
        program version bump so the executor's content-addressed compile
        cache (executor._program_key) sees the change."""
        self.desc.sharding = list(sharding) if sharding is not None else None
        if isinstance(self, Parameter):
            self.sharding = (tuple(sharding) if sharding is not None
                             else None)
        self.block.program._bump_version()

    def __repr__(self):
        return (f"Variable(name={self.name}, shape={self.shape}, "
                f"dtype={self.dtype}, lod_level={self.lod_level})")


class Parameter(Variable):
    """Trainable persistable variable — mirror of framework.py:987, plus a TPU
    sharding annotation (tuple of mesh-axis names or None per dim)."""

    def __init__(self, block, name, shape, dtype="float32", trainable=True,
                 optimize_attr=None, regularizer=None, gradient_clip_attr=None,
                 sharding: Optional[Sequence[Optional[str]]] = None, **kw):
        super().__init__(block, name, dtype=dtype, shape=shape,
                         persistable=True, stop_gradient=not trainable, **kw)
        self.trainable = trainable
        self.optimize_attr = optimize_attr or {"learning_rate": 1.0}
        self.regularizer = regularizer
        self.gradient_clip_attr = gradient_clip_attr
        self.sharding = tuple(sharding) if sharding is not None else None
        if sharding is not None:
            self.desc.sharding = list(sharding)

    def __repr__(self):
        return f"Parameter(name={self.name}, shape={self.shape}, dtype={self.dtype})"


class Operator:
    """Mirror of framework.py:361 — validates slots and runs abstract shape
    inference over the registered emitter (the analog of C++ InferShape +
    VarTypeInference, done once at graph-build time)."""

    def __init__(self, block: "Block", desc: OpDesc):
        self.block = block
        self.desc = desc

    @property
    def type(self) -> str:
        return self.desc.type

    def input(self, slot):
        return self.desc.input(slot)

    def output(self, slot):
        return self.desc.output(slot)

    @property
    def input_names(self):
        return self.desc.input_names()

    @property
    def output_names(self):
        return self.desc.output_names()

    def attr(self, name, default=None):
        return self.desc.attr(name, default)

    def set_attr(self, name, val):
        self.desc.attrs[name] = val
        self.block.program._bump_version()

    @property
    def attrs(self):
        return self.desc.attrs

    def __repr__(self):
        return f"Operator({self.desc!r})"


class Block:
    """Mirror of framework.py:632 backed by a BlockDesc."""

    def __init__(self, program: "Program", desc: BlockDesc):
        self.program = program
        self.desc = desc
        self.vars: Dict[str, Variable] = {}
        self.ops: List[Operator] = []

    @property
    def idx(self) -> int:
        return self.desc.idx

    @property
    def parent_idx(self) -> int:
        return self.desc.parent_idx

    @property
    def parent_block(self) -> Optional["Block"]:
        if self.parent_idx < 0:
            return None
        return self.program.block(self.parent_idx)

    # -- var management ------------------------------------------------------
    def create_var(self, name=None, **kw) -> Variable:
        name = name or unique_name.generate("tmp")
        v = Variable(self, name, **kw)
        self.vars[name] = v
        self.program._bump_version()
        return v

    def create_parameter(self, name=None, shape=None, dtype="float32",
                         **kw) -> Parameter:
        name = name or unique_name.generate("param")
        p = Parameter(self, name, shape=shape, dtype=dtype, **kw)
        self.vars[name] = p
        self.program._bump_version()
        return p

    def var(self, name: str) -> Variable:
        """Lookup in this block, then ancestors (scope-chain semantics of the
        reference's Block::var)."""
        b: Optional[Block] = self
        while b is not None:
            if name in b.vars:
                return b.vars[name]
            b = b.parent_block
        raise KeyError(f"variable {name!r} not found in block {self.idx}")

    def has_var(self, name: str) -> bool:
        try:
            self.var(name)
            return True
        except KeyError:
            return False

    def all_parameters(self) -> List[Parameter]:
        return [v for v in self.vars.values() if isinstance(v, Parameter)]

    # -- op management -------------------------------------------------------
    def append_op(self, type: str, inputs=None, outputs=None, attrs=None,
                  infer_shape: bool = True) -> Operator:
        attrs = dict(attrs or {})
        consumes_rng = type in _RANDOM_OPS
        if type == "fused_attention" and not attrs.get("dropout_rate"):
            consumes_rng = False  # deterministic unless dropout is on
        if consumes_rng and "__rng_salt__" not in attrs:
            attrs["__rng_salt__"] = self.program._next_rng_salt()
        desc = OpDesc(type=type,
                      inputs=_names_dict(inputs),
                      outputs=_names_dict(outputs),
                      attrs=attrs)
        self.desc.append_op(desc)
        op = Operator(self, desc)
        self.ops.append(op)
        out_vars = _vars_dict(outputs)
        for vs in out_vars.values():
            for v in vs:
                v.op = op
        if infer_shape and type not in _NO_INFER_OPS:
            self._infer_op(desc, _vars_dict(inputs), out_vars)
        self.program._bump_version()
        return op

    def prepend_op(self, type: str, inputs=None, outputs=None, attrs=None,
                   infer_shape: bool = True) -> Operator:
        op = self.append_op(type, inputs, outputs, attrs, infer_shape)
        self.desc.ops.remove(op.desc)
        self.desc.prepend_op(op.desc)
        self.ops.remove(op)
        self.ops.insert(0, op)
        return op

    def _infer_op(self, desc: OpDesc, in_vars, out_vars) -> None:
        """Abstractly evaluate the emitter to fill output VarDescs."""
        import jax

        info = get_op_info(desc.type)
        abstract_ins = {}
        batch_dyn = False
        try:
            for slot, vs in in_vars.items():
                abstract_ins[slot] = [v.abstract_value() for v in vs]
                batch_dyn = batch_dyn or any(
                    v.shape and v.shape[0] == -1 for v in vs)
        except ValueError as e:
            if _STRICT_INFER:
                raise RuntimeError(
                    f"shape inference failed for op {desc.type}: {e}") from e
            return

        def f(ins):
            ctx = EmitCtx(desc, rng=jax.random.key(0))
            return info.emit(ctx, ins)

        try:
            out_abs = jax.eval_shape(f, abstract_ins)
        except Exception as e:  # inference is advisory, like reference batch dims
            if _STRICT_INFER:
                raise RuntimeError(
                    f"shape inference failed for op {desc.type}: {e}") from e
            return
        for slot, vals in out_abs.items():
            for var, av in zip(out_vars.get(slot, []), vals):
                red = reduce_abstract(av)
                if red is None:
                    continue  # opaque value (RankTable, TensorArray, ...)
                shape, dt, lod = red
                var.desc.lod_level = (max(var.desc.lod_level, lod)
                                      if lod else 0)
                if batch_dyn and shape and shape[0] == _DUMMY_BATCH:
                    shape[0] = -1
                var.desc.shape = shape
                var.desc.dtype = canonical_dtype(dt)


def abstract_from_meta(shape, dtype: str, lod_level: int = 0,
                       name: str = "<var>"):
    """ShapeDtypeStruct (or SeqArray/NestedSeqArray) from recorded var
    metadata — dummy extents for dynamic dims, int64 narrowed to the
    runtime's int32.  The ONE encoding shared by build-time inference
    (Variable.abstract_value) and the analyzer's shape re-check
    (analysis/passes.py); keeping a single copy is what guarantees the
    re-check re-runs exactly the recorded procedure."""
    import jax

    if shape is None:
        raise ValueError(f"variable {name} has no shape")
    shape = [(_DUMMY_BATCH if d == -1 else d) for d in shape]
    np_dt = np.int32 if dtype == "int64" else dtype
    if lod_level >= 2:
        from .core.lod import NestedSeqArray

        data = jax.ShapeDtypeStruct(
            (shape[0], _DUMMY_TIME, _DUMMY_TIME, *shape[1:]), np_dt)
        outer = jax.ShapeDtypeStruct((shape[0],), np.int32)
        inner = jax.ShapeDtypeStruct((shape[0], _DUMMY_TIME), np.int32)
        return NestedSeqArray(data, outer, inner)
    if lod_level > 0:
        data = jax.ShapeDtypeStruct((shape[0], _DUMMY_TIME, *shape[1:]),
                                    np_dt)
        lens = jax.ShapeDtypeStruct((shape[0],), np.int32)
        return SeqArray(data, lens)
    return jax.ShapeDtypeStruct(tuple(shape), np_dt)


def reduce_abstract(av):
    """Collapse an abstract output value to its recorded-desc form:
    ``(shape, dtype_name, lod_level)`` — dropping the dummy time axes a
    SeqArray/NestedSeqArray carries — or None for opaque values
    (RankTable, TensorArray, ...).  The inverse-direction twin of
    ``abstract_from_meta``, shared by _infer_op and the analyzer."""
    from .core.lod import NestedSeqArray

    if isinstance(av, NestedSeqArray):
        dshape = list(av.data.shape)
        return [dshape[0]] + dshape[3:], np.dtype(av.data.dtype).name, 2
    if isinstance(av, SeqArray):
        dshape = list(av.data.shape)
        return [dshape[0]] + dshape[2:], np.dtype(av.data.dtype).name, 1
    if hasattr(av, "shape") and hasattr(av, "dtype"):
        return list(av.shape), np.dtype(av.dtype).name, 0
    return None


_STRICT_INFER = False


@contextlib.contextmanager
def strict_shape_inference():
    global _STRICT_INFER
    old, _STRICT_INFER = _STRICT_INFER, True
    try:
        yield
    finally:
        _STRICT_INFER = old


def _names_dict(d) -> Dict[str, List[str]]:
    out = {}
    for slot, vs in (d or {}).items():
        if vs is None:
            continue
        if not isinstance(vs, (list, tuple)):
            vs = [vs]
        out[slot] = [v.name if isinstance(v, Variable) else str(v) for v in vs]
    return out


def _vars_dict(d) -> Dict[str, List[Variable]]:
    out = {}
    for slot, vs in (d or {}).items():
        if vs is None:
            continue
        if not isinstance(vs, (list, tuple)):
            vs = [vs]
        out[slot] = [v for v in vs if isinstance(v, Variable)]
    return out


class Program:
    """Mirror of framework.py:826 — a ProgramDesc plus Python Block wrappers,
    with clone/prune/inference_optimize capabilities."""

    def __init__(self):
        self.desc = ProgramDesc()
        self.blocks: List[Block] = [Block(self, self.desc.global_block())]
        self._current_block_idx = 0
        self._version = 0
        self._seed: Optional[int] = None  # program-level RNG seed override
        self._rng_salt = 0                # per-program __rng_salt__ counter

    # -- versioning (compile-cache key support) ------------------------------
    def _bump_version(self):
        self._version += 1

    def _next_rng_salt(self) -> int:
        """Next per-program RNG salt — deterministic for a given build
        sequence, so two identical builds serialize byte-identically."""
        self._rng_salt += 1
        return self._rng_salt

    @property
    def version(self) -> int:
        return self._version

    # -- block management ----------------------------------------------------
    def block(self, idx: int) -> Block:
        return self.blocks[idx]

    def global_block(self) -> Block:
        return self.blocks[0]

    def current_block(self) -> Block:
        return self.blocks[self._current_block_idx]

    def create_block(self) -> Block:
        parent = self._current_block_idx
        bd = self.desc.append_block(parent)
        b = Block(self, bd)
        self.blocks.append(b)
        self._current_block_idx = b.idx
        return b

    def rollback(self):
        self._current_block_idx = self.current_block().parent_idx

    # -- serialization & cloning --------------------------------------------
    def to_string(self) -> str:
        import json

        return json.dumps(self.desc.to_dict(), indent=2)

    def serialize_to_string(self) -> bytes:
        return self.desc.serialize_to_string()

    @classmethod
    def parse_from_string(cls, data: bytes) -> "Program":
        p = cls()
        p._load_desc(ProgramDesc.parse_from_string(data))
        return p

    def _load_desc(self, desc: ProgramDesc):
        self.desc = desc
        self.blocks = []
        for bd in desc.blocks:
            b = Block(self, bd)
            for name, vd in bd.vars.items():
                v = Variable(b, name)
                b.vars[name] = v
            for od in bd.ops:
                b.ops.append(Operator(b, od))
            self.blocks.append(b)
        self._current_block_idx = 0
        # resume the per-program salt counter past every deserialized
        # salt: an op appended AFTER the load must never collide with
        # (= derive the same RNG stream as) an existing random op
        self._rng_salt = max(
            (int(od.attrs["__rng_salt__"])
             for bd in desc.blocks for od in bd.ops
             if "__rng_salt__" in od.attrs), default=0)
        self._bump_version()

    def clone(self, for_test: bool = False) -> "Program":
        """Deep-copy via serialization (reference Program.clone at
        framework.py:893).  ``for_test=True`` flips is_test on ops that behave
        differently at inference (dropout, batch_norm) — the analog of
        inference_optimize."""
        p = Program.parse_from_string(self.serialize_to_string())
        # preserve Parameter-ness (class info is not in the desc wire format)
        for b_src, b_dst in zip(self.blocks, p.blocks):
            for name, v in b_src.vars.items():
                if isinstance(v, Parameter):
                    pv = Parameter.__new__(Parameter)
                    pv.block = b_dst
                    pv.desc = b_dst.desc.vars[name]
                    pv.op = None
                    pv.trainable = v.trainable
                    pv.optimize_attr = v.optimize_attr
                    pv.regularizer = v.regularizer
                    pv.gradient_clip_attr = v.gradient_clip_attr
                    pv.sharding = v.sharding
                    b_dst.vars[name] = pv
        if for_test:
            for b in p.blocks:
                for op in b.ops:
                    if "is_test" in _TEST_SENSITIVE_OPS.get(op.type, ()):
                        op.desc.attrs["is_test"] = True
        p._seed = self._seed
        return p

    @property
    def random_seed(self):
        return self._seed

    @random_seed.setter
    def random_seed(self, seed):
        self._seed = seed

    def analyze(self, level: str = "full", fetch_list=None,
                passes=None, options=None):
        """Run the static analyzer (fluid/analysis) over this program —
        dataflow verification, grad-graph lint, sharding/donation safety,
        and (at ``level="full"``) abstract shape/dtype re-checking against
        the recorded descs.  ``level="cost"`` instead runs the static
        cost family (peak-HBM planner, roofline estimate, recompile-
        hazard lint, comms estimator); ``options`` feeds those passes
        (assume_batch, chip, budget_bytes, batch/time_buckets,
        mesh_axes, dcn_axes) and their structured output lands in the
        returned report's ``.reports``.  Returns a ``Diagnostics``
        report; pass ``fetch_list`` (vars or names you intend to read)
        so dead-code findings reflect real intent."""
        from .analysis import analyze_program

        return analyze_program(self, level=level, fetch=fetch_list,
                               passes=passes, options=options)

    def list_vars(self):
        for b in self.blocks:
            yield from b.vars.values()

    def __repr__(self):
        nops = sum(len(b.ops) for b in self.blocks)
        return f"Program(blocks={len(self.blocks)}, ops={nops})"


# ops whose behavior depends on train/test mode, and via which attr
_TEST_SENSITIVE_OPS = {
    "dropout": ("is_test",),
    "batch_norm": ("is_test",),
    "fused_attention": ("is_test",),
    # with its forward: the two agree on dropout, whose mask they share
    "fused_attention_grad": ("is_test",),
}


_main_program = Program()
_startup_program = Program()


def default_main_program() -> Program:
    return _main_program


def default_startup_program() -> Program:
    return _startup_program


def switch_main_program(program: Program) -> Program:
    global _main_program
    old, _main_program = _main_program, program
    return old


def switch_startup_program(program: Program) -> Program:
    global _startup_program
    old, _startup_program = _startup_program, program
    return old


@contextlib.contextmanager
def program_guard(main_program: Program, startup_program: Optional[Program] = None):
    """Analog of fluid.program_guard."""
    old_main = switch_main_program(main_program)
    old_startup = None
    if startup_program is not None:
        old_startup = switch_startup_program(startup_program)
    try:
        yield
    finally:
        switch_main_program(old_main)
        if old_startup is not None:
            switch_startup_program(old_startup)
