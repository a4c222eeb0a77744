"""shardprop — whole-program SPMD sharding inference over the desc.

The reference's DistributeTranspiler *rewrites* a program for a fixed
cluster before anything runs (distribute_transpiler.py:82); the GSPMD
world instead annotates a handful of vars (params, feeds) and lets the
partitioner infer the rest at compile time.  A pod compile is far too
expensive to be the first place a bad sharding plan is discovered, so
this pass re-implements the *propagation* half of that inference
statically: given only the per-dim mesh-axis annotations
(``VarDesc.sharding``) and a mesh spec, it walks the shared
``ProgramView`` dataflow in program order and infers a PartitionSpec
for every intermediate var in every block.

Per-op propagation rules register like shape/cost rules
(``@prop_rule("mul", ...)``).  The core algebra is GSPMD's:

* a matmul-family contraction over a sharded dim yields a *partial
  sum* — the all-reduce is materialized at the producing op (XLA
  attaches it to the dot's source location, which is what
  ``Executor.collective_analysis`` measures);
* elementwise/broadcast ops align operand specs dim-by-dim;
* reshape/transpose track axes through dim regrouping;
* ``*_grad`` ops get the transposed rule for free: the grad of var V
  adopts V's forward spec, and any mesh axis carried by the incoming
  output-grads that the target spec does not contain becomes a partial
  sum (this is exactly the dp grad-sync all-reduce and the
  tensor-parallel backward all-reduce, derived rather than special-cased).

Findings (all with exact block/op#/slot coordinates):

* ``shard/resharding-hazard`` — a consumer forces an implicit
  all-gather / all-to-all (priced in bytes via comms.py's wire rules);
* ``shard/replicated-giant`` — a persistable above a byte threshold
  left fully replicated while a model axis exists;
* ``shard/partial-sum-unreduced`` — a contracted-dim partial product
  escapes its block or reaches a fetch without its all-reduce;
* ``shard/dp-grad-divergence`` — a param updated from tensors not
  identically sharded across the batch (dp) axis: silent replica drift;
* ``shard/unregistered-prop-rule`` — an op with sharded inputs but no
  propagation rule (mirrors cost.py's unregistered-cost-rule).

The inferred collective graph (op coordinate, HLO kind, payload bytes,
ICI-vs-DCN tier) is attached to ``Diagnostics.reports["shardprop"]``
and becomes the comms estimator's input instead of its heuristic scan;
``compare_collectives`` is the differential gate against
``Executor.collective_analysis`` on compiled virtual-mesh programs.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from .cost import var_bytes
from .dataflow import CONTROL_FLOW_OPS, HOST_IO_OPS, ProgramView
from .diagnostics import ERROR, INFO, WARNING, Diagnostics, Finding

__all__ = ["prop_rule", "has_prop_rule", "PROP_RULES",
           "PROPAGATION_OPAQUE", "infer_sharding", "ShardPropResult",
           "shardprop_pass", "compare_collectives",
           "REPLICATED_GIANT_BYTES_DEFAULT"]

# default threshold for shard/replicated-giant (a fully replicated
# persistable this large on a model-axis mesh is almost always a bug)
REPLICATED_GIANT_BYTES_DEFAULT = 256 << 20

# HLO collective kinds (the vocabulary collective_analysis measures)
ALL_REDUCE = "all-reduce"
ALL_GATHER = "all-gather"
REDUCE_SCATTER = "reduce-scatter"
ALL_TO_ALL = "all-to-all"

# ops the walk skips outright: host IO boundary + the executor's own
# feed/fetch plumbing (they move values, never repartition them)
_SKIP_OPS = HOST_IO_OPS | {"feed", "fetch", "print", "assert"}

# ---------------------------------------------------------------------------
# rule registry — keyed by op type, like shape/cost rules
# ---------------------------------------------------------------------------

PROP_RULES: Dict[str, Callable] = {}

# op families that legitimately have *no* propagation rule: their
# outputs carry no stable dim correspondence to any input (lod/index
# bookkeeping, host-side metrics).  Listed explicitly so the rule-sweep
# test can insist every cost-modelled op is either ruled or opaque.
PROPAGATION_OPAQUE = frozenset({
    "accuracy",          # host metric triple; handled as reduce-all below
})


def prop_rule(*op_types: str):
    def deco(fn):
        for t in op_types:
            PROP_RULES[t] = fn
        return fn
    return deco


def has_prop_rule(op_type: str) -> bool:
    """True when ``op_type`` propagates: a direct rule, the generic
    transposed ``*_grad`` rule, or an explicit opaque listing."""
    if op_type in PROP_RULES or op_type in PROPAGATION_OPAQUE:
        return True
    if op_type.endswith("_grad"):
        return True        # generic transposed rule (derived from forward)
    return False


# ---------------------------------------------------------------------------
# result type
# ---------------------------------------------------------------------------

class ShardPropResult:
    """Inferred specs + collective graph + findings for one program."""

    __slots__ = ("axis_sizes", "dcn_axes", "assume_batch", "collectives",
                 "var_specs", "findings", "annotated_vars")

    def per_kind(self) -> Dict[str, Dict[str, float]]:
        out: Dict[str, Dict[str, float]] = {}
        for c in self.collectives:
            d = out.setdefault(c["hlo_kind"],
                               {"count": 0, "payload_bytes": 0.0})
            d["count"] += 1
            d["payload_bytes"] += c["payload_bytes"]
        return out

    @property
    def total_payload_bytes(self) -> float:
        return sum(c["payload_bytes"] for c in self.collectives)

    def to_dict(self) -> Dict[str, Any]:
        sharded = sum(1 for s in self.var_specs.values()
                      if any(a for a in s))
        return {"mesh_axes": dict(self.axis_sizes),
                "dcn_axes": sorted(self.dcn_axes),
                "assume_batch": self.assume_batch,
                "collectives": list(self.collectives),
                "per_kind": self.per_kind(),
                "total_payload_bytes": self.total_payload_bytes,
                "annotated_vars": self.annotated_vars,
                "sharded_vars": sharded}


def compare_collectives(predicted: Dict[str, Dict],
                        measured: Dict[str, Dict]) -> Dict[str, Any]:
    """Differential gate: shardprop's per-kind collective tally vs the
    one ``Executor.collective_analysis`` measured from compiled HLO.
    ``match`` demands op-for-op agreement — equal counts AND equal
    payload bytes per kind (rel_err 0.0 is the acceptance bar)."""
    kinds = sorted(set(predicted) | set(measured))
    per_kind, rel_err, match = {}, 0.0, True
    for k in kinds:
        p = predicted.get(k, {"count": 0, "payload_bytes": 0.0})
        m = measured.get(k, {"count": 0, "payload_bytes": 0.0})
        pb, mb = float(p["payload_bytes"]), float(m["payload_bytes"])
        err = abs(pb - mb) / max(abs(mb), 1.0)
        rel_err = max(rel_err, err)
        ok = int(p["count"]) == int(m["count"]) and pb == mb
        match = match and ok
        per_kind[k] = {"predicted_count": int(p["count"]),
                       "measured_count": int(m["count"]),
                       "predicted_bytes": pb, "measured_bytes": mb,
                       "rel_err": err, "match": ok}
    return {"per_kind": per_kind, "rel_err": rel_err, "match": match}


# ---------------------------------------------------------------------------
# spec helpers
# ---------------------------------------------------------------------------

def _axes_of(spec: Tuple) -> set:
    return {a for a in (spec or ()) if a}


def _fit_spec(spec: Tuple, in_shape, out_shape) -> Tuple:
    """Carry axes dim-by-dim onto an output of possibly different rank:
    an axis survives only where the dim extent is unchanged (dynamic -1
    matches dynamic -1); new/changed dims come out replicated."""
    if out_shape is None:
        return tuple(spec or ())
    out = [None] * len(out_shape)
    if spec and in_shape is not None:
        for i in range(min(len(spec), len(in_shape), len(out_shape))):
            if spec[i] and in_shape[i] == out_shape[i]:
                out[i] = spec[i]
    elif spec:
        for i in range(min(len(spec), len(out_shape))):
            out[i] = spec[i]
    return tuple(out)


def _dim_groups(src: Sequence[int], dst: Sequence[int]):
    """Two-pointer factor grouping between a reshape's recorded in/out
    shapes: yields (src_dims, dst_dims) lists with equal products.
    Dynamic dims (-1/None) are replaced by a sentinel prime so they can
    only ever match each other.  Returns None when the shapes don't
    factor cleanly (axis tracking gives up, replicated)."""
    big = 999983
    a = [big if d is None or d < 0 else max(1, int(d)) for d in src]
    b = [big if d is None or d < 0 else max(1, int(d)) for d in dst]
    groups, i, j = [], 0, 0
    while i < len(a) or j < len(b):
        gi, gj = [], []
        pi = pj = 1
        while True:
            if pi == pj and gi and gj:
                break
            if pi <= pj and i < len(a):
                pi *= a[i]
                gi.append(i)
                i += 1
            elif j < len(b):
                pj *= b[j]
                gj.append(j)
                j += 1
            else:
                break
        if pi != pj:
            return None
        groups.append((gi, gj))
    return groups


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

class _Engine:
    def __init__(self, view: ProgramView, sizes: Dict[str, int],
                 dcn_axes: set, assume_batch: int, fetch: Sequence[str],
                 giant_bytes: int):
        self.view = view
        self.sizes = {a: int(n) for a, n in sizes.items()}
        self.dcn_axes = dcn_axes
        self.assume_batch = max(1, int(assume_batch))
        self.fetch = set(fetch or ())
        self.giant_bytes = giant_bytes
        # (owner_block, name) -> spec tuple; partials never persist —
        # they materialize (or error) at the producing op
        self.states: Dict[Tuple[int, str], Tuple] = {}
        self.collectives: List[Dict] = []
        self.findings: List[Finding] = []
        self.annotated = 0
        self._warned: set = set()

    # -- mesh ---------------------------------------------------------------

    def axis_size(self, ax: str) -> int:
        return self.sizes.get(ax, 2)

    def batch_axes(self) -> List[str]:
        from .comms import BATCH_AXES
        return [a for a in self.sizes if a in BATCH_AXES
                and self.sizes[a] > 1]

    def model_axes(self) -> List[str]:
        from .comms import BATCH_AXES
        return [a for a in self.sizes if a not in BATCH_AXES
                and self.sizes[a] > 1]

    # -- states -------------------------------------------------------------

    def _key(self, bidx: int, name: str) -> Tuple[int, str]:
        owner = self.view.owner_block(bidx, name)
        return (bidx if owner is None else owner, name)

    def spec(self, bidx: int, name: str) -> Tuple:
        key = self._key(bidx, name)
        if key in self.states:
            return self.states[key]
        vd = self.view.visible_var(bidx, name)
        rank = len(vd.shape) if vd is not None and vd.shape is not None \
            else 0
        return (None,) * rank

    def shape(self, bidx: int, name: str):
        vd = self.view.visible_var(bidx, name)
        return None if vd is None else vd.shape

    def norm_annotation(self, vd) -> Optional[Tuple]:
        """Mirror of parallel.mesh.state_sharding's static half: keep an
        annotated axis only where the dim extent divides it; a deferred
        ``ax?`` marker binds to the first divisible dim, preferring the
        dim it was written on.  Axes of extent <= 1 vanish."""
        sh = getattr(vd, "sharding", None)
        if sh is None:
            return None
        shape = vd.shape or ()
        spec: List[Optional[str]] = [None] * len(sh)
        deferred: List[Tuple[int, str]] = []

        def divides(dim_idx: int, n: int) -> bool:
            if dim_idx >= len(shape):
                return False
            d = shape[dim_idx]
            if d is None or d < 0:
                # dynamic dim: assume the runtime honors the annotation
                return True
            return d % n == 0

        for i, ax in enumerate(sh):
            if not ax:
                continue
            if ax.endswith("?"):
                deferred.append((i, ax[:-1]))
                continue
            n = self.axis_size(ax)
            if n > 1 and divides(i, n):
                spec[i] = ax
        for i, ax in deferred:
            n = self.axis_size(ax)
            if n <= 1 or ax in spec:
                continue
            for j in [i] + [k for k in range(len(sh)) if k != i]:
                if spec[j] is None and divides(j, n):
                    spec[j] = ax
                    break
        return tuple(spec)

    # -- payloads -----------------------------------------------------------

    def payload(self, bidx: int, name: str, spec: Tuple) -> float:
        """Per-shard bytes of ``name`` under ``spec`` — full logical
        bytes (assume_batch substituted for dynamic dims, like
        cost.var_bytes) divided by the extents of the sharded dims."""
        vd = self.view.visible_var(bidx, name)
        full, _ = var_bytes(vd, self.assume_batch)
        if not full:
            return 0.0
        shape = vd.shape or ()
        div = 1
        for i, ax in enumerate(spec or ()):
            if not ax or i >= len(shape):
                continue
            n = self.axis_size(ax)
            d = shape[i]
            if d is None or d < 0:
                d = self.assume_batch if i == 0 else 1
            if n > 1 and d % n == 0:
                div *= n
        return float(full // div)

    # -- emission -----------------------------------------------------------

    def record(self, kind: str, axis: str, payload: float, bidx: int,
               op, grad: bool = False) -> None:
        self.collectives.append({
            "axis": axis, "hlo_kind": kind,
            "kind": f"{kind}({'grad-sync' if grad else 'inferred'})",
            "payload_bytes": float(payload),
            "at": f"block {bidx} op#{op.idx} ({op.type})",
            "block": bidx, "op": op.idx, "op_type": op.type,
            "tier": "dcn" if axis in self.dcn_axes else "ici",
            "grad": bool(grad)})

    def finding(self, severity: str, code: str, message: str, bidx: int,
                op=None, slot: Optional[str] = None,
                var: Optional[str] = None) -> None:
        self.findings.append(Finding(
            severity, "shard", code, message, block=bidx,
            op=None if op is None else op.idx,
            op_type=None if op is None else op.type, slot=slot, var=var))


class _OpCtx:
    """What a propagation rule sees: one op, with spec/shape accessors
    and the set_out/hazard emission helpers."""

    __slots__ = ("eng", "bidx", "op", "od")

    def __init__(self, eng: _Engine, bidx: int, op):
        self.eng = eng
        self.bidx = bidx
        self.op = op
        self.od = op.desc

    # accessors
    def attr(self, name: str, default=None):
        return self.od.attrs.get(name, default)

    def input(self, slot: str) -> List[str]:
        return list(self.od.inputs.get(slot) or ())

    def first(self, slot: str) -> Optional[str]:
        names = self.od.inputs.get(slot)
        return names[0] if names else None

    def spec(self, name: str) -> Tuple:
        return self.eng.spec(self.bidx, name)

    def shape(self, name: str):
        return self.eng.shape(self.bidx, name)

    def fit(self, name: str, out_name: str) -> Tuple:
        return _fit_spec(self.spec(name), self.shape(name),
                         self.shape(out_name))

    # emission
    def set_out(self, name: str, spec, partial=(),
                slot: Optional[str] = None, grad: bool = False,
                reduced: bool = True) -> None:
        """Record ``name``'s inferred spec.  ``partial`` axes all-reduce
        at this op.  ``reduced=True`` (reductions, grads) means the
        cross-shard combine is part of the op's own semantics — always
        priced, never an error.  ``reduced=False`` (a raw contraction
        partial, matmul/conv) errors when the value escapes its block,
        reaches a fetch, or lands in a persistable *before* anything
        reduces it."""
        eng = self.eng
        vd = eng.view.visible_var(self.bidx, name)
        rank = len(vd.shape) if vd is not None and vd.shape is not None \
            else len(tuple(spec or ()))
        spec = tuple(spec or ())[:rank]
        spec = spec + (None,) * (rank - len(spec))
        # drop axes the mesh doesn't split, and second uses of an axis
        seen: set = set()
        norm = []
        for ax in spec:
            if ax and eng.axis_size(ax) > 1 and ax not in seen:
                seen.add(ax)
                norm.append(ax)
            else:
                norm.append(None)
        spec = tuple(norm)
        partial = {a for a in partial
                   if a and eng.axis_size(a) > 1 and a not in seen}

        # declared annotation wins — a conflict with the propagated spec
        # is a forced repartition (all-to-all when both are sharded, an
        # all-gather when the annotation replicates a sharded value)
        declared = eng.norm_annotation(vd) if vd is not None else None
        if declared is not None and _axes_of(spec) \
                and tuple(declared) != spec:
            kind = ALL_TO_ALL if _axes_of(declared) else ALL_GATHER
            axis = sorted(_axes_of(spec) | _axes_of(declared))[0]
            eng.record(kind, axis, eng.payload(self.bidx, name, spec),
                       self.bidx, self.op)
            eng.finding(
                ERROR, "resharding-hazard",
                f"var '{name}' is declared "
                f"{_fmt(declared)} but dataflow propagates {_fmt(spec)} "
                f"— the partitioner must insert an implicit {kind} here",
                self.bidx, self.op, slot=slot, var=name)
            spec = tuple(declared)
            partial -= _axes_of(spec)
        elif declared is not None and not _axes_of(spec) \
                and _axes_of(declared):
            # replicated value written into a sharded layout: a local
            # slice, free — adopt the declared spec
            spec = tuple(declared)
            partial -= _axes_of(spec)

        if partial:
            owner = eng.view.owner_block(self.bidx, name)
            owner = self.bidx if owner is None else owner
            escapes = owner != self.bidx
            fetched = owner == 0 and name in eng.fetch
            persistable = vd is not None and vd.persistable
            if not reduced and (escapes or fetched or persistable):
                where = ("escapes its block" if escapes else
                         "reaches a fetch" if fetched else
                         "lands in a persistable")
                eng.finding(
                    ERROR, "partial-sum-unreduced",
                    f"var '{name}' is a partial sum over mesh axis "
                    f"{sorted(partial)} and {where} without its "
                    f"all-reduce — each shard holds a different value",
                    self.bidx, self.op, slot=slot, var=name)
            else:
                pay = eng.payload(self.bidx, name, spec)
                batch = set(eng.batch_axes())
                for ax in sorted(partial):
                    eng.record(ALL_REDUCE, ax, pay, self.bidx, self.op,
                               grad=grad and ax in batch)
        eng.states[eng._key(self.bidx, name)] = spec

    def hazard(self, kind: str, axis: str, payload_name: str,
               message: str, slot: Optional[str] = None) -> None:
        eng = self.eng
        pay = eng.payload(self.bidx, payload_name,
                          self.spec(payload_name))
        eng.record(kind, axis, pay, self.bidx, self.op)
        eng.finding(ERROR, "resharding-hazard",
                    f"{message} — the partitioner must insert an "
                    f"implicit {kind} over axis '{axis}' "
                    f"({pay:.0f} B)", self.bidx, self.op,
                    slot=slot, var=payload_name)


def _fmt(spec) -> str:
    return "(" + ", ".join(a if a else "-" for a in (spec or ())) + ")"


# ---------------------------------------------------------------------------
# propagation rules
# ---------------------------------------------------------------------------

_EW_UNARY = (
    "relu", "relu6", "sigmoid", "tanh", "exp", "sqrt", "rsqrt", "square",
    "abs", "log", "floor", "ceil", "round", "sign", "scale", "cast",
    "assign", "dropout", "clip", "clip_by_norm", "increment", "gelu",
    "swish", "silu", "hard_swish", "hard_sigmoid", "leaky_relu", "elu",
    "softplus", "softsign", "pow", "sequence_mask", "one_hot",
    "label_smooth", "isfinite", "logical_not", "uniform_random_like",
    "shuffle_channel", "dequantize", "sequence_expand", "pad",
    "expand", "tile", "slice", "lod_reset", "im2sequence",
)


@prop_rule(*_EW_UNARY)
def _r_identity(ctx: _OpCtx) -> None:
    """Dim-preserving ops: every output adopts the primary input's spec
    where the dim extents survive (changed dims come out replicated —
    a local slice/pad of a sharded dim never moves bytes here)."""
    src = ctx.first("X") or ctx.first("Input")
    if src is None:
        ins = [n for _, _, n in ctx.op.reads]
        src = ins[0] if ins else None
    for slot, pos, name in ctx.op.writes:
        if src is None:
            ctx.set_out(name, (), slot=f"{slot}#{pos}")
        else:
            ctx.set_out(name, ctx.fit(src, name), slot=f"{slot}#{pos}")


_EW_BINARY = (
    "elementwise_add", "elementwise_sub", "elementwise_mul",
    "elementwise_div", "elementwise_max", "elementwise_min",
    "elementwise_pow", "less_than", "equal", "greater_than",
    "logical_and", "logical_or",
)


@prop_rule(*_EW_BINARY)
def _r_elementwise(ctx: _OpCtx) -> None:
    """Broadcast alignment (elementwise_op_function.h): Y aligns with X
    at the ``axis`` attr (default trailing).  Same dim sharded on two
    different axes is a forced repartition of Y."""
    x, y = ctx.first("X"), ctx.first("Y")
    xs = list(ctx.spec(x)) if x else []
    xshape = ctx.shape(x) if x else None
    merged = list(xs)
    if y is not None:
        ys = ctx.spec(y)
        yshape = ctx.shape(y) or ()
        axis = ctx.attr("axis", -1)
        if len(ys) == len(xs):
            off = 0
        elif axis in (-1, None):
            off = len(xs) - len(ys)
        else:
            off = int(axis)
        for j, ax in enumerate(ys):
            i = off + j
            if not ax or not (0 <= i < len(merged)):
                continue
            # a broadcast (size-1) dim can't really be sharded
            if j < len(yshape) and yshape[j] == 1:
                continue
            if merged[i] is None:
                merged[i] = ax
            elif merged[i] != ax:
                ctx.hazard(ALL_GATHER, ax, y,
                           f"operands of '{ctx.op.type}' are sharded "
                           f"differently on dim {i} ('{merged[i]}' vs "
                           f"'{ax}')", slot="Y#0")
    out_shape_src = x if x is not None else y
    for slot, pos, name in ctx.op.writes:
        ctx.set_out(name, _fit_spec(tuple(merged), xshape,
                                    ctx.shape(name)),
                    slot=f"{slot}#{pos}")


@prop_rule("sum", "sums")
def _r_nary_sum(ctx: _OpCtx) -> None:
    ins = [n for _, _, n in ctx.op.reads]
    merged: List[Optional[str]] = []
    for n in ins:
        s = ctx.spec(n)
        if len(s) > len(merged):
            merged += [None] * (len(s) - len(merged))
        for i, ax in enumerate(s):
            if not ax:
                continue
            if merged[i] is None:
                merged[i] = ax
            elif merged[i] != ax:
                ctx.hazard(ALL_GATHER, ax, n,
                           f"'{ctx.op.type}' addend '{n}' is sharded "
                           f"'{ax}' on dim {i} where another addend is "
                           f"'{merged[i]}'")
    for slot, pos, name in ctx.op.writes:
        ctx.set_out(name, tuple(merged), slot=f"{slot}#{pos}")


@prop_rule("mul", "matmul", "quantized_mul", "quantized_matmul")
def _r_matmul(ctx: _OpCtx) -> None:
    """GSPMD dot rule: contracted-dim mesh axes become partial sums on
    the output (all-reduce at this op); row/col axes pass through."""
    x, y = ctx.first("X"), ctx.first("Y")
    xs, ys = ctx.spec(x), ctx.spec(y)
    nx, ny = len(xs), len(ys)
    if ctx.op.type in ("mul", "quantized_mul"):
        xd = int(ctx.attr("x_num_col_dims", 1))
        yd = int(ctx.attr("y_num_col_dims", 1))
        x_keep = list(range(xd))
        x_con = list(range(xd, nx))
        y_con = list(range(yd))
        y_keep = list(range(yd, ny))
    else:
        tx = bool(ctx.attr("transpose_X", False))
        ty = bool(ctx.attr("transpose_Y", False))
        x_con = [nx - 2 if tx else nx - 1] if nx >= 1 else []
        x_keep = [i for i in range(nx) if i not in x_con]
        y_con = [ny - 1 if ty else ny - 2] if ny >= 2 else []
        y_keep = [i for i in range(ny) if i not in y_con]
        # batched matmul: leading y batch dims align with x's, drop them
        # from the kept tail (out = x batch/row dims + y's last col dim)
        if len(y_keep) > 1:
            y_keep = y_keep[-1:]
    partial = set()
    for pos, (i, j) in enumerate(zip(x_con, y_con)):
        ax, ay = xs[i] if i < nx else None, ys[j] if j < ny else None
        if ax and ay and ax != ay:
            ctx.hazard(ALL_GATHER, ay, y,
                       f"contracted dim of '{ctx.op.type}' is sharded "
                       f"'{ax}' on X but '{ay}' on Y", slot="Y#0")
            ay = None
        partial |= {a for a in (ax, ay) if a}
    # unmatched contracted tails (mul flattens)
    for i in x_con[len(y_con):]:
        if i < nx and xs[i]:
            partial.add(xs[i])
    for j in y_con[len(x_con):]:
        if j < ny and ys[j]:
            partial.add(ys[j])
    out_spec = [xs[i] if i < nx else None for i in x_keep] + \
               [ys[j] if j < ny else None for j in y_keep]
    partial -= _axes_of(tuple(out_spec))
    for slot, pos, name in ctx.op.writes:
        ctx.set_out(name, tuple(out_spec), partial=partial,
                    slot=f"{slot}#{pos}", reduced=False)


def _reduced_dims(ctx: _OpCtx, rank: int) -> List[int]:
    dim = ctx.attr("dim", [0])
    if ctx.attr("reduce_all", False):
        return list(range(rank))
    dims = (dim,) if isinstance(dim, int) else tuple(dim)
    return sorted({d % rank for d in dims}) if rank else []


@prop_rule("reduce_sum", "reduce_mean", "reduce_max", "reduce_min",
           "reduce_prod")
def _r_reduce(ctx: _OpCtx) -> None:
    x = ctx.first("X")
    xs = ctx.spec(x)
    rank = len(xs)
    dims = _reduced_dims(ctx, rank)
    partial = {xs[d] for d in dims if d < rank and xs[d]}
    keep = bool(ctx.attr("keep_dim", False))
    out_spec = [None if i in dims else xs[i] for i in range(rank)] \
        if keep else [xs[i] for i in range(rank) if i not in dims]
    for slot, pos, name in ctx.op.writes:
        ctx.set_out(name, _fit_spec(tuple(out_spec), None,
                                    ctx.shape(name)) if not keep
                    else tuple(out_spec),
                    partial=partial, slot=f"{slot}#{pos}")


@prop_rule("mean", "accuracy", "norm", "cos_sim", "clip_by_norm")
def _r_reduce_all(ctx: _OpCtx) -> None:
    """Full reductions to (near-)scalars: the output is a partial sum
    over every axis the input was sharded on — this is the loss-mean
    all-reduce the heuristic estimator used to miss."""
    axes = set()
    for _, _, n in ctx.op.reads:
        axes |= _axes_of(ctx.spec(n))
    for slot, pos, name in ctx.op.writes:
        rank = len(ctx.shape(name) or ())
        ctx.set_out(name, (None,) * rank, partial=axes,
                    slot=f"{slot}#{pos}")


@prop_rule("cross_entropy")
def _r_cross_entropy(ctx: _OpCtx) -> None:
    x = ctx.first("X")
    xs = ctx.spec(x)
    partial = {xs[-1]} if xs and xs[-1] else set()
    out_spec = tuple(xs[:-1]) + (None,) if xs else ()
    for slot, pos, name in ctx.op.writes:
        ctx.set_out(name, _fit_spec(out_spec, None, ctx.shape(name)),
                    partial=partial, slot=f"{slot}#{pos}")


@prop_rule("softmax_with_cross_entropy")
def _r_softmax_ce(ctx: _OpCtx) -> None:
    x = ctx.first("Logits") or ctx.first("X")
    xs = ctx.spec(x)
    partial = {xs[-1]} if xs and xs[-1] else set()
    for slot, pos, name in ctx.op.writes:
        if slot == "Softmax":
            ctx.set_out(name, tuple(xs), slot=f"{slot}#{pos}")
        else:
            ctx.set_out(name, tuple(xs[:-1]) + (None,) if xs else (),
                        partial=partial, slot=f"{slot}#{pos}")


@prop_rule("softmax", "sequence_softmax", "log_softmax")
def _r_softmax(ctx: _OpCtx) -> None:
    x = ctx.first("X")
    xs = list(ctx.spec(x))
    axis = int(ctx.attr("axis", -1)) % max(1, len(xs)) if xs else 0
    if xs and xs[axis]:
        ctx.hazard(ALL_GATHER, xs[axis], x,
                   f"softmax normalizes dim {axis}, which is sharded",
                   slot="X#0")
        xs[axis] = None
    for slot, pos, name in ctx.op.writes:
        ctx.set_out(name, tuple(xs), slot=f"{slot}#{pos}")


@prop_rule("layer_norm")
def _r_layer_norm(ctx: _OpCtx) -> None:
    x = ctx.first("X")
    xs = list(ctx.spec(x))
    bna = int(ctx.attr("begin_norm_axis", 1))
    for i in range(bna, len(xs)):
        if xs[i]:
            ctx.hazard(ALL_GATHER, xs[i], x,
                       f"layer_norm normalizes dim {i}, which is "
                       f"sharded", slot="X#0")
            xs[i] = None
    for slot, pos, name in ctx.op.writes:
        if slot == "Y":
            ctx.set_out(name, tuple(xs), slot=f"{slot}#{pos}")
        else:   # Mean / Variance: one value per row
            ctx.set_out(name, _fit_spec(tuple(xs[:bna]), None,
                                        ctx.shape(name)),
                        slot=f"{slot}#{pos}")


@prop_rule("batch_norm", "group_norm")
def _r_batch_norm(ctx: _OpCtx) -> None:
    x = ctx.first("X")
    for slot, pos, name in ctx.op.writes:
        if slot in ("Y", "Out"):
            ctx.set_out(name, ctx.fit(x, name), slot=f"{slot}#{pos}")
        else:
            ctx.set_out(name, (), slot=f"{slot}#{pos}")


@prop_rule("reshape", "squeeze", "unsqueeze", "flatten")
def _r_reshape(ctx: _OpCtx) -> None:
    """Axis tracking through dim regrouping: a sharded dim survives when
    it is the major factor of its group and the receiving dim still
    divides the axis extent; otherwise the layout must move."""
    x = ctx.first("X")
    xs = ctx.spec(x)
    in_shape = ctx.shape(x)
    for slot, pos, name in ctx.op.writes:
        if slot in ("XShape",):
            ctx.set_out(name, (), slot=f"{slot}#{pos}")
            continue
        out_shape = ctx.shape(name)
        if in_shape is None or out_shape is None:
            ctx.set_out(name, (), slot=f"{slot}#{pos}")
            continue
        groups = _dim_groups(in_shape, out_shape)
        if groups is None:
            if _axes_of(xs):
                ax = sorted(_axes_of(xs))[0]
                ctx.hazard(ALL_TO_ALL, ax, x,
                           f"'{ctx.op.type}' regroups dims in a way "
                           f"axis tracking can't follow", slot="X#0")
            ctx.set_out(name, (), slot=f"{slot}#{pos}")
            continue
        big = 999983
        out_spec: List[Optional[str]] = [None] * len(out_shape)
        for gi, gj in groups:
            sharded = [i for i in gi if i < len(xs) and xs[i]]
            if not sharded:
                continue
            ax = xs[sharded[0]]
            n = ctx.eng.axis_size(ax)
            # the shard boundary survives iff some dst dim starts at the
            # same element offset (equal prefix products within the
            # group) and still divides the axis extent
            pre = 1
            for i in gi:
                if i == sharded[0]:
                    break
                d = in_shape[i]
                pre *= big if d is None or d < 0 else max(1, int(d))
            dst, acc = None, 1
            for j in gj:
                dj = out_shape[j]
                v = big if dj is None or dj < 0 else max(1, int(dj))
                if acc == pre and v != 1:   # size-1 dims shift nothing
                    if v == big or v % n == 0:
                        dst = j
                    break
                if acc > pre:
                    break
                acc *= v
            if len(sharded) == 1 and dst is not None:
                out_spec[dst] = ax
            else:
                ctx.hazard(ALL_TO_ALL, ax, x,
                           f"'{ctx.op.type}' splits/merges sharded dim "
                           f"{sharded[0]} across the '{ax}' axis "
                           f"boundary", slot="X#0")
        ctx.set_out(name, tuple(out_spec), slot=f"{slot}#{pos}")


@prop_rule("transpose")
def _r_transpose(ctx: _OpCtx) -> None:
    x = ctx.first("X")
    xs = ctx.spec(x)
    perm = ctx.attr("axis") or list(range(len(xs)))
    out_spec = tuple(xs[p] if 0 <= p < len(xs) else None for p in perm)
    for slot, pos, name in ctx.op.writes:
        ctx.set_out(name, out_spec, slot=f"{slot}#{pos}")


@prop_rule("concat")
def _r_concat(ctx: _OpCtx) -> None:
    ins = [n for _, _, n in ctx.op.reads]
    axis = int(ctx.attr("axis", 0))
    merged: List[Optional[str]] = []
    for n in ins:
        s = ctx.spec(n)
        if len(s) > len(merged):
            merged += [None] * (len(s) - len(merged))
        for i, ax in enumerate(s):
            if not ax:
                continue
            if i == axis % max(1, len(s)):
                ctx.hazard(ALL_GATHER, ax, n,
                           f"concat along dim {i}, which is sharded on "
                           f"'{ax}' in operand '{n}'")
                continue
            if merged[i] is None:
                merged[i] = ax
            elif merged[i] != ax:
                ctx.hazard(ALL_GATHER, ax, n,
                           f"concat operand '{n}' sharded '{ax}' on dim "
                           f"{i} where another operand is "
                           f"'{merged[i]}'")
    if merged:
        merged[axis % len(merged)] = None
    for slot, pos, name in ctx.op.writes:
        ctx.set_out(name, tuple(merged), slot=f"{slot}#{pos}")


@prop_rule("split")
def _r_split(ctx: _OpCtx) -> None:
    x = ctx.first("X")
    xs = list(ctx.spec(x))
    axis = int(ctx.attr("axis", 0)) % max(1, len(xs)) if xs else 0
    if xs and xs[axis]:
        ctx.hazard(ALL_GATHER, xs[axis], x,
                   f"split along dim {axis}, which is sharded",
                   slot="X#0")
        xs[axis] = None
    for slot, pos, name in ctx.op.writes:
        ctx.set_out(name, tuple(xs), slot=f"{slot}#{pos}")


@prop_rule("stack")
def _r_stack(ctx: _OpCtx) -> None:
    ins = [n for _, _, n in ctx.op.reads]
    base = ctx.spec(ins[0]) if ins else ()
    axis = int(ctx.attr("axis", 0))
    axis %= (len(base) + 1) if base or axis >= 0 else 1
    out_spec = tuple(base[:axis]) + (None,) + tuple(base[axis:])
    for slot, pos, name in ctx.op.writes:
        ctx.set_out(name, out_spec, slot=f"{slot}#{pos}")


@prop_rule("gather", "batch_gather")
def _r_gather(ctx: _OpCtx) -> None:
    x = ctx.first("X")
    xs = list(ctx.spec(x))
    if xs and xs[0]:
        ctx.hazard(ALL_GATHER, xs[0], x,
                   "gather indexes dim 0 of a dim-0-sharded operand",
                   slot="X#0")
        xs[0] = None
    for slot, pos, name in ctx.op.writes:
        ctx.set_out(name, _fit_spec(tuple(xs), ctx.shape(x),
                                    ctx.shape(name)),
                    slot=f"{slot}#{pos}")


@prop_rule("scatter")
def _r_scatter(ctx: _OpCtx) -> None:
    x = ctx.first("X")
    xs = ctx.spec(x)
    upd = ctx.first("Updates")
    partial = (_axes_of(ctx.spec(upd)) if upd else set()) - _axes_of(xs)
    for slot, pos, name in ctx.op.writes:
        ctx.set_out(name, tuple(xs), partial=partial,
                    slot=f"{slot}#{pos}")


@prop_rule("lookup_table", "embedding")
def _r_lookup(ctx: _OpCtx) -> None:
    """Vocab-parallel embedding: a dim-0-sharded table makes the lookup
    a one-hot matmul with a contracted sharded dim — partial sum.  A
    dim-1 (feature) sharded table passes through to the output."""
    w = ctx.first("W")
    ids = ctx.first("Ids")
    ws = ctx.spec(w)
    ids_spec = ctx.spec(ids) if ids else ()
    partial = {ws[0]} if ws and ws[0] else set()
    for slot, pos, name in ctx.op.writes:
        rank = len(ctx.shape(name) or ())
        out = [None] * rank
        for i, ax in enumerate(ids_spec):
            if i < rank - 1 and ax:
                out[i] = ax
        if rank and len(ws) > 1 and ws[1]:
            out[-1] = ws[1]
        ctx.set_out(name, tuple(out), partial=partial,
                    slot=f"{slot}#{pos}")


@prop_rule("top_k", "topk", "argmax", "arg_max")
def _r_topk(ctx: _OpCtx) -> None:
    x = ctx.first("X")
    xs = list(ctx.spec(x))
    axis = int(ctx.attr("axis", -1)) % max(1, len(xs)) if xs else 0
    if xs and xs[axis]:
        ctx.hazard(ALL_GATHER, xs[axis], x,
                   f"'{ctx.op.type}' selects along dim {axis}, which "
                   f"is sharded", slot="X#0")
        xs[axis] = None
    for slot, pos, name in ctx.op.writes:
        ctx.set_out(name, _fit_spec(tuple(xs), ctx.shape(x),
                                    ctx.shape(name)),
                    slot=f"{slot}#{pos}")


@prop_rule("conv2d", "quantized_conv2d", "depthwise_conv2d",
           "conv2d_transpose", "conv3d")
def _r_conv(ctx: _OpCtx) -> None:
    """NCHW conv: channels-in is the contracted dim (partial sum when
    sharded); batch passes through, channels-out comes from the filter.
    Spatial sharding needs halo exchange — flagged, not modelled."""
    x = ctx.first("Input") or ctx.first("X")
    f = ctx.first("Filter")
    xs, fs = ctx.spec(x), ctx.spec(f)
    partial = set()
    if len(xs) > 1 and xs[1]:
        partial.add(xs[1])
    if len(fs) > 1 and fs[1] and fs[1] not in partial:
        partial.add(fs[1])
    for i in range(2, len(xs)):
        if xs[i]:
            ctx.hazard(ALL_GATHER, xs[i], x,
                       f"conv over sharded spatial dim {i} needs a halo "
                       f"exchange", slot="Input#0")
    for slot, pos, name in ctx.op.writes:
        rank = len(ctx.shape(name) or ())
        out = [None] * rank
        if rank and xs:
            out[0] = xs[0]
        if rank > 1 and fs:
            out[1] = fs[0]
        partial -= _axes_of(tuple(out))
        ctx.set_out(name, tuple(out), partial=partial,
                    slot=f"{slot}#{pos}", reduced=False)


@prop_rule("pool2d", "pool3d")
def _r_pool(ctx: _OpCtx) -> None:
    x = ctx.first("X")
    xs = ctx.spec(x)
    for i in range(2, len(xs)):
        if xs[i]:
            ctx.hazard(ALL_GATHER, xs[i], x,
                       f"pooling over sharded spatial dim {i}",
                       slot="X#0")
    for slot, pos, name in ctx.op.writes:
        rank = len(ctx.shape(name) or ())
        out = [xs[i] if i < min(2, len(xs)) else None
               for i in range(rank)]
        ctx.set_out(name, tuple(out), slot=f"{slot}#{pos}")


@prop_rule("sequence_pool")
def _r_sequence_pool(ctx: _OpCtx) -> None:
    x = ctx.first("X")
    xs = ctx.spec(x)
    partial = {xs[1]} if len(xs) > 1 and xs[1] else set()
    out_spec = tuple(xs[:1]) + tuple(xs[2:])
    for slot, pos, name in ctx.op.writes:
        ctx.set_out(name, _fit_spec(out_spec, None, ctx.shape(name)),
                    partial=partial, slot=f"{slot}#{pos}")


_FILL_OPS = ("fill_constant", "fill_zeros_like", "uniform_random",
             "gaussian_random", "truncated_gaussian_random", "range",
             "assign_value", "shape")


@prop_rule(*_FILL_OPS)
def _r_fill(ctx: _OpCtx) -> None:
    for slot, pos, name in ctx.op.writes:
        ctx.set_out(name, (), slot=f"{slot}#{pos}")


@prop_rule("fill_constant_batch_size_like")
def _r_fill_like(ctx: _OpCtx) -> None:
    src = ctx.first("Input") or ctx.first("X")
    s = ctx.spec(src) if src else ()
    for slot, pos, name in ctx.op.writes:
        rank = len(ctx.shape(name) or ())
        out = [None] * rank
        if rank and s:
            out[0] = s[0]
        ctx.set_out(name, tuple(out), slot=f"{slot}#{pos}")


@prop_rule("quantize")
def _r_quantize(ctx: _OpCtx) -> None:
    x = ctx.first("X")
    xs = ctx.spec(x)
    axis = ctx.attr("axis", None)
    for slot, pos, name in ctx.op.writes:
        if slot == "Out":
            ctx.set_out(name, tuple(xs), slot=f"{slot}#{pos}")
        else:   # Scale: abs-max reduce over every dim but `axis`
            partial = {ax for i, ax in enumerate(xs)
                       if ax and (axis is None or i != axis)}
            keep = xs[axis] if axis is not None and axis < len(xs) \
                else None
            ctx.set_out(name, _fit_spec((keep,), None, ctx.shape(name)),
                        partial=partial, slot=f"{slot}#{pos}")


@prop_rule("cache_write")
def _r_cache_write(ctx: _OpCtx) -> None:
    cache = ctx.first("Cache")
    for slot, pos, name in ctx.op.writes:
        ctx.set_out(name, ctx.spec(cache) if cache else (),
                    slot=f"{slot}#{pos}")


@prop_rule("decode_attention", "fused_attention")
def _r_attention(ctx: _OpCtx) -> None:
    q = ctx.first("Q") or ctx.first("X")
    for slot, pos, name in ctx.op.writes:
        ctx.set_out(name, ctx.fit(q, name) if q else (),
                    slot=f"{slot}#{pos}")


@prop_rule("paged_cache_write", "quantized_paged_cache_write")
def _r_paged_write(ctx: _OpCtx) -> None:
    """The pool is [rows, page, heads*d] (its minor axis shards by
    whole heads); K/V updates are [lanes, t, heads, d].  The head axis
    must agree — a head-sharded pool written from a differently-sharded
    K forces an all-to-all."""
    pool = ctx.first("Pool")
    ps = ctx.spec(pool) if pool else ()
    for kn in (ctx.first("K"), ctx.first("V")):
        if kn is None:
            continue
        ks = ctx.spec(kn)
        if len(ks) > 2 and ks[2] and ps and ps[-1] and ks[2] != ps[-1]:
            ctx.hazard(ALL_TO_ALL, ks[2], kn,
                       f"KV update head dim sharded '{ks[2]}' but the "
                       f"pool's head dim is '{ps[-1]}'", slot="K#0")
    scales = ctx.first("Scales")
    for slot, pos, name in ctx.op.writes:
        if slot == "ScalesOut" and scales is not None:
            ctx.set_out(name, ctx.spec(scales), slot=f"{slot}#{pos}")
        else:
            ctx.set_out(name, tuple(ps), slot=f"{slot}#{pos}")


@prop_rule("ragged_decode_attention")
def _r_ragged_attention(ctx: _OpCtx) -> None:
    q = ctx.first("Q")
    pool = ctx.first("Pool")
    qs = ctx.spec(q) if q else ()
    ps = ctx.spec(pool) if pool else ()
    # Q's head dim is rank-2 ([lanes, heads, d] / [lanes, t, heads, d])
    if len(qs) >= 2 and ps and ps[-1] and qs[-2] and qs[-2] != ps[-1]:
        ctx.hazard(ALL_TO_ALL, ps[-1], pool,
                   f"pool head dim sharded '{ps[-1]}' but Q's head dim "
                   f"is '{qs[-2]}'", slot="Pool#0")
    for slot, pos, name in ctx.op.writes:
        ctx.set_out(name, ctx.fit(q, name) if q else (),
                    slot=f"{slot}#{pos}")


@prop_rule("paged_page_copy", "quantized_paged_page_copy")
def _r_page_copy(ctx: _OpCtx) -> None:
    pool = ctx.first("Pool")
    scales = ctx.first("Scales")
    for slot, pos, name in ctx.op.writes:
        src = scales if slot == "ScalesOut" else pool
        ctx.set_out(name, ctx.spec(src) if src else (),
                    slot=f"{slot}#{pos}")


@prop_rule("paged_page_gather", "quantized_paged_page_gather")
def _r_page_gather(ctx: _OpCtx) -> None:
    """KV-tier download: the slab is whole pool rows —
    [W*2L, ps, h*d] has the pool's rank and its heads-minor layout, so
    Out keeps the pool's sharding and the scale slab mirrors the scales
    sidecar."""
    pool = ctx.first("Pool")
    scales = ctx.first("Scales")
    for slot, pos, name in ctx.op.writes:
        src = scales if slot == "ScalesOut" else pool
        ctx.set_out(name, ctx.spec(src) if src else (),
                    slot=f"{slot}#{pos}")


@prop_rule("paged_page_scatter", "quantized_paged_page_scatter")
def _r_page_scatter(ctx: _OpCtx) -> None:
    """KV-tier upload: Out aliases Pool (ScalesOut aliases Scales), so
    each target keeps its own sharding; a slab whose head dim disagrees
    with a head-sharded pool would force an all-to-all first."""
    pool = ctx.first("Pool")
    ps = ctx.spec(pool) if pool else ()
    data = ctx.first("Data")
    if data is not None:
        ds = ctx.spec(data)
        if ps and ps[-1] and ds and ds[-1] and ds[-1] != ps[-1]:
            ctx.hazard(ALL_TO_ALL, ps[-1], data,
                       f"upload slab head dim sharded '{ds[-1]}' but the "
                       f"pool's head dim is '{ps[-1]}'", slot="Data#0")
    scales = ctx.first("Scales")
    for slot, pos, name in ctx.op.writes:
        src = scales if slot == "ScalesOut" else pool
        ctx.set_out(name, ctx.spec(src) if src else (),
                    slot=f"{slot}#{pos}")


@prop_rule("fused_vocab_cross_entropy")
def _r_vocab_ce(ctx: _OpCtx) -> None:
    x = ctx.first("X")
    w = ctx.first("W") or ctx.first("Weight")
    xs = ctx.spec(x) if x else ()
    ws = ctx.spec(w) if w else ()
    partial = set()
    if len(ws) > 1 and ws[1]:
        partial.add(ws[1])          # vocab-parallel logits
    if xs and xs[-1]:
        partial.add(xs[-1])         # contracted d_model
    for slot, pos, name in ctx.op.writes:
        ctx.set_out(name, _fit_spec(tuple(xs[:-1]) + (None,), None,
                                    ctx.shape(name)),
                    partial=partial - _axes_of(tuple(xs[:-1])),
                    slot=f"{slot}#{pos}")


_OPTIMIZER_OPS = ("sgd", "momentum", "adam", "adagrad", "rmsprop",
                  "adamax", "adamw", "lamb")


@prop_rule(*_OPTIMIZER_OPS)
def _r_optimizer(ctx: _OpCtx) -> None:
    """Param update: every output keeps its matching input's spec
    (ParamOut <- Param, MomentOut <- Moment, ...).  A gradient still
    carrying a batch axis here means each dp replica applies a
    *different* update — silent replica drift."""
    eng = ctx.eng
    param = ctx.first("Param")
    pspec = ctx.spec(param) if param else ()
    batch = set(eng.batch_axes())
    grad = ctx.first("Grad")
    if grad is not None:
        gs = ctx.spec(grad)
        bad = _axes_of(gs) & batch
        if bad:
            eng.finding(
                ERROR, "dp-grad-divergence",
                f"param '{param}' is updated from grad '{grad}' still "
                f"sharded over batch axis {sorted(bad)} — replicas "
                f"would apply different updates (missing grad "
                f"all-reduce)", ctx.bidx, ctx.op, slot="Grad#0",
                var=param)
        model_mismatch = (_axes_of(gs) - batch) - _axes_of(pspec)
        if model_mismatch:
            ctx.hazard(ALL_GATHER, sorted(model_mismatch)[0], grad,
                       f"grad '{grad}' sharded {_fmt(gs)} but param "
                       f"'{param}' is {_fmt(pspec)}", slot="Grad#0")
    by_slot = {slot: names[0] for slot, names in ctx.od.inputs.items()
               if names}
    for slot, pos, name in ctx.op.writes:
        src = None
        if slot.endswith("Out") and slot[:-3] in by_slot:
            src = by_slot[slot[:-3]]
        elif param is not None:
            src = param
        ctx.set_out(name, ctx.spec(src) if src else (),
                    slot=f"{slot}#{pos}")


# ---------------------------------------------------------------------------
# the generic transposed *_grad rule
# ---------------------------------------------------------------------------

def _generic_grad(ctx: _OpCtx) -> None:
    """d(V) adopts V's forward spec; mesh axes carried by the incoming
    output-grads that the target spec lacks were *contracted* by the
    transposed computation — partial sums, all-reduced here.  This one
    rule derives both the dp grad-sync and the tensor-parallel backward
    all-reduce from the forward specs."""
    eng = ctx.eng
    in_axes: set = set()
    for _, _, name in ctx.op.reads:
        if "@GRAD" in name:
            in_axes |= _axes_of(ctx.spec(name))
    for slot, pos, name in ctx.op.writes:
        if "@GRAD" in name:
            base = name.split("@GRAD")[0]
            fwd = ctx.spec(base)
            spec = _fit_spec(fwd, ctx.shape(base), ctx.shape(name))
            partial = in_axes - _axes_of(spec)
            vd = eng.view.visible_var(ctx.bidx, base)
            is_param_grad = vd is not None and vd.persistable
            ctx.set_out(name, spec, partial=partial,
                        slot=f"{slot}#{pos}", grad=is_param_grad)
        else:
            ctx.set_out(name, ctx.spec(name), slot=f"{slot}#{pos}")


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def _seed(eng: _Engine) -> None:
    """Initial states: every annotated var (params, the KV pool — the
    static mirror of mesh.state_sharding) plus the feed surface, whose
    dim 0 the executor shards over the first batch axis
    (mesh.feed_sharding) when one exists."""
    view = eng.view
    for b in view.blocks:
        for name, vd in b.desc.vars.items():
            spec = eng.norm_annotation(vd)
            if spec is not None:
                eng.annotated += 1
                eng.states[(b.idx, name)] = spec
    batch = eng.batch_axes()
    if not batch:
        return
    from .recompile import feed_vars
    ax = batch[0]
    n = eng.axis_size(ax)
    for name in feed_vars(view):
        key = (0, name)
        if key in eng.states:
            continue
        vd = view.visible_var(0, name)
        if vd is None or not vd.shape:
            continue
        d0 = vd.shape[0]
        if d0 is None or d0 < 0 or d0 % n == 0:
            eng.states[key] = (ax,) + (None,) * (len(vd.shape) - 1)


def _default_rule(ctx: _OpCtx) -> None:
    """No propagation rule: outputs come out replicated; if any input
    was sharded this silently drops a layout (an implicit all-gather at
    best), so say so — mirrors cost.py's unregistered-cost-rule."""
    eng = ctx.eng
    sharded = [n for _, _, n in ctx.op.reads if _axes_of(ctx.spec(n))]
    if sharded and ctx.op.type not in eng._warned \
            and ctx.op.type not in PROPAGATION_OPAQUE:
        eng._warned.add(ctx.op.type)
        eng.finding(
            WARNING, "unregistered-prop-rule",
            f"op '{ctx.op.type}' has no sharding propagation rule but "
            f"reads sharded var(s) {sharded[:3]} — treating outputs as "
            f"replicated (register a @prop_rule or list it "
            f"propagation-opaque)", ctx.bidx, ctx.op)
    for slot, pos, name in ctx.op.writes:
        ctx.set_out(name, (), slot=f"{slot}#{pos}")


def _run_block(eng: _Engine, bidx: int, depth: int = 0) -> None:
    if depth > 16:
        return
    b = eng.view.blocks[bidx]
    for op in b.ops:
        if op.type in _SKIP_OPS:
            continue
        if op.sub_blocks or op.type in CONTROL_FLOW_OPS:
            for si in op.sub_blocks:
                _run_block(eng, si, depth + 1)
            # sub-block writes already updated owner states; the op's
            # own outputs keep whatever the body established
            continue
        ctx = _OpCtx(eng, bidx, op)
        rule = PROP_RULES.get(op.type)
        if rule is None and op.type.endswith("_grad"):
            rule = _generic_grad
        try:
            if rule is not None:
                rule(ctx)
            else:
                _default_rule(ctx)
        except Exception:
            # a rule must never take down the pre-flight — degrade to
            # replicated outputs for this op
            for slot, pos, name in op.writes:
                eng.states[eng._key(bidx, name)] = ()


def _check_replicated_giants(eng: _Engine) -> None:
    model_axes = eng.model_axes()
    if not model_axes or eng.giant_bytes is None:
        return
    seen: set = set()
    for b in eng.view.blocks:
        for name, vd in b.desc.vars.items():
            if not vd.persistable or name in seen:
                continue
            seen.add(name)
            spec = eng.states.get((b.idx, name), ())
            if _axes_of(spec) & set(model_axes):
                continue
            full, approx = var_bytes(vd, eng.assume_batch)
            if not approx and full >= eng.giant_bytes:
                eng.finding(
                    ERROR, "replicated-giant",
                    f"persistable '{name}' ({full / 2**20:.1f} MiB) is "
                    f"fully replicated on model axis "
                    f"{sorted(model_axes)} — shard it or raise "
                    f"--replicated-giant-bytes", b.idx, var=name)


def infer_sharding(view_or_program, options: Optional[Dict] = None,
                   fetch: Sequence[str] = ()) -> ShardPropResult:
    """Run the propagation over a Program/ProgramDesc/ProgramView.

    Options: ``mesh_axes`` ({axis: size}; defaults to the active mesh,
    then to axes named by annotations at an assumed 2 — same resolution
    as the comms estimator), ``dcn_axes``, ``assume_batch`` (dynamic
    dim-0 substitution for payloads), ``replicated_giant_bytes``
    (threshold for shard/replicated-giant; None disables)."""
    from .comms import _axis_sizes

    view = view_or_program if isinstance(view_or_program, ProgramView) \
        else ProgramView(getattr(view_or_program, "desc",
                                 view_or_program))
    opts = options or {}
    sizes = _axis_sizes(view, opts)
    eng = _Engine(
        view, sizes,
        {str(a) for a in (opts.get("dcn_axes") or ())},
        int(opts.get("assume_batch", 1)), fetch,
        opts.get("replicated_giant_bytes",
                 REPLICATED_GIANT_BYTES_DEFAULT))
    _seed(eng)
    if view.blocks:
        _run_block(eng, 0)
    _check_replicated_giants(eng)

    res = ShardPropResult.__new__(ShardPropResult)
    res.axis_sizes = eng.sizes
    res.dcn_axes = eng.dcn_axes
    res.assume_batch = eng.assume_batch
    res.collectives = eng.collectives
    res.var_specs = dict(eng.states)
    res.findings = eng.findings
    res.annotated_vars = eng.annotated
    return res


def shardprop_pass(ctx, diag: Diagnostics) -> None:
    """Whole-program sharding inference; attaches the inferred
    collective graph to ``diag.reports["shardprop"]`` (the comms pass
    prices it instead of its heuristic scan when present)."""
    opts = getattr(ctx, "options", {}) or {}
    res = infer_sharding(ctx.view, options=opts,
                         fetch=getattr(ctx, "fetch", ()))
    for f in res.findings:
        diag.add(f)
    diag.reports["shardprop"] = res.to_dict()
    if res.annotated_vars or res.collectives:
        pk = res.per_kind()
        kinds = ", ".join(f"{k}×{int(v['count'])}"
                          for k, v in sorted(pk.items())) or "none"
        diag.add(Finding(
            INFO, "shard", "summary",
            f"{res.annotated_vars} annotated var(s) propagated over "
            f"mesh {res.axis_sizes}; inferred collectives: {kinds} "
            f"({res.total_payload_bytes / 2**20:.3f} MiB payload)"))
