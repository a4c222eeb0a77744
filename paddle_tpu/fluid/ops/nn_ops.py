"""Convolution / pooling / normalization / dropout ops.

Replaces the reference's conv_op.cc (+conv_cudnn_op.cu.cc), pool_op.cc,
batch_norm_op.cc, layer_norm_op.cc, dropout_op.cc, nce_op.cc and the
im2col/vol2col/pooling helpers in paddle/operators/math/.  Convs lower to
lax.conv_general_dilated — XLA tiles them onto the MXU directly, where the
reference needed im2col+GEMM or cuDNN algorithm selection.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core.registry import OpInfo, primitive, register


def _match_conv_dtype(x, w):
    """Master-weight mixed precision for convs (lax.conv rejects mixed
    operand dtypes) — delegates to the shared AMP rule in math_ops."""
    from .math_ops import match_master_dtype

    return match_master_dtype(x, w)


def _conv_pet(x):
    """preferred_element_type for convs: f32 accumulate for f32 inputs;
    None for bf16 (MXU accumulation is f32 internally either way, and an
    explicit f32 PET breaks the conv transpose rule under bf16)."""
    return jnp.float32 if x.dtype == jnp.float32 else None


@primitive("conv2d", inputs=["Input", "Filter"], outputs=["Output"])
def conv2d(ctx, x, w):
    """NCHW conv — reference conv_op.cc.  Filter layout OIHW (out, in/groups,
    h, w), matching the reference."""
    w = _match_conv_dtype(x, w)
    strides = tuple(ctx.attr("strides", [1, 1]))
    p = ctx.attr("paddings", [0, 0])
    dil = tuple(ctx.attr("dilations", [1, 1]))
    groups = ctx.attr("groups", 1)
    return jax.lax.conv_general_dilated(
        x, w, window_strides=strides,
        padding=[(p[0], p[0]), (p[1], p[1])],
        rhs_dilation=dil, feature_group_count=groups,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        preferred_element_type=_conv_pet(x)).astype(x.dtype)


@primitive("depthwise_conv2d", inputs=["Input", "Filter"], outputs=["Output"])
def depthwise_conv2d(ctx, x, w):
    """reference conv_op.cc depthwise variant (function/DepthwiseConvOp)."""
    w = _match_conv_dtype(x, w)
    strides = tuple(ctx.attr("strides", [1, 1]))
    p = ctx.attr("paddings", [0, 0])
    c = x.shape[1]
    return jax.lax.conv_general_dilated(
        x, w, window_strides=strides,
        padding=[(p[0], p[0]), (p[1], p[1])],
        feature_group_count=c,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        preferred_element_type=_conv_pet(x)).astype(x.dtype)


@primitive("conv2d_transpose", inputs=["Input", "Filter"], outputs=["Output"])
def conv2d_transpose(ctx, x, w):
    """reference conv_transpose_op.cc — implemented as the standard
    lhs-dilated conv with a flipped, transposed kernel (filter layout IOHW).
    Output spatial = (in-1)*stride + filter - 2*pad."""
    w = _match_conv_dtype(x, w)
    s = ctx.attr("strides", [1, 1])
    p = ctx.attr("paddings", [0, 0])
    wf = jnp.flip(w, axis=(2, 3)).transpose(1, 0, 2, 3)  # IOHW -> OIHW
    fh, fw = w.shape[2], w.shape[3]
    return jax.lax.conv_general_dilated(
        x, wf, window_strides=(1, 1),
        padding=[(fh - 1 - p[0], fh - 1 - p[0]),
                 (fw - 1 - p[1], fw - 1 - p[1])],
        lhs_dilation=tuple(s),
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        preferred_element_type=_conv_pet(x)).astype(x.dtype)


@primitive("conv3d", inputs=["Input", "Filter"], outputs=["Output"])
def conv3d(ctx, x, w):
    """NCDHW 3-D conv — capability of the reference's Conv3DLayer.cpp /
    DSL img_conv3d_layer (filter layout OIDHW).  One
    lax.conv_general_dilated call; XLA tiles 3-D convs onto the MXU the
    same way it does 2-D."""
    w = _match_conv_dtype(x, w)
    strides = tuple(ctx.attr("strides", [1, 1, 1]))
    p = ctx.attr("paddings", [0, 0, 0])
    dil = tuple(ctx.attr("dilations", [1, 1, 1]))
    groups = ctx.attr("groups", 1)
    return jax.lax.conv_general_dilated(
        x, w, window_strides=strides,
        padding=[(pi, pi) for pi in p],
        rhs_dilation=dil, feature_group_count=groups,
        dimension_numbers=("NCDHW", "OIDHW", "NCDHW"),
        preferred_element_type=_conv_pet(x)).astype(x.dtype)


def _ceil_extra_pad(in_size, k, s, p, ceil_mode):
    """End-padding beyond ``p`` so the last (partial) window is kept when
    ceil_mode — reference pooling's ceil output-shape rule."""
    if not ceil_mode:
        return 0
    out = -((in_size + 2 * p - k) // -s) + 1          # ceil div
    return max((out - 1) * s + k - (in_size + 2 * p), 0)


@primitive("pool3d")
def pool3d(ctx, x):
    """NCDHW 3-D pooling — reference Pool3DLayer.cpp / DSL
    img_pool3d_layer.  Average pooling uses exclusive counts like
    pool2d; ceil_mode keeps the trailing partial window (the
    img_pool3d_layer default)."""
    ptype = ctx.attr("pooling_type", "max")
    ceil_mode = ctx.attr("ceil_mode", False)
    if ctx.attr("global_pooling", False):
        ksize = list(x.shape[2:])
        strides, pads = ksize, [0, 0, 0]
        ceil_mode = False
    else:
        ksize = ctx.attr("ksize", [2, 2, 2])
        strides = ctx.attr("strides", [2, 2, 2])
        pads = ctx.attr("paddings", [0, 0, 0])
    window = (1, 1) + tuple(ksize)
    strides5 = (1, 1) + tuple(strides)
    padding = ((0, 0), (0, 0)) + tuple(
        (pi, pi + _ceil_extra_pad(x.shape[i + 2], ksize[i], strides[i],
                                  pi, ceil_mode))
        for i, pi in enumerate(pads))
    if ptype == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) \
            else jnp.iinfo(x.dtype).min
        return jax.lax.reduce_window(x, init, jax.lax.max, window,
                                     strides5, padding)
    total = jax.lax.reduce_window(x, 0.0, jax.lax.add, window, strides5,
                                  padding)
    if not any(pads) and not ceil_mode:
        return total / float(np.prod(ksize))
    count = jax.lax.reduce_window(jnp.ones_like(x), 0.0, jax.lax.add,
                                  window, strides5, padding)
    return total / count


@primitive("pool2d")
def pool2d(ctx, x):
    """reference pool_op.cc (operators/math/pooling.cc).  Average pooling
    uses exclusive counts (padding excluded), matching the reference."""
    ptype = ctx.attr("pooling_type", "max")
    ceil_mode = ctx.attr("ceil_mode", False)
    if ctx.attr("global_pooling", False):
        ksize = [x.shape[2], x.shape[3]]
        strides = ksize
        pads = [0, 0]
        ceil_mode = False
    else:
        ksize = ctx.attr("ksize", [2, 2])
        strides = ctx.attr("strides", [2, 2])
        pads = ctx.attr("paddings", [0, 0])
    window = (1, 1, ksize[0], ksize[1])
    strides4 = (1, 1, strides[0], strides[1])
    padding = ((0, 0), (0, 0)) + tuple(
        (pi, pi + _ceil_extra_pad(x.shape[i + 2], ksize[i], strides[i],
                                  pi, ceil_mode))
        for i, pi in enumerate(pads))
    if ptype == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
        return jax.lax.reduce_window(x, init, jax.lax.max, window, strides4,
                                     padding)
    total = jax.lax.reduce_window(x, 0.0, jax.lax.add, window, strides4,
                                  padding)
    if pads[0] == 0 and pads[1] == 0 and not ceil_mode:
        return total / (ksize[0] * ksize[1])
    ones = jnp.ones_like(x)
    count = jax.lax.reduce_window(ones, 0.0, jax.lax.add, window, strides4,
                                  padding)
    return total / count


@primitive("batch_norm",
           inputs=["X", "Scale", "Bias", "Mean", "Variance"],
           outputs=["Y", "MeanOut", "VarianceOut", "SavedMean",
                    "SavedVariance"],
           stop_grad_slots=("Mean", "Variance"))
def batch_norm(ctx, x, scale, bias, mean, variance):
    """reference batch_norm_op.cc.  Train: batch statistics + moving-average
    update (MeanOut/VarianceOut write back onto the same persistable vars).
    Test (is_test attr, set by Program.clone(for_test=True)): moving stats."""
    eps = ctx.attr("epsilon", 1e-5)
    momentum = ctx.attr("momentum", 0.9)
    is_test = ctx.attr("is_test", False) or ctx.mode == "infer"
    layout = ctx.attr("data_layout", "NCHW")
    axes = (0, 2, 3) if (x.ndim == 4 and layout == "NCHW") else \
        tuple(i for i in range(x.ndim) if i != x.ndim - 1) if x.ndim > 1 else (0,)
    shape = [1] * x.ndim
    c_axis = 1 if (x.ndim == 4 and layout == "NCHW") else x.ndim - 1
    shape[c_axis] = x.shape[c_axis]

    if is_test:
        bm, bv = mean, variance
        new_mean, new_var = mean, variance
    else:
        xf = x.astype(jnp.float32)
        bm = xf.mean(axis=axes)
        bv = xf.var(axis=axes)
        new_mean = momentum * mean + (1 - momentum) * bm
        new_var = momentum * variance + (1 - momentum) * bv
    inv = jax.lax.rsqrt(bv.astype(jnp.float32) + eps)
    y = (x.astype(jnp.float32) - bm.reshape(shape)) * inv.reshape(shape)
    y = y * scale.reshape(shape) + bias.reshape(shape)
    return (y.astype(x.dtype),
            jax.lax.stop_gradient(new_mean),
            jax.lax.stop_gradient(new_var),
            jax.lax.stop_gradient(bm),
            jax.lax.stop_gradient(inv))


@primitive("layer_norm", inputs=["X", "Scale?", "Bias?"],
           outputs=["Y", "Mean", "Variance"])
def layer_norm(ctx, x, scale, bias):
    """reference layer_norm_op.cc: normalize over dims [begin_norm_axis:)."""
    eps = ctx.attr("epsilon", 1e-5)
    axis = ctx.attr("begin_norm_axis", 1)
    lead = x.shape[:axis]
    x2 = x.reshape(*lead, -1).astype(jnp.float32)
    mu = x2.mean(axis=-1, keepdims=True)
    var = x2.var(axis=-1, keepdims=True)
    y = (x2 - mu) * jax.lax.rsqrt(var + eps)
    if scale is not None:
        y = y * scale.reshape(-1)
    if bias is not None:
        y = y + bias.reshape(-1)
    return (y.reshape(x.shape).astype(x.dtype),
            jax.lax.stop_gradient(mu.reshape(lead)),
            jax.lax.stop_gradient(var.reshape(lead)))


@primitive("dropout", outputs=["Out", "Mask"], seq_transparent=True)
def dropout(ctx, x):
    """reference dropout_op.cc.  The mask is derived from the op's salted RNG
    key; the vjp-recomputed backward regenerates the identical mask (see
    lowering.py) — no mask tensor needs saving.

    The per-element bits come from the counter-hash the attention kernels
    use (kernels/flash_attention.keep_scale), seeded by ONE scalar draw
    from the op's key: a full threefry tensor draw cost ~8% of the
    Transformer step (measured, BENCH_NOTES §9); the murmur-style
    finalizer is a handful of fused VPU ops per element and keeps the
    fwd/bwd-recompute determinism contract unchanged."""
    p = ctx.attr("dropout_prob", 0.5)
    if ctx.attr("is_test", False) or ctx.mode == "infer" or p == 0.0:
        return x, jnp.ones_like(x)
    from ...kernels.flash_attention import keep_scale

    seed = jax.random.bits(ctx.rng, (), jnp.uint32)
    idx = jax.lax.broadcasted_iota(jnp.int32, (x.size, 1), 0)
    scale = keep_scale(seed, jnp.uint32(0), idx, jnp.int32(0), float(p))
    scale = scale.reshape(x.shape).astype(x.dtype)
    # scale is {0, 1/(1-p)} (inverted dropout); Mask keeps the 0/1 view
    return x * scale, jax.lax.stop_gradient(
        (scale > 0).astype(x.dtype))


@primitive("l2_normalize")
def l2_normalize(ctx, x):
    axis = ctx.attr("axis", -1)
    eps = ctx.attr("epsilon", 1e-12)
    norm = jnp.sqrt((x * x).sum(axis=axis, keepdims=True) + eps)
    return x / norm


@primitive("nce", inputs=["Input", "Label", "Weight", "Bias"],
           outputs=["Cost"], stop_grad_slots=("Label",))
def nce(ctx, x, label, w, b):
    """Noise-contrastive estimation — reference nce_op.cc.  Uniform negative
    sampling from the op RNG; per-row BCE over 1 positive + k negatives."""
    k = ctx.attr("num_neg_samples", 10)
    n_classes = ctx.attr("num_total_classes")
    batch = x.shape[0]
    neg = jax.random.randint(ctx.rng, (batch, k), 0, n_classes)
    pos = label.reshape(batch, 1).astype(jnp.int32)
    ids = jnp.concatenate([pos, neg], axis=1)          # [b, 1+k]
    wj = jnp.take(w, ids, axis=0)                      # [b, 1+k, d]
    bj = jnp.take(b, ids, axis=0)                      # [b, 1+k]
    logits = jnp.einsum("bd,bkd->bk", x, wj) + bj
    labels = jnp.concatenate(
        [jnp.ones((batch, 1)), jnp.zeros((batch, k))], axis=1)
    loss = jnp.maximum(logits, 0) - logits * labels + jnp.log1p(
        jnp.exp(-jnp.abs(logits)))
    return loss.sum(axis=1, keepdims=True)


@primitive("im2sequence")
def im2sequence(ctx, x):
    """reference im2sequence_op.cc: image patches -> [b, n_patches, c*kh*kw]."""
    k = ctx.attr("kernels", [1, 1])
    s = ctx.attr("strides", [1, 1])
    p = ctx.attr("paddings", [0, 0])
    patches = jax.lax.conv_general_dilated_patches(
        x, filter_shape=tuple(k), window_strides=tuple(s),
        padding=[(p[0], p[0]), (p[1], p[1])],
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    b, f, oh, ow = patches.shape
    return patches.reshape(b, f, oh * ow).transpose(0, 2, 1)


def _attention_args(ctx):
    """The kernel arguments a ``fused_attention`` op and its grad op both
    read off their (shared) attributes and salted key: the grad op
    inherits ``__rng_salt__``, so its dropout seed — the in-kernel hash
    mask — is the forward's."""
    rate = ctx.attr("dropout_rate", 0.0)
    if ctx.attr("is_test", False) or ctx.mode == "infer":
        rate = 0.0
    seed = jax.random.bits(ctx.rng, (), jnp.uint32) if rate else None
    return dict(causal=ctx.attr("causal", False),
                sm_scale=ctx.attr("sm_scale", None),
                impl=ctx.attr("impl", None), dropout_rate=rate,
                dropout_seed=seed, layout=ctx.attr("layout", "bhld"))


def _attention_backward_half(q_shape, k_shape, has_bias, impl, layout):
    """``(direct, wants_lse)``: whether ``fused_attention_grad`` calls the
    kernel's backward half on its forward op's results
    (``kernels.flash_attention.backward_half``) and whether ``Lse`` is
    among them.  Not direct — ``jax.vjp`` over the forward emitter —
    under an active mesh (the sharded and sequence-parallel forms have
    no backward half of their own yet) and at lengths the kernel pads.
    Decided by what both ops of the pair see alike."""
    from ...kernels.flash_attention import backward_half
    from ...parallel import mesh as _pmesh

    if _pmesh.current_mesh() is not None:
        return False, False
    return backward_half(q_shape, k_shape, has_bias, impl, layout)


def _lse_shape(q_shape, layout):
    """[b*h, lq] of a q in either layout (negative where b is dynamic)."""
    seq, head = (1, 2) if layout == "blhd" else (2, 1)
    return [q_shape[0] * q_shape[head], q_shape[seq]]


def _attention_grad_inputs(op, block):
    """Grad maker: the grad op takes the forward's ``Out`` and, where the
    Pallas backward would read them, its row statistics — for which the
    forward op it is given gains the output ``Lse``, float32 [b*h, lq].
    Decided from the shapes alone, as for a TPU: the program is the same
    whatever host built it, and the lowering, which sees the backend and
    the mesh, leaves the slot unused where the statistics are not."""
    from ...kernels.flash_attention import backward_half

    out = block.var(op.desc.outputs["Out"][0])
    extra = {"Out": [out]}
    layout = op.attr("layout", "bhld")
    q, k = (block.var(op.desc.inputs[s][0]) for s in ("Q", "K"))
    if not q.shape or not k.shape:
        return extra
    shape = _lse_shape(q.shape, layout)
    if shape[1] < 0 or _lse_shape(k.shape, layout)[1] < 0:
        return extra
    if backward_half(q.shape, k.shape, bool(op.desc.inputs.get("Bias")),
                     op.attr("impl") or "pallas", layout)[1]:
        if not op.desc.outputs.get("Lse"):
            lse = block.create_var(
                name=out.name + "@LSE", dtype="float32",
                shape=shape if shape[0] > 0 else None, stop_gradient=True)
            lse.op = op
            op.desc.outputs["Lse"] = [lse.name]
        extra["Lse"] = [block.var(op.desc.outputs["Lse"][0])]
    return extra


@primitive("fused_attention", inputs=["Q", "K", "V", "Bias?"],
           outputs=["Out", "Lse?"], grad_maker=_attention_grad_inputs)
def fused_attention(ctx, q, k, v, bias):
    """Fused scaled-dot-product attention over [b, h, l, d] tensors.

    The TPU replacement for the reference's explicit matmul->softmax->matmul
    attention composition (its Transformer config builds [lq, lk] score
    tensors) — O(L) memory via the Pallas flash kernel
    (paddle_tpu/kernels/flash_attention.py).  With an active mesh that has a
    sequence axis, routes to a sequence-parallel strategy chosen by the
    sp_impl attr: ring attention over the ICI (kernels/ring_attention.py,
    default) or Ulysses all-to-all (kernels/ulysses_attention.py) —
    sequence parallelism the 2018 reference had no analog for.  Under any
    other mesh (data / tensor parallel) the kernel maps over the mesh's
    batch and head axes (flash_attention_sharded).

    ``Lse`` exists only on an op whose grad maker added the slot (a
    training program, where the Pallas backward will run): the row
    statistics [b*h, lq] in a training step that takes the direct route,
    and NaN in their place wherever ``fused_attention_grad`` will not
    read them (XLA drops the dead array).  Every other op — every
    inference program — is the primal-only call.
    """
    from ...kernels import flash_attention as _flash
    from ...kernels import flash_attention_sharded as _flash_sharded
    from ...kernels import ring_attention_sharded as _ring
    from ...kernels import ulysses_attention_sharded as _ulysses
    from ...kernels.flash_attention import flash_attention_stats

    kw = _attention_args(ctx)
    layout = kw["layout"]
    lse = None
    if ctx.op.outputs.get("Lse"):
        if ctx.mode == "train" and _attention_backward_half(
                q.shape, k.shape, bias is not None, kw["impl"], layout)[1]:
            return flash_attention_stats(q, k, v, **kw)
        lse = jnp.full(_lse_shape(q.shape, layout), jnp.nan, jnp.float32)
    from ...parallel import mesh as _pmesh

    mesh = _pmesh.current_mesh()
    if ctx.attr("seq_parallel", False) and mesh is not None \
            and "sp" in mesh.axis_names:
        # strategy: "ring" rotates k/v shards (scales past the head
        # count); "ulysses" re-shards seq<->heads with two all-to-alls
        # (wins when ring-step latency dominates; needs heads % sp == 0)
        sp_impl = ctx.attr("sp_impl", "ring")
        if sp_impl not in ("ring", "ulysses"):
            raise ValueError(
                f"fused_attention: sp_impl must be 'ring' or 'ulysses', "
                f"got {sp_impl!r}")
        shard_fn = _ring if sp_impl == "ring" else _ulysses
        if layout == "blhd":  # sp shards the seq axis of [b, h, l, d]
            q, k, v = (jnp.transpose(x, (0, 2, 1, 3)) for x in (q, k, v))
        out = shard_fn(mesh, q, k, v, bias=bias, dp_axis="dp",
                       mp_axis="mp", sp_axis="sp",
                       **{a: kw[a] for a in kw if a != "layout"})
        if layout == "blhd":
            out = jnp.transpose(out, (0, 2, 1, 3))
        return out, lse
    if mesh is not None:
        b_ax, h_ax = _pmesh.kernel_axes(
            mesh, batch=q.shape[0],
            heads=q.shape[2 if layout == "blhd" else 1])
        return _flash_sharded(mesh, q, k, v, bias, batch_axis=b_ax,
                              head_axis=h_ax, **kw), lse
    return _flash(q, k, v, bias=bias, **kw), lse


def _emit_fused_attention_grad(ctx, ins):
    """Hand-written adjoint of ``fused_attention`` (preempts the generic
    vjp, which would run the flash forward kernel a second time to get
    what the forward op already produced): the kernel's backward half on
    the forward's own ``Out`` and, at Pallas-backward lengths, ``Lse``.
    Where ``_attention_backward_half`` says the direct route does not apply —
    or the program was built without the slots it reads — the generic vjp
    over the forward emitter, as for any other op."""
    from ...kernels.flash_attention import flash_attention_grad
    from ..lowering import _emit_generic_grad

    q, k, v = (ins[s][0] for s in ("Q", "K", "V"))
    bias, out, lse = (ins.get(s, [None])[0] for s in ("Bias", "Out", "Lse"))
    kw = _attention_args(ctx)
    direct, use_lse = False, False
    if out is not None:
        direct, use_lse = _attention_backward_half(
            q.shape, k.shape, bias is not None, kw["impl"], kw["layout"])
    if not direct or (use_lse and (lse is None or ctx.mode != "train")):
        ctx.note("attn_grad", route="vjp", lse=False)
        return _emit_generic_grad(
            ctx, ctx.op,
            {s: vals for s, vals in ins.items() if s not in ("Out", "Lse")})
    ctx.note("attn_grad", route="direct", lse=use_lse)
    grads = flash_attention_grad(
        q, k, v, bias, out, lse if use_lse else None,
        ins["Out@GRAD"][0].astype(out.dtype), **kw)
    return {slot + "@GRAD": [g] for slot, g in
            zip(("Q", "K", "V", "Bias"), grads) if g is not None}


register(OpInfo("fused_attention_grad", _emit_fused_attention_grad,
                no_grad=True, doc=_emit_fused_attention_grad.__doc__))
