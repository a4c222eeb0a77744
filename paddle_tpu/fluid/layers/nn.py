"""Rich neural-net layers — analog of python/paddle/v2/fluid/layers/nn.py
(fc:71, embedding:192, conv2d:1135, pool2d:1424, batch_norm:1473,
dropout, cross_entropy, accuracy, topk, reduce_*:1953+, matmul:2278, ...).

Each layer appends ops to the current block via LayerHelper, exactly like the
reference; the ops themselves lower to XLA (see ops/)."""

from __future__ import annotations

from typing import List, Optional, Sequence, Union

from ..framework import Variable
from ..layer_helper import LayerHelper
from ..param_attr import ParamAttr

__all__ = [
    "fc", "embedding", "dropout", "cross_entropy", "square_error_cost",
    "sigmoid_cross_entropy_with_logits", "cos_sim",
    "accuracy", "auc", "topk", "conv2d", "conv2d_transpose", "pool2d",
    "batch_norm", "layer_norm", "reduce_sum", "reduce_mean", "reduce_max",
    "reduce_min", "reduce_prod", "reshape", "transpose", "matmul", "one_hot",
    "softmax_with_cross_entropy", "smooth_l1", "l2_normalize", "split",
    "nce", "im2sequence", "beam_search", "beam_search_decode", "batch_gather",
    "gather", "expand", "multiplex", "fused_attention", "decode_attention",
    "ragged_decode_attention", "rms_norm", "rotary_embedding", "swiglu",
    "sigmoid_gate",
    "routed_experts", "gated_ffn", "latent_absorb", "vocab_logits",
    "quantize", "dequantize", "quantized_mul",
    "quantized_matmul", "quantized_conv2d",
    "pad", "crop", "lod_reset", "lrn", "label_smooth", "rank_loss",
    "margin_rank_loss", "log_loss", "conv_shift", "row_conv",
    "dynamic_lstmp", "roi_pool", "spp", "unpool", "prior_box",
    "bipartite_match", "multiclass_nms", "max_pool2d_with_index",
    "fused_vocab_cross_entropy", "maxout", "squeeze", "unsqueeze",
    "hsigmoid", "sampling_id", "bilinear_interp", "prelu",
    "ssd_loss", "conv3d", "pool3d", "selective_fc", "scale_sub_region",
    "cross_entropy_with_selfnorm", "cross_entropy_over_beam",
    "rotate", "detection_output", "switch_moe",
]


def fc(input, size, num_flatten_dims=1, param_attr=None, bias_attr=None,
       act=None, name=None, main_program=None, startup_program=None,
       use_mkldnn=False):
    """Fully connected — reference layers/nn.py fc:71.  Multiple inputs each
    get their own weight (mul op); partial sums are added; bias + activation
    follow.  The mul ops map straight onto the MXU."""
    helper = LayerHelper("fc", input=input, param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name,
                         main_program=main_program,
                         startup_program=startup_program)
    dtype = helper.input_dtype()
    mul_results = []
    for input_var in helper.multiple_input():
        input_shape = input_var.shape
        if input_var.lod_level > 0:
            # padded seq input [b, t, f...]: weight covers feature dims
            flat = input_shape[1:]
            num_flat = num_flatten_dims + 1
        else:
            flat = input_shape[num_flatten_dims:]
            num_flat = num_flatten_dims
        import numpy as np

        in_features = int(np.prod(flat))
        w = helper.create_parameter(helper.param_attr,
                                    shape=[in_features, size], dtype=dtype)
        tmp = helper.create_tmp_variable(dtype,
                                         lod_level=input_var.lod_level)
        helper.append_op("mul", {"X": input_var, "Y": w}, {"Out": tmp},
                         {"x_num_col_dims": num_flat, "y_num_col_dims": 1})
        mul_results.append(tmp)
    if len(mul_results) == 1:
        pre_bias = mul_results[0]
    else:
        pre_bias = helper.create_tmp_variable(dtype)
        helper.append_op("sum", {"X": mul_results}, {"Out": pre_bias})
    lod = pre_bias.lod_level
    # bias is always [size], broadcast on the last (feature) axis: that is
    # num_flatten_dims for dense inputs (reference fc dim_start), +1 for
    # the implicit time axis of padded sequence inputs
    pre_act = helper.append_bias_op(pre_bias,
                                    dim_start=num_flatten_dims + (1 if lod else 0),
                                    bias_shape=[size])
    return helper.append_activation(pre_act)


def embedding(input, size, is_sparse=False, padding_idx=None,
              param_attr=None, dtype="float32", name=None,
              main_program=None, startup_program=None):
    """Embedding lookup — reference layers/nn.py embedding:192.  is_sparse
    selects the SelectedRows gradient path (rows+values of the looked-up
    ids only — no dense [vocab, dim] scatter), exactly like the reference's
    lookup_table_op SelectedRows grad; sgd/adagrad apply it as an exact row
    scatter, momentum/adam as lazy row-sparse moment updates (reference
    ParameterServer2.h:243-344 capability), and the remaining optimizers
    densify."""
    helper = LayerHelper("embedding", param_attr=param_attr, name=name,
                         main_program=main_program,
                         startup_program=startup_program)
    w = helper.create_parameter(helper.param_attr, shape=list(size),
                                dtype=dtype)
    out = helper.create_tmp_variable(dtype, lod_level=input.lod_level)
    attrs = {"is_sparse": bool(is_sparse)}
    if padding_idx is not None:
        attrs["padding_idx"] = int(padding_idx)
    helper.append_op("lookup_table", {"W": w, "Ids": input}, {"Out": out},
                     attrs)
    return out


def dropout(x, dropout_prob, is_test=False, seed=None, name=None):
    helper = LayerHelper("dropout", name=name)
    out = helper.create_tmp_variable(x.dtype, lod_level=x.lod_level)
    helper.append_op("dropout", {"X": x}, {"Out": out},
                     {"dropout_prob": float(dropout_prob),
                      "is_test": is_test})
    return out


def cross_entropy(input, label, soft_label=False, name=None):
    helper = LayerHelper("cross_entropy", name=name)
    out = helper.create_tmp_variable(input.dtype,
                                     lod_level=input.lod_level)
    helper.append_op("cross_entropy", {"X": input, "Label": label},
                     {"Out": out}, {"soft_label": soft_label})
    return out


def softmax_with_cross_entropy(logits, label, soft_label=False):
    helper = LayerHelper("softmax_with_cross_entropy")
    softmax = helper.create_tmp_variable(logits.dtype)
    loss = helper.create_tmp_variable(logits.dtype)
    helper.append_op("softmax_with_cross_entropy",
                     {"Logits": logits, "Label": label},
                     {"Softmax": softmax, "Loss": loss},
                     {"soft_label": soft_label})
    return loss


def sigmoid_cross_entropy_with_logits(x, label, name=None):
    """Per-element binary CE on logits — reference
    sigmoid_cross_entropy_with_logits_op.cc / layers usage in CTR nets."""
    helper = LayerHelper("sigmoid_cross_entropy_with_logits", name=name)
    out = helper.create_tmp_variable(x.dtype, lod_level=x.lod_level)
    helper.append_op("sigmoid_cross_entropy_with_logits",
                     {"X": x, "Label": label}, {"Out": out})
    return out


def square_error_cost(input, label, name=None):
    helper = LayerHelper("square_error_cost", name=name)
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op("square_error_cost", {"X": input, "Y": label},
                     {"Out": out})
    return out


def cos_sim(X, Y, name=None):
    """Row-wise cosine similarity — reference layers cos_sim (cos_sim_op.cc)."""
    helper = LayerHelper("cos_sim", name=name)
    out = helper.create_tmp_variable(X.dtype)
    xnorm = helper.create_tmp_variable(X.dtype, stop_gradient=True)
    ynorm = helper.create_tmp_variable(X.dtype, stop_gradient=True)
    helper.append_op("cos_sim", {"X": X, "Y": Y},
                     {"Out": out, "XNorm": xnorm, "YNorm": ynorm})
    return out


def smooth_l1(x, y, sigma=1.0):
    helper = LayerHelper("smooth_l1")
    diff = helper.create_tmp_variable(x.dtype)
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op("smooth_l1_loss", {"X": x, "Y": y},
                     {"Diff": diff, "Out": out}, {"sigma": sigma})
    return out


def accuracy(input, label, k=1, correct=None, total=None, **kw):
    """reference layers/nn.py accuracy — top-k accuracy via top_k op."""
    helper = LayerHelper("accuracy")
    topk_out = helper.create_tmp_variable(input.dtype, stop_gradient=True)
    topk_indices = helper.create_tmp_variable("int32", stop_gradient=True)
    helper.append_op("top_k", {"X": input},
                     {"Out": topk_out, "Indices": topk_indices}, {"k": k})
    acc_out = helper.create_tmp_variable("float32", stop_gradient=True)
    correct = correct or helper.create_tmp_variable("int32",
                                                    stop_gradient=True)
    total = total or helper.create_tmp_variable("int32", stop_gradient=True)
    helper.append_op("accuracy",
                     {"Out": topk_out, "Indices": topk_indices,
                      "Label": label},
                     {"Accuracy": acc_out, "Correct": correct,
                      "Total": total})
    return acc_out


def auc(input, label, curve="ROC", num_thresholds=200, topk=1):
    helper = LayerHelper("auc")
    topk_out = helper.create_tmp_variable(input.dtype, stop_gradient=True)
    topk_indices = helper.create_tmp_variable("int32", stop_gradient=True)
    helper.append_op("top_k", {"X": input},
                     {"Out": topk_out, "Indices": topk_indices}, {"k": topk})
    out = helper.create_tmp_variable("float32", stop_gradient=True)
    helper.append_op("auc", {"Out": input, "Indices": topk_indices,
                             "Label": label}, {"AUC": out},
                     {"curve": curve, "num_thresholds": num_thresholds})
    return out


def topk(input, k=1):
    helper = LayerHelper("top_k")
    vals = helper.create_tmp_variable(input.dtype, stop_gradient=True)
    idx = helper.create_tmp_variable("int32", stop_gradient=True)
    helper.append_op("top_k", {"X": input}, {"Out": vals, "Indices": idx},
                     {"k": k})
    return vals, idx


def one_hot(input, depth):
    helper = LayerHelper("one_hot")
    out = helper.create_tmp_variable("float32")
    helper.append_op("one_hot", {"X": input}, {"Out": out}, {"depth": depth})
    return out


def conv2d(input, num_filters, filter_size, stride=1, padding=0, groups=1,
           dilation=1, param_attr=None, bias_attr=None, act=None,
           use_cudnn=True, name=None, main_program=None,
           startup_program=None):
    """2-D convolution (NCHW) — reference layers/nn.py conv2d:1135 /
    conv_op.cc; lowers to lax.conv_general_dilated which XLA tiles onto the
    MXU (the reference needed im2col+gemm or cuDNN)."""
    helper = LayerHelper("conv2d", param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name,
                         main_program=main_program,
                         startup_program=startup_program)
    dtype = input.dtype
    stride = _pair(stride)
    padding = _pair(padding)
    dilation = _pair(dilation)
    fsize = _pair(filter_size)
    num_channels = input.shape[1]
    filter_shape = [num_filters, num_channels // groups] + list(fsize)

    import numpy as np

    from ..initializer import NormalInitializer

    std = (2.0 / (fsize[0] * fsize[1] * num_channels)) ** 0.5
    w = helper.create_parameter(helper.param_attr, shape=filter_shape,
                                dtype=dtype,
                                default_initializer=NormalInitializer(0.0, std))
    pre_bias = helper.create_tmp_variable(dtype)
    helper.append_op("conv2d", {"Input": input, "Filter": w},
                     {"Output": pre_bias},
                     {"strides": stride, "paddings": padding,
                      "dilations": dilation, "groups": groups})
    pre_act = _append_channel_bias(helper, pre_bias)
    return helper.append_activation(pre_act)


def conv2d_transpose(input, num_filters, output_size=None, filter_size=None,
                     padding=0, stride=1, dilation=1, param_attr=None,
                     bias_attr=None, act=None, name=None):
    """reference conv2d_transpose:1574 / conv_transpose_op.cc."""
    helper = LayerHelper("conv2d_transpose", param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    dtype = input.dtype
    stride = _pair(stride)
    padding = _pair(padding)
    fsize = _pair(filter_size)
    in_channels = input.shape[1]
    filter_shape = [in_channels, num_filters] + list(fsize)
    w = helper.create_parameter(helper.param_attr, shape=filter_shape,
                                dtype=dtype)
    pre_bias = helper.create_tmp_variable(dtype)
    helper.append_op("conv2d_transpose", {"Input": input, "Filter": w},
                     {"Output": pre_bias},
                     {"strides": stride, "paddings": padding,
                      "dilations": _pair(dilation)})
    pre_act = _append_channel_bias(helper, pre_bias)
    return helper.append_activation(pre_act)


def pool2d(input, pool_size=-1, pool_type="max", pool_stride=1,
           pool_padding=0, global_pooling=False, use_cudnn=True,
           ceil_mode=False, name=None, main_program=None,
           startup_program=None):
    """reference pool2d:1424 / pool_op.cc."""
    helper = LayerHelper("pool2d", name=name, main_program=main_program,
                         startup_program=startup_program)
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op("pool2d", {"X": input}, {"Out": out},
                     {"pooling_type": pool_type,
                      "ksize": _pair(pool_size),
                      "strides": _pair(pool_stride),
                      "paddings": _pair(pool_padding),
                      "global_pooling": global_pooling,
                      "ceil_mode": ceil_mode})
    return out


def batch_norm(input, act=None, is_test=False, momentum=0.9, epsilon=1e-5,
               param_attr=None, bias_attr=None, data_layout="NCHW",
               name=None, moving_mean_name=None, moving_variance_name=None,
               main_program=None, startup_program=None):
    """reference batch_norm:1473 / batch_norm_op.cc.  Moving stats are
    persistable state vars updated functionally by the op."""
    from ..initializer import ConstantInitializer

    helper = LayerHelper("batch_norm", param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name,
                         main_program=main_program,
                         startup_program=startup_program)
    dtype = input.dtype
    channels = input.shape[1] if data_layout == "NCHW" else input.shape[-1]
    pshape = [channels]
    scale = helper.create_parameter(
        helper.param_attr, shape=pshape, dtype=dtype,
        default_initializer=ConstantInitializer(1.0), suffix="scale")
    bias = helper.create_parameter(helper.bias_attr or ParamAttr(),
                                   shape=pshape, dtype=dtype, is_bias=True,
                                   suffix="offset")
    mean = helper.create_global_variable(
        shape=pshape, dtype=dtype, persistable=True,
        name=moving_mean_name)
    helper.set_variable_initializer(mean, ConstantInitializer(0.0))
    variance = helper.create_global_variable(
        shape=pshape, dtype=dtype, persistable=True,
        name=moving_variance_name)
    helper.set_variable_initializer(variance, ConstantInitializer(1.0))

    saved_mean = helper.create_tmp_variable(dtype, stop_gradient=True)
    saved_var = helper.create_tmp_variable(dtype, stop_gradient=True)
    out = helper.create_tmp_variable(dtype)
    helper.append_op(
        "batch_norm",
        {"X": input, "Scale": scale, "Bias": bias, "Mean": mean,
         "Variance": variance},
        {"Y": out, "MeanOut": mean, "VarianceOut": variance,
         "SavedMean": saved_mean, "SavedVariance": saved_var},
        {"momentum": momentum, "epsilon": epsilon, "is_test": is_test,
         "data_layout": data_layout})
    return helper.append_activation(out)


def layer_norm(input, scale=True, shift=True, begin_norm_axis=1,
               epsilon=1e-5, param_attr=None, bias_attr=None, act=None,
               name=None):
    """reference layer_norm_op.cc."""
    from ..initializer import ConstantInitializer
    import numpy as np

    helper = LayerHelper("layer_norm", param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    dtype = input.dtype
    norm_shape = [int(np.prod(input.shape[begin_norm_axis:]))]
    inputs = {"X": input}
    if scale:
        inputs["Scale"] = helper.create_parameter(
            helper.param_attr, shape=norm_shape, dtype=dtype,
            default_initializer=ConstantInitializer(1.0), suffix="scale")
    if shift:
        inputs["Bias"] = helper.create_parameter(
            helper.bias_attr or ParamAttr(), shape=norm_shape,
            dtype=dtype, is_bias=True)
    out = helper.create_tmp_variable(dtype, lod_level=input.lod_level)
    mean = helper.create_tmp_variable(dtype, stop_gradient=True)
    var = helper.create_tmp_variable(dtype, stop_gradient=True)
    helper.append_op("layer_norm", inputs,
                     {"Y": out, "Mean": mean, "Variance": var},
                     {"epsilon": epsilon, "begin_norm_axis": begin_norm_axis})
    return helper.append_activation(out)


def _make_reduce(op_type):
    def layer(input, dim=None, keep_dim=False, name=None):
        helper = LayerHelper(op_type, name=name)
        out = helper.create_tmp_variable(input.dtype)
        attrs = {"keep_dim": keep_dim, "reduce_all": dim is None}
        if dim is not None:
            attrs["dim"] = dim if isinstance(dim, (list, tuple)) else [dim]
        helper.append_op(op_type, {"X": input}, {"Out": out}, attrs)
        return out

    layer.__name__ = op_type
    return layer


reduce_sum = _make_reduce("reduce_sum")
reduce_mean = _make_reduce("reduce_mean")
reduce_max = _make_reduce("reduce_max")
reduce_min = _make_reduce("reduce_min")
reduce_prod = _make_reduce("reduce_prod")


def reshape(x, shape, act=None, name=None):
    helper = LayerHelper("reshape", name=name, act=act)
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op("reshape", {"X": x}, {"Out": out},
                     {"shape": list(shape)})
    return helper.append_activation(out)


def transpose(x, perm, name=None):
    helper = LayerHelper("transpose", name=name)
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op("transpose", {"X": x}, {"Out": out},
                     {"axis": list(perm)})
    return out


def hsigmoid(input, label, num_classes, param_attr=None, bias_attr=None,
             name=None):
    """Hierarchical sigmoid classification cost over the default
    complete binary tree (reference gserver HierarchicalSigmoidLayer +
    math/MatrixBitCode SimpleCode) — O(log C) per sample instead of a
    C-wide softmax.  Returns the per-row cost [B, 1]."""
    helper = LayerHelper("hsigmoid", param_attr=param_attr,
                         bias_attr=bias_attr, name=name)
    dtype = input.dtype
    feat = input.shape[-1]
    w = helper.create_parameter(helper.param_attr,
                                shape=[num_classes - 1, feat], dtype=dtype)
    inputs = {"X": input, "Label": label, "W": w}
    if bias_attr is not False:
        b = helper.create_parameter(helper.bias_attr or ParamAttr(),
                                    shape=[num_classes - 1], dtype=dtype,
                                    is_bias=True)
        inputs["Bias"] = b
    out = helper.create_tmp_variable(dtype)
    helper.append_op("hsigmoid", inputs, {"Out": out},
                     {"num_classes": int(num_classes)})
    return out


def sampling_id(x, name=None):
    """Sample one class id per row from a probability row (reference
    gserver SamplingIdLayer — generation-time stochastic pick)."""
    helper = LayerHelper("sampling_id", name=name)
    out = helper.create_tmp_variable("int32", stop_gradient=True)
    helper.append_op("sampling_id", {"X": x}, {"Out": out}, {})
    return out


def bilinear_interp(input, out_h, out_w, name=None):
    """Bilinear upsampling of [B, C, H, W] with the reference's
    align-corners ratio (gserver BilinearInterpLayer)."""
    helper = LayerHelper("bilinear_interp", name=name)
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op("bilinear_interp", {"X": input}, {"Out": out},
                     {"out_h": int(out_h), "out_w": int(out_w)})
    return out


def prelu(x, mode="all", param_attr=None, name=None):
    """Parametric ReLU with a LEARNED negative slope (reference gserver
    ParameterReluLayer / trainer_config_helpers prelu_layer).  mode:
    'all' one shared alpha, 'channel' one per channel (NCHW dim 1),
    'element' one per feature element."""
    from ..initializer import ConstantInitializer

    helper = LayerHelper("prelu", param_attr=param_attr, name=name)
    if mode == "all":
        shape = [1]
    elif mode == "channel":
        shape = [x.shape[1]]
    elif mode == "element":
        shape = list(x.shape[1:])
    else:
        raise ValueError(f"prelu: unknown mode {mode!r}")
    alpha = helper.create_parameter(
        helper.param_attr, shape=shape, dtype=x.dtype,
        default_initializer=ConstantInitializer(0.25))
    out = helper.create_tmp_variable(x.dtype, lod_level=x.lod_level)
    helper.append_op("prelu", {"X": x, "Alpha": alpha}, {"Out": out},
                     {"mode": mode})
    return out


def squeeze(input, axes, name=None):
    """reference squeeze_op.cc — drop size-1 dims at ``axes``."""
    helper = LayerHelper("squeeze", name=name)
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op("squeeze", {"X": input}, {"Out": out},
                     {"axes": list(axes)})
    return out


def unsqueeze(input, axes, name=None):
    """reference unsqueeze_op.cc — insert size-1 dims at ``axes``."""
    helper = LayerHelper("unsqueeze", name=name)
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op("unsqueeze", {"X": input}, {"Out": out},
                     {"axes": list(axes)})
    return out


def matmul(x, y, transpose_x=False, transpose_y=False, alpha=1.0, name=None):
    helper = LayerHelper("matmul", name=name)
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op("matmul", {"X": x, "Y": y}, {"Out": out},
                     {"transpose_X": transpose_x, "transpose_Y": transpose_y,
                      "alpha": float(alpha)})
    return out


def split(input, num_or_sections, dim=-1, name=None):
    helper = LayerHelper("split", name=name)
    dim = dim if dim >= 0 else dim + len(input.shape)
    if isinstance(num_or_sections, int):
        num = num_or_sections
        attrs = {"num": num, "axis": dim}
    else:
        num = len(num_or_sections)
        attrs = {"sections": list(num_or_sections), "axis": dim}
    outs = [helper.create_tmp_variable(input.dtype) for _ in range(num)]
    helper.append_op("split", {"X": input}, {"Out": outs}, attrs)
    return outs


def l2_normalize(x, axis=-1, epsilon=1e-12, name=None):
    helper = LayerHelper("l2_normalize", name=name)
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op("l2_normalize", {"X": x}, {"Out": out},
                     {"axis": axis, "epsilon": epsilon})
    return out


def nce(input, label, num_total_classes, sample_weight=None, param_attr=None,
        bias_attr=None, num_neg_samples=None, name=None):
    """Noise-contrastive estimation — reference nce_op.cc.  Samples negatives
    inside the op with the executor-threaded RNG."""
    helper = LayerHelper("nce", param_attr=param_attr, bias_attr=bias_attr,
                         name=name)
    dim = input.shape[1]
    w = helper.create_parameter(helper.param_attr,
                                shape=[num_total_classes, dim],
                                dtype=input.dtype)
    b = helper.create_parameter(helper.bias_attr or ParamAttr(),
                                shape=[num_total_classes], dtype=input.dtype,
                                is_bias=True)
    cost = helper.create_tmp_variable(input.dtype)
    helper.append_op("nce", {"Input": input, "Label": label,
                             "Weight": w, "Bias": b}, {"Cost": cost},
                     {"num_total_classes": num_total_classes,
                      "num_neg_samples": num_neg_samples or 10})
    return cost


def im2sequence(input, filter_size=1, stride=1, padding=0, name=None):
    helper = LayerHelper("im2sequence", name=name)
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op("im2sequence", {"X": input}, {"Out": out},
                     {"kernels": _pair(filter_size),
                      "strides": _pair(stride), "paddings": _pair(padding)})
    return out


def beam_search(pre_ids, pre_scores, ids, scores, beam_size, end_id,
                level=0, is_accumulated=False, name=None):
    """One beam-search step — reference layers/nn.py beam_search:1801 /
    beam_search_op.cc, re-laid-out on a dense [batch, beam] grid (see
    ops/beam_ops.py).  Returns (selected_ids, selected_scores, parent_idx);
    the extra parent_idx output replaces the LoD ancestry encoding."""
    helper = LayerHelper("beam_search", name=name)
    sel_ids = helper.create_tmp_variable(pre_ids.dtype)
    sel_scores = helper.create_tmp_variable("float32")
    parent = helper.create_tmp_variable("int32")
    sel_ids.stop_gradient = parent.stop_gradient = True
    helper.append_op(
        "beam_search",
        {"pre_ids": pre_ids, "pre_scores": pre_scores, "ids": ids,
         "scores": scores},
        {"selected_ids": sel_ids, "selected_scores": sel_scores,
         "parent_idx": parent},
        {"beam_size": beam_size, "end_id": end_id, "level": level,
         "is_accumulated": is_accumulated})
    return sel_ids, sel_scores, parent


def beam_search_decode(ids, scores, parents, end_id, name=None):
    """Backtrace beam arrays into ranked hypotheses — reference
    beam_search_decode_op.cc (LoD backtrace becomes a reverse scan over the
    explicit parent pointers)."""
    helper = LayerHelper("beam_search_decode", name=name)
    sent_ids = helper.create_tmp_variable(ids.dtype)
    sent_scores = helper.create_tmp_variable("float32")
    sent_ids.stop_gradient = sent_scores.stop_gradient = True
    helper.append_op(
        "beam_search_decode",
        {"Ids": ids, "Scores": scores, "Parents": parents},
        {"SentenceIds": sent_ids, "SentenceScores": sent_scores},
        {"end_id": end_id})
    return sent_ids, sent_scores


def batch_gather(x, index, name=None):
    """out[b, j] = x[b, index[b, j]] — the dense-beam state reorder (the
    reference reorders decoder state via LoD sequence_expand instead)."""
    helper = LayerHelper("batch_gather", name=name)
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op("batch_gather", {"X": x, "Index": index}, {"Out": out})
    return out


def gather(input, index, name=None):
    """reference gather_op.cc — rows of input by index."""
    helper = LayerHelper("gather", name=name)
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op("gather", {"X": input, "Index": index}, {"Out": out})
    return out


def expand(x, expand_times, name=None):
    """reference expand_op.cc — tile each dim expand_times[i] times."""
    helper = LayerHelper("expand", name=name)
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op("expand", {"X": x}, {"Out": out},
                     {"expand_times": list(expand_times)})
    return out


def multiplex(inputs, index, name=None):
    """reference multiplex_op.cc — per-row select among candidate tensors."""
    helper = LayerHelper("multiplex", name=name)
    out = helper.create_tmp_variable(inputs[0].dtype)
    helper.append_op("multiplex", {"Ids": index, "X": inputs}, {"Out": out})
    return out


def _pair(x):
    if isinstance(x, (list, tuple)):
        return list(x)
    return [x, x]


def _triple(x):
    if isinstance(x, (list, tuple)):
        return list(x)
    return [x, x, x]


def conv3d(input, num_filters, filter_size, stride=1, padding=0, groups=1,
           dilation=1, param_attr=None, bias_attr=None, act=None,
           name=None):
    """3-D convolution (NCDHW) — capability of the reference's
    Conv3DLayer.cpp / DSL img_conv3d_layer; one lax.conv_general_dilated
    (see ops/nn_ops.py conv3d)."""
    from ..initializer import NormalInitializer

    helper = LayerHelper("conv3d", param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    dtype = input.dtype
    stride, padding = _triple(stride), _triple(padding)
    dilation, fsize = _triple(dilation), _triple(filter_size)
    num_channels = input.shape[1]
    filter_shape = [num_filters, num_channels // groups] + list(fsize)
    import numpy as np

    std = (2.0 / (np.prod(fsize) * num_channels)) ** 0.5
    w = helper.create_parameter(
        helper.param_attr, shape=filter_shape, dtype=dtype,
        default_initializer=NormalInitializer(0.0, float(std)))
    pre_bias = helper.create_tmp_variable(dtype)
    helper.append_op("conv3d", {"Input": input, "Filter": w},
                     {"Output": pre_bias},
                     {"strides": stride, "paddings": padding,
                      "dilations": dilation, "groups": groups})
    pre_act = _append_channel_bias(helper, pre_bias)
    return helper.append_activation(pre_act)


def pool3d(input, pool_size=-1, pool_type="max", pool_stride=1,
           pool_padding=0, global_pooling=False, ceil_mode=False,
           name=None):
    """3-D pooling (NCDHW) — reference Pool3DLayer.cpp / DSL
    img_pool3d_layer."""
    helper = LayerHelper("pool3d", name=name)
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op("pool3d", {"X": input}, {"Out": out},
                     {"pooling_type": pool_type,
                      "ksize": _triple(pool_size),
                      "strides": _triple(pool_stride),
                      "paddings": _triple(pool_padding),
                      "global_pooling": global_pooling,
                      "ceil_mode": ceil_mode})
    return out


def selective_fc(input, size, select=None, act=None, param_attr=None,
                 bias_attr=None, name=None):
    """Selective fc — reference SelectiveFullyConnectedLayer.cpp / DSL
    selective_fc_layer: with ``select`` ([B, k] column ids, -1 padded)
    only the selected output columns are computed ([B, k] dense);
    without it this is exactly ``fc``."""
    if select is None:
        return fc(input, size, act=act, param_attr=param_attr,
                  bias_attr=bias_attr, name=name)
    helper = LayerHelper("selective_fc", param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    dtype = input.dtype
    in_features = int(input.shape[-1])
    w = helper.create_parameter(helper.param_attr,
                                shape=[in_features, size], dtype=dtype)
    inputs = {"X": input, "W": w, "Select": select}
    if helper.bias_attr is not None:
        inputs["Bias"] = helper.create_parameter(
            helper.bias_attr, shape=[size], dtype=dtype, is_bias=True)
    out = helper.create_tmp_variable(dtype)
    helper.append_op("selective_fc", inputs, {"Out": out})
    return helper.append_activation(out)


def scale_sub_region(input, indices, value, name=None):
    """Scale a per-sample CHW sub-region by ``value`` — reference
    function/ScaleSubRegionOp.cpp / DSL scale_sub_region_layer.
    ``indices`` [B, 6] 1-based inclusive [c0, c1, h0, h1, w0, w1]."""
    helper = LayerHelper("scale_sub_region", name=name)
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op("scale_sub_region",
                     {"X": input, "Indices": indices}, {"Out": out},
                     {"value": float(value)})
    return out


def rotate(x, name=None):
    """Rotate each [H, W] feature map 90 degrees clockwise — reference
    RotateLayer.cpp (see ops/misc_ops.py rotate)."""
    return _single_out_layer("rotate", {"X": x}, {}, name=name)


def detection_output(loc, conf, prior_box, prior_var,
                     background_id=0, nms_threshold=0.45, nms_top_k=400,
                     keep_top_k=200, confidence_threshold=0.01,
                     name=None):
    """SSD inference head — decode loc predictions against the priors,
    softmax confidences, per-class NMS (reference
    DetectionOutputLayer.cpp; see ops/detection_ops.py)."""
    return _single_out_layer(
        "detection_output",
        {"Location": loc, "Confidence": conf, "PriorBox": prior_box,
         "PriorVar": prior_var},
        {"background_id": int(background_id),
         "nms_threshold": float(nms_threshold),
         "nms_top_k": int(nms_top_k), "keep_top_k": int(keep_top_k),
         "confidence_threshold": float(confidence_threshold)},
        stop_gradient=True, name=name)


def switch_moe(input, num_experts, d_hidden, capacity_factor=1.25,
               act="relu", param_attr=None, name=None):
    """Switch-Transformer MoE FFN layer — top-1 capacity-bounded
    routing over ``num_experts`` two-matmul experts (ops/moe_ops.py).
    Under a mesh with an 'ep' axis of size num_experts the experts
    shard one-per-device (parallel.switch_moe_call); otherwise the same
    routing runs densely.  ``input`` [B, T, d] or [T, d]."""
    from ..initializer import XavierInitializer

    helper = LayerHelper("switch_moe", param_attr=param_attr, name=name)
    dtype = input.dtype
    d = int(input.shape[-1])
    gate_w = helper.create_parameter(helper.param_attr,
                                     shape=[d, num_experts], dtype=dtype,
                                     suffix="gate")
    # per-expert Glorot over (d, d_hidden): the default fan rule would
    # read the 3-d shapes as conv filters and shrink init ~d_hidden-fold
    w1 = helper.create_parameter(
        helper.param_attr, shape=[num_experts, d, d_hidden], dtype=dtype,
        suffix="w1",
        default_initializer=XavierInitializer(fan_in=d,
                                              fan_out=d_hidden))
    w2 = helper.create_parameter(
        helper.param_attr, shape=[num_experts, d_hidden, d], dtype=dtype,
        suffix="w2",
        default_initializer=XavierInitializer(fan_in=d_hidden,
                                              fan_out=d))
    out = helper.create_tmp_variable(dtype)
    helper.append_op("switch_moe",
                     {"X": input, "GateW": gate_w, "W1": w1, "W2": w2},
                     {"Out": out},
                     {"capacity_factor": float(capacity_factor),
                      "act": str(act)})
    return out


def cross_entropy_over_beam(beams, name=None):
    """Learning-to-search beam cost (reference CrossEntropyOverBeam.cpp;
    see ops/loss_ops.py for the math).  ``beams`` is a list of
    (candidate_scores, selected_ids, gold) triples, one per beam
    expansion -> [B, 1] per-sequence cost."""
    helper = LayerHelper("cross_entropy_over_beam", name=name)
    out = helper.create_tmp_variable("float32")
    helper.append_op("cross_entropy_over_beam",
                     {"Scores": [b[0] for b in beams],
                      "Ids": [b[1] for b in beams],
                      "Gold": [b[2] for b in beams]},
                     {"Out": out})
    return out


def cross_entropy_with_selfnorm(input, label, softmax_selfnorm_alpha=0.1,
                                name=None):
    """Self-normalized CE on unnormalized positive scores — reference
    CostLayer.cpp:113 (see ops/loss_ops.py) -> [B, 1] per-row cost."""
    helper = LayerHelper("cross_entropy_with_selfnorm", name=name)
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op("cross_entropy_with_selfnorm",
                     {"X": input, "Label": label}, {"Out": out},
                     {"softmax_selfnorm_alpha": float(softmax_selfnorm_alpha)})
    return out


def _append_channel_bias(helper, pre_bias):
    bias_attr = helper.bias_attr
    if bias_attr is None:
        return pre_bias
    channels = pre_bias.shape[1]
    b = helper.create_parameter(bias_attr, shape=[channels],
                                dtype=pre_bias.dtype, is_bias=True)
    out = helper.create_tmp_variable(pre_bias.dtype)
    helper.append_op("elementwise_add", {"X": pre_bias, "Y": b},
                     {"Out": out}, {"axis": 1})
    return out


def fused_attention(q, k, v, bias=None, causal=False, sm_scale=None,
                    seq_parallel=False, sp_impl="ring", impl=None,
                    dropout_rate=0.0, is_test=False, layout="bhld",
                    name=None):
    """Fused scaled-dot-product attention — flash attention on one chip;
    over an 'sp' mesh axis when ``seq_parallel`` and the active mesh
    shard the sequence, either ring attention (``sp_impl='ring'``,
    default — k/v shards rotate around the ICI, scales past the head
    count) or Ulysses all-to-all (``sp_impl='ulysses'`` — two
    all-to-alls re-shard seq<->heads; needs heads % sp == 0).  O(L)
    memory either way, unlike the matmul+softmax composition which
    materialises [lq, lk].
    ``layout='bhld'`` takes [b, h, l, d] tensors; ``'blhd'`` takes
    [b, l, h, d] head-interleaved tensors directly — the Pallas kernels
    index them in place, so callers skip the split-heads transposes (the
    last elementwise-traffic tier in BENCH_NOTES §2).
    ``dropout_rate`` applies attention-probability dropout inside the kernel
    (counter-based hash mask, train mode only) — same semantics as the
    softmax→dropout→matmul composition."""
    if sp_impl not in ("ring", "ulysses"):
        raise ValueError(
            f"fused_attention: sp_impl must be 'ring' or 'ulysses', "
            f"got {sp_impl!r}")
    helper = LayerHelper("fused_attention", name=name)
    out = helper.create_tmp_variable(q.dtype)
    inputs = {"Q": q, "K": k, "V": v}
    if bias is not None:
        inputs["Bias"] = bias
    attrs = {"causal": bool(causal), "seq_parallel": bool(seq_parallel),
             "sp_impl": str(sp_impl),
             "dropout_rate": float(dropout_rate), "is_test": bool(is_test),
             "layout": str(layout)}
    if sm_scale is not None:
        attrs["sm_scale"] = float(sm_scale)
    if impl is not None:
        attrs["impl"] = impl
    helper.append_op("fused_attention", inputs, {"Out": out}, attrs)
    return out


def decode_attention(q, k_cache, v_cache, lengths, sm_scale=None,
                     name=None):
    """One decode step's attention against a preallocated KV cache with a
    per-sequence length mask — the serving-path counterpart of
    ``fused_attention`` (ops/cache_ops.decode_attention).  Layout is
    head-interleaved 'blhd': q [B, Lq, H, D] (Lq=1 in steady state),
    caches [B, Lmax, H, D], lengths [B] int32 = live cache rows.  O(Lmax)
    per emitted token instead of the O(L^2) full causal re-run."""
    helper = LayerHelper("decode_attention", name=name)
    out = helper.create_tmp_variable(q.dtype, stop_gradient=True)
    attrs = {}
    if sm_scale is not None:
        attrs["sm_scale"] = float(sm_scale)
    helper.append_op("decode_attention",
                     {"Q": q, "KCache": k_cache, "VCache": v_cache,
                      "Lengths": lengths},
                     {"Out": out}, attrs)
    return out


def ragged_decode_attention(q, pool, page_table, lengths, q_base=None,
                            layer=0, n_layer=1, causal=True, sm_scale=None,
                            impl=None, scales=None, name=None, v_pool=None,
                            window=None, sink=None, ring_top=None,
                            out_scale=None, scope=None, latent_values=None):
    """Attention of per-lane query blocks against the paged KV pool,
    walking each lane's page list (ops/cache_ops.ragged_decode_attention;
    the Pallas kernel lives in kernels/flash_attention).  q [B, C, H, D]
    (C=1 steady-state decode, C=chunk during chunked prefill), pool
    [R, page_size, H*D], page_table [B, P] int32 logical pages, lengths
    [B] int32 live positions, q_base [B] int32 global query start
    (required when causal).  ``scales`` ([1, R, page_size] fp32) rides
    along for int8 pools — K/V dequantize in-register during the walk.

    With ``v_pool`` the cache is a SPLIT pair (keys [R, page, Hkv*Dk] in
    ``pool``, values [R, page, Hkv*Dv] in ``v_pool``): H query heads on
    Hkv KV heads, values of another width than keys, ``window`` (keys
    q-window < j <= q), ``sink`` ([H], a logit per query head in the
    softmax's denominator only), ``ring_top`` ([B]: the table is a ring
    of pages), ``out_scale`` (the result times a constant) and ``scope``
    (the name its device operations carry in a trace).

    With ``latent_values`` the cache is ONE pool of latent rows ([R, page,
    Dk], one KV head all H query heads share) whose leading
    ``latent_values`` columns are the values: q [B, C, H, Dk] gives
    [B, C, H, latent_values]; ``out_scale`` and ``scope`` as above."""
    helper = LayerHelper("ragged_decode_attention", name=name)
    out = helper.create_tmp_variable(q.dtype, stop_gradient=True)
    attrs = {"layer": int(layer), "n_layer": int(n_layer),
             "causal": bool(causal)}
    if sm_scale is not None:
        attrs["sm_scale"] = float(sm_scale)
    if impl is not None:
        attrs["impl"] = impl
    inputs = {"Q": q, "Pool": pool, "PageTable": page_table,
              "Lengths": lengths}
    if q_base is not None:
        inputs["QBase"] = q_base
    if scales is not None:
        inputs["Scales"] = scales
    for slot, var in (("VPool", v_pool), ("Sink", sink),
                      ("RingTop", ring_top)):
        if var is not None:
            inputs[slot] = var
    for key, val in (("window", window), ("out_scale", out_scale),
                     ("scope", scope), ("latent_values", latent_values)):
        if val is not None:
            attrs[key] = val
    helper.append_op("ragged_decode_attention", inputs, {"Out": out}, attrs)
    return out


def vocab_logits(x, size, param_attr=None, name=None):
    """Output head with float32 logits (ops/llm_ops.vocab_logits); the
    weight [d, size] is stored in x's type."""
    helper = LayerHelper("vocab_logits", param_attr=param_attr, name=name)
    w = helper.create_parameter(helper.param_attr,
                                shape=[x.shape[-1], int(size)],
                                dtype=x.dtype)
    out = helper.create_tmp_variable("float32", stop_gradient=True)
    helper.append_op("vocab_logits", {"X": x, "W": w}, {"Out": out}, {})
    return out


def rms_norm(x, param_attr=None, epsilon=1e-5, out_dtype=None, name=None,
             scope=None):
    """RMS normalisation over the last axis with a learned scale
    (ops/llm_ops.rms_norm), computed in float32; ``out_dtype`` is what the
    next product reads.  x [T, H, D] normalises every head by itself with
    one [D] scale (QK-norm); ``scope`` names its device operations in a
    trace."""
    helper = LayerHelper("rms_norm", param_attr=param_attr, name=name)
    from ..initializer import ConstantInitializer

    scale = helper.create_parameter(
        helper.param_attr, shape=[x.shape[-1]], dtype="float32",
        default_initializer=ConstantInitializer(1.0))
    out = helper.create_tmp_variable(out_dtype or x.dtype,
                                     stop_gradient=True)
    attrs = {"epsilon": float(epsilon)}
    if out_dtype is not None:
        attrs["out_dtype"] = str(out_dtype)
    if scope is not None:
        attrs["scope"] = str(scope)
    helper.append_op("rms_norm", {"X": x, "Scale": scale}, {"Out": out},
                     attrs)
    return out


def rotary_embedding(x, pos, rotary_dim, base=10000.0, name=None):
    """Partial rotary position embedding of x [T, H, D] at positions pos
    [T]: the first ``rotary_dim`` dims of every head rotate (halves), the
    rest pass (ops/llm_ops.rotary_embedding)."""
    helper = LayerHelper("rotary_embedding", name=name)
    out = helper.create_tmp_variable(x.dtype, stop_gradient=True)
    helper.append_op("rotary_embedding", {"X": x, "Pos": pos}, {"Out": out},
                     {"rotary_dim": int(rotary_dim), "base": float(base)})
    return out


def swiglu(gate, up, name=None):
    """``silu(gate) * up``: the gate of a gated feed-forward."""
    helper = LayerHelper("swiglu", name=name)
    out = helper.create_tmp_variable(gate.dtype, stop_gradient=True)
    helper.append_op("swiglu", {"Gate": gate, "Up": up}, {"Out": out}, {})
    return out


def sigmoid_gate(x, gate, scope=None, name=None):
    """``x * sigmoid(gate)`` in float32, back in x's type
    (ops/llm_ops.sigmoid_gate): the gate on an attention output; its
    device operations carry ``scope`` in a trace."""
    helper = LayerHelper("sigmoid_gate", name=name)
    out = helper.create_tmp_variable(x.dtype, stop_gradient=True)
    helper.append_op("sigmoid_gate", {"X": x, "Gate": gate}, {"Out": out},
                     {} if scope is None else {"scope": str(scope)})
    return out


def routed_experts(x, n_experts, held, first_expert, top_k, d_inner,
                   param_prefix, dtype=None, live=None, impl=None,
                   name=None, routed_scale=None):
    """A device's share of a routed-expert layer (ops/llm_ops.
    routed_experts): routes over all ``n_experts`` in float32 (``x`` is
    the float32 norm output; sigmoid scores, a selection bias, ``top_k`` a
    token), computes the ``held`` experts from ``first_expert`` on in
    ``dtype``.  ``live`` [T] marks the rows that are a request's tokens;
    the others make no pair.  ``routed_scale`` (a model's
    ``routed_scaling_factor``) multiplies the normalised weights.
    Parameters, under ``param_prefix``:
    ``router.w`` [d, n_experts] and ``router.bias`` [n_experts] (float32),
    ``experts.gate.w`` / ``experts.up.w`` [held, d, d_inner],
    ``experts.down.w`` [held, d_inner, d] (``dtype``).  Returns (out
    [T, d] in ``dtype``, load [held] int32: pairs per held expert)."""
    from ..param_attr import ParamAttr

    helper = LayerHelper("routed_experts", name=name)
    dtype = dtype or x.dtype
    d = x.shape[-1]

    def param(suffix, shape, dt):
        return helper.create_parameter(
            ParamAttr(name=f"{param_prefix}.{suffix}", keep_dtype=True),
            shape=shape, dtype=dt)

    inputs = {"X": x,
              "RouterW": param("router.w", [d, n_experts], "float32"),
              "RouterBias": param("router.bias", [n_experts], "float32"),
              "WGate": param("experts.gate.w", [held, d, d_inner], dtype),
              "WUp": param("experts.up.w", [held, d, d_inner], dtype),
              "WDown": param("experts.down.w", [held, d_inner, d], dtype)}
    if live is not None:
        inputs["Live"] = live
    out = helper.create_tmp_variable(dtype, stop_gradient=True)
    load = helper.create_tmp_variable("int32", stop_gradient=True)
    attrs = {"top_k": int(top_k), "first_expert": int(first_expert)}
    if impl is not None:
        attrs["impl"] = impl
    if routed_scale is not None:
        attrs["routed_scale"] = float(routed_scale)
    helper.append_op("routed_experts", inputs, {"Out": out, "Load": load},
                     attrs)
    return out, load


def gated_ffn(x, d_inner, param_prefix, dtype=None, scope=None, name=None):
    """A SiLU-gated feed-forward as one op (ops/llm_ops.gated_ffn), whose
    device operations carry ``scope`` in a trace.  Parameters, under
    ``param_prefix`` and in ``dtype``: ``gate.w`` / ``up.w`` [d, d_inner],
    ``down.w`` [d_inner, d].  ``x`` of any float type; out in ``dtype``."""
    from ..param_attr import ParamAttr

    helper = LayerHelper("gated_ffn", name=name)
    dtype = dtype or x.dtype
    d = x.shape[-1]
    shapes = {"WGate": ("gate.w", [d, d_inner]), "WUp": ("up.w", [d, d_inner]),
              "WDown": ("down.w", [d_inner, d])}
    inputs = {"X": x}
    for slot, (suffix, shape) in shapes.items():
        inputs[slot] = helper.create_parameter(
            ParamAttr(name=f"{param_prefix}.{suffix}", keep_dtype=True),
            shape=shape, dtype=dtype)
    out = helper.create_tmp_variable(dtype, stop_gradient=True)
    helper.append_op("gated_ffn", inputs, {"Out": out},
                     {} if scope is None else {"scope": str(scope)})
    return out


def latent_absorb(x, w, side, d_nope, name=None):
    """Latent attention's up-projection ``w`` [r, H * (d_nope + dv)]
    absorbed (ops/llm_ops.latent_absorb): ``side`` ``"query"`` takes x
    [T, H, d_nope] to [T, H, r], ``"output"`` x [T, H, r] to [T, H * dv]."""
    helper = LayerHelper("latent_absorb", name=name)
    out = helper.create_tmp_variable(x.dtype, stop_gradient=True)
    helper.append_op("latent_absorb", {"X": x, "W": w}, {"Out": out},
                     {"side": str(side), "d_nope": int(d_nope)})
    return out


# ---------------------------------------------------------------------------
# post-training quantization wrappers (ops/quant_ops.py; transform in
# fluid/transforms/quantize.py)
# ---------------------------------------------------------------------------

def quantize(x, axis=None, name=None):
    """Symmetric max-abs int8 quantization: returns (int8 out, fp32
    scale).  ``axis`` selects the per-channel dim; None = one per-tensor
    scalar scale."""
    helper = LayerHelper("quantize", name=name)
    out = helper.create_tmp_variable("int8", stop_gradient=True)
    scale = helper.create_tmp_variable("float32", stop_gradient=True)
    attrs = {}
    if axis is not None:
        attrs["axis"] = int(axis)
    helper.append_op("quantize", {"X": x}, {"Out": out, "Scale": scale},
                     attrs)
    return out, scale


def dequantize(x, scale, axis=None, out_dtype="float32", name=None):
    """int8 x * scale -> float (inverse of ``quantize``; ``axis`` must
    match)."""
    helper = LayerHelper("dequantize", name=name)
    out = helper.create_tmp_variable(out_dtype, stop_gradient=True)
    attrs = {"out_dtype": str(out_dtype)}
    if axis is not None:
        attrs["axis"] = int(axis)
    helper.append_op("dequantize", {"X": x, "Scale": scale}, {"Out": out},
                     attrs)
    return out


def quantized_mul(x, y, scale, x_num_col_dims=1, y_num_col_dims=1,
                  name=None):
    """``mul`` with an int8 ``y`` and per-output-channel fp32 ``scale``
    (ops/quant_ops.quantized_mul) — the op the PTQ transform rewrites
    projection matmuls into."""
    helper = LayerHelper("quantized_mul", name=name)
    out = helper.create_tmp_variable(x.dtype, stop_gradient=True)
    helper.append_op("quantized_mul", {"X": x, "Y": y, "Scale": scale},
                     {"Out": out},
                     {"x_num_col_dims": x_num_col_dims,
                      "y_num_col_dims": y_num_col_dims})
    return out


def quantized_matmul(x, y, scale, transpose_x=False, transpose_y=False,
                     alpha=1.0, name=None):
    """``matmul`` with an int8 ``y``; ``scale`` is per the result's last
    dim (the output channel after any transpose) or scalar."""
    helper = LayerHelper("quantized_matmul", name=name)
    out = helper.create_tmp_variable(x.dtype, stop_gradient=True)
    helper.append_op("quantized_matmul", {"X": x, "Y": y, "Scale": scale},
                     {"Out": out},
                     {"transpose_X": bool(transpose_x),
                      "transpose_Y": bool(transpose_y),
                      "alpha": float(alpha)})
    return out


def quantized_conv2d(x, w, scale, strides=(1, 1), paddings=(0, 0),
                     dilations=(1, 1), groups=1, name=None):
    """``conv2d`` with an int8 OIHW filter and per-output-channel fp32
    scale (dequantized in-register — HBM moves 1/4 the filter bytes)."""
    helper = LayerHelper("quantized_conv2d", name=name)
    out = helper.create_tmp_variable(x.dtype, stop_gradient=True)
    helper.append_op("quantized_conv2d",
                     {"Input": x, "Filter": w, "Scale": scale},
                     {"Output": out},
                     {"strides": list(strides), "paddings": list(paddings),
                      "dilations": list(dilations), "groups": int(groups)})
    return out


# ---------------------------------------------------------------------------
# r2 operator batch wrappers (VERDICT missing#7)
# ---------------------------------------------------------------------------

def maxout(x, groups, name=None):
    """Channel-group max over NCHW (reference maxout_op.cc)."""
    return _single_out_layer("maxout", {"X": x},
                             {"groups": int(groups)}, name=name)


def _single_out_layer(op_type, inputs, attrs=None, dtype=None, lod=0,
                      extra_outputs=None, stop_gradient=False, name=None):
    helper = LayerHelper(op_type, name=name)
    first = next(iter(inputs.values()))
    first = first[0] if isinstance(first, list) else first
    out = helper.create_tmp_variable(dtype or first.dtype, lod_level=lod,
                                     stop_gradient=stop_gradient)
    outputs = {"Out": out}
    tmp = []
    for slot in (extra_outputs or []):
        v = helper.create_tmp_variable(first.dtype, stop_gradient=True)
        outputs[slot] = v
        tmp.append(v)
    helper.append_op(op_type, inputs, outputs, attrs or {})
    return out


def pad(x, paddings, pad_value=0.0, name=None):
    """reference pad_op.cc."""
    return _single_out_layer("pad", {"X": x},
                             {"paddings": list(paddings),
                              "pad_value": float(pad_value)}, name=name)


def crop(x, shape=None, offsets=None, y=None, name=None):
    """reference crop_op.cc (shape from attr or a second input)."""
    inputs = {"X": x}
    attrs = {"offsets": list(offsets or [0] * len(x.shape))}
    if y is not None:
        inputs["Y"] = y
    else:
        attrs["shape"] = list(shape)
    return _single_out_layer("crop", inputs, attrs, name=name)


def lod_reset(x, y=None, target_lod=None, name=None):
    """reference lod_reset_op.cc — re-length a sequence batch."""
    inputs = {"X": x}
    attrs = {}
    if y is not None:
        inputs["Y"] = y
    else:
        attrs["target_lod"] = list(target_lod)
    return _single_out_layer("lod_reset", inputs, attrs, lod=1, name=name)


def lrn(input, n=5, k=2.0, alpha=1e-4, beta=0.75, name=None):
    """reference lrn_op.cc."""
    return _single_out_layer("lrn", {"X": input},
                             {"n": n, "k": k, "alpha": alpha,
                              "beta": beta},
                             extra_outputs=["MidOut"], name=name)


def label_smooth(label, prior_dist=None, epsilon=0.1, name=None):
    """reference label_smooth_op.cc."""
    inputs = {"X": label}
    if prior_dist is not None:
        inputs["PriorDist"] = prior_dist
    return _single_out_layer("label_smooth", inputs,
                             {"epsilon": float(epsilon)}, name=name)


def rank_loss(label, left, right, name=None):
    """reference rank_loss_op.cc (RankNet)."""
    return _single_out_layer("rank_loss",
                             {"Label": label, "Left": left,
                              "Right": right}, name=name)


def margin_rank_loss(label, left, right, margin=0.1, name=None):
    """reference margin_rank_loss_op.cc."""
    return _single_out_layer("margin_rank_loss",
                             {"Label": label, "X1": left, "X2": right},
                             {"margin": float(margin)},
                             extra_outputs=["Activated"], name=name)


def log_loss(input, label, epsilon=1e-4, name=None):
    """reference log_loss_op.cc."""
    helper = LayerHelper("log_loss", name=name)
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op("log_loss", {"Predicted": input, "Labels": label},
                     {"Loss": out}, {"epsilon": float(epsilon)})
    return out


def conv_shift(x, y, name=None):
    """reference conv_shift_op.cc — circular correlation (NTM)."""
    return _single_out_layer("conv_shift", {"X": x, "Y": y}, name=name)


def row_conv(input, future_context_size, param_attr=None, act=None,
             name=None):
    """reference layers row_conv (row_conv_op.cc, DeepSpeech2 lookahead)."""
    helper = LayerHelper("row_conv", param_attr=param_attr, act=act,
                         name=name)
    feat = input.shape[-1]
    w = helper.create_parameter(helper.param_attr,
                                shape=[future_context_size + 1, feat],
                                dtype=input.dtype)
    out = helper.create_tmp_variable(input.dtype, lod_level=1)
    helper.append_op("row_conv", {"X": input, "Filter": w}, {"Out": out})
    return helper.append_activation(out)


def dynamic_lstmp(input, size, proj_size, param_attr=None, bias_attr=None,
                  use_peepholes=True, is_reverse=False,
                  gate_activation="sigmoid", cell_activation="tanh",
                  candidate_activation="tanh", proj_activation="tanh",
                  name=None):
    """reference layers dynamic_lstmp (lstmp_op.cc) — LSTM with recurrent
    projection; `input` carries the 4*size gate pre-activations."""
    helper = LayerHelper("lstmp", param_attr=param_attr,
                         bias_attr=bias_attr, name=name)
    w = helper.create_parameter(helper.param_attr,
                                shape=[proj_size, 4 * size],
                                dtype=input.dtype)
    w_proj = helper.create_parameter(helper.param_attr,
                                     shape=[size, proj_size],
                                     dtype=input.dtype)
    bias_size = 7 * size if use_peepholes else 4 * size
    b = helper.create_parameter(helper.bias_attr or ParamAttr(),
                                shape=[1, bias_size], dtype=input.dtype,
                                is_bias=True)
    proj = helper.create_tmp_variable(input.dtype, lod_level=1)
    cell = helper.create_tmp_variable(input.dtype, lod_level=1)
    helper.append_op("lstmp",
                     {"Input": input, "Weight": w, "ProjWeight": w_proj,
                      "Bias": b},
                     {"Projection": proj, "Cell": cell},
                     {"use_peepholes": use_peepholes,
                      "is_reverse": is_reverse,
                      "gate_activation": gate_activation,
                      "cell_activation": cell_activation,
                      "candidate_activation": candidate_activation,
                      "proj_activation": proj_activation})
    return proj, cell


def roi_pool(input, rois, pooled_height=1, pooled_width=1,
             spatial_scale=1.0, name=None):
    """reference roi_pool_op.cc; rois [R,5]=(batch_idx,x1,y1,x2,y2)."""
    return _single_out_layer("roi_pool", {"X": input, "ROIs": rois},
                             {"pooled_height": pooled_height,
                              "pooled_width": pooled_width,
                              "spatial_scale": spatial_scale}, name=name)


def spp(input, pyramid_height=3, pool_type="max", name=None):
    """reference spp_op.cc — spatial pyramid pooling."""
    return _single_out_layer("spp", {"X": input},
                             {"pyramid_height": pyramid_height,
                              "pooling_type": pool_type}, name=name)


def unpool(x, indices, unpooled_size, name=None):
    """reference unpool_op.cc (consumes max_pool2d_with_index's mask)."""
    return _single_out_layer("unpool", {"X": x, "Indices": indices},
                             {"unpooled_size": list(unpooled_size)},
                             name=name)


def prior_box(input, image, min_sizes, max_sizes=None, aspect_ratios=None,
              variances=None, flip=False, clip=False, step_h=0.0,
              step_w=0.0, offset=0.5, name=None):
    """reference prior_box_op.cc (SSD anchors)."""
    helper = LayerHelper("prior_box", name=name)
    boxes = helper.create_tmp_variable(input.dtype, stop_gradient=True)
    var = helper.create_tmp_variable(input.dtype, stop_gradient=True)
    helper.append_op("prior_box", {"Input": input, "Image": image},
                     {"Boxes": boxes, "Variances": var},
                     {"min_sizes": list(min_sizes),
                      "max_sizes": list(max_sizes or []),
                      "aspect_ratios": list(aspect_ratios or [1.0]),
                      "variances": list(variances
                                        or [0.1, 0.1, 0.2, 0.2]),
                      "flip": flip, "clip": clip, "step_h": step_h,
                      "step_w": step_w, "offset": offset})
    return boxes, var


def bipartite_match(dist_matrix, match_type="bipartite",
                    dist_threshold=0.5, name=None):
    """reference bipartite_match_op.cc."""
    helper = LayerHelper("bipartite_match", name=name)
    idx = helper.create_tmp_variable("int32", stop_gradient=True)
    dist = helper.create_tmp_variable("float32", stop_gradient=True)
    helper.append_op("bipartite_match", {"DistMat": dist_matrix},
                     {"ColToRowMatchIndices": idx,
                      "ColToRowMatchDist": dist},
                     {"match_type": match_type,
                      "dist_threshold": dist_threshold})
    return idx, dist


def multiclass_nms(bboxes, scores, score_threshold=0.01,
                   nms_threshold=0.45, nms_top_k=16, keep_top_k=16,
                   name=None):
    """detection_output analog: per-class NMS over [n,4] boxes."""
    return _single_out_layer("multiclass_nms",
                             {"BBoxes": bboxes, "Scores": scores},
                             {"score_threshold": score_threshold,
                              "nms_threshold": nms_threshold,
                              "nms_top_k": nms_top_k,
                              "keep_top_k": keep_top_k},
                             stop_gradient=True, name=name)


def max_pool2d_with_index(input, pool_size, pool_stride=None, name=None):
    """reference pool_with_index_op.cc — max pool returning the flat
    argmax Mask that `unpool` consumes."""
    helper = LayerHelper("max_pool2d_with_index", name=name)
    k = pool_size if isinstance(pool_size, (list, tuple)) \
        else [pool_size, pool_size]
    s = pool_stride if pool_stride is not None else list(k)
    s = s if isinstance(s, (list, tuple)) else [s, s]
    out = helper.create_tmp_variable(input.dtype)
    mask = helper.create_tmp_variable("int32", stop_gradient=True)
    helper.append_op("max_pool2d_with_index", {"X": input},
                     {"Out": out, "Mask": mask},
                     {"ksize": list(k), "strides": list(s)})
    return out, mask


def fused_vocab_cross_entropy(input, label, vocab_size, chunk=8192,
                              param_attr=None, name=None):
    """Streaming projection + softmax + cross-entropy against a [D, V]
    vocab matrix — same math as ``fc(bias_attr=False)`` +
    ``softmax_with_cross_entropy`` but the [N, V] logits never touch HBM
    (chunked online logsumexp; see ops/loss_ops.py
    fused_vocab_cross_entropy).  Share the projection with an inference
    head by passing the same ``param_attr`` name to an ``fc``."""
    helper = LayerHelper("fused_vocab_cross_entropy", param_attr=param_attr,
                         name=name)
    d = input.shape[-1]
    w = helper.create_parameter(helper.param_attr, shape=[d, vocab_size],
                                dtype=input.dtype)
    loss = helper.create_tmp_variable("float32")
    helper.append_op("fused_vocab_cross_entropy",
                     {"X": input, "W": w, "Label": label}, {"Loss": loss},
                     {"chunk": int(chunk)})
    return loss


def ssd_loss(location, confidence, gt_box, gt_label, prior_box_var,
             overlap_threshold=0.5, neg_pos_ratio=3.0,
             background_label=0, name=None):
    """SSD MultiBox training loss (reference gserver MultiBoxLossLayer +
    fluid ssd_loss): smooth-L1 on matched priors + mined softmax
    confidence loss, per-image [B, 1].  ``prior_box_var`` is the
    (boxes, variances) pair prior_box returns."""
    helper = LayerHelper("ssd_loss", name=name)
    pb, pv = prior_box_var
    out = helper.create_tmp_variable("float32")
    helper.append_op("ssd_loss",
                     {"Location": location, "Confidence": confidence,
                      "GTBox": gt_box, "GTLabel": gt_label,
                      "PriorBox": pb, "PriorVar": pv},
                     {"Out": out},
                     {"overlap_threshold": float(overlap_threshold),
                      "neg_pos_ratio": float(neg_pos_ratio),
                      "background_label": int(background_label)})
    return out
