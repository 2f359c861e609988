"""fluid.layers NN surface (reference: python/paddle/fluid/layers/nn.py —
153 layer functions emitting ops via LayerHelper.append_op)."""
import numpy as np

from ..framework.core import Variable
from ..framework import initializer as init_mod
from .layer_helper import LayerHelper
from ..param_attr import ParamAttr


def fc(input, size, num_flatten_dims=1, param_attr=None, bias_attr=None,
       act=None, name=None):
    """Fully-connected (reference layers/nn.py fc -> mul + elementwise_add)."""
    helper = LayerHelper("fc", param_attr=param_attr, bias_attr=bias_attr,
                         act=act, name=name)
    input_shape = input.shape
    in_features = int(np.prod(input_shape[num_flatten_dims:]))
    w = helper.create_parameter(helper.param_attr,
                                shape=[in_features, size],
                                dtype=input.dtype)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(
        type="mul", inputs={"X": [input], "Y": [w]},
        outputs={"Out": [out]},
        attrs={"x_num_col_dims": num_flatten_dims, "y_num_col_dims": 1})
    out = helper.append_bias_op(out, dim_start=num_flatten_dims)
    return helper.append_activation(out, act)


def _emit_embedding(op_type, input, size, is_sparse, is_distributed,
                    padding_idx, param_attr, dtype, name=None):
    """Shared body of layers.embedding (lookup_table, v1 trailing-[.,1]
    ids) and fluid.embedding (lookup_table_v2, any-rank ids). A
    negative padding_idx normalizes to size[0]+padding_idx (reference
    input.py / layers/nn.py both do this); -1 stays the kernel's
    no-padding sentinel only when the user passed None."""
    helper = LayerHelper("embedding", param_attr=param_attr, name=name)
    if padding_idx is None:
        padding_idx = -1
    elif padding_idx < 0:
        padding_idx = int(size[0]) + int(padding_idx)
    w = helper.create_parameter(helper.param_attr, shape=list(size),
                                dtype=dtype)
    out = helper.create_variable_for_type_inference(dtype=dtype)
    helper.append_op(
        type=op_type, inputs={"W": [w], "Ids": [input]},
        outputs={"Out": [out]},
        attrs={"padding_idx": padding_idx,
               "is_sparse": is_sparse, "is_distributed": is_distributed})
    return out


def embedding(input, size, is_sparse=False, is_distributed=False,
              padding_idx=None, param_attr=None, dtype="float32", name=None):
    return _emit_embedding("lookup_table", input, size, is_sparse,
                           is_distributed, padding_idx, param_attr,
                           dtype, name=name)


def distributed_embedding(input, size, table_name, endpoint, name=None):
    """Sparse embedding served from a host parameter-server table
    (reference distributed_lookup_table_op.cc + parameter_prefetch.cc;
    the table lives on the pserver, only touched rows cross the host
    boundary, and sparse grads are applied server-side on push). `size` is
    (vocab, dim); the table must be hosted via ParameterServer.
    host_sparse_table(table_name, ...)."""
    from .tensor import fill_constant
    helper = LayerHelper("distributed_embedding", name=name)
    stub = fill_constant([1], "float32", 0.0)
    stub.stop_gradient = False      # gives autodiff a path to the push
    out = helper.create_variable_for_type_inference(dtype="float32")
    helper.append_op(
        type="distributed_lookup_table",
        inputs={"Ids": [input], "W": [stub]},
        outputs={"Out": [out]},
        attrs={"table_name": table_name, "endpoint": endpoint,
               "emb_dim": int(size[1])},
        infer_shape=False)
    out.shape = tuple(input.shape or ()) + (int(size[1]),)
    out.dtype = "float32"
    return out


def conv2d(input, num_filters, filter_size, stride=1, padding=0, dilation=1,
           groups=1, param_attr=None, bias_attr=None, act=None,
           use_cudnn=True, name=None, data_format="NCHW"):
    helper = LayerHelper("conv2d", param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    num_channels = input.shape[1]
    filter_size = _pair(filter_size)
    stride = _pair(stride)
    dilation = _pair(dilation)
    padding, algo = _conv_padding(padding)
    filter_shape = [num_filters, num_channels // groups] + list(filter_size)
    std = (2.0 / (filter_size[0] * filter_size[1] * num_channels)) ** 0.5
    w = helper.create_parameter(
        helper.param_attr, shape=filter_shape, dtype=input.dtype,
        default_initializer=init_mod.NormalInitializer(0.0, std))
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(
        type="conv2d",
        inputs={"Input": [input], "Filter": [w]},
        outputs={"Output": [out]},
        attrs={"strides": list(stride), "paddings": list(padding),
               "dilations": list(dilation), "groups": groups,
               "padding_algorithm": algo, "data_format": data_format})
    out = _append_channel_bias(helper, out)
    return helper.append_activation(out, act)


def conv2d_transpose(input, num_filters, output_size=None, filter_size=None,
                     stride=1, padding=0, dilation=1, groups=1,
                     param_attr=None, bias_attr=None, act=None, name=None):
    helper = LayerHelper("conv2d_transpose", param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    num_channels = input.shape[1]
    stride = _pair(stride)
    dilation = _pair(dilation)
    padding, algo = _conv_padding(padding)
    if filter_size is None:
        raise ValueError("filter_size required (output_size-only inference "
                         "not supported)")
    filter_size = _pair(filter_size)
    filter_shape = [num_channels, num_filters // groups] + list(filter_size)
    w = helper.create_parameter(helper.param_attr, shape=filter_shape,
                                dtype=input.dtype)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(
        type="conv2d_transpose",
        inputs={"Input": [input], "Filter": [w]},
        outputs={"Output": [out]},
        attrs={"strides": list(stride), "paddings": list(padding),
               "dilations": list(dilation), "groups": groups,
               "padding_algorithm": algo})
    out = _append_channel_bias(helper, out)
    return helper.append_activation(out, act)


def pool2d(input, pool_size=-1, pool_type="max", pool_stride=1,
           pool_padding=0, global_pooling=False, use_cudnn=True,
           ceil_mode=False, exclusive=True, name=None):
    helper = LayerHelper("pool2d", name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(
        type="pool2d", inputs={"X": [input]}, outputs={"Out": [out]},
        attrs={"pooling_type": pool_type, "ksize": list(_pair(pool_size)),
               "strides": list(_pair(pool_stride)),
               "paddings": list(_pair(pool_padding)),
               "global_pooling": global_pooling, "ceil_mode": ceil_mode,
               "exclusive": exclusive})
    return out


def adaptive_pool2d(input, pool_size, pool_type="max", name=None):
    helper = LayerHelper("adaptive_pool2d", name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(
        type="pool2d", inputs={"X": [input]}, outputs={"Out": [out]},
        attrs={"pooling_type": pool_type, "ksize": list(_pair(pool_size)),
               "adaptive": True})
    return out


def batch_norm(input, act=None, is_test=False, momentum=0.9, epsilon=1e-5,
               param_attr=None, bias_attr=None, data_layout="NCHW",
               name=None, moving_mean_name=None, moving_variance_name=None,
               use_global_stats=False, sync=False):
    helper = LayerHelper("batch_norm", param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    caxis = 1 if data_layout == "NCHW" else len(input.shape) - 1
    c = input.shape[caxis]
    dtype = input.dtype if input.dtype != "float16" else "float32"
    scale = helper.create_parameter(
        helper.param_attr, shape=[c], dtype=dtype,
        default_initializer=init_mod.ConstantInitializer(1.0))
    bias = helper.create_parameter(helper.bias_attr, shape=[c], dtype=dtype,
                                   is_bias=True)
    mean = helper.create_global_variable(
        shape=[c], dtype=dtype, name=moving_mean_name,
        initializer=init_mod.ConstantInitializer(0.0))
    variance = helper.create_global_variable(
        shape=[c], dtype=dtype, name=moving_variance_name,
        initializer=init_mod.ConstantInitializer(1.0))
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    saved_m = helper.create_variable_for_type_inference(dtype=dtype,
                                                        stop_gradient=True)
    saved_v = helper.create_variable_for_type_inference(dtype=dtype,
                                                        stop_gradient=True)
    helper.append_op(
        type="sync_batch_norm" if sync else "batch_norm",
        inputs={"X": [input], "Scale": [scale], "Bias": [bias],
                "Mean": [mean], "Variance": [variance]},
        outputs={"Y": [out], "MeanOut": [mean], "VarianceOut": [variance],
                 "SavedMean": [saved_m], "SavedVariance": [saved_v]},
        attrs={"momentum": momentum, "epsilon": epsilon, "is_test": is_test,
               "data_layout": data_layout,
               "use_global_stats": use_global_stats})
    return helper.append_activation(out, act)


def layer_norm(input, scale=True, shift=True, begin_norm_axis=1,
               epsilon=1e-5, param_attr=None, bias_attr=None, act=None,
               name=None):
    helper = LayerHelper("layer_norm", param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    norm_shape = [int(np.prod(input.shape[begin_norm_axis:]))]
    inputs = {"X": [input]}
    if scale:
        s = helper.create_parameter(
            helper.param_attr, shape=norm_shape, dtype=input.dtype,
            default_initializer=init_mod.ConstantInitializer(1.0))
        inputs["Scale"] = [s]
    if shift:
        b = helper.create_parameter(helper.bias_attr, shape=norm_shape,
                                    dtype=input.dtype, is_bias=True)
        inputs["Bias"] = [b]
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    mean = helper.create_variable_for_type_inference(dtype=input.dtype,
                                                     stop_gradient=True)
    var = helper.create_variable_for_type_inference(dtype=input.dtype,
                                                    stop_gradient=True)
    helper.append_op(
        type="layer_norm", inputs=inputs,
        outputs={"Y": [out], "Mean": [mean], "Variance": [var]},
        attrs={"begin_norm_axis": begin_norm_axis, "epsilon": epsilon})
    return helper.append_activation(out, act)


def group_norm(input, groups, epsilon=1e-5, param_attr=None, bias_attr=None,
               act=None, name=None):
    helper = LayerHelper("group_norm", param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    c = input.shape[1]
    inputs = {"X": [input]}
    if helper.param_attr is not False:
        s = helper.create_parameter(
            helper.param_attr, shape=[c], dtype=input.dtype,
            default_initializer=init_mod.ConstantInitializer(1.0))
        inputs["Scale"] = [s]
    if helper.bias_attr is not False:
        b = helper.create_parameter(helper.bias_attr, shape=[c],
                                    dtype=input.dtype, is_bias=True)
        inputs["Bias"] = [b]
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    mean = helper.create_variable_for_type_inference(dtype=input.dtype,
                                                     stop_gradient=True)
    var = helper.create_variable_for_type_inference(dtype=input.dtype,
                                                    stop_gradient=True)
    helper.append_op(type="group_norm", inputs=inputs,
                     outputs={"Y": [out], "Mean": [mean], "Variance": [var]},
                     attrs={"groups": groups, "epsilon": epsilon})
    return helper.append_activation(out, act)


def dropout(x, dropout_prob, is_test=False, seed=None, name=None,
            dropout_implementation="downgrade_in_infer"):
    helper = LayerHelper("dropout", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    mask = helper.create_variable_for_type_inference(dtype=x.dtype,
                                                     stop_gradient=True)
    helper.append_op(
        type="dropout", inputs={"X": [x]},
        outputs={"Out": [out], "Mask": [mask]},
        attrs={"dropout_prob": dropout_prob, "is_test": is_test,
               "seed": seed or 0,
               "dropout_implementation": dropout_implementation})
    return out


def softmax(input, axis=-1, use_cudnn=False, name=None):
    helper = LayerHelper("softmax", name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(type="softmax", inputs={"X": [input]},
                     outputs={"Out": [out]}, attrs={"axis": axis})
    return out


def log_softmax(input, axis=-1, name=None):
    helper = LayerHelper("log_softmax", name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(type="log_softmax", inputs={"X": [input]},
                     outputs={"Out": [out]}, attrs={"axis": axis})
    return out


def relu(x, name=None):
    return _unary("relu", x, name)


def sigmoid(x, name=None):
    return _unary("sigmoid", x, name)


def tanh(x, name=None):
    return _unary("tanh", x, name)


def gelu(x, approximate=False, name=None):
    return _unary("gelu", x, name, {"approximate": approximate})


def leaky_relu(x, alpha=0.02, name=None):
    return _unary("leaky_relu", x, name, {"alpha": alpha})


def relu6(x, threshold=6.0, name=None):
    return _unary("relu6", x, name, {"threshold": threshold})


def elu(x, alpha=1.0, name=None):
    return _unary("elu", x, name, {"alpha": alpha})


def swish(x, beta=1.0, name=None):
    return _unary("swish", x, name, {"beta": beta})


def hard_swish(x, name=None):
    return _unary("hard_swish", x, name)


def hard_sigmoid(x, slope=0.2, offset=0.5, name=None):
    return _unary("hard_sigmoid", x, name, {"slope": slope,
                                            "offset": offset})


def exp(x, name=None):
    return _unary("exp", x, name)


def log(x, name=None):
    return _unary("log", x, name)


def sqrt(x, name=None):
    return _unary("sqrt", x, name)


def rsqrt(x, name=None):
    return _unary("rsqrt", x, name)


def square(x, name=None):
    return _unary("square", x, name)


def abs(x, name=None):
    return _unary("abs", x, name)


def floor(x, name=None):
    return _unary("floor", x, name)


def ceil(x, name=None):
    return _unary("ceil", x, name)


def round(x, name=None):
    return _unary("round", x, name)


def sign(x, name=None):
    return _unary("sign", x, name)


def sin(x, name=None):
    return _unary("sin", x, name)


def cos(x, name=None):
    return _unary("cos", x, name)


def erf(x, name=None):
    return _unary("erf", x, name)


def softplus(x, name=None):
    return _unary("softplus", x, name)


def softsign(x, name=None):
    return _unary("softsign", x, name)


def logsigmoid(x, name=None):
    return _unary("logsigmoid", x, name)


def pow(x, factor=1.0, name=None):
    return _unary("pow", x, name, {"factor": factor})


def prelu(x, mode="all", param_attr=None, name=None):
    helper = LayerHelper("prelu", param_attr=param_attr, name=name)
    if mode == "all":
        alpha_shape = [1]
    elif mode == "channel":
        alpha_shape = [x.shape[1]]
    else:
        alpha_shape = list(x.shape[1:])
    alpha = helper.create_parameter(
        helper.param_attr, shape=alpha_shape, dtype=x.dtype,
        default_initializer=init_mod.ConstantInitializer(0.25))
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(type="prelu", inputs={"X": [x], "Alpha": [alpha]},
                     outputs={"Out": [out]}, attrs={"mode": mode})
    return out


def matmul(x, y, transpose_x=False, transpose_y=False, alpha=1.0, name=None):
    helper = LayerHelper("matmul", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type="matmul", inputs={"X": [x], "Y": [y]}, outputs={"Out": [out]},
        attrs={"transpose_X": transpose_x, "transpose_Y": transpose_y,
               "alpha": float(alpha)})
    return out


def mul(x, y, x_num_col_dims=1, y_num_col_dims=1, name=None):
    helper = LayerHelper("mul", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type="mul", inputs={"X": [x], "Y": [y]}, outputs={"Out": [out]},
        attrs={"x_num_col_dims": x_num_col_dims,
               "y_num_col_dims": y_num_col_dims})
    return out


def bmm(x, y, name=None):
    helper = LayerHelper("bmm", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(type="bmm", inputs={"X": [x], "Y": [y]},
                     outputs={"Out": [out]})
    return out


def topk(input, k, name=None):
    helper = LayerHelper("top_k", name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    idx = helper.create_variable_for_type_inference(dtype="int64",
                                                    stop_gradient=True)
    helper.append_op(type="top_k", inputs={"X": [input]},
                     outputs={"Out": [out], "Indices": [idx]},
                     attrs={"k": k})
    return out, idx


def accuracy(input, label, k=1, correct=None, total=None):
    helper = LayerHelper("accuracy")
    _, idx = topk(input, k)
    acc = helper.create_variable_for_type_inference(dtype="float32",
                                                    stop_gradient=True)
    correct = correct or helper.create_variable_for_type_inference(
        dtype="int32", stop_gradient=True)
    total = total or helper.create_variable_for_type_inference(
        dtype="int32", stop_gradient=True)
    helper.append_op(
        type="accuracy",
        inputs={"Out": [input], "Indices": [idx], "Label": [label]},
        outputs={"Accuracy": [acc], "Correct": [correct], "Total": [total]})
    return acc


def auc(input, label, curve="ROC", num_thresholds=4095, topk=1,
        slide_steps=1):
    helper = LayerHelper("auc")
    stat_pos = helper.create_global_variable(
        shape=[num_thresholds + 1], dtype="int64",
        initializer=init_mod.ConstantInitializer(0))
    stat_neg = helper.create_global_variable(
        shape=[num_thresholds + 1], dtype="int64",
        initializer=init_mod.ConstantInitializer(0))
    auc_out = helper.create_variable_for_type_inference(dtype="float32",
                                                        stop_gradient=True)
    helper.append_op(
        type="auc",
        inputs={"Predict": [input], "Label": [label],
                "StatPos": [stat_pos], "StatNeg": [stat_neg]},
        outputs={"AUC": [auc_out], "StatPosOut": [stat_pos],
                 "StatNegOut": [stat_neg]},
        attrs={"num_thresholds": num_thresholds, "curve": curve})
    return auc_out, auc_out, [stat_pos, stat_neg]


def l2_normalize(x, axis, epsilon=1e-12, name=None):
    helper = LayerHelper("l2_normalize", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    norm = helper.create_variable_for_type_inference(dtype=x.dtype,
                                                     stop_gradient=True)
    helper.append_op(type="norm", inputs={"X": [x]},
                     outputs={"Out": [out], "Norm": [norm]},
                     attrs={"axis": axis, "epsilon": epsilon})
    return out


def label_smooth(label, prior_dist=None, epsilon=0.1, name=None):
    helper = LayerHelper("label_smooth", name=name)
    out = helper.create_variable_for_type_inference(dtype=label.dtype)
    inputs = {"X": [label]}
    if prior_dist is not None:
        inputs["PriorDist"] = [prior_dist]
    helper.append_op(type="label_smooth", inputs=inputs,
                     outputs={"Out": [out]}, attrs={"epsilon": epsilon})
    return out


def clip(x, min, max, name=None):
    return _unary("clip", x, name, {"min": min, "max": max})


def clip_by_norm(x, max_norm, name=None):
    return _unary("clip_by_norm", x, name, {"max_norm": max_norm})


def image_resize(input, out_shape, resample="BILINEAR", name=None):
    helper = LayerHelper("image_resize", name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    op = "bilinear_interp" if resample.upper() == "BILINEAR" \
        else "nearest_interp"
    helper.append_op(type=op, inputs={"X": [input]}, outputs={"Out": [out]},
                     attrs={"out_h": out_shape[0], "out_w": out_shape[1]})
    return out


def pad(x, paddings, pad_value=0.0, name=None):
    return _unary("pad", x, name, {"paddings": list(paddings),
                                   "pad_value": pad_value})


def pad2d(x, paddings, mode="constant", pad_value=0.0, name=None):
    return _unary("pad2d", x, name, {"paddings": list(paddings),
                                     "mode": mode, "pad_value": pad_value})


def unsqueeze(input, axes, name=None):
    helper = LayerHelper("unsqueeze", name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    xshape = helper.create_variable_for_type_inference(dtype=input.dtype,
                                                       stop_gradient=True)
    helper.append_op(type="unsqueeze2", inputs={"X": [input]},
                     outputs={"Out": [out], "XShape": [xshape]},
                     attrs={"axes": list(axes)})
    return out


def squeeze(input, axes=None, name=None):
    helper = LayerHelper("squeeze", name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    xshape = helper.create_variable_for_type_inference(dtype=input.dtype,
                                                       stop_gradient=True)
    helper.append_op(type="squeeze2", inputs={"X": [input]},
                     outputs={"Out": [out], "XShape": [xshape]},
                     attrs={"axes": list(axes or [])})
    return out


def flatten(x, axis=1, name=None):
    helper = LayerHelper("flatten", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    xshape = helper.create_variable_for_type_inference(dtype=x.dtype,
                                                       stop_gradient=True)
    helper.append_op(type="flatten2", inputs={"X": [x]},
                     outputs={"Out": [out], "XShape": [xshape]},
                     attrs={"axis": axis})
    return out


# ---- helpers ----

def _unary(op_type, x, name=None, attrs=None):
    helper = LayerHelper(op_type, name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(type=op_type, inputs={"X": [x]},
                     outputs={"Out": [out]}, attrs=attrs or {})
    return out


def _pair(v):
    if isinstance(v, (list, tuple)):
        return tuple(v)
    return (v, v)


def _conv_padding(padding):
    if isinstance(padding, str):
        return [0, 0], padding.upper()
    return list(_pair(padding)), "EXPLICIT"


def _append_channel_bias(helper, out):
    bias_attr = helper.bias_attr
    if bias_attr is False:
        return out
    bias = helper.create_parameter(bias_attr, shape=[out.shape[1]],
                                   dtype=out.dtype, is_bias=True)
    tmp = helper.create_variable_for_type_inference(dtype=out.dtype)
    helper.append_op(type="elementwise_add",
                     inputs={"X": [out], "Y": [bias]},
                     outputs={"Out": [tmp]}, attrs={"axis": 1})
    return tmp


def switch_moe(input, num_experts, d_hidden, capacity_factor=1.25,
               param_attr=None, name=None):
    """Switch-style Mixture-of-Experts FFN block (north-star extra; no
    reference counterpart — see ops/moe_ops.py). Expert weights are
    stacked [E, ...] and sharded over the "ep" mesh axis; returns
    (out, aux_loss) where aux_loss is the load-balance term to add to the
    training loss."""
    helper = LayerHelper("switch_moe", param_attr=param_attr, name=name)
    d = int(input.shape[-1])
    E, H = int(num_experts), int(d_hidden)
    gate_w = helper.create_parameter(helper.param_attr, shape=[d, E],
                                     dtype=input.dtype)
    std1 = (2.0 / (d + H)) ** 0.5
    w1 = helper.create_parameter(
        helper.param_attr, shape=[E, d, H], dtype=input.dtype,
        default_initializer=init_mod.NormalInitializer(0.0, std1),
        dist_attr=("ep",))
    b1 = helper.create_parameter(helper.param_attr, shape=[E, H],
                                 dtype=input.dtype, is_bias=True,
                                 dist_attr=("ep",))
    w2 = helper.create_parameter(
        helper.param_attr, shape=[E, H, d], dtype=input.dtype,
        default_initializer=init_mod.NormalInitializer(0.0, std1),
        dist_attr=("ep",))
    b2 = helper.create_parameter(helper.param_attr, shape=[E, d],
                                 dtype=input.dtype, is_bias=True,
                                 dist_attr=("ep",))
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    aux = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(
        type="switch_moe",
        inputs={"X": [input], "GateW": [gate_w], "W1": [w1], "B1": [b1],
                "W2": [w2], "B2": [b2]},
        outputs={"Out": [out], "AuxLoss": [aux]},
        attrs={"capacity_factor": float(capacity_factor)},
        infer_shape=False)
    out.shape = tuple(input.shape or ())
    out.dtype = input.dtype
    aux.shape = ()
    aux.dtype = input.dtype
    return out, aux


def lstm_unit(x_t, hidden_t_prev, cell_t_prev, forget_bias=0.0,
              param_attr=None, bias_attr=None, name=None):
    """One LSTM step for use inside StaticRNN (reference layers/nn.py
    lstm_unit -> operators/lstm_unit_op.h; here the x/h projections and
    gate math are one fused MXU-friendly op). Returns (hidden_t, cell_t)."""
    helper = LayerHelper("lstm_unit", param_attr=param_attr,
                         bias_attr=bias_attr, name=name)
    D = int(x_t.shape[-1])
    H = int(hidden_t_prev.shape[-1])
    w = helper.create_parameter(helper.param_attr, shape=[D + H, 4 * H],
                                dtype=x_t.dtype)
    b = helper.create_parameter(helper.bias_attr, shape=[4 * H],
                                dtype=x_t.dtype, is_bias=True)
    h = helper.create_variable_for_type_inference(dtype=x_t.dtype)
    c = helper.create_variable_for_type_inference(dtype=x_t.dtype)
    helper.append_op(
        type="lstm_cell_fused",
        inputs={"X": [x_t], "HPrev": [hidden_t_prev],
                "CPrev": [cell_t_prev], "W": [w], "B": [b]},
        outputs={"H": [h], "C": [c]},
        attrs={"forget_bias": float(forget_bias)})
    return h, c


def gru_unit(input, hidden, size=None, param_attr=None, bias_attr=None,
             name=None):
    """One GRU step for use inside StaticRNN (reference layers/nn.py
    gru_unit -> operators/gru_unit_op.h, fused). Returns hidden_t."""
    helper = LayerHelper("gru_unit", param_attr=param_attr,
                         bias_attr=bias_attr, name=name)
    D = int(input.shape[-1])
    H = int(hidden.shape[-1])

    def _suffixed(attr, suffix):
        # gru_unit owns TWO weight/bias pairs; a user-fixed attr name must
        # not collide between them
        from ..param_attr import ParamAttr
        attr = ParamAttr._to_attr(attr)
        if attr and attr.name:
            import copy as _copy
            attr = _copy.copy(attr)
            attr.name = attr.name + suffix
        return attr

    wg = helper.create_parameter(_suffixed(helper.param_attr, ".gate"),
                                 shape=[D + H, 2 * H], dtype=input.dtype)
    bg = helper.create_parameter(_suffixed(helper.bias_attr, ".gate"),
                                 shape=[2 * H], dtype=input.dtype,
                                 is_bias=True)
    wc = helper.create_parameter(_suffixed(helper.param_attr, ".cand"),
                                 shape=[D + H, H], dtype=input.dtype)
    bc = helper.create_parameter(_suffixed(helper.bias_attr, ".cand"),
                                 shape=[H], dtype=input.dtype,
                                 is_bias=True)
    h = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(
        type="gru_cell_fused",
        inputs={"X": [input], "HPrev": [hidden], "WGate": [wg],
                "BGate": [bg], "WCand": [wc], "BCand": [bc]},
        outputs={"H": [h]}, attrs={})
    return h


def ring_attention(q, k, v, attn_bias=None, scale=0.0, mechanism="ring",
                   causal=False, name=None):
    """Sequence-parallel attention for long contexts (north-star extra;
    the reference's sequences are single-device — SURVEY §5.7). q/k/v:
    [B, n_head, S, d_head] with S sharded over the "sp" mesh axis.
    mechanism="ring" rotates K/V blocks around the sp ring with online
    softmax (no full K/V on any chip); "ulysses" all-to-alls the shard
    dim from sequence to heads. `causal` masks from block/iota indices
    (the RING never materializes an [S, S] mask and skips fully-dead
    blocks — a FLOP/energy saving, not a latency one, since the ring
    synchronizes every hop; ulysses scores are dense per device either
    way). Exact math either way; identical to plain attention without
    an sp axis."""
    assert mechanism in ("ring", "ulysses")
    helper = LayerHelper(f"{mechanism}_attention", name=name)
    out = helper.create_variable_for_type_inference(dtype=q.dtype)
    ins = {"Q": [q], "K": [k], "V": [v]}
    if attn_bias is not None:
        ins["Bias"] = [attn_bias]
    helper.append_op(
        type=f"{mechanism}_attention", inputs=ins,
        outputs={"Out": [out]},
        attrs={"scale": float(scale), "causal": bool(causal)},
        infer_shape=False)
    out.shape = tuple(q.shape or ())
    out.dtype = q.dtype
    return out


def flash_attention(q, k, v, attn_bias=None, scale=0.0, causal=False,
                    impl=None, block_q=None, block_k=None, name=None,
                    window=None, scope=None):
    """Fused blockwise attention (Pallas kernel on TPU; exact XLA composite
    elsewhere). q: [B, n_head, S, d_head]; k/v the same or with fewer
    heads (grouped queries: n_head a multiple of theirs); attn_bias:
    optional additive key mask [B, 1, 1, S] (constant — no gradient
    flows to it); ``window`` with ``causal`` keeps the last ``window``
    keys of each query; ``scope`` is a ``jax.named_scope`` the op's
    computation runs under, so that a trace tells the kinds of layer
    apart. Never materializes the [S, S] score matrix in HBM on the
    Pallas path."""
    helper = LayerHelper("flash_attention", name=name)
    out = helper.create_variable_for_type_inference(dtype=q.dtype)
    ins = {"Q": [q], "K": [k], "V": [v]}
    if attn_bias is not None:
        ins["Bias"] = [attn_bias]
    helper.append_op(
        type="flash_attention", inputs=ins,
        outputs={"Out": [out]},
        attrs={"scale": float(scale), "causal": bool(causal),
               "impl": impl or "",
               "block_q": int(block_q or 0), "block_k": int(block_k or 0),
               "window": int(window or 0), "scope": scope or ""},
        infer_shape=False)
    out.shape = tuple(q.shape or ())
    out.dtype = q.dtype
    return out


def kv_cache_write(cache, kv, pos, name=None):
    """Append ``kv`` [B, H, S, D] into the preallocated KV ``cache``
    [B, H, max_len, D] at each row's own ``pos`` [B] int32 (vmapped
    position-indexed ``dynamic_update_slice``). Returns the updated
    cache; the append into a dense cache (the serving programs append
    through block tables: :func:`paged_kv_cache_write`)."""
    helper = LayerHelper("kv_cache_write", name=name)
    out = helper.create_variable_for_type_inference(dtype=cache.dtype)
    helper.append_op(
        type="kv_cache_write",
        inputs={"Cache": [cache], "KV": [kv], "Pos": [pos]},
        outputs={"Out": [out]}, attrs={}, infer_shape=False)
    out.shape = tuple(cache.shape or ())
    out.dtype = cache.dtype
    return out


def paged_kv_cache_write(cache, kv, tables, pos, scale=None, limit=None,
                         name=None, ring=False):
    """Append S new ``kv`` vectors [B, H, S, D] into the block-paged
    pool ``cache`` (logically [num_blocks, H, block_size, D]; fed in
    the stored shape of ``kernels/paged_attention``) at each row's own
    ``pos`` [B] int32, routed through the per-row block ``tables``
    [B, nblk] int32. Optional ``limit`` [B] int32 marks how many of the
    S vectors are real per row (chunked prefill's ragged tail; the rest
    route to the trash block). For an int8 pool pass its stored
    ``scale`` array; the op quantizes and returns
    ``(updated_pool, updated_scale)``, else just the updated pool.
    ``ring`` says the table is a window layer's ring (one token a row)."""
    helper = LayerHelper("paged_kv_cache_write", name=name)
    out = helper.create_variable_for_type_inference(dtype=cache.dtype)
    ins = {"Cache": [cache], "KV": [kv], "Tables": [tables],
           "Pos": [pos]}
    if limit is not None:
        ins["Limit"] = [limit]
    outs = {"Out": [out]}
    out_scale = None
    if scale is not None:
        ins["Scale"] = [scale]
        out_scale = helper.create_variable_for_type_inference(
            dtype=scale.dtype)
        outs["OutScale"] = [out_scale]
    helper.append_op(
        type="paged_kv_cache_write", inputs=ins, outputs=outs,
        attrs={"ring": bool(ring)}, infer_shape=False)
    out.shape = tuple(cache.shape or ())
    out.dtype = cache.dtype
    if out_scale is not None:
        out_scale.shape = tuple(scale.shape or ())
        out_scale.dtype = scale.dtype
        return out, out_scale
    return out


def paged_attention(q, k_cache, v_cache, tables, pos, k_scale=None,
                    v_scale=None, scale=0.0, impl=None, name=None,
                    window=None, scope=None, kv_heads=None):
    """Decode attention of S queries per row (``q`` [B, H, S, D] —
    S=1 decode, S>1 chunked prefill) over the block-paged KV pool
    (logically [num_blocks, Hkv, block_size, D]; fed in the stored shape
    of ``kernels/paged_attention``, int8 pools with their stored
    scales), gathered through the per-row block ``tables`` and masked by
    per-row ``pos`` counters (key slot j visible to query i iff
    j <= pos[b] + i). Fused Pallas gather+attend on TPU for
    S=1; ``jnp.take`` reference elsewhere and for S>1. The pools may
    have fewer heads than ``q`` (grouped queries: ``kv_heads`` says how
    many, H where it is left out); ``window`` makes ``tables`` a ring
    and keeps each query's last ``window`` keys."""
    helper = LayerHelper("paged_attention", name=name)
    out = helper.create_variable_for_type_inference(dtype=q.dtype)
    ins = {"Q": [q], "K": [k_cache], "V": [v_cache],
           "Tables": [tables], "Pos": [pos]}
    if k_scale is not None:
        ins["KScale"] = [k_scale]
        ins["VScale"] = [v_scale]
    helper.append_op(
        type="paged_attention", inputs=ins, outputs={"Out": [out]},
        attrs={"scale": float(scale), "impl": impl or "",
               "window": int(window or 0), "scope": scope or "",
               "kv_heads": int(kv_heads or 0)},
        infer_shape=False)
    out.shape = tuple(q.shape or ())
    out.dtype = q.dtype
    return out


def rms_norm(input, epsilon=1e-6, param_attr=None, name=None):
    """RMSNorm over the last axis with a learned gain (float32
    statistics, float32 result)."""
    helper = LayerHelper("rms_norm", param_attr=param_attr, name=name)
    gain = helper.create_parameter(
        helper.param_attr, shape=[int(input.shape[-1])], dtype="float32",
        default_initializer=init_mod.ConstantInitializer(1.0))
    out = helper.create_variable_for_type_inference(dtype="float32")
    helper.append_op(
        type="rms_norm", inputs={"X": [input], "Scale": [gain]},
        outputs={"Y": [out]}, attrs={"epsilon": float(epsilon)},
        infer_shape=False)
    out.shape = tuple(input.shape or ())
    out.dtype = "float32"
    return out


def rotary_embedding(x, pos, inv_freq, attention_factor=1.0, name=None):
    """Rotary position embedding of ``x`` [B, H, S, D] at positions
    ``pos`` [B, S] int32: lane i pairs with lane i + D/2, angles
    ``pos * inv_freq[i]`` (``inv_freq``: D/2 floats, from the model's
    config), cos and sin scaled by ``attention_factor``."""
    helper = LayerHelper("rotary_embedding", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type="rotary_embedding", inputs={"X": [x], "Pos": [pos]},
        outputs={"Out": [out]},
        attrs={"inv_freq": [float(f) for f in inv_freq],
               "attention_factor": float(attention_factor)},
        infer_shape=False)
    out.shape = tuple(x.shape or ())
    out.dtype = x.dtype
    return out


def dense_acc32(input, size, dtype="float32", param_attr=None, name=None):
    """``input`` [..., d] @ W [d, size] with no bias: W is held in
    ``dtype``, the input is rounded to it, the product accumulates in
    float32 and comes out float32."""
    helper = LayerHelper("dense_acc32", param_attr=param_attr, name=name)
    w = helper.create_parameter(
        helper.param_attr, shape=[int(input.shape[-1]), int(size)],
        dtype=dtype)
    out = helper.create_variable_for_type_inference(dtype="float32")
    helper.append_op(
        type="dense_acc32", inputs={"X": [input], "W": [w]},
        outputs={"Out": [out]}, attrs={}, infer_shape=False)
    out.shape = tuple(input.shape[:-1] or ()) + (int(size),)
    out.dtype = "float32"
    return out


def routed_experts(input, num_experts, num_experts_per_tok,
                   intermediate_size, norm_topk_prob=True, dtype="float32",
                   valid=None, name=None, param_attr=None, impl=None):
    """Top-k routed SwiGLU experts, dropless (ops/moe_ops.routed_experts):
    a float32 router [d, E] (softmax over all experts, the k largest,
    renormalised with ``norm_topk_prob``) and per-expert gate, up
    [E, d, f] and down [E, f, d] matrices held in ``dtype``.
    ``param_attr`` maps ``router``, ``gate``, ``up``, ``down`` to a
    ParamAttr each; ``valid`` marks real tokens (padding routes
    nowhere). Returns ``(out, counts)``: out like ``input`` in float32,
    counts [E] int32 the assignments each expert got."""
    helper = LayerHelper("routed_experts", name=name)
    attr = param_attr or {}
    d, E, f = int(input.shape[-1]), int(num_experts), int(intermediate_size)
    router = helper.create_parameter(attr.get("router"), shape=[d, E],
                                     dtype="float32")
    w_gate = helper.create_parameter(attr.get("gate"), shape=[E, d, f],
                                     dtype=dtype)
    w_up = helper.create_parameter(attr.get("up"), shape=[E, d, f],
                                   dtype=dtype)
    w_down = helper.create_parameter(attr.get("down"), shape=[E, f, d],
                                     dtype=dtype)
    out = helper.create_variable_for_type_inference(dtype="float32")
    counts = helper.create_variable_for_type_inference(dtype="int32")
    ins = {"X": [input], "RouterW": [router], "WGate": [w_gate],
           "WUp": [w_up], "WDown": [w_down]}
    if valid is not None:
        ins["Valid"] = [valid]
    helper.append_op(
        type="routed_experts", inputs=ins,
        outputs={"Out": [out], "Counts": [counts]},
        attrs={"top_k": int(num_experts_per_tok),
               "norm_topk_prob": bool(norm_topk_prob), "impl": impl or ""},
        infer_shape=False)
    out.shape = tuple(input.shape or ())
    out.dtype = "float32"
    counts.shape = (E,)
    counts.dtype = "int32"
    return out, counts


def row_gather(x, index, name=None):
    """Out[b] = x[b, index[b]] — per-row gather along axis 1 (e.g. the
    last real token's position of a right-padded batch)."""
    helper = LayerHelper("row_gather", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type="row_gather", inputs={"X": [x], "Index": [index]},
        outputs={"Out": [out]}, attrs={}, infer_shape=False)
    out.shape = tuple(x.shape[:1] or ()) + tuple(x.shape[2:] or ())
    out.dtype = x.dtype
    return out


def dense_acc32_nt(input, weight, name=None):
    """``input`` [..., d] @ ``weight``^T for a matrix that is there
    already and stored ``[n, d]`` (a head tied to the embedding table):
    the input is rounded to the matrix's dtype, the product accumulates
    in float32 and comes out float32."""
    helper = LayerHelper("dense_acc32_nt", name=name)
    out = helper.create_variable_for_type_inference(dtype="float32")
    helper.append_op(
        type="dense_acc32_nt", inputs={"X": [input], "W": [weight]},
        outputs={"Out": [out]}, attrs={}, infer_shape=False)
    out.shape = tuple(input.shape[:-1] or ()) + (int(weight.shape[0]),)
    out.dtype = "float32"
    return out


def causal_conv1d(input, kernel_size, tail=None, length=None,
                  param_attr=None, bias_attr=None, name=None):
    """Causal depthwise convolution over ``input`` [B, L, C] with a bias
    and a silu (ops/ssm_ops.causal_conv1d): taps ``[kernel_size, C]``,
    the last the current token's. ``tail`` [B, (kernel_size - 1) * C]
    holds the inputs before the first (a served row's; zeros without),
    ``length`` [B] int32 the real tokens of each right-padded row.
    Returns ``(out [B, L, C] float32, new_tail)``: the inputs a later
    call starts from, after each row's last real token, in ``tail``'s
    dtype (float32 without one)."""
    helper = LayerHelper("causal_conv1d", name=name)
    ch, k = int(input.shape[-1]), int(kernel_size)
    w = helper.create_parameter(param_attr, shape=[k, ch], dtype="float32")
    b = helper.create_parameter(
        bias_attr, shape=[ch], dtype="float32",
        default_initializer=init_mod.ConstantInitializer(0.0))
    out = helper.create_variable_for_type_inference(dtype="float32")
    tail_dtype = tail.dtype if tail is not None else "float32"
    new_tail = helper.create_variable_for_type_inference(dtype=tail_dtype)
    ins = {"X": [input], "W": [w], "Bias": [b]}
    if tail is not None:
        ins["Tail"] = [tail]
    if length is not None:
        ins["Length"] = [length]
    helper.append_op(
        type="causal_conv1d", inputs=ins,
        outputs={"Out": [out], "NewTail": [new_tail]}, attrs={},
        infer_shape=False)
    out.shape = tuple(input.shape or ())
    out.dtype = "float32"
    new_tail.shape = tuple(input.shape[:1] or ()) + ((k - 1) * ch,)
    new_tail.dtype = tail_dtype
    return out, new_tail


def selective_scan(x, delta, z, b, c, state=None, length=None,
                   param_attr=None, name=None):
    """The selective state-space recurrence of a Mamba-1 mixer
    (ops/ssm_ops.selective_scan; kernels/selective_scan.py): ``x``,
    ``delta`` (the step before its bias and softplus) and the gate ``z``
    [B, L, C], the token's maps ``b`` and ``c`` [B, L, N]. Creates the
    float32 parameters ``a_log`` [C, N], ``d`` [C] and ``dt_bias`` [C]
    (``param_attr`` maps those three names to a ParamAttr each).
    ``state`` [B, N, C] is a served row's (zeros without), ``length``
    [B] int32 the real tokens of each right-padded row. Returns ``(out
    [B, L, C] float32, new_state [B, N, C] float32)``."""
    helper = LayerHelper("selective_scan", name=name)
    attr = param_attr or {}
    ch, n = int(x.shape[-1]), int(b.shape[-1])
    a_log = helper.create_parameter(
        attr.get("a_log"), shape=[ch, n], dtype="float32",
        default_initializer=init_mod.ConstantInitializer(0.0))
    d_skip = helper.create_parameter(
        attr.get("d"), shape=[ch], dtype="float32",
        default_initializer=init_mod.ConstantInitializer(1.0))
    dt_bias = helper.create_parameter(
        attr.get("dt_bias"), shape=[ch], dtype="float32",
        default_initializer=init_mod.ConstantInitializer(0.0))
    out = helper.create_variable_for_type_inference(dtype="float32")
    new_state = helper.create_variable_for_type_inference(dtype="float32")
    ins = {"X": [x], "Delta": [delta], "Z": [z], "B": [b], "C": [c],
           "ALog": [a_log], "D": [d_skip], "DtBias": [dt_bias]}
    if state is not None:
        ins["State"] = [state]
    if length is not None:
        ins["Length"] = [length]
    helper.append_op(
        type="selective_scan", inputs=ins,
        outputs={"Out": [out], "NewState": [new_state]}, attrs={},
        infer_shape=False)
    out.shape = tuple(x.shape or ())
    out.dtype = "float32"
    new_state.shape = tuple(x.shape[:1] or ()) + (n, ch)
    new_state.dtype = "float32"
    return out, new_state


def sample_tokens(logits, temperature, top_k=None, seed=0, name=None):
    """Next-token selection over ``logits`` [B, V] with per-row sampling
    config: ``temperature`` [B] float32 (<= 0 -> greedy argmax), optional
    ``top_k`` [B] int32 (> 0 -> restrict sampling to the k highest
    logits). Draws from the framework RNG stream — fixed executor seed
    gives bitwise-reproducible sequences. Returns sampled ids [B] int32."""
    helper = LayerHelper("sample_tokens", name=name)
    out = helper.create_variable_for_type_inference(dtype="int32")
    ins = {"X": [logits], "Temperature": [temperature]}
    if top_k is not None:
        ins["TopK"] = [top_k]
    helper.append_op(
        type="sample_tokens", inputs=ins, outputs={"Out": [out]},
        attrs={"seed": int(seed)}, infer_shape=False)
    out.shape = tuple(logits.shape[:1] or ())
    out.dtype = "int32"
    return out


def spec_accept(logits, draft, temperature, num_draft, top_k=None,
                seed=0, name=None):
    """Speculative-decoding acceptance over a verified span: ``logits``
    [B, S, V] (the verify step's per-position distributions), ``draft``
    [B, K] int32 proposals (K = S-1), per-row ``temperature`` [B] /
    optional ``top_k`` [B] sampling config (matching
    :func:`sample_tokens` exactly), ``num_draft`` [B] int32 real draft
    counts. Returns ``(tokens [B, S] int32, accepted [B] int32)`` —
    row b emits ``tokens[b, :accepted[b] + 1]``. Greedy rows are
    bitwise-identical to sequential decode; stochastic rows preserve
    the sampler's output distribution via rejection sampling."""
    helper = LayerHelper("spec_accept", name=name)
    out = helper.create_variable_for_type_inference(dtype="int32")
    acc = helper.create_variable_for_type_inference(dtype="int32")
    ins = {"X": [logits], "Draft": [draft],
           "Temperature": [temperature], "NumDraft": [num_draft]}
    if top_k is not None:
        ins["TopK"] = [top_k]
    helper.append_op(
        type="spec_accept", inputs=ins,
        outputs={"Out": [out], "Accepted": [acc]},
        attrs={"seed": int(seed)}, infer_shape=False)
    out.shape = tuple(logits.shape[:2] or ())
    out.dtype = "int32"
    acc.shape = tuple(logits.shape[:1] or ())
    acc.dtype = "int32"
    return out, acc


def beam_search(pre_ids, pre_scores, scores, beam_size, end_id=0,
                name=None):
    """One beam expansion step (reference layers/rnn.py beam_search ->
    beam_search_op). Returns (selected_ids [B, beam] int32,
    selected_scores [B, beam], parent_idx [B, beam] int32)."""
    helper = LayerHelper("beam_search", name=name)
    B = pre_ids.shape[0] if pre_ids.shape else -1
    outs = []
    for suffix, dtype in (("ids", "int32"), ("scores", "float32"),
                          ("parents", "int32")):
        outs.append(helper.block.create_var(
            name=f"{helper.name}.{suffix}", dtype=dtype,
            shape=(B, beam_size)))
    helper.append_op(
        type="beam_search",
        inputs={"pre_ids": [pre_ids], "pre_scores": [pre_scores],
                "scores": [scores]},
        outputs={"selected_ids": [outs[0]],
                 "selected_scores": [outs[1]],
                 "parent_idx": [outs[2]]},
        attrs={"beam_size": int(beam_size), "end_id": int(end_id)},
        infer_shape=False)
    return tuple(outs)


def gather_tree(ids, parents, name=None):
    """Back-trace beam parents into sequences (reference
    layers gather_tree -> gather_tree_op). ids/parents [T, B, beam]."""
    helper = LayerHelper("gather_tree", name=name)
    out = helper.block.create_var(name=f"{helper.name}.out",
                                  dtype="int32",
                                  shape=tuple(ids.shape or ()))
    helper.append_op(type="gather_tree",
                     inputs={"Ids": [ids], "Parents": [parents]},
                     outputs={"Out": [out]}, attrs={}, infer_shape=False)
    return out


def py_func(func, x, out, backward_func=None,
            skip_vars_in_backward_input=None, name=None):
    """Run user Python inside the program (reference layers/nn.py:12799
    py_func + py_func_op.cc). `func(*numpy_inputs)` fills `out` (pre-made
    Variable(s) carrying the static shape/dtype the TPU program needs);
    `backward_func(*inputs, *outputs, *out_grads)` returns per-input
    grads (None allowed). Both must be PURE — the compiled program may
    re-invoke them (jax.pure_callback semantics).
    `skip_vars_in_backward_input` is accepted for API parity; the
    backward here always receives the full (inputs, outputs, grads)
    tuple and may ignore entries."""
    from ..framework.core import Variable
    from ..ops.extra_ops import register_py_func
    helper = LayerHelper("py_func", name=name)
    xs = [x] if isinstance(x, Variable) else list(x)
    outs = [out] if isinstance(out, Variable) else list(out)
    for v in outs:
        if v.shape is None or any(s is None or s < 0 for s in v.shape):
            raise ValueError(
                f"py_func out {v.name!r} needs a fully static shape "
                f"(got {v.shape}) — XLA compiles the callback's result "
                f"buffer ahead of time")
    attrs = {"func_id": register_py_func(func),
             "out_shapes": [list(v.shape) for v in outs],
             "out_dtypes": [str(v.dtype) for v in outs]}
    if backward_func is not None:
        attrs["bwd_func_id"] = register_py_func(backward_func)
    helper.append_op(type="py_func", inputs={"X": xs},
                     outputs={"Out": outs}, attrs=attrs,
                     infer_shape=False)
    return out


# ---- round-4 layer-surface wrappers over existing op lowerings ----

def scatter_nd_add(ref, index, updates, name=None):
    helper = LayerHelper("scatter_nd_add", name=name)
    out = helper.create_variable_for_type_inference(dtype=ref.dtype)
    helper.append_op(type="scatter_nd_add",
                     inputs={"X": [ref], "Index": [index],
                             "Updates": [updates]},
                     outputs={"Out": [out]}, infer_shape=False)
    return out


def strided_slice(input, axes, starts, ends, strides, name=None):
    helper = LayerHelper("strided_slice", name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(type="strided_slice", inputs={"Input": [input]},
                     outputs={"Out": [out]},
                     attrs={"axes": list(axes), "starts": list(starts),
                            "ends": list(ends),
                            "strides": list(strides)},
                     infer_shape=False)
    return out


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    helper = LayerHelper("unfold", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)

    def _pair2(v):
        return [v, v] if isinstance(v, int) else list(v)
    helper.append_op(type="unfold", inputs={"X": [x]},
                     outputs={"Y": [out]},
                     attrs={"kernel_sizes": _pair2(kernel_sizes),
                            "strides": _pair2(strides),
                            "paddings": (list(paddings)
                                         if isinstance(paddings,
                                                       (list, tuple))
                                         else [paddings] * 4),
                            "dilations": _pair2(dilations)},
                     infer_shape=False)
    return out


def pixel_shuffle(x, upscale_factor, name=None):
    return _unary("pixel_shuffle", x, name=name,
                  attrs={"upscale_factor": int(upscale_factor)})


def shuffle_channel(x, group, name=None):
    return _unary("shuffle_channel", x, name=name,
                  attrs={"group": int(group)})


def temporal_shift(x, seg_num, shift_ratio=0.25, name=None):
    return _unary("temporal_shift", x, name=name,
                  attrs={"seg_num": int(seg_num),
                         "shift_ratio": float(shift_ratio)})


def pad_constant_like(x, y, pad_value=0.0, name=None):
    helper = LayerHelper("pad_constant_like", name=name)
    out = helper.create_variable_for_type_inference(dtype=y.dtype)
    helper.append_op(type="pad_constant_like",
                     inputs={"X": [x], "Y": [y]},
                     outputs={"Out": [out]},
                     attrs={"pad_value": float(pad_value)},
                     infer_shape=False)
    return out


def _crop_impl(op_type, x, shape, offsets, name):
    from ..framework.core import Variable
    helper = LayerHelper(op_type, name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    ins = {"X": [x]}
    attrs = {}
    if isinstance(shape, Variable):
        raise ValueError(
            f"{op_type}: a tensor `shape` is a dynamic output shape — "
            f"pass a static list on TPU (offsets MAY be a tensor)")
    if shape is not None:
        attrs["shape"] = list(shape)
    if isinstance(offsets, Variable):
        ins["Offsets"] = [offsets]      # runtime offsets: dynamic_slice
    elif offsets is not None:
        attrs["offsets"] = list(offsets)
    helper.append_op(type=op_type, inputs=ins, outputs={"Out": [out]},
                     attrs=attrs, infer_shape=False)
    return out


def crop(x, shape=None, offsets=None, name=None):
    return _crop_impl("crop", x, shape, offsets, name)


def crop_tensor(x, shape=None, offsets=None, name=None):
    return _crop_impl("crop_tensor", x, shape, offsets, name)


def expand_as(x, target_tensor, name=None):
    helper = LayerHelper("expand_as", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(type="expand_as",
                     inputs={"X": [x], "target_tensor": [target_tensor]},
                     outputs={"Out": [out]}, infer_shape=False)
    return out


def gaussian_random(shape, mean=0.0, std=1.0, seed=0, dtype="float32",
                    name=None):
    helper = LayerHelper("gaussian_random", name=name)
    out = helper.create_variable_for_type_inference(dtype=dtype)
    helper.append_op(type="gaussian_random", inputs={},
                     outputs={"Out": [out]},
                     attrs={"shape": list(shape), "mean": float(mean),
                            "std": float(std), "seed": int(seed),
                            "dtype": dtype},
                     infer_shape=False)
    return out


def maxout(x, groups, name=None, axis=1):
    return _unary("maxout", x, name=name,
                  attrs={"groups": int(groups), "axis": int(axis)})


def space_to_depth(x, blocksize, name=None):
    return _unary("space_to_depth", x, name=name,
                  attrs={"blocksize": int(blocksize)})


def affine_channel(x, scale=None, bias=None, data_layout="NCHW",
                   name=None, act=None):
    helper = LayerHelper("affine_channel", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    ins = {"X": [x]}
    if scale is not None:
        ins["Scale"] = [scale]
    if bias is not None:
        ins["Bias"] = [bias]
    helper.append_op(type="affine_channel", inputs=ins,
                     outputs={"Out": [out]},
                     attrs={"data_layout": data_layout},
                     infer_shape=False)
    return helper.append_activation(out, act)


def unique_with_counts(x, dtype="int32"):
    helper = LayerHelper("unique_with_counts")
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    index = helper.create_variable_for_type_inference(dtype=dtype)
    count = helper.create_variable_for_type_inference(dtype=dtype)
    helper.append_op(type="unique_with_counts", inputs={"X": [x]},
                     outputs={"Out": [out], "Index": [index],
                              "Count": [count]},
                     attrs={"dtype": dtype}, infer_shape=False)
    return out, index, count


def fsp_matrix(x, y, name=None):
    """FSP matrix for distillation (reference layers/nn.py fsp_matrix /
    fsp_op.h)."""
    helper = LayerHelper("fsp", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(type="fsp", inputs={"X": [x], "Y": [y]},
                     outputs={"Out": [out]}, infer_shape=False)
    return out


def continuous_value_model(input, cvm, use_cvm=True, name=None):
    """CVM op for CTR (reference layers/nn.py continuous_value_model /
    cvm_op.h)."""
    helper = LayerHelper("cvm", name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(type="cvm",
                     inputs={"X": [input], "CVM": [cvm]},
                     outputs={"Y": [out]},
                     attrs={"use_cvm": bool(use_cvm)},
                     infer_shape=False)
    return out


# ---- round-4 batch 2: remaining fluid.layers surface ----

def brelu(x, t_min=0.0, t_max=24.0, name=None):
    return _unary("brelu", x, name=name,
                  attrs={"t_min": float(t_min), "t_max": float(t_max)})


def selu(x, scale=1.0507009873554805, alpha=1.6732632423543772,
         name=None):
    return _unary("selu", x, name=name,
                  attrs={"scale": float(scale), "alpha": float(alpha)})


def stanh(x, scale_a=0.67, scale_b=1.7159, name=None):
    return _unary("stanh", x, name=name,
                  attrs={"scale_a": float(scale_a),
                         "scale_b": float(scale_b)})


def _triple(v):
    if isinstance(v, (list, tuple)):
        return tuple(v)
    return (v, v, v)


def conv3d(input, num_filters, filter_size, stride=1, padding=0,
           dilation=1, groups=1, param_attr=None, bias_attr=None,
           act=None, use_cudnn=True, name=None, data_format="NCDHW"):
    helper = LayerHelper("conv3d", param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    num_channels = input.shape[1]
    filter_size = _triple(filter_size)
    stride = _triple(stride)
    dilation = _triple(dilation)
    if isinstance(padding, str):
        paddings, algo = [0, 0, 0], padding.upper()
    else:
        paddings, algo = list(_triple(padding)), "EXPLICIT"
    filter_shape = [num_filters, num_channels // groups] + \
        list(filter_size)
    fan = filter_size[0] * filter_size[1] * filter_size[2] * num_channels
    w = helper.create_parameter(
        helper.param_attr, shape=filter_shape, dtype=input.dtype,
        default_initializer=init_mod.NormalInitializer(
            0.0, (2.0 / fan) ** 0.5))
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(
        type="conv3d",
        inputs={"Input": [input], "Filter": [w]},
        outputs={"Output": [out]},
        attrs={"strides": list(stride), "paddings": paddings,
               "dilations": list(dilation), "groups": groups,
               "padding_algorithm": algo, "data_format": data_format})
    out = _append_channel_bias(helper, out)
    return helper.append_activation(out, act)


def conv3d_transpose(input, num_filters, output_size=None,
                     filter_size=None, stride=1, padding=0, dilation=1,
                     groups=1, param_attr=None, bias_attr=None, act=None,
                     name=None):
    helper = LayerHelper("conv3d_transpose", param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    num_channels = input.shape[1]
    if filter_size is None:
        raise ValueError("filter_size required")
    filter_size = _triple(filter_size)
    stride = _triple(stride)
    dilation = _triple(dilation)
    if isinstance(padding, str):
        paddings, algo = [0, 0, 0], padding.upper()
    else:
        paddings, algo = list(_triple(padding)), "EXPLICIT"
    filter_shape = [num_channels, num_filters // groups] + \
        list(filter_size)
    w = helper.create_parameter(helper.param_attr, shape=filter_shape,
                                dtype=input.dtype)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(
        type="conv3d_transpose",
        inputs={"Input": [input], "Filter": [w]},
        outputs={"Output": [out]},
        attrs={"strides": list(stride), "paddings": paddings,
               "dilations": list(dilation), "groups": groups,
               "padding_algorithm": algo})
    out = _append_channel_bias(helper, out)
    return helper.append_activation(out, act)


def lrn(input, n=5, k=1.0, alpha=1e-4, beta=0.75, name=None,
        data_format="NCHW"):
    return _unary("lrn", input, name=name,
                  attrs={"n": int(n), "k": float(k),
                         "alpha": float(alpha), "beta": float(beta)})


def instance_norm(input, epsilon=1e-5, param_attr=None, bias_attr=None,
                  name=None):
    helper = LayerHelper("instance_norm", param_attr=param_attr,
                         bias_attr=bias_attr, name=name)
    C = input.shape[1]
    ins = {"X": [input]}
    if param_attr is not False:
        scale = helper.create_parameter(
            helper.param_attr, shape=[C], dtype=input.dtype,
            default_initializer=init_mod.ConstantInitializer(1.0))
        ins["Scale"] = [scale]
    if bias_attr is not False:
        bias = helper.create_parameter(
            helper.bias_attr, shape=[C], dtype=input.dtype,
            default_initializer=init_mod.ConstantInitializer(0.0))
        ins["Bias"] = [bias]
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(type="instance_norm", inputs=ins,
                     outputs={"Y": [out]},
                     attrs={"epsilon": float(epsilon)},
                     infer_shape=False)
    return out


def data_norm(input, act=None, epsilon=1e-5, param_attr=None,
              data_layout="NCHW", in_place=False, name=None,
              moving_mean_name=None, moving_variance_name=None,
              do_model_average_for_mean_and_var=True, slot_dim=-1,
              sync_stats=False, summary_decay_rate=0.9999999,
              enable_scale_and_shift=False):
    """Streaming feature normalization (reference layers/nn.py data_norm
    / data_norm_op.h): batch-count/sum/square-sum accumulators are
    persistable parameters updated functionally every step."""
    helper = LayerHelper("data_norm", param_attr=param_attr, name=name)
    D = input.shape[-1]
    dtype = input.dtype
    # reference contract (layers/nn.py:3245): param_attr keys
    # batch_size/batch_sum/batch_square hold the accumulators' INITIAL
    # VALUES
    pa = param_attr if isinstance(param_attr, dict) else {}
    size = helper.create_parameter(
        ParamAttr(), shape=[D], dtype=dtype,
        default_initializer=init_mod.ConstantInitializer(
            float(pa.get("batch_size", 1e4))))
    bsum = helper.create_parameter(
        ParamAttr(), shape=[D], dtype=dtype,
        default_initializer=init_mod.ConstantInitializer(
            float(pa.get("batch_sum", 0.0))))
    sqsum = helper.create_parameter(
        ParamAttr(), shape=[D], dtype=dtype,
        default_initializer=init_mod.ConstantInitializer(
            float(pa.get("batch_square", 1e4))))
    out = helper.create_variable_for_type_inference(dtype=dtype)
    means = helper.create_variable_for_type_inference(dtype=dtype)
    scales = helper.create_variable_for_type_inference(dtype=dtype)
    helper.append_op(
        type="data_norm",
        inputs={"X": [input], "BatchSize": [size], "BatchSum": [bsum],
                "BatchSquareSum": [sqsum]},
        outputs={"Y": [out], "Means": [means], "Scales": [scales],
                 "BatchSizeOut": [size], "BatchSumOut": [bsum],
                 "BatchSquareSumOut": [sqsum]},
        attrs={"epsilon": float(epsilon)},
        infer_shape=False)
    return helper.append_activation(out, act)


def spectral_norm(weight, dim=0, power_iters=1, eps=1e-12, name=None):
    helper = LayerHelper("spectral_norm", name=name)
    w_shape = list(weight.shape)
    h = w_shape[dim]
    wdim = 1
    for i, s in enumerate(w_shape):
        if i != dim:
            wdim *= s
    u = helper.create_parameter(
        ParamAttr(name=None, trainable=False), shape=[h],
        dtype=weight.dtype,
        default_initializer=init_mod.NormalInitializer(0.0, 1.0))
    v = helper.create_parameter(
        ParamAttr(name=None, trainable=False), shape=[wdim],
        dtype=weight.dtype,
        default_initializer=init_mod.NormalInitializer(0.0, 1.0))
    out = helper.create_variable_for_type_inference(dtype=weight.dtype)
    helper.append_op(
        type="spectral_norm",
        inputs={"Weight": [weight], "U": [u], "V": [v]},
        outputs={"Out": [out], "UOut": [u], "VOut": [v]},
        attrs={"dim": int(dim), "power_iters": int(power_iters),
               "eps": float(eps)},
        infer_shape=False)
    return out


def multiplex(inputs, index, name=None):
    helper = LayerHelper("multiplex", name=name)
    out = helper.create_variable_for_type_inference(
        dtype=inputs[0].dtype)
    helper.append_op(type="multiplex",
                     inputs={"X": list(inputs), "Ids": [index]},
                     outputs={"Out": [out]}, infer_shape=False)
    return out


def reverse(x, axis, name=None):
    if isinstance(axis, int):
        axis = [axis]
    return _unary("reverse", x, name=name, attrs={"axis": list(axis)})


def is_empty(x, cond=None, name=None):
    helper = LayerHelper("is_empty", name=name)
    out = cond or helper.create_variable_for_type_inference(dtype="bool")
    helper.append_op(type="is_empty", inputs={"X": [x]},
                     outputs={"Out": [out]}, infer_shape=False)
    return out


def chunk_eval(input, label, chunk_scheme, num_chunk_types,
               excluded_chunk_types=None, seq_length=None):
    helper = LayerHelper("chunk_eval")
    outs = [helper.create_variable_for_type_inference(dtype=d)
            for d in ("float32", "float32", "float32", "int64", "int64",
                      "int64")]
    ins = {"Inference": [input], "Label": [label]}
    if seq_length is not None:
        ins["SeqLength"] = [seq_length]
    helper.append_op(
        type="chunk_eval", inputs=ins,
        outputs={"Precision": [outs[0]], "Recall": [outs[1]],
                 "F1-Score": [outs[2]], "NumInferChunks": [outs[3]],
                 "NumLabelChunks": [outs[4]],
                 "NumCorrectChunks": [outs[5]]},
        attrs={"num_chunk_types": int(num_chunk_types),
               "chunk_scheme": chunk_scheme,
               "excluded_chunk_types": list(excluded_chunk_types or [])},
        infer_shape=False)
    return tuple(outs)


def roi_align(input, rois, pooled_height=1, pooled_width=1,
              spatial_scale=1.0, sampling_ratio=-1, name=None,
              rois_num=None):
    helper = LayerHelper("roi_align", name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    ins = {"X": [input], "ROIs": [rois]}
    if rois_num is not None:
        ins["RoisNum"] = [rois_num]
    helper.append_op(type="roi_align", inputs=ins,
                     outputs={"Out": [out]},
                     attrs={"pooled_height": int(pooled_height),
                            "pooled_width": int(pooled_width),
                            "spatial_scale": float(spatial_scale),
                            "sampling_ratio": int(sampling_ratio)},
                     infer_shape=False)
    return out


def roi_pool(input, rois, pooled_height=1, pooled_width=1,
             spatial_scale=1.0, rois_num=None, name=None):
    helper = LayerHelper("roi_pool", name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    argmax = helper.create_variable_for_type_inference(dtype="int32")
    ins = {"X": [input], "ROIs": [rois]}
    if rois_num is not None:
        ins["RoisNum"] = [rois_num]
    helper.append_op(type="roi_pool", inputs=ins,
                     outputs={"Out": [out], "Argmax": [argmax]},
                     attrs={"pooled_height": int(pooled_height),
                            "pooled_width": int(pooled_width),
                            "spatial_scale": float(spatial_scale)},
                     infer_shape=False)
    return out


def resize_bilinear(input, out_shape=None, scale=None, name=None,
                    actual_shape=None, align_corners=True, align_mode=1,
                    data_format="NCHW"):
    if out_shape is None and scale is not None:
        out_shape = [int(input.shape[2] * scale),
                     int(input.shape[3] * scale)]
    return image_resize(input, out_shape, resample="BILINEAR", name=name)


def resize_nearest(input, out_shape=None, scale=None, name=None,
                   actual_shape=None, align_corners=True,
                   data_format="NCHW"):
    if out_shape is None and scale is not None:
        out_shape = [int(input.shape[2] * scale),
                     int(input.shape[3] * scale)]
    return image_resize(input, out_shape, resample="NEAREST", name=name)


def resize_trilinear(input, out_shape=None, scale=None, name=None,
                     actual_shape=None, align_corners=True, align_mode=1,
                     data_format="NCDHW"):
    if out_shape is None and scale is not None:
        out_shape = [int(s * scale) for s in input.shape[2:]]
    d, h, w = [int(v) for v in out_shape]
    helper = LayerHelper("trilinear_interp", name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(type="trilinear_interp", inputs={"X": [input]},
                     outputs={"Out": [out]},
                     attrs={"out_d": d, "out_h": h, "out_w": w,
                            "align_corners": bool(align_corners),
                            "align_mode": int(align_mode)},
                     infer_shape=False)
    return out


def image_resize_short(input, out_short_len, resample="BILINEAR"):
    """Resize so the SHORT side equals out_short_len, keeping aspect
    (reference layers/nn.py image_resize_short)."""
    h, w = int(input.shape[2]), int(input.shape[3])
    short, long_ = (h, w) if h < w else (w, h)
    ratio = out_short_len / float(short)
    out_shape = ([out_short_len, int(w * ratio)] if h < w
                 else [int(h * ratio), out_short_len])
    return image_resize(input, out_shape, resample=resample)


def warpctc(input, label, blank=0, norm_by_times=False,
            input_length=None, label_length=None):
    """CTC loss (reference warpctc_op.h). Masked-dense layout: Logits
    [B, T, V] batch-major padded + input_length/label_length (the
    reference's LoD form is time-major packed)."""
    helper = LayerHelper("warpctc")
    loss = helper.create_variable_for_type_inference(dtype=input.dtype)
    ins = {"Logits": [input], "Label": [label]}
    if input_length is not None:
        ins["LogitsLength"] = [input_length]
    if label_length is not None:
        ins["LabelLength"] = [label_length]
    helper.append_op(type="warpctc", inputs=ins,
                     outputs={"Loss": [loss]},
                     attrs={"blank": int(blank),
                            "norm_by_times": bool(norm_by_times)},
                     infer_shape=False)
    return loss


def nce(input, label, num_total_classes, sample_weight=None,
        param_attr=None, bias_attr=None, num_neg_samples=None,
        name=None, sampler="uniform", custom_dist=None, seed=0,
        is_sparse=False):
    helper = LayerHelper("nce", param_attr=param_attr,
                         bias_attr=bias_attr, name=name)
    D = input.shape[-1]
    w = helper.create_parameter(helper.param_attr,
                                shape=[num_total_classes, D],
                                dtype=input.dtype)
    ins = {"Input": [input], "Label": [label], "Weight": [w]}
    if bias_attr is not False:
        b = helper.create_parameter(
            helper.bias_attr, shape=[num_total_classes],
            dtype=input.dtype,
            default_initializer=init_mod.ConstantInitializer(0.0))
        ins["Bias"] = [b]
    cost = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(type="nce", inputs=ins,
                     outputs={"Cost": [cost]},
                     attrs={"num_total_classes": int(num_total_classes),
                            "num_neg_samples": int(num_neg_samples or 10),
                            "seed": int(seed)},
                     infer_shape=False)
    return cost


def similarity_focus(input, axis, indexes, name=None):
    return _unary("similarity_focus", input, name=name,
                  attrs={"axis": int(axis),
                         "indexes": [int(i) for i in indexes]})


def filter_by_instag(ins, ins_tag, filter_tag, is_lod=True,
                     out_val_if_empty=0):
    helper = LayerHelper("filter_by_instag")
    out = helper.create_variable_for_type_inference(dtype=ins.dtype)
    loss_weight = helper.create_variable_for_type_inference(
        dtype="float32")
    index_map = helper.create_variable_for_type_inference(dtype="int32")
    out_count = helper.create_variable_for_type_inference(dtype="int32")
    helper.append_op(
        type="filter_by_instag",
        inputs={"Ins": [ins], "Ins_tag": [ins_tag],
                "Filter_tag": [filter_tag]},
        outputs={"Out": [out], "LossWeight": [loss_weight],
                 "IndexMap": [index_map], "OutCount": [out_count]},
        attrs={"is_lod": bool(is_lod)},
        infer_shape=False)
    return out, loss_weight, index_map


def uniform_random(shape, dtype="float32", min=-1.0, max=1.0, seed=0,
                   name=None):
    helper = LayerHelper("uniform_random", name=name)
    out = helper.create_variable_for_type_inference(dtype=dtype)
    helper.append_op(type="uniform_random", inputs={},
                     outputs={"Out": [out]},
                     attrs={"shape": list(shape), "min": float(min),
                            "max": float(max), "seed": int(seed),
                            "dtype": dtype},
                     infer_shape=False)
    return out


def _random_batch_size_like(op_type, input, shape, input_dim_idx,
                            output_dim_idx, dtype, extra):
    helper = LayerHelper(op_type)
    out = helper.create_variable_for_type_inference(dtype=dtype)
    helper.append_op(type=op_type, inputs={"Input": [input]},
                     outputs={"Out": [out]},
                     attrs=dict(extra, shape=list(shape),
                                input_dim_idx=int(input_dim_idx),
                                output_dim_idx=int(output_dim_idx),
                                dtype=dtype),
                     infer_shape=False)
    return out


def uniform_random_batch_size_like(input, shape, dtype="float32",
                                   input_dim_idx=0, output_dim_idx=0,
                                   min=-1.0, max=1.0, seed=0):
    return _random_batch_size_like(
        "uniform_random_batch_size_like", input, shape, input_dim_idx,
        output_dim_idx, dtype,
        {"min": float(min), "max": float(max), "seed": int(seed)})


def gaussian_random_batch_size_like(input, shape, input_dim_idx=0,
                                    output_dim_idx=0, mean=0.0, std=1.0,
                                    seed=0, dtype="float32"):
    return _random_batch_size_like(
        "gaussian_random_batch_size_like", input, shape, input_dim_idx,
        output_dim_idx, dtype,
        {"mean": float(mean), "std": float(std), "seed": int(seed)})


def inplace_abn(input, act=None, is_test=False, momentum=0.9,
                epsilon=1e-5, param_attr=None, bias_attr=None,
                data_layout="NCHW", name=None, **kwargs):
    """Inplace activated batch norm (reference inplace_abn_op.cc) — on
    TPU 'inplace' is XLA's buffer planning; this is batch_norm + act."""
    return batch_norm(input, act=act, is_test=is_test, momentum=momentum,
                      epsilon=epsilon, param_attr=param_attr,
                      bias_attr=bias_attr, data_layout=data_layout,
                      name=name)


def deformable_roi_pooling(input, rois, trans, no_trans=False,
                           spatial_scale=1.0, group_size=(1, 1),
                           pooled_height=1, pooled_width=1,
                           part_size=None, sample_per_part=1,
                           trans_std=0.1, position_sensitive=False,
                           name=None):
    helper = LayerHelper("deformable_psroi_pooling", name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    top_count = helper.create_variable_for_type_inference(dtype="int32")
    part = part_size or (pooled_height, pooled_width)
    helper.append_op(
        type="deformable_psroi_pooling",
        inputs={"Input": [input], "ROIs": [rois], "Trans": [trans]},
        outputs={"Output": [out], "TopCount": [top_count]},
        attrs={"no_trans": bool(no_trans),
               "spatial_scale": float(spatial_scale),
               "output_dim": int(input.shape[1]) // (
                   int(group_size[0]) * int(group_size[1]))
               if position_sensitive else int(input.shape[1]),
               "group_size": [int(g) for g in group_size],
               "pooled_height": int(pooled_height),
               "pooled_width": int(pooled_width),
               "part_size": [int(p) for p in part],
               "sample_per_part": int(sample_per_part),
               "trans_std": float(trans_std)},
        infer_shape=False)
    return out


def unique(x, dtype="int32"):
    """TPU divergence (PARITY.md): `unique` has a data-dependent output
    shape; use unique_with_counts (padded + count)."""
    raise NotImplementedError(
        "unique has a data-dependent output shape on TPU; use "
        "layers.unique_with_counts (first-occurrence order, padded "
        "with a Count output) instead")


# ---- layer_function_generator parity (reference
# python/paddle/fluid/layers/layer_function_generator.py) ----

def templatedoc(op_type=None):
    """Doc-templating decorator (reference layer_function_generator.py
    templatedoc): docs are authored directly here, so it is identity."""
    def deco(fn):
        return fn
    return deco


def autodoc(comment=""):
    def deco(fn):
        fn.__doc__ = comment + (fn.__doc__ or "")
        return fn
    return deco


def deprecated(since=None, instead=None, reason=""):
    """Mark a layer deprecated (reference annotations): warns on call."""
    def deco(fn):
        import functools
        import warnings

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            warnings.warn(
                f"{fn.__name__} is deprecated"
                + (f" since {since}" if since else "")
                + (f"; use {instead}" if instead else "")
                + (f" ({reason})" if reason else ""),
                DeprecationWarning, stacklevel=2)
            return fn(*args, **kwargs)
        return wrapper
    return deco


def generate_layer_fn(op_type):
    """Build a layer fn for a registered op type (reference
    layer_function_generator.py generate_layer_fn): inputs by slot
    kwargs, single Out."""
    def fn(*args, **kwargs):
        helper = LayerHelper(op_type, name=kwargs.pop("name", None))
        ins = {}
        first = None
        for slot in list(kwargs):
            v = kwargs[slot]
            if isinstance(v, Variable):
                ins[slot] = [kwargs.pop(slot)]
                first = first or v
            elif isinstance(v, (list, tuple)) and v and \
                    all(isinstance(e, Variable) for e in v):
                ins[slot] = list(kwargs.pop(slot))
                first = first or v[0]
        if len(args) == 1:
            ins["X"] = [args[0]]
        elif len(args) == 2:
            ins["X"], ins["Y"] = [args[0]], [args[1]]
        elif len(args) > 2:
            ins["X"] = list(args)       # variadic ops (sum/concat style)
        if args:
            first = first or args[0]
        out = helper.create_variable_for_type_inference(
            dtype=first.dtype if first is not None else "float32")
        helper.append_op(type=op_type, inputs=ins,
                         outputs={"Out": [out]}, attrs=dict(kwargs),
                         infer_shape=False)
        return out
    fn.__name__ = op_type
    return fn


def generate_activation_fn(op_type):
    def fn(x, name=None):
        return _unary(op_type, x, name=name)
    fn.__name__ = op_type
    return fn


# ---- reader plumbing (by-design divergence, PARITY.md: the host
# DataLoader owns async feeding; these names guide users there) ----

def py_reader(capacity, shapes, dtypes, lod_levels=None, name=None,
              use_double_buffer=True):
    raise NotImplementedError(
        "py_reader's feed-queue ops are replaced by the host DataLoader "
        "on TPU (by-design, PARITY.md): use "
        "fluid.io.PyReader(feed_list=..., capacity=...) or "
        "fluid.io.DataLoader.from_generator(...) — same capability, "
        "host-side double buffering")


def create_py_reader_by_data(capacity, feed_list, name=None,
                             use_double_buffer=True):
    from ..dataio.reader import PyReader as _PyReader
    return _PyReader(feed_list=feed_list, capacity=capacity,
                     use_double_buffer=use_double_buffer)


def double_buffer(reader, place=None, name=None):
    """Identity: the DataLoader double-buffers host-side (by design)."""
    return reader


def read_file(reader):
    raise NotImplementedError(
        "read_file consumes py_reader's queue vars; on TPU feed through "
        "the DataLoader's batch dicts instead (PARITY.md reader-ops row)")


def load(out, file_path, load_as_fp16=None):
    """reference layers/io.py load / load_op.cc: fill `out` from a saved
    .npy file at EXECUTION time (host callback). When `out` carries no
    static shape (create_tensor), the shape/dtype come from the file
    HEADER at build time (mmap — no data read)."""
    import numpy as _np
    helper = LayerHelper("load")
    shape, dtype = out.shape, out.dtype
    if shape is None or any(s is None or s < 0 for s in shape):
        probe = _np.load(file_path, mmap_mode="r", allow_pickle=False)
        shape = probe.shape
        dtype = str(probe.dtype)
        out.shape = tuple(shape)
        out.dtype = dtype
    if load_as_fp16:
        dtype = "float16"
        out.dtype = dtype

    def _read():
        arr = _np.load(file_path, allow_pickle=False)
        return arr.astype(_np.float16) if load_as_fp16 else arr

    from ..ops.extra_ops import register_py_func
    helper.append_op(
        type="py_func", inputs={"X": []}, outputs={"Out": [out]},
        attrs={"func_id": register_py_func(_read),
               "out_shapes": [list(shape)],
               "out_dtypes": [str(dtype)]},
        infer_shape=False)
    return out


def sampled_softmax_with_cross_entropy(logits, label, num_samples,
                                       num_true=1, remove_accidental_hits=True,
                                       use_customized_samples=False,
                                       customized_samples=None,
                                       customized_probabilities=None,
                                       seed=0):
    """reference layers/nn.py sampled_softmax_with_cross_entropy /
    sample_logits_op.cc (uniform sampler). Unsupported parity args
    raise rather than silently change semantics."""
    if use_customized_samples or customized_samples is not None:
        raise NotImplementedError(
            "sampled_softmax_with_cross_entropy: customized samplers "
            "are not supported on TPU (uniform sampler only); pass "
            "use_customized_samples=False")
    if num_true != 1:
        raise NotImplementedError(
            "sampled_softmax_with_cross_entropy: num_true must be 1")
    helper = LayerHelper("sampled_softmax_with_cross_entropy")
    loss = helper.create_variable_for_type_inference(dtype=logits.dtype)
    helper.append_op(
        type="sampled_softmax_with_cross_entropy",
        inputs={"Logits": [logits], "Label": [label]},
        outputs={"Loss": [loss]},
        attrs={"num_samples": int(num_samples), "seed": int(seed),
               "remove_accidental_hits": bool(remove_accidental_hits)},
        infer_shape=False)
    return loss


def tensor_array_to_tensor(input, axis=1, name=None, use_stack=False):
    """reference tensor_array_to_tensor (layers/tensor.py): concat or
    stack a tensor array's entries."""
    helper = LayerHelper("tensor_array_to_tensor", name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    out_index = helper.create_variable_for_type_inference(dtype="int32")
    helper.append_op(type="tensor_array_to_tensor", inputs={},
                     outputs={"Out": [out], "OutIndex": [out_index]},
                     attrs={"array_name": input.name, "axis": int(axis),
                            "use_stack": bool(use_stack)},
                     infer_shape=False)
    return out, out_index
