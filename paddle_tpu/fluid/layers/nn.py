"""Neural-network layers (reference: python/paddle/fluid/layers/nn.py).

Each layer builds OpDescs into the current program block; lowering to XLA
happens at Executor compile time.  Shapes are inferred eagerly so later
layers can read ``input.shape`` like the reference's C++ InferShape provides.
"""

import copy

import numpy as np

from .. import core
from ..framework import Variable, default_main_program
from ..layer_helper import LayerHelper
from ..initializer import Normal, Constant
from ..param_attr import ParamAttr

__all__ = [
    'fc', 'embedding', 'conv2d', 'conv3d', 'conv2d_transpose',
    'pool2d', 'pool3d', 'batch_norm', 'layer_norm', 'dropout',
    'softmax', 'softmax_with_cross_entropy', 'cross_entropy',
    'square_error_cost', 'mean', 'mul', 'matmul', 'topk', 'transpose',
    'reshape', 'concat', 'split', 'reduce_sum', 'reduce_mean', 'reduce_max',
    'reduce_min', 'reduce_prod', 'l2_normalize', 'one_hot', 'relu',
    'log', 'autoincreased_step_counter', 'label_smooth', 'clip', 'clip_by_norm',
    'lrn', 'pad',
    'pad2d', 'image_resize', 'resize_bilinear', 'expand', 'stack', 'unstack',
    'squeeze', 'unsqueeze', 'gather', 'scatter', 'slice', 'shape',
    'sigmoid_cross_entropy_with_logits', 'smooth_l1', 'log_loss', 'maxout',
    'prelu', 'leaky_relu', 'soft_relu', 'flatten', 'random_crop', 'im2sequence',
    'hsigmoid', 'nce', 'multiplex', 'dropout', 'layer_norm', 'lstm_unit',
    'linear_chain_crf', 'crf_decoding', 'cos_sim', 'flash_attention',
    'rms_norm', 'swiglu', 'residual_add', 'causal_conv1d', 'ssd_scan',
    'moe_router', 'moe_experts', 'moe_bias_update',
    'moe_ffn', 'warpctc', 'ctc_greedy_decoder', 'edit_distance', 'roi_pool',
    'conv3d_transpose', 'crop', 'dice_loss', 'image_resize_short',
    'lod_reset', 'mean_iou', 'pad_constant_like', 'rank_loss',
]


def _prod(xs):
    out = 1
    for x in xs:
        out *= int(x)
    return out


def fc(input,
       size,
       num_flatten_dims=1,
       param_attr=None,
       bias_attr=None,
       act=None,
       is_test=False,
       name=None):
    """Fully-connected layer — mul + elementwise_add + activation
    (reference layers/nn.py:118; mul hits the MXU)."""
    helper = LayerHelper('fc', **locals())
    dtype = helper.input_dtype()
    mul_results = []
    for input_var, param_attr in helper.iter_inputs_and_params():
        input_shape = input_var.shape
        param_shape = [
            _prod(input_shape[num_flatten_dims:])
        ] + [size]
        w = helper.create_parameter(
            attr=param_attr, shape=param_shape, dtype=dtype, is_bias=False)
        tmp = helper.create_variable_for_type_inference(dtype)
        tmp.shape = tuple(input_shape[:num_flatten_dims]) + (size, )
        helper.append_op(
            type='mul',
            inputs={'X': [input_var],
                    'Y': [w]},
            outputs={'Out': [tmp]},
            attrs={
                'x_num_col_dims': num_flatten_dims,
                'y_num_col_dims': 1
            })
        mul_results.append(tmp)
    if len(mul_results) == 1:
        pre_bias = mul_results[0]
    else:
        pre_bias = helper.create_variable_for_type_inference(dtype)
        pre_bias.shape = mul_results[0].shape
        helper.append_op(
            type='sum',
            inputs={'X': mul_results},
            outputs={'Out': pre_bias})
    pre_activation = helper.append_bias_op(pre_bias, dim_start=num_flatten_dims)
    return helper.append_activation(pre_activation)


def embedding(input,
              size,
              is_sparse=False,
              is_distributed=False,
              padding_idx=None,
              param_attr=None,
              dtype='float32'):
    """Lookup-table layer (reference layers/nn.py embedding;
    operators/lookup_table_op.cc).  On TPU the is_sparse path is the same
    dense gather — XLA fuses it; sharded embeddings come from the SPMD layer."""
    helper = LayerHelper('embedding', **locals())
    w = helper.create_parameter(
        attr=helper.param_attr, shape=size, dtype=dtype, is_bias=False)
    tmp = helper.create_variable_for_type_inference(dtype)
    in_shape = tuple(input.shape)
    if in_shape and in_shape[-1] == 1:
        tmp.shape = in_shape[:-1] + (size[1], )
    else:
        tmp.shape = in_shape + (size[1], )
    tmp.lod_level = input.lod_level
    padding_idx = -1 if padding_idx is None else (
        padding_idx if padding_idx >= 0 else size[0] + padding_idx)
    helper.append_op(
        type='lookup_table',
        inputs={'Ids': [input],
                'W': [w]},
        outputs={'Out': [tmp]},
        attrs={
            'is_sparse': is_sparse,
            'is_distributed': is_distributed,
            'padding_idx': padding_idx
        })
    return tmp


def _pair(v):
    return list(v) if isinstance(v, (list, tuple)) else [v, v]


def _conv_out_size(i, k, p, s, d=1):
    return (i + 2 * p - (d * (k - 1) + 1)) // s + 1


def conv2d(input,
           num_filters,
           filter_size,
           stride=1,
           padding=0,
           dilation=1,
           groups=None,
           param_attr=None,
           bias_attr=None,
           use_cudnn=True,
           act=None,
           name=None):
    """2-D convolution (reference layers/nn.py conv2d; operators/conv_op.cc).
    ``use_cudnn`` is accepted for API parity and ignored — XLA owns kernels."""
    helper = LayerHelper('conv2d', **locals())
    dtype = helper.input_dtype()
    num_channels = input.shape[1]
    groups = groups or 1
    filter_size = _pair(filter_size)
    stride = _pair(stride)
    padding = _pair(padding)
    dilation = _pair(dilation)
    filter_shape = [num_filters, num_channels // groups] + filter_size

    def _get_default_param_initializer():
        std = (2.0 / (filter_size[0]**2 * num_channels))**0.5
        return Normal(0.0, std, 0)

    w = helper.create_parameter(
        attr=helper.param_attr,
        shape=filter_shape,
        dtype=dtype,
        default_initializer=_get_default_param_initializer())
    pre_bias = helper.create_variable_for_type_inference(dtype)
    n, c, h, w_ = input.shape
    pre_bias.shape = (n, num_filters,
                      _conv_out_size(h, filter_size[0], padding[0], stride[0],
                                     dilation[0]),
                      _conv_out_size(w_, filter_size[1], padding[1], stride[1],
                                     dilation[1]))
    op_type = 'depthwise_conv2d' if (groups == num_channels and
                                     num_channels == num_filters and
                                     groups > 1) else 'conv2d'
    helper.append_op(
        type=op_type,
        inputs={'Input': [input],
                'Filter': [w]},
        outputs={'Output': [pre_bias]},
        attrs={
            'strides': stride,
            'paddings': padding,
            'dilations': dilation,
            'groups': groups,
            'use_cudnn': False,
        })
    pre_act = helper.append_bias_op(pre_bias, dim_start=1, dim_end=2)
    return helper.append_activation(pre_act)


def conv3d(input,
           num_filters,
           filter_size,
           stride=1,
           padding=0,
           dilation=1,
           groups=None,
           param_attr=None,
           bias_attr=None,
           use_cudnn=True,
           act=None,
           name=None):
    helper = LayerHelper('conv3d', **locals())
    dtype = helper.input_dtype()
    num_channels = input.shape[1]
    groups = groups or 1

    def _triple(v):
        return list(v) if isinstance(v, (list, tuple)) else [v] * 3

    filter_size = _triple(filter_size)
    stride = _triple(stride)
    padding = _triple(padding)
    dilation = _triple(dilation)
    filter_shape = [num_filters, num_channels // groups] + filter_size
    std = (2.0 / (_prod(filter_size) * num_channels))**0.5
    w = helper.create_parameter(
        attr=helper.param_attr,
        shape=filter_shape,
        dtype=dtype,
        default_initializer=Normal(0.0, std, 0))
    pre_bias = helper.create_variable_for_type_inference(dtype)
    dims = input.shape
    pre_bias.shape = (dims[0], num_filters) + tuple(
        _conv_out_size(dims[2 + i], filter_size[i], padding[i], stride[i],
                       dilation[i]) for i in range(3))
    helper.append_op(
        type='conv3d',
        inputs={'Input': [input],
                'Filter': [w]},
        outputs={'Output': [pre_bias]},
        attrs={
            'strides': stride,
            'paddings': padding,
            'dilations': dilation,
            'groups': groups
        })
    pre_act = helper.append_bias_op(pre_bias, dim_start=1, dim_end=2)
    return helper.append_activation(pre_act)


def conv2d_transpose(input,
                     num_filters,
                     output_size=None,
                     filter_size=None,
                     padding=0,
                     stride=1,
                     dilation=1,
                     groups=None,
                     param_attr=None,
                     bias_attr=None,
                     use_cudnn=True,
                     act=None,
                     name=None):
    helper = LayerHelper('conv2d_transpose', **locals())
    dtype = helper.input_dtype()
    num_channels = input.shape[1]
    groups = groups or 1
    stride = _pair(stride)
    padding = _pair(padding)
    dilation = _pair(dilation)
    n, c, h, w_ = input.shape
    if filter_size is None:
        output_size = _pair(output_size)
        # reference conv2d_transpose: k = (out + 2p - (in-1)s - 1)//d + 1
        filter_size = [
            (output_size[i] + 2 * padding[i] - (s - 1) * stride[i] - 1) //
            dilation[i] + 1 for i, s in enumerate((h, w_))
        ]
    else:
        filter_size = _pair(filter_size)
    filter_shape = [num_channels, num_filters // groups] + filter_size
    w = helper.create_parameter(
        attr=helper.param_attr, shape=filter_shape, dtype=dtype)
    pre_bias = helper.create_variable_for_type_inference(dtype)
    oh = (h - 1) * stride[0] - 2 * padding[0] + dilation[0] * (
        filter_size[0] - 1) + 1
    ow = (w_ - 1) * stride[1] - 2 * padding[1] + dilation[1] * (
        filter_size[1] - 1) + 1
    pre_bias.shape = (n, num_filters, oh, ow)
    helper.append_op(
        type='conv2d_transpose',
        inputs={'Input': [input],
                'Filter': [w]},
        outputs={'Output': [pre_bias]},
        attrs={
            'strides': stride,
            'paddings': padding,
            'dilations': dilation,
            'groups': groups
        })
    pre_act = helper.append_bias_op(pre_bias, dim_start=1, dim_end=2)
    return helper.append_activation(pre_act)


def pool2d(input,
           pool_size=-1,
           pool_type='max',
           pool_stride=1,
           pool_padding=0,
           global_pooling=False,
           use_cudnn=True,
           ceil_mode=False,
           name=None,
           exclusive=True):
    """2-D pooling (reference layers/nn.py pool2d; operators/pool_op.cc)."""
    helper = LayerHelper('pool2d', **locals())
    dtype = helper.input_dtype()
    pool_size = _pair(pool_size)
    pool_stride = _pair(pool_stride)
    pool_padding = _pair(pool_padding)
    out = helper.create_variable_for_type_inference(dtype)
    n, c, h, w = input.shape
    if global_pooling:
        out.shape = (n, c, 1, 1)
    else:
        out.shape = (n, c,
                     _conv_out_size(h, pool_size[0], pool_padding[0],
                                    pool_stride[0]),
                     _conv_out_size(w, pool_size[1], pool_padding[1],
                                    pool_stride[1]))
    helper.append_op(
        type='pool2d',
        inputs={'X': [input]},
        outputs={'Out': [out]},
        attrs={
            'pooling_type': pool_type,
            'ksize': pool_size,
            'global_pooling': global_pooling,
            'strides': pool_stride,
            'paddings': pool_padding,
            'ceil_mode': ceil_mode,
            'exclusive': exclusive,
        })
    return out


def pool3d(input,
           pool_size=-1,
           pool_type='max',
           pool_stride=1,
           pool_padding=0,
           global_pooling=False,
           use_cudnn=True,
           ceil_mode=False,
           name=None):
    helper = LayerHelper('pool3d', **locals())
    dtype = helper.input_dtype()

    def _triple(v):
        return list(v) if isinstance(v, (list, tuple)) else [v] * 3

    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type='pool3d',
        inputs={'X': [input]},
        outputs={'Out': [out]},
        attrs={
            'pooling_type': pool_type,
            'ksize': _triple(pool_size),
            'global_pooling': global_pooling,
            'strides': _triple(pool_stride),
            'paddings': _triple(pool_padding),
            'ceil_mode': ceil_mode,
        })
    return out


def batch_norm(input,
               act=None,
               is_test=False,
               momentum=0.9,
               epsilon=1e-05,
               param_attr=None,
               bias_attr=None,
               data_layout='NCHW',
               in_place=False,
               name=None,
               moving_mean_name=None,
               moving_variance_name=None,
               do_model_average_for_mean_and_var=False,
               fuse_with_relu=False,
               use_global_stats=None):
    """Batch normalization (reference layers/nn.py batch_norm;
    operators/batch_norm_op.cc).  ``use_global_stats``: None = follow
    is_test / clone(for_test); True = always moving statistics; an
    EXPLICIT False keeps batch statistics even through
    clone(for_test=True) — the legacy DSL's documented False mode."""
    helper = LayerHelper('batch_norm', **locals())
    dtype = helper.input_dtype()
    input_shape = input.shape
    if data_layout == 'NCHW':
        channel_num = input_shape[1]
    else:
        channel_num = input_shape[-1]
    param_shape = [channel_num]

    scale = helper.create_parameter(
        attr=helper.param_attr,
        shape=param_shape,
        dtype=dtype,
        default_initializer=Constant(1.0))
    bias = helper.create_parameter(
        attr=helper.bias_attr, shape=param_shape, dtype=dtype, is_bias=True)

    mean = helper.create_parameter(
        attr=ParamAttr(
            name=moving_mean_name,
            initializer=Constant(0.0),
            trainable=False,
            do_model_average=do_model_average_for_mean_and_var),
        shape=param_shape,
        dtype=dtype)
    mean.stop_gradient = True
    variance = helper.create_parameter(
        attr=ParamAttr(
            name=moving_variance_name,
            initializer=Constant(1.0),
            trainable=False,
            do_model_average=do_model_average_for_mean_and_var),
        shape=param_shape,
        dtype=dtype)
    variance.stop_gradient = True

    saved_mean = helper.create_variable_for_type_inference(
        dtype=dtype, stop_gradient=True)
    saved_variance = helper.create_variable_for_type_inference(
        dtype=dtype, stop_gradient=True)
    batch_norm_out = input if in_place else \
        helper.create_variable_for_type_inference(dtype)
    batch_norm_out.shape = input.shape

    helper.append_op(
        type='batch_norm',
        inputs={
            'X': [input],
            'Scale': [scale],
            'Bias': [bias],
            'Mean': [mean],
            'Variance': [variance]
        },
        outputs={
            'Y': [batch_norm_out],
            'MeanOut': [mean],
            'VarianceOut': [variance],
            'SavedMean': [saved_mean],
            'SavedVariance': [saved_variance]
        },
        attrs={
            'momentum': momentum,
            'epsilon': epsilon,
            # is_test is stored RAW: it gates the running-statistics
            # update only.  WHICH statistics normalize is resolved in
            # the lowering from use_global_stats (an EXPLICIT value
            # wins over is_test in both directions; the tri-state
            # "follow is_test" default is represented by OMITTING the
            # attr — None is unserializable on the proto wire) — so
            # use_global_stats=False at test time uses batch statistics
            # WITHOUT the eval batches drifting the checkpointed
            # moving averages
            'is_test': bool(is_test),
            'data_layout': data_layout,
            **({} if use_global_stats is None
               else {'use_global_stats': bool(use_global_stats)}),
        })
    return helper.append_activation(batch_norm_out)


def layer_norm(input,
               scale=True,
               shift=True,
               begin_norm_axis=1,
               epsilon=1e-05,
               param_attr=None,
               bias_attr=None,
               act=None,
               name=None):
    helper = LayerHelper('layer_norm', **locals())
    dtype = helper.input_dtype()
    input_shape = input.shape
    param_shape = [_prod(input_shape[begin_norm_axis:])]
    inputs = {'X': [input]}
    if scale:
        s = helper.create_parameter(
            attr=helper.param_attr,
            shape=param_shape,
            dtype=dtype,
            default_initializer=Constant(1.0))
        inputs['Scale'] = [s]
    if shift:
        b = helper.create_parameter(
            attr=helper.bias_attr, shape=param_shape, dtype=dtype,
            is_bias=True)
        inputs['Bias'] = [b]
    mean_out = helper.create_variable_for_type_inference(
        dtype=dtype, stop_gradient=True)
    variance_out = helper.create_variable_for_type_inference(
        dtype=dtype, stop_gradient=True)
    out = helper.create_variable_for_type_inference(dtype)
    out.shape = input.shape
    helper.append_op(
        type='layer_norm',
        inputs=inputs,
        outputs={
            'Y': [out],
            'Mean': [mean_out],
            'Variance': [variance_out]
        },
        attrs={'epsilon': epsilon,
               'begin_norm_axis': begin_norm_axis})
    return helper.append_activation(out)


def dropout(x, dropout_prob, is_test=False, seed=None, name=None):
    helper = LayerHelper('dropout', **locals())
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    out.shape = x.shape
    mask = helper.create_variable_for_type_inference(
        dtype=x.dtype, stop_gradient=True)
    helper.append_op(
        type='dropout',
        inputs={'X': [x]},
        outputs={'Out': [out],
                 'Mask': [mask]},
        attrs={
            'dropout_prob': dropout_prob,
            'is_test': is_test,
            'seed': seed if seed is not None else 0,
        })
    return out


def softmax(input, use_cudnn=True, name=None):
    helper = LayerHelper('softmax', **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    out.shape = input.shape
    helper.append_op(
        type='softmax',
        inputs={'X': [input]},
        outputs={'Out': [out]})
    return out


def softmax_with_cross_entropy(logits,
                               label,
                               soft_label=False,
                               ignore_index=-100):
    helper = LayerHelper('softmax_with_cross_entropy', **locals())
    softmax = helper.create_variable_for_type_inference(dtype=logits.dtype)
    softmax.shape = logits.shape
    loss = helper.create_variable_for_type_inference(dtype=logits.dtype)
    loss.shape = tuple(logits.shape[:-1]) + (1, )
    helper.append_op(
        type='softmax_with_cross_entropy',
        inputs={'Logits': [logits],
                'Label': [label]},
        outputs={'Softmax': [softmax],
                 'Loss': [loss]},
        attrs={'soft_label': soft_label,
               'ignore_index': ignore_index})
    return loss


def cross_entropy(input, label, soft_label=False, ignore_index=-100):
    helper = LayerHelper('cross_entropy', **locals())
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    out.shape = tuple(input.shape[:-1]) + (1, )
    helper.append_op(
        type='cross_entropy',
        inputs={'X': [input],
                'Label': [label]},
        outputs={'Y': [out]},
        attrs={'soft_label': soft_label,
               'ignore_index': ignore_index})
    return out


def square_error_cost(input, label):
    """(input - label)^2 (reference layers/nn.py square_error_cost)."""
    helper = LayerHelper('square_error_cost', **locals())
    minus_out = helper.create_variable_for_type_inference(dtype=input.dtype)
    minus_out.shape = input.shape
    helper.append_op(
        type='elementwise_sub',
        inputs={'X': [input],
                'Y': [label]},
        outputs={'Out': [minus_out]})
    square_out = helper.create_variable_for_type_inference(dtype=input.dtype)
    square_out.shape = input.shape
    helper.append_op(
        type='square',
        inputs={'X': [minus_out]},
        outputs={'Out': [square_out]})
    return square_out


def mean(x, name=None):
    helper = LayerHelper('mean', **locals())
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    out.shape = (1, )
    helper.append_op(type='mean', inputs={'X': [x]}, outputs={'Out': [out]})
    return out


def mul(x, y, x_num_col_dims=1, y_num_col_dims=1, name=None):
    helper = LayerHelper('mul', **locals())
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    out.shape = tuple(x.shape[:x_num_col_dims]) + tuple(
        y.shape[y_num_col_dims:])
    helper.append_op(
        type='mul',
        inputs={'X': [x],
                'Y': [y]},
        outputs={'Out': [out]},
        attrs={
            'x_num_col_dims': x_num_col_dims,
            'y_num_col_dims': y_num_col_dims
        })
    return out


def matmul(x, y, transpose_x=False, transpose_y=False, alpha=1.0, name=None):
    helper = LayerHelper('matmul', **locals())
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    xs = list(x.shape)
    ys = list(y.shape)
    if transpose_x and len(xs) >= 2:
        xs[-1], xs[-2] = xs[-2], xs[-1]
    if transpose_y and len(ys) >= 2:
        ys[-1], ys[-2] = ys[-2], ys[-1]
    if len(xs) >= 2 and len(ys) >= 2:
        out.shape = tuple(xs[:-1]) + (ys[-1], )
    helper.append_op(
        type='matmul',
        inputs={'X': [x],
                'Y': [y]},
        outputs={'Out': [out]},
        attrs={
            'transpose_X': transpose_x,
            'transpose_Y': transpose_y,
            'alpha': float(alpha)
        })
    return out


def topk(input, k, name=None):
    helper = LayerHelper('top_k', **locals())
    values = helper.create_variable_for_type_inference(dtype=input.dtype)
    indices = helper.create_variable_for_type_inference(dtype='int64')
    values.shape = tuple(input.shape[:-1]) + (k, )
    indices.shape = values.shape
    helper.append_op(
        type='top_k',
        inputs={'X': [input]},
        outputs={'Out': [values],
                 'Indices': [indices]},
        attrs={'k': k})
    values.stop_gradient = True
    indices.stop_gradient = True
    return values, indices


def transpose(x, perm, name=None):
    helper = LayerHelper('transpose', **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    out.shape = tuple(x.shape[p] for p in perm) if x.shape else ()
    helper.append_op(
        type='transpose',
        inputs={'X': [x]},
        outputs={'Out': [out]},
        attrs={'axis': list(perm)})
    return out


def reshape(x, shape, actual_shape=None, act=None, inplace=False, name=None):
    helper = LayerHelper('reshape', **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    new_shape = list(shape)
    total = _prod([s for s in x.shape]) if all(
        s >= 0 for s in x.shape) else None
    # resolve 0 (copy input dim) first so -1 inference sees them
    resolved = [
        x.shape[i] if s == 0 else s for i, s in enumerate(new_shape)
    ]
    known = _prod([s for s in resolved if s > 0])
    resolved = [
        (total // max(known, 1)) if (s == -1 and total is not None) else s
        for s in resolved
    ]
    out.shape = tuple(resolved)
    inputs = {'X': [x]}
    if actual_shape is not None:
        inputs['Shape'] = [actual_shape]
    helper.append_op(
        type='reshape',
        inputs=inputs,
        outputs={'Out': [out]},
        attrs={'shape': list(shape)})
    return helper.append_activation(out)


def flatten(x, axis=1, name=None):
    helper = LayerHelper('flatten', **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    out.shape = (_prod(x.shape[:axis]), _prod(x.shape[axis:]))
    helper.append_op(
        type='reshape',
        inputs={'X': [x]},
        outputs={'Out': [out]},
        attrs={'shape': [int(s) for s in out.shape]})
    return out


def concat(input, axis=0, name=None):
    helper = LayerHelper('concat', **locals())
    out = helper.create_variable_for_type_inference(dtype=input[0].dtype)
    shapes = [list(i.shape) for i in input]
    if shapes and all(len(s) == len(shapes[0]) for s in shapes):
        out_shape = list(shapes[0])
        out_shape[axis] = sum(s[axis] for s in shapes)
        out.shape = tuple(out_shape)
    helper.append_op(
        type='concat',
        inputs={'X': input},
        outputs={'Out': [out]},
        attrs={'axis': axis})
    return out


def split(input, num_or_sections, dim=-1, name=None):
    helper = LayerHelper('split', **locals())
    input_shape = input.shape
    dim_ = dim if dim >= 0 else len(input_shape) + dim
    if isinstance(num_or_sections, int):
        num = num_or_sections
        sections = [input_shape[dim_] // num] * num
    else:
        num = len(num_or_sections)
        sections = list(num_or_sections)
    outs = []
    for sec in sections:
        o = helper.create_variable_for_type_inference(dtype=input.dtype)
        s = list(input_shape)
        s[dim_] = sec
        o.shape = tuple(s)
        outs.append(o)
    helper.append_op(
        type='split',
        inputs={'X': [input]},
        outputs={'Out': outs},
        attrs={
            'num': num_or_sections if isinstance(num_or_sections, int) else 0,
            'sections': sections,
            'axis': dim_
        })
    return outs


def _reduce(op_type, input, dim=None, keep_dim=False, name=None):
    helper = LayerHelper(op_type, **locals())
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    if dim is not None and not isinstance(dim, (list, tuple)):
        dim = [dim]
    shape = list(input.shape)
    if dim is None or not shape:
        out.shape = (1, )
    else:
        dims = sorted(d % len(shape) for d in dim)
        if keep_dim:
            for d in dims:
                shape[d] = 1
            out.shape = tuple(shape)
        else:
            out.shape = tuple(s for i, s in enumerate(shape)
                              if i not in dims) or (1, )
    helper.append_op(
        type=op_type,
        inputs={'X': [input]},
        outputs={'Out': [out]},
        attrs={
            'dim': dim if dim is not None else [0],
            'keep_dim': keep_dim,
            'reduce_all': dim is None
        })
    return out


def reduce_sum(input, dim=None, keep_dim=False, name=None):
    return _reduce('reduce_sum', input, dim, keep_dim, name)


def reduce_mean(input, dim=None, keep_dim=False, name=None):
    return _reduce('reduce_mean', input, dim, keep_dim, name)


def reduce_max(input, dim=None, keep_dim=False, name=None):
    return _reduce('reduce_max', input, dim, keep_dim, name)


def reduce_min(input, dim=None, keep_dim=False, name=None):
    return _reduce('reduce_min', input, dim, keep_dim, name)


def reduce_prod(input, dim=None, keep_dim=False, name=None):
    return _reduce('reduce_prod', input, dim, keep_dim, name)


def l2_normalize(x, axis, epsilon=1e-12, name=None):
    helper = LayerHelper('l2_normalize', **locals())
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    out.shape = x.shape
    norm = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type='norm',
        inputs={'X': [x]},
        outputs={'Out': [out],
                 'Norm': [norm]},
        attrs={'axis': 1 if axis is None else axis,
               'epsilon': epsilon})
    return out


def one_hot(input, depth):
    helper = LayerHelper('one_hot', **locals())
    out = helper.create_variable_for_type_inference(dtype='float32')
    helper.append_op(
        type='one_hot',
        inputs={'X': [input]},
        outputs={'Out': [out]},
        attrs={'depth': depth})
    out.stop_gradient = True
    return out


def relu(x, name=None):
    helper = LayerHelper('relu', **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    out.shape = x.shape
    helper.append_op(type='relu', inputs={'X': [x]}, outputs={'Out': [out]})
    return out


def log(x, name=None):
    helper = LayerHelper('log', **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    out.shape = x.shape
    helper.append_op(type='log', inputs={'X': [x]}, outputs={'Out': [out]})
    return out


def leaky_relu(x, alpha=0.02, name=None):
    helper = LayerHelper('leaky_relu', **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    out.shape = x.shape
    helper.append_op(
        type='leaky_relu',
        inputs={'X': [x]},
        outputs={'Out': [out]},
        attrs={'alpha': alpha})
    return out


def soft_relu(x, threshold=40.0, name=None):
    helper = LayerHelper('soft_relu', **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    out.shape = x.shape
    helper.append_op(
        type='soft_relu',
        inputs={'X': [x]},
        outputs={'Out': [out]},
        attrs={'threshold': threshold})
    return out


def prelu(x, mode, param_attr=None, name=None):
    helper = LayerHelper('prelu', **locals())
    if mode not in ('all', 'channel', 'element'):
        raise ValueError("mode should be 'all', 'channel' or 'element'")
    alpha_shape = [1]
    if mode == 'channel':
        alpha_shape = [1, x.shape[1], 1, 1]
    elif mode == 'element':
        alpha_shape = list(x.shape)
    alpha = helper.create_parameter(
        attr=helper.param_attr,
        shape=alpha_shape,
        dtype='float32',
        is_bias=False,
        default_initializer=Constant(0.25))
    out = helper.create_variable_for_type_inference(x.dtype)
    out.shape = x.shape
    helper.append_op(
        type='prelu',
        inputs={'X': [x],
                'Alpha': [alpha]},
        outputs={'Out': [out]},
        attrs={'mode': mode})
    return out


def maxout(x, groups, name=None):
    helper = LayerHelper('maxout', **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    n, c, h, w = x.shape
    out.shape = (n, c // groups, h, w)
    helper.append_op(
        type='maxout',
        inputs={'X': [x]},
        outputs={'Out': [out]},
        attrs={'groups': groups})
    return out


def lrn(input, n=5, k=1.0, alpha=1e-4, beta=0.75, name=None):
    helper = LayerHelper('lrn', **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    mid = helper.create_variable_for_type_inference(
        input.dtype, stop_gradient=True)
    out.shape = input.shape
    helper.append_op(
        type='lrn',
        inputs={'X': [input]},
        outputs={'Out': [out],
                 'MidOut': [mid]},
        attrs={'n': n,
               'k': k,
               'alpha': alpha,
               'beta': beta})
    return out


def pad(x, paddings, pad_value=0.0, name=None):
    helper = LayerHelper('pad', **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    if getattr(x, 'shape', None):
        shape = list(x.shape)
        for i in range(min(len(shape), len(paddings) // 2)):
            if shape[i] is not None and int(shape[i]) >= 0:
                shape[i] = int(shape[i]) + paddings[2 * i] + \
                    paddings[2 * i + 1]
        out.shape = tuple(shape)
    helper.append_op(
        type='pad',
        inputs={'X': [x]},
        outputs={'Out': [out]},
        attrs={'paddings': list(paddings),
               'pad_value': float(pad_value)})
    return out


def pad2d(input,
          paddings=[0, 0, 0, 0],
          mode='constant',
          pad_value=0.0,
          data_format='NCHW',
          name=None):
    helper = LayerHelper('pad2d', **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type='pad2d',
        inputs={'X': [input]},
        outputs={'Out': [out]},
        attrs={
            'paddings': list(paddings),
            'mode': mode,
            'pad_value': float(pad_value)
        })
    return out


def image_resize(input, out_shape=None, scale=None, name=None,
                 resample='BILINEAR'):
    helper = LayerHelper('bilinear_interp', **locals())
    if out_shape is None:
        out_shape = [int(input.shape[2] * scale), int(input.shape[3] * scale)]
    out = helper.create_variable_for_type_inference(input.dtype)
    out.shape = (input.shape[0], input.shape[1], out_shape[0], out_shape[1])
    op_type = 'bilinear_interp' if resample == 'BILINEAR' else 'nearest_interp'
    helper.append_op(
        type=op_type,
        inputs={'X': [input]},
        outputs={'Out': [out]},
        attrs={'out_h': out_shape[0],
               'out_w': out_shape[1]})
    return out


def resize_bilinear(input, out_shape=None, scale=None, name=None):
    return image_resize(input, out_shape, scale, name, 'BILINEAR')


def expand(x, expand_times, name=None):
    helper = LayerHelper('expand', **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    out.shape = tuple(
        s * t for s, t in zip(x.shape, expand_times))
    helper.append_op(
        type='expand',
        inputs={'X': [x]},
        outputs={'Out': [out]},
        attrs={'expand_times': list(expand_times)})
    return out


def stack(x, axis=0):
    helper = LayerHelper('stack', **locals())
    if isinstance(x, Variable):
        x = [x]
    out = helper.create_variable_for_type_inference(x[0].dtype)
    helper.append_op(
        type='stack',
        inputs={'X': x},
        outputs={'Y': [out]},
        attrs={'axis': axis})
    return out


def unstack(x, axis=0, num=None):
    helper = LayerHelper('unstack', **locals())
    if num is None:
        num = x.shape[axis]
    outs = [
        helper.create_variable_for_type_inference(x.dtype) for _ in range(num)
    ]
    helper.append_op(
        type='unstack',
        inputs={'X': [x]},
        outputs={'Y': outs},
        attrs={'axis': axis,
               'num': num})
    return outs


def squeeze(input, axes, name=None):
    helper = LayerHelper('squeeze', **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type='squeeze',
        inputs={'X': [input]},
        outputs={'Out': [out]},
        attrs={'axes': list(axes)})
    return out


def unsqueeze(input, axes, name=None):
    helper = LayerHelper('unsqueeze', **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type='unsqueeze',
        inputs={'X': [input]},
        outputs={'Out': [out]},
        attrs={'axes': list(axes)})
    return out


def gather(input, index):
    helper = LayerHelper('gather', **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type='gather',
        inputs={'X': [input],
                'Index': [index]},
        outputs={'Out': [out]})
    return out


def scatter(input, index, updates, name=None):
    helper = LayerHelper('scatter', **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type='scatter',
        inputs={'X': [input],
                'Ids': [index],
                'Updates': [updates]},
        outputs={'Out': [out]})
    return out


def slice(input, axes, starts, ends):
    helper = LayerHelper('slice', **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    if getattr(input, 'shape', None):
        # mirror the runtime's Python slice semantics (negative indices,
        # INT_MAX-as-open-end); unknown dims (-1) stay unknown
        INT_MAX = 2**31 - 1
        shape = list(input.shape)
        for ax, s, e in zip(axes, starts, ends):
            if not (0 <= ax < len(shape)):
                continue
            dim = shape[ax]
            if dim is None or int(dim) < 0:
                continue
            import builtins
            shape[ax] = len(range(int(dim))[builtins.slice(
                None if s <= -INT_MAX else s,
                None if e >= INT_MAX else e)])
        out.shape = tuple(shape)
    helper.append_op(
        type='slice',
        inputs={'Input': [input]},
        outputs={'Out': [out]},
        attrs={
            'axes': list(axes),
            'starts': list(starts),
            'ends': list(ends)
        })
    return out


def shape(input):
    helper = LayerHelper('shape', **locals())
    out = helper.create_variable_for_type_inference(dtype='int32')
    helper.append_op(
        type='shape', inputs={'Input': [input]}, outputs={'Out': [out]})
    return out


def clip(x, min, max, name=None):
    helper = LayerHelper('clip', **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    out.shape = x.shape
    helper.append_op(
        type='clip',
        inputs={'X': [x]},
        outputs={'Out': [out]},
        attrs={'min': min,
               'max': max})
    return out


def clip_by_norm(x, max_norm, name=None):
    helper = LayerHelper('clip_by_norm', **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    out.shape = x.shape
    helper.append_op(
        type='clip_by_norm',
        inputs={'X': [x]},
        outputs={'Out': [out]},
        attrs={'max_norm': max_norm})
    return out


def label_smooth(label,
                 prior_dist=None,
                 epsilon=0.1,
                 dtype='float32',
                 name=None):
    helper = LayerHelper('label_smooth', **locals())
    out = helper.create_variable_for_type_inference(dtype)
    inputs = {'X': [label]}
    if prior_dist is not None:
        inputs['PriorDist'] = [prior_dist]
    helper.append_op(
        type='label_smooth',
        inputs=inputs,
        outputs={'Out': [out]},
        attrs={'epsilon': float(epsilon)})
    return out


def sigmoid_cross_entropy_with_logits(x, label, name=None):
    helper = LayerHelper('sigmoid_cross_entropy_with_logits', **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    out.shape = x.shape
    helper.append_op(
        type='sigmoid_cross_entropy_with_logits',
        inputs={'X': [x],
                'Label': [label]},
        outputs={'Out': [out]})
    return out


def smooth_l1(x, y, inside_weight=None, outside_weight=None, sigma=None):
    helper = LayerHelper('smooth_l1_loss', **locals())
    diff = helper.create_variable_for_type_inference(dtype=x.dtype)
    loss = helper.create_variable_for_type_inference(dtype=x.dtype)
    inputs = {'X': [x], 'Y': [y]}
    if inside_weight is not None:
        inputs['InsideWeight'] = [inside_weight]
    if outside_weight is not None:
        inputs['OutsideWeight'] = [outside_weight]
    helper.append_op(
        type='smooth_l1_loss',
        inputs=inputs,
        outputs={'Diff': [diff],
                 'Out': [loss]},
        attrs={'sigma': sigma if sigma is not None else 1.0})
    return loss


def log_loss(input, label, epsilon=1e-4, name=None):
    helper = LayerHelper('log_loss', **locals())
    loss = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(
        type='log_loss',
        inputs={'Predicted': [input],
                'Labels': [label]},
        outputs={'Loss': [loss]},
        attrs={'epsilon': epsilon})
    return loss


def multiplex(inputs, index):
    helper = LayerHelper('multiplex', **locals())
    out = helper.create_variable_for_type_inference(inputs[0].dtype)
    helper.append_op(
        type='multiplex',
        inputs={'X': inputs,
                'Ids': [index]},
        outputs={'Out': [out]})
    return out


def random_crop(x, shape, seed=None):
    helper = LayerHelper('random_crop', **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type='random_crop',
        inputs={'X': [x]},
        outputs={'Out': [out]},
        attrs={'shape': list(shape)})
    return out


def im2sequence(input,
                filter_size=1,
                stride=1,
                padding=0,
                input_image_size=None,
                out_stride=1,
                name=None):
    helper = LayerHelper('im2sequence', **locals())
    filter_size = _pair(filter_size)
    stride = _pair(stride)
    if not isinstance(padding, (list, tuple)):
        padding = [padding] * 4
    elif len(padding) == 2:
        padding = list(padding) * 2
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type='im2sequence',
        inputs={'X': [input]},
        outputs={'Out': [out]},
        attrs={
            'kernels': filter_size,
            'strides': stride,
            'paddings': list(padding)
        })
    return out


def autoincreased_step_counter(counter_name=None, begin=1, step=1):
    """Global step counter var incremented once per executor run
    (reference layers/nn.py autoincreased_step_counter)."""
    helper = LayerHelper('global_step_counter')
    counter_name = counter_name or '@STEP_COUNTER@'
    counter = helper.create_or_get_global_variable(
        name=counter_name,
        dtype='int64',
        shape=[1],
        persistable=True)
    if counter.op is None:
        helper.set_variable_initializer(
            counter, initializer=Constant(value=begin - 1))
        counter.op = helper.append_op(
            type='increment',
            inputs={'X': [counter]},
            outputs={'Out': [counter]},
            attrs={'step': float(step)})
        counter.stop_gradient = True
    return counter


def hsigmoid(input, label, num_classes, param_attr=None, bias_attr=None,
             name=None):
    """Hierarchical sigmoid (reference operators/hsigmoid_op.cc).  Lowered as
    a dense binary-code formulation."""
    helper = LayerHelper('hsigmoid', **locals())
    dtype = helper.input_dtype()
    w = helper.create_parameter(
        attr=helper.param_attr,
        shape=[num_classes - 1, input.shape[1]],
        dtype=dtype)
    inputs = {'X': [input], 'W': [w], 'Label': [label]}
    if helper.bias_attr:
        bias = helper.create_parameter(
            attr=helper.bias_attr,
            shape=[1, num_classes - 1],
            dtype=dtype,
            is_bias=True)
        inputs['Bias'] = [bias]
    out = helper.create_variable_for_type_inference(dtype)
    pre_out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type='hsigmoid',
        inputs=inputs,
        outputs={'Out': [out],
                 'PreOut': [pre_out]},
        attrs={'num_classes': num_classes})
    return out


def nce(input,
        label,
        num_total_classes,
        sample_weight=None,
        param_attr=None,
        bias_attr=None,
        num_neg_samples=None,
        name=None):
    """Noise-contrastive estimation loss (reference operators/nce_op.cc)."""
    helper = LayerHelper('nce', **locals())
    dtype = helper.input_dtype()
    dim = input.shape[1]
    num_neg_samples = 10 if num_neg_samples is None else num_neg_samples
    w = helper.create_parameter(
        attr=helper.param_attr, shape=[num_total_classes, dim], dtype=dtype)
    b = helper.create_parameter(
        attr=helper.bias_attr,
        shape=[num_total_classes, 1],
        dtype=dtype,
        is_bias=True)
    cost = helper.create_variable_for_type_inference(dtype)
    sample_logits = helper.create_variable_for_type_inference(dtype)
    sample_labels = helper.create_variable_for_type_inference(dtype='int64')
    helper.append_op(
        type='nce',
        inputs={'Input': [input],
                'Label': [label],
                'Weight': [w],
                'Bias': [b]},
        outputs={
            'Cost': [cost],
            'SampleLogits': [sample_logits],
            'SampleLabels': [sample_labels]
        },
        attrs={
            'num_total_classes': int(num_total_classes),
            'num_neg_samples': int(num_neg_samples)
        })
    return cost


def lstm_unit(x_t,
              hidden_t_prev,
              cell_t_prev,
              forget_bias=0.0,
              param_attr=None,
              bias_attr=None,
              name=None):
    """Single LSTM step built from fc + lstm_unit op
    (reference layers/nn.py lstm_unit)."""
    helper = LayerHelper('lstm_unit', **locals())
    size = cell_t_prev.shape[1]
    concat_out = concat(input=[x_t, hidden_t_prev], axis=1)
    fc_out = fc(input=concat_out,
                size=4 * size,
                param_attr=param_attr,
                bias_attr=bias_attr)
    c = helper.create_variable_for_type_inference(x_t.dtype)
    h = helper.create_variable_for_type_inference(x_t.dtype)
    c.shape = cell_t_prev.shape
    h.shape = hidden_t_prev.shape
    helper.append_op(
        type='lstm_unit',
        inputs={'X': [fc_out],
                'C_prev': [cell_t_prev]},
        outputs={'C': [c],
                 'H': [h]},
        attrs={'forget_bias': forget_bias})
    return h, c


def linear_chain_crf(input, label, param_attr=None):
    """Linear-chain CRF negative log-likelihood per sequence
    (reference layers/nn.py linear_chain_crf;
    operators/linear_chain_crf_op.cc).  Creates the [size+2, size]
    transition parameter (row 0 start, row 1 end weights)."""
    helper = LayerHelper('linear_chain_crf', **locals())
    size = input.shape[-1]
    transition = helper.create_parameter(
        attr=helper.param_attr, shape=[size + 2, size], dtype='float32')
    alpha = helper.create_variable_for_type_inference('float32')
    emission_exps = helper.create_variable_for_type_inference('float32')
    transition_exps = helper.create_variable_for_type_inference('float32')
    log_likelihood = helper.create_variable_for_type_inference('float32')
    helper.append_op(
        type='linear_chain_crf',
        inputs={'Emission': [input],
                'Transition': [transition],
                'Label': [label]},
        outputs={
            'Alpha': [alpha],
            'EmissionExps': [emission_exps],
            'TransitionExps': [transition_exps],
            'LogLikelihood': [log_likelihood],
        })
    return log_likelihood


def crf_decoding(input, param_attr, label=None):
    """Viterbi decode with the CRF transition parameter (reference
    layers/nn.py crf_decoding; operators/crf_decoding_op.cc).  With a
    label input, emits the per-token correctness indicator instead."""
    helper = LayerHelper('crf_decoding', **locals())
    try:
        transition = helper.get_parameter(param_attr.name)
    except ValueError:
        # decoding-only program (built fresh, weights loaded afterwards by
        # name): create the slot zero-initialized — deterministic garbage
        # until load_persistables fills it, never silent random output
        import warnings
        warnings.warn(
            "crf_decoding: transition parameter %r does not exist in this "
            "program; creating it zero-initialized (expecting "
            "load_persistables to fill it)" % param_attr.name)
        size = input.shape[-1]
        transition = helper.create_parameter(
            attr=helper.param_attr, shape=[size + 2, size],
            dtype='float32', default_initializer=Constant(0.0))
    viterbi_path = helper.create_variable_for_type_inference('int64')
    viterbi_path.lod_level = input.lod_level
    inputs = {'Emission': [input], 'Transition': [transition]}
    if label is not None:
        inputs['Label'] = [label]
    helper.append_op(
        type='crf_decoding',
        inputs=inputs,
        outputs={'ViterbiPath': [viterbi_path]})
    return viterbi_path


def cos_sim(X, Y):
    """Row-wise cosine similarity [B, 1] (reference layers/nn.py cos_sim;
    operators/cos_sim_op.cc)."""
    helper = LayerHelper('cos_sim', **locals())
    out = helper.create_variable_for_type_inference(X.dtype)
    xnorm = helper.create_variable_for_type_inference(X.dtype)
    ynorm = helper.create_variable_for_type_inference(X.dtype)
    out.shape = (X.shape[0], 1)
    helper.append_op(
        type='cos_sim',
        inputs={'X': [X],
                'Y': [Y]},
        outputs={'Out': [out],
                 'XNorm': [xnorm],
                 'YNorm': [ynorm]})
    return out


def flash_attention(q, k, v, num_heads=None, causal=False, scale=None,
                    impl='auto', sp_axis='sp', name=None, num_kv_heads=None):
    """Fused scaled-dot-product attention (TPU-native extension).

    The reference builds attention out of matmul/softmax primitives
    (nets.py scaled_dot_product_attention) with no sequence parallelism;
    here ONE op lowers to ring attention over a context-parallel 'sp' mesh
    axis, a Pallas flash kernel on a single TPU chip, or dense XLA —
    see ops/attention_ops.py.

    q, k, v: [batch, seq, heads, head_dim] Variables, or
             [batch, seq, heads*head_dim] with num_heads given.
    num_kv_heads: the heads of k and v where they are fewer than q's
             (grouped-query attention: num_heads a whole multiple of it;
             query head i reads key-value head i // (num_heads /
             num_kv_heads)).  4-D k and v say it by their shape.
    scale: the multiplier of q k^T, passed through as given; None or 0:
             head_dim ** -0.5.  No positional signal is added.
    impl: 'auto' | 'ring' | 'ulysses' | 'pallas' | 'dense'.  'auto' takes
             the fused kernel on an accelerator place where, with k and v
             repeated to q's heads, head_dim is 32, 64 or 128, the heads
             tile 128 lanes, Lq is 128 to 2048 and Lk from
             max(128, 2 head_dim) to 2048, and no mesh axis but the
             batch's is larger than 1 (one tile to L=256, several beyond).
    Returns a Variable with q's shape.
    """
    helper = LayerHelper('flash_attention', **locals())
    squeeze_back = False
    if len(q.shape) == 3:
        if not num_heads:
            raise ValueError('3-D q/k/v need num_heads to split the fused '
                             'head dim')
        squeeze_back = True
        kv_heads = int(num_kv_heads or num_heads)
        q = reshape(q, [0, 0, num_heads, q.shape[-1] // num_heads])
        k = reshape(k, [0, 0, kv_heads, k.shape[-1] // kv_heads])
        v = reshape(v, [0, 0, kv_heads, v.shape[-1] // kv_heads])
    if int(q.shape[2]) % int(k.shape[2]) or k.shape[2] != v.shape[2]:
        raise ValueError(
            'flash_attention: %d query heads over %d key and %d value '
            'heads: the query heads must be a whole multiple of the '
            'key-value heads' % (q.shape[2], k.shape[2], v.shape[2]))
    out = helper.create_variable_for_type_inference(q.dtype)
    # attention output carries V's head_dim (may differ from Q's)
    out.shape = tuple(q.shape[:-1]) + (v.shape[-1], )
    helper.append_op(
        type='flash_attention',
        inputs={'Q': [q], 'K': [k], 'V': [v]},
        outputs={'Out': [out]},
        attrs={
            'causal': bool(causal),
            'scale': float(scale) if scale else -1.0,
            'impl': impl,
            'sp_axis': sp_axis,
        })
    if squeeze_back:
        out = reshape(out, [0, 0, int(num_heads) * int(v.shape[-1])])
    return out


def rms_norm(input, epsilon=1e-05, param_attr=None, gate=None, name=None,
             groups=1):
    """Root-mean-square norm over the last axis (TPU-native extension):
    ``x / sqrt(mean(x^2) + epsilon) * w``, ``w`` a parameter of the last
    axis's width, ones at the start.  With ``gate`` (x's shape) the op is
    ``gated_rms_norm``: ``x * silu(gate)`` is what is normalised (Mamba-2's
    output norm).  ``groups``: the last axis as that many equal runs of
    channels, each normalised by its own root-mean-square (Mamba-2's
    ``n_groups``).  Statistics are f32 under AMP."""
    helper = LayerHelper('gated_rms_norm' if gate is not None
                         else 'rms_norm', **locals())
    dtype = helper.input_dtype()
    w = helper.create_parameter(
        attr=helper.param_attr, shape=[int(input.shape[-1])], dtype=dtype,
        default_initializer=Constant(1.0))
    out = helper.create_variable_for_type_inference(dtype)
    out.shape = input.shape
    inputs = {'X': [input], 'Scale': [w]}
    if gate is not None:
        inputs['Gate'] = [gate]
    helper.append_op(type=helper.layer_type, inputs=inputs,
                     outputs={'Y': [out]},
                     attrs={'epsilon': float(epsilon), 'groups': int(groups)})
    return out


def swiglu(x, name=None):
    """``silu(g) * u`` for ``[g, u]`` the two halves of x's last axis: the
    activation of a gated feed-forward whose two input projections are one
    matrix (TPU-native extension)."""
    helper = LayerHelper('swiglu', **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    out.shape = tuple(x.shape[:-1]) + (int(x.shape[-1]) // 2, )
    helper.append_op(type='swiglu', inputs={'X': [x]},
                     outputs={'Out': [out]})
    return out


def residual_add(x, y, scale=1.0, name=None):
    """``x + scale * y`` in x's dtype (TPU-native extension): a pre-norm
    residual stream with the model's residual multiplier.  Unlike
    ``elementwise_add`` it never narrows x under AMP: y is widened."""
    helper = LayerHelper('residual_add', **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    out.shape = x.shape
    helper.append_op(type='residual_add', inputs={'X': [x], 'Y': [y]},
                     outputs={'Out': [out]}, attrs={'scale': float(scale)})
    return out


def causal_conv1d(input, filter_size=4, param_attr=None, bias_attr=None,
                  act=None, name=None):
    """Depthwise convolution over time of a [batch, seq, channels] input
    (TPU-native extension): each channel over its own last ``filter_size``
    positions, zeros before the sequence's start, so no position reads a
    later one.  Filter [channels, filter_size] (the last tap weighs the
    current position), bias [channels]; ``act``: None or 'silu', applied
    inside the op (the upstream kernel's own option)."""
    helper = LayerHelper('causal_conv1d', **locals())
    dtype = helper.input_dtype()
    channels = int(input.shape[-1])
    inputs = {
        'X': [input],
        'Filter': [helper.create_parameter(
            attr=helper.param_attr, shape=[channels, int(filter_size)],
            dtype=dtype)],
        'Bias': [helper.create_parameter(
            attr=helper.bias_attr, shape=[channels], dtype=dtype,
            is_bias=True)]}
    if act not in (None, 'silu'):
        raise ValueError("causal_conv1d: act is None or 'silu', got %r"
                         % (act, ))
    out = helper.create_variable_for_type_inference(dtype)
    out.shape = input.shape
    helper.append_op(type='causal_conv1d', inputs=inputs,
                     outputs={'Out': [out]},
                     attrs={'activation': act or ''})
    return out


def ssd_scan(x, dt, a, b, c, d, dt_bias, chunk=256, impl='auto',
             name=None):
    """Mamba-2's selective state-space scan in its chunked (SSD) form
    (TPU-native extension; ops/ssm_ops.py).  Per head, with a state S of
    [head_dim, state]:

        S_t = exp(dt_t a) S_{t-1} + dt_t x_t b_t^T,   y_t = S_t c_t + d x_t

    x [batch, seq, heads, head_dim]; a, d, dt_bias [heads]; b, c [batch,
    seq, groups, state] (head h reads group h // (heads / groups)).  ``dt``
    [batch, seq, heads] is the projection's raw output: the step dt_t above
    is softplus(dt + dt_bias), made in f32 inside the op with the rest of
    the decay arithmetic, whatever AMP says.  The state is zero before
    the sequence's start and is not reset inside it.  ``chunk``: positions
    a chunk (the result does not depend on it beyond rounding).  ``impl``:
    'auto' (the lowering picks from the place, the mesh and the shapes),
    'xla' or 'pallas' (ops/ssm_ops.py).  Returns y with x's shape."""
    helper = LayerHelper('ssd_scan', **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    out.shape = x.shape
    helper.append_op(
        type='ssd_scan',
        inputs={'X': [x], 'Dt': [dt], 'A': [a], 'B': [b], 'C': [c],
                'D': [d], 'DtBias': [dt_bias]},
        outputs={'Y': [out]}, attrs={'chunk': int(chunk), 'impl': impl})
    return out


def _suffixed_attr(base, suffix):
    """One user attr names several differently-shaped weights: the name is
    suffixed a weight, so that a named ParamAttr does not collide on the
    shared-parameter path; an unnamed one is passed on as it is."""
    if base is None or base is False or getattr(base, 'name', None) is None:
        return base
    named = copy.copy(base)
    named.name = '%s.%s' % (base.name, suffix)
    return named


def moe_ffn(input, num_experts, d_ff, capacity_factor=1.25,
            ep_axis='ep', param_attr=None, bias_attr=None, name=None):
    """Switch-style Mixture-of-Experts FFN (TPU-native extension; the
    reference predates MoE).

    Top-1 routing with a static per-expert capacity (GShard dense
    dispatch, ops/moe_ops.py): over-capacity tokens pass through with
    zero expert output, the gate probability scales the kept ones so
    the router trains.  Expert weights carry a leading [num_experts,
    ...] axis annotated PartitionSpec(ep_axis): under a
    ParallelExecutor mesh with an 'ep' axis GSPMD shards the experts
    and partitions the dispatch/combine einsums — expert parallelism
    through the same annotation mechanism tensor-parallel fc uses.
    (For the hand-scheduled all_to_all variant outside the Program IR
    see paddle_tpu.parallel.moe_ffn_spmd.)

    input: [..., d_model] Variable.  Returns same shape.
    """
    helper = LayerHelper('moe_ffn', **locals())
    from ...parallel import shard as _shard
    dtype = helper.input_dtype()
    d = int(input.shape[-1])
    e, dff = int(num_experts), int(d_ff)

    gate_w = helper.create_parameter(
        attr=_suffixed_attr(helper.param_attr, 'gate'),
        shape=[d, e], dtype=dtype)
    w1 = helper.create_parameter(
        attr=_suffixed_attr(helper.param_attr, 'w1'),
        shape=[e, d, dff], dtype=dtype)
    w2 = helper.create_parameter(
        attr=_suffixed_attr(helper.param_attr, 'w2'),
        shape=[e, dff, d], dtype=dtype)
    experts = [w1, w2]
    inputs = {'X': [input], 'GateW': [gate_w], 'W1': [w1], 'W2': [w2]}
    if bias_attr is not False:
        # bias_attr=False means NO bias at all (the repo-wide fc/conv
        # convention), not a frozen zero parameter
        b1 = helper.create_parameter(
            attr=_suffixed_attr(helper.bias_attr, 'b1'),
            shape=[e, dff], dtype=dtype,
            is_bias=True)
        b2 = helper.create_parameter(
            attr=_suffixed_attr(helper.bias_attr, 'b2'),
            shape=[e, d], dtype=dtype,
            is_bias=True)
        experts += [b1, b2]
        inputs['B1'] = [b1]
        inputs['B2'] = [b2]
    for p in experts:
        _shard(p, ep_axis)          # leading expert axis over 'ep'
    out = helper.create_variable_for_type_inference(dtype)
    out.shape = tuple(input.shape)
    helper.append_op(
        type='moe_ffn',
        inputs=inputs,
        outputs={'Out': [out]},
        attrs={'capacity_factor': float(capacity_factor),
               'ep_axis': ep_axis})
    return out


def moe_router(input, num_experts, top_k, score_func='sigmoid',
               norm_topk_prob=True, routed_scaling_factor=1.0,
               param_attr=None, bias_attr=None, name=None):
    """The k experts of ``num_experts`` each token selects, and the weight
    of each (TPU-native extension; ops/moe_ops.py).  Scores are
    ``score_func`` ('sigmoid' | 'softmax') of a float32 product with the
    router's [d_model, num_experts] matrix.  Selection takes the k largest
    of ``score + bias``; the bias ([num_experts], zeros at the start) is a
    buffer that selects and never weighs, and no gradient reaches it
    (``bias_attr`` names it).  A weight is the expert's own score, divided
    by the sum over the k selected where ``norm_topk_prob``, times
    ``routed_scaling_factor``.

    input: [..., d_model].  Returns (indices [..., k] int32, weights
    [..., k] float32) for ``moe_experts``."""
    helper = LayerHelper('moe_router', **locals())
    dtype = helper.input_dtype()
    d, e, k = int(input.shape[-1]), int(num_experts), int(top_k)
    if not 1 <= k <= e:
        raise ValueError('moe_router: top_k %d of %d experts' % (k, e))
    weight = helper.create_parameter(attr=helper.param_attr, shape=[d, e],
                                     dtype=dtype)
    bias = helper.create_parameter(
        attr=ParamAttr(name=getattr(bias_attr, 'name', None),
                       initializer=Constant(0.0), trainable=False),
        shape=[e], dtype=dtype)
    bias.stop_gradient = True
    idx = helper.create_variable_for_type_inference('int32')
    idx.shape = tuple(input.shape[:-1]) + (k, )
    idx.stop_gradient = True
    out = helper.create_variable_for_type_inference(dtype)
    out.shape = idx.shape
    helper.append_op(
        type='moe_router',
        inputs={'X': [input], 'Weight': [weight], 'Bias': [bias]},
        outputs={'TopkIdx': [idx], 'TopkWeight': [out]},
        attrs={'score_func': score_func, 'top_k': k,
               'norm_topk_prob': bool(norm_topk_prob),
               'routed_scaling_factor': float(routed_scaling_factor)})
    return idx, out


def moe_bias_update(bias, topk_idx, rate=0.001, name=None):
    """The balancing update of a router's selection bias from one pass's
    selections (TPU-native extension; ops/moe_ops.py): an expert that got
    fewer (token, slot) pairs than the mean has its bias raised by
    ``rate``, one that got more has it lowered (the auxiliary-loss-free
    rule of the models that select by ``score + bias``).  In place on
    ``bias``, outside the gradient.  Where it goes is the builder's choice:
    a forward-only program of its own that a set-up runs on a few batches
    (``models/nemotron_h.py``'s ``balance``), or after
    ``optimizer.minimize`` in a training program, so that the step's
    forward and backward read the bias the selections were made with and
    the next step the moved one."""
    helper = LayerHelper('moe_bias_update', **locals())
    helper.append_op(
        type='moe_bias_update', inputs={'Bias': [bias], 'TopkIdx': [topk_idx]},
        outputs={'BiasOut': [bias]}, attrs={'rate': float(rate)})
    return bias


def moe_experts(input, topk_idx, topk_weight, num_held, d_ff, first_expert=0,
                act='relu2', param_attr=None, impl='auto', name=None):
    """One chip's share of a layer of routed experts (TPU-native extension;
    ops/moe_ops.py): of all the experts ``moe_router`` selects among, this
    op holds ``num_held``, numbered from ``first_expert``, and returns

        sum over a token's selected slots k whose expert e is held of
            weight_k * W_down,e act(W_up,e x)

    with ungated experts of width ``d_ff`` (``act``: 'relu2', relu
    squared, the one there is; no bias).  What experts held elsewhere would
    add is left out; nothing stands in for their exchange.  No
    (token, slot) pair is dropped at any load: the products run over a
    buffer of every pair, and their cost follows the rows held
    (``impl``: 'auto' takes the Pallas grouped matmul on an accelerator
    place without a mesh and ``jax.lax.ragged_dot`` elsewhere; 'pallas' |
    'xla' force one).  The expert weights are ``<name>.w_up`` and
    ``<name>.w_down`` of ``param_attr``'s name, both [num_held, d_ff,
    d_model]: ``w_up[e]`` is the expert's input matrix as a Linear stores
    it, out x in (a last side of d_ff, seldom a multiple of the TPU's 128
    lanes, would be kept transposed on the device and copied whole at every
    dispatch).

    input: [..., d_model]; topk_idx, topk_weight: [..., k].  Returns
    input's shape."""
    helper = LayerHelper('moe_experts', **locals())
    dtype = helper.input_dtype()
    d, e, dff = int(input.shape[-1]), int(num_held), int(d_ff)
    w_up = helper.create_parameter(
        attr=_suffixed_attr(helper.param_attr, 'w_up'), shape=[e, dff, d],
        dtype=dtype)
    w_down = helper.create_parameter(
        attr=_suffixed_attr(helper.param_attr, 'w_down'), shape=[e, dff, d],
        dtype=dtype)
    out = helper.create_variable_for_type_inference(dtype)
    out.shape = tuple(input.shape)
    helper.append_op(
        type='moe_experts',
        inputs={'X': [input], 'TopkIdx': [topk_idx],
                'TopkWeight': [topk_weight], 'WUp': [w_up],
                'WDown': [w_down]},
        outputs={'Out': [out]},
        attrs={'first_expert': int(first_expert), 'activation': act,
               'impl': impl})
    return out


def warpctc(input, label, blank=0, norm_by_times=False):
    """CTC loss over a LoD batch of logit sequences (reference nn.py
    warpctc; operators/warpctc_op.cc).  Computed natively as a lax.scan
    alpha recursion (ops/ctc_ops.py) instead of wrapping warp-ctc; the
    gradient comes from autodiff rather than the WarpCTCGrad side tensor.
    Returns per-sequence loss (N, 1)."""
    helper = LayerHelper('warpctc', **locals())
    loss_out = helper.create_variable_for_type_inference(dtype=input.dtype)
    loss_out.shape = (-1, 1)
    helper.append_op(
        type='warpctc',
        inputs={'Logits': [input],
                'Label': [label]},
        outputs={'Loss': [loss_out]},
        attrs={'blank': blank,
               'norm_by_times': norm_by_times})
    return loss_out


def ctc_greedy_decoder(input, blank, name=None):
    """Best-path CTC decode: argmax per step, merge repeats, drop blanks
    (reference nn.py ctc_greedy_decoder = top_k + ctc_align)."""
    helper = LayerHelper('ctc_greedy_decoder', **locals())
    argmax_out = helper.create_variable_for_type_inference(dtype='int64')
    argmax_out.shape = tuple(input.shape[:-1])
    helper.append_op(
        type='argmax',
        inputs={'X': [input]},
        outputs={'Out': [argmax_out]},
        attrs={'axis': -1})
    out = helper.create_variable_for_type_inference(dtype='int64')
    helper.append_op(
        type='ctc_align',
        inputs={'Input': [argmax_out]},
        outputs={'Output': [out]},
        attrs={'blank': blank,
               'merge_repeated': True})
    out.stop_gradient = True
    return out


def edit_distance(input, label, normalized=True, ignored_tokens=None,
                  name=None):
    """Levenshtein distance between hypothesis and reference sequences
    (reference nn.py edit_distance; operators/edit_distance_op.cc).
    Returns (distance (N, 1), sequence_num (1,))."""
    from .sequence import sequence_erase
    helper = LayerHelper('edit_distance', **locals())
    if ignored_tokens is not None and len(ignored_tokens) > 0:
        input = sequence_erase(input, ignored_tokens)
        label = sequence_erase(label, ignored_tokens)
    edit_distance_out = helper.create_variable_for_type_inference(
        dtype='float32')
    sequence_num = helper.create_variable_for_type_inference(dtype='int64')
    helper.append_op(
        type='edit_distance',
        inputs={'Hyps': [input],
                'Refs': [label]},
        outputs={'Out': [edit_distance_out],
                 'SequenceNum': [sequence_num]},
        attrs={'normalized': normalized})
    edit_distance_out.stop_gradient = True
    sequence_num.stop_gradient = True
    return edit_distance_out, sequence_num


def roi_pool(input, rois, pooled_height=1, pooled_width=1,
             spatial_scale=1.0):
    """Max-pool features inside each region of interest (reference nn.py
    roi_pool; operators/roi_pool_op.cc).  rois: LoD (num_rois, 4) boxes
    per image."""
    helper = LayerHelper('roi_pool', **locals())
    dtype = helper.input_dtype()
    out = helper.create_variable_for_type_inference(dtype)
    argmaxes = helper.create_variable_for_type_inference(dtype='int32')
    out.shape = (-1, input.shape[1], pooled_height, pooled_width)
    helper.append_op(
        type='roi_pool',
        inputs={'X': [input],
                'ROIs': [rois]},
        outputs={'Out': [out],
                 'Argmax': [argmaxes]},
        attrs={
            'pooled_height': pooled_height,
            'pooled_width': pooled_width,
            'spatial_scale': spatial_scale
        })
    return out


def conv3d_transpose(input,
                     num_filters,
                     output_size=None,
                     filter_size=None,
                     padding=0,
                     stride=1,
                     dilation=1,
                     groups=None,
                     param_attr=None,
                     bias_attr=None,
                     use_cudnn=True,
                     act=None,
                     name=None):
    """Transposed 3D convolution (reference nn.py:2426 conv3d_transpose;
    operators/conv_transpose_op.cc)."""

    def _triple(v):
        return list(v) if isinstance(v, (list, tuple)) else [v] * 3

    helper = LayerHelper('conv3d_transpose', **locals())
    dtype = helper.input_dtype()
    num_channels = input.shape[1]
    groups = groups or 1
    stride = _triple(stride)
    padding = _triple(padding)
    dilation = _triple(dilation)
    n, c, d, h, w_ = input.shape
    if filter_size is None:
        output_size = _triple(output_size)
        # reference conv3d_transpose: k = (out + 2p - (in-1)s - 1)//d + 1
        filter_size = [
            (output_size[i] + 2 * padding[i] - (s - 1) * stride[i] - 1) //
            dilation[i] + 1 for i, s in enumerate((d, h, w_))
        ]
    else:
        filter_size = _triple(filter_size)
    filter_shape = [num_channels, num_filters // groups] + filter_size
    w = helper.create_parameter(
        attr=helper.param_attr, shape=filter_shape, dtype=dtype)
    pre_bias = helper.create_variable_for_type_inference(dtype)
    out_spatial = [
        (s - 1) * stride[i] - 2 * padding[i] + dilation[i] *
        (filter_size[i] - 1) + 1 for i, s in enumerate((d, h, w_))
    ]
    pre_bias.shape = tuple([n, num_filters] + out_spatial)
    helper.append_op(
        type='conv3d_transpose',
        inputs={'Input': [input],
                'Filter': [w]},
        outputs={'Output': [pre_bias]},
        attrs={
            'strides': stride,
            'paddings': padding,
            'dilations': dilation,
            'groups': groups
        })
    pre_act = helper.append_bias_op(pre_bias, dim_start=1, dim_end=2)
    return helper.append_activation(pre_act)


def crop(x, shape=None, offsets=None, name=None):
    """Crop x to ``shape`` starting at ``offsets`` (reference nn.py:5453;
    operators/crop_op.cc).  ``shape`` may be a Variable whose dims give
    the target shape."""
    helper = LayerHelper('crop', **locals())
    inputs = {'X': [x]}
    attrs = {}
    if shape is None:
        raise ValueError(
            'crop: shape is required — a list of output dims or a '
            'Variable whose shape is the target (reference nn.py:5453 '
            'asserts the same)')
    if isinstance(shape, Variable):
        inputs['Y'] = [shape]
        out_shape = shape.shape
    else:
        attrs['shape'] = list(shape)
        out_shape = tuple(shape)
    if offsets is None:
        offsets = [0] * len(x.shape)
    attrs['offsets'] = list(offsets)
    out = helper.create_variable_for_type_inference(x.dtype)
    out.shape = tuple(out_shape)
    helper.append_op(
        type='crop', inputs=inputs, outputs={'Out': [out]}, attrs=attrs)
    return out


def dice_loss(input, label, epsilon=0.00001):
    """Dice loss for binary segmentation (reference nn.py:5032): a pure
    composition — one_hot the labels, per-sample intersection and area
    sums over every non-batch dim, 1 - 2I/(A + eps), batch mean."""
    label = one_hot(label, depth=input.shape[-1])
    reduce_dim = list(range(1, len(input.shape)))
    inse = reduce_sum(input * label, dim=reduce_dim)
    dice_denominator = reduce_sum(input, dim=reduce_dim) + reduce_sum(
        label, dim=reduce_dim)
    dice_score = 1 - inse * 2 / (dice_denominator + epsilon)
    return reduce_mean(dice_score)


def image_resize_short(input, out_short_len, resample='BILINEAR'):
    """Resize so the short image edge equals out_short_len, keeping the
    aspect ratio (reference nn.py:5175)."""
    in_shape = input.shape
    if len(in_shape) != 4:
        raise ValueError(
            'The rank of input must be 4 (num_batches, channels, in_h, '
            'in_w).')
    hw = list(in_shape[2:4])
    short_idx = hw.index(min(hw))
    long_idx = 1 - short_idx
    out_shape = list(hw)
    out_shape[short_idx] = out_short_len
    out_shape[long_idx] = int(
        float(out_shape[long_idx]) *
        (float(out_short_len) / float(hw[short_idx])) + 0.5)
    return image_resize(input=input, out_shape=out_shape, resample=resample)


def lod_reset(x, y=None, target_lod=None):
    """Re-assign x's LoD from y or target_lod (reference nn.py:4625;
    operators/lod_reset_op.cc).  Under the padded+SEQLEN lowering the
    dense payload is unchanged; the new lengths ride the side-band."""
    helper = LayerHelper('lod_reset', **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    out.shape = tuple(x.shape)
    out.lod_level = 1
    if y is not None:
        helper.append_op(
            type='lod_reset', inputs={'X': [x], 'Y': [y]},
            outputs={'Out': [out]})
    elif target_lod is not None:
        helper.append_op(
            type='lod_reset', inputs={'X': [x]},
            outputs={'Out': [out]},
            attrs={'target_lod': [int(v) for v in target_lod]})
    else:
        raise ValueError('lod_reset: y and target_lod cannot both be None')
    return out


def mean_iou(input, label, num_classes):
    """Mean intersection-over-union (reference nn.py:5403;
    operators/mean_iou_op.cc).  Returns (mean_iou, out_wrong,
    out_correct)."""
    helper = LayerHelper('mean_iou', **locals())
    iou = helper.create_variable_for_type_inference('float32')
    out_wrong = helper.create_variable_for_type_inference('int32')
    out_correct = helper.create_variable_for_type_inference('int32')
    iou.shape = (1, )
    # per-class counts (reference mean_iou_op.cc SetOutputDim)
    out_wrong.shape = (num_classes, )
    out_correct.shape = (num_classes, )
    for v in (iou, out_wrong, out_correct):
        v.stop_gradient = True
    helper.append_op(
        type='mean_iou',
        inputs={'Predictions': [input],
                'Labels': [label]},
        outputs={
            'OutMeanIou': [iou],
            'OutWrong': [out_wrong],
            'OutCorrect': [out_correct]
        },
        attrs={'num_classes': num_classes})
    return iou, out_wrong, out_correct


def pad_constant_like(x, y, pad_value=0., name=None):
    """Pad y with pad_value so its shape matches x (reference nn.py:4849;
    operators/pad_constant_like_op.cc)."""
    helper = LayerHelper('pad_constant_like', **locals())
    out = helper.create_variable_for_type_inference(y.dtype)
    out.shape = tuple(x.shape)
    helper.append_op(
        type='pad_constant_like',
        inputs={'X': [x],
                'Y': [y]},
        outputs={'Out': [out]},
        attrs={'pad_value': float(pad_value)})
    return out


def rank_loss(label, left, right, name=None):
    """RankNet pairwise loss (reference nn.py:5551;
    operators/rank_loss_op.cc)."""
    helper = LayerHelper('rank_loss', **locals())
    for v, n in ((label, 'label'), (left, 'left'), (right, 'right')):
        if not isinstance(v, Variable):
            raise ValueError('rank_loss: %s must be a Variable' % n)
    out = helper.create_variable_for_type_inference('float32')
    out.shape = tuple(left.shape)
    helper.append_op(
        type='rank_loss',
        inputs={'Label': [label],
                'Left': [left],
                'Right': [right]},
        outputs={'Out': [out]})
    return out
