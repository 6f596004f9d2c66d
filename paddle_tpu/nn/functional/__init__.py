"""``paddle_tpu.nn.functional`` — functional op surface.

Reference parity: ``python/paddle/nn/functional/`` (~40 modules).  Raw-array
implementations live in the submodules; this namespace is wrapped by
``framework.dispatch.install_ops`` so the public functions follow the
Tensor-facade calling convention (eager tape / raw passthrough).
"""
from .activation import (  # noqa: F401
    elu, gelu, glu, gumbel_softmax, hardshrink, hardsigmoid, hardswish,
    hardtanh, leaky_relu, log_sigmoid, log_softmax, maxout, mish, prelu, relu,
    relu6, relu_, selu, sigmoid, silu, softmax, softplus, softshrink, softsign,
    swish, tanh, tanhshrink, thresholded_relu,
)
from .common import (  # noqa: F401
    alpha_dropout, bilinear, diag_embed, dropout, dropout2d, dropout3d,
    embedding, gather_tree, interpolate, label_smooth, linear, one_hot, pad,
    pixel_shuffle, rotary_embedding, rotary_embedding_pairs,
    scaled_dot_product_attention, sequence_mask, temporal_shift, unfold,
    upsample, yarn_inv_freq, yarn_mscale,
)
from .conv import (  # noqa: F401
    conv1d, conv1d_transpose, conv2d, conv2d_transpose, conv3d, conv3d_transpose,
)
from .loss import (  # noqa: F401
    bce_loss, binary_cross_entropy, binary_cross_entropy_with_logits,
    cosine_similarity, cross_entropy, ctc_loss, dice_loss,
    hinge_embedding_loss, hsigmoid_loss, kl_div, l1_loss, log_loss,
    margin_ranking_loss, mse_loss, nll_loss, npair_loss, sigmoid_focal_loss,
    smooth_l1_loss, softmax_with_cross_entropy, square_error_cost,
)
from .norm import (  # noqa: F401
    batch_norm, group_norm, instance_norm, layer_norm, local_response_norm,
    normalize, rms_norm,
)
from .moe import (EXPERT_ACTIVATIONS, expert_route, route_top_k,  # noqa: F401
                  sparse_experts,
                  touched_share)
from .vision import affine_grid, grid_sample  # noqa: F401
from .pooling import (  # noqa: F401
    adaptive_avg_pool1d, adaptive_avg_pool2d, adaptive_avg_pool3d,
    adaptive_max_pool1d, adaptive_max_pool2d, adaptive_max_pool3d, avg_pool1d,
    avg_pool2d, avg_pool3d, max_pool1d, max_pool2d, max_pool3d,
)


def _install():
    from ...framework import dispatch
    from ...tensor import _compat

    _compat.install_name_kwarg(globals())
    dispatch.install_ops(globals())

    # Public F.batch_norm matches the paddle signature (returns out, updates
    # the running-stat tensors in place); layers use the pure triple-return
    # impl directly for functional state threading.
    _bn_full = globals()["batch_norm"]
    globals()["_bn_triple"] = _bn_full  # pure triple-return, used by nn.layer.norm

    def batch_norm(
        x, running_mean, running_var, weight=None, bias=None, training=False,
        momentum=0.9, epsilon=1e-5, data_format="NCHW", use_global_stats=None, name=None,
    ):
        from ...framework.tensor import Tensor as _T

        out, nm, nv = _bn_full(
            x, running_mean, running_var, weight, bias, training, momentum,
            epsilon, data_format, use_global_stats,
        )
        if training and use_global_stats is not True:
            if isinstance(running_mean, _T):
                running_mean.set_value(nm)
            if isinstance(running_var, _T):
                running_var.set_value(nv)
        return out

    globals()["batch_norm"] = batch_norm


_install()

# gated power retention (ops/power_retention.py): raw arrays in and out, as
# the decode-attention ops; imported after ``_install`` so that the state
# pytree a step returns is not wrapped
from ...ops.power_retention import (  # noqa: E402,F401
    power_retention_chunked, power_retention_quadratic,
    power_retention_step, symmetric_square,
)


def _install_inplace_acts():
    """F.elu_/softmax_/tanh_ (reference inplace activations) via the shared
    factory (framework/tensor.py make_inplace)."""
    from ...framework.tensor import make_inplace

    for base_name in ("elu", "softmax", "tanh"):
        nm = base_name + "_"
        globals()[nm] = make_inplace(globals()[base_name], nm)


_install_inplace_acts()
