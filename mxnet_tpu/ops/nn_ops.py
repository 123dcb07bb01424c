"""Neural-network ops.

Reference: src/operator/nn/ (fully_connected.cc, convolution.cc, pooling.cc,
batch_norm.cc, layer_norm.cc, dropout.cc, activation.cc, softmax.cc, lrn.cc,
upsampling.cc, deconvolution.cc), src/operator/{softmax_output,regression_output,
leaky_relu,l2_normalization,instance_norm}.cc, sequence_*.cc, rnn-inl.h.

TPU-native notes:
  * Convolutions keep the reference's NCHW *API* layout but are computed by
    ``lax.conv_general_dilated``; on TPU, XLA's layout assignment retiles to
    the MXU-preferred internal layout, so no hand-written im2col (the analog
    of the MKLDNN layout trick noted at SURVEY §7 hard-part f).
  * BatchNorm returns (out, mean, var) in training so the *caller* updates
    running stats — keeps the op pure for XLA; the Gluon layer and CachedOp
    thread aux state functionally.
  * The fused RNN op is a ``lax.scan`` over time — the compiler pipelines the
    per-step matmuls; weights stay resident in VMEM across steps.
"""
from __future__ import annotations

import numpy as _np

from .registry import register, alias


def _jnp():
    import jax.numpy as jnp
    return jnp


def _lax():
    import jax.lax as lax
    return lax


def _pair(v, n=2):
    if isinstance(v, (list, tuple)):
        t = tuple(int(x) for x in v)
        return t if len(t) == n else t * n
    return (int(v),) * n


# ---------------------------------------------------------------------------
# FullyConnected
# ---------------------------------------------------------------------------

@register("FullyConnected")
def _fully_connected(attrs, data, weight, bias=None):
    """y = x @ W^T + b  (src/operator/nn/fully_connected.cc:239-328)."""
    jnp = _jnp()
    flatten = bool(attrs.get("flatten", True))
    if flatten and data.ndim > 2:
        data = data.reshape((data.shape[0], -1))
    out = jnp.matmul(data, weight.T)
    if not attrs.get("no_bias", False) and bias is not None:
        out = out + bias
    return out


# ---------------------------------------------------------------------------
# Convolution / Deconvolution
# ---------------------------------------------------------------------------

def _conv_dims(ndim, layout=None):
    """Dimension-number strings for the requested data layout.

    Channel-first is the reference default; channel-last (NWC/NHWC/NDHWC,
    convolution.cc's layout parameter) is the TPU-preferred layout — with it
    XLA needs no transposes at the graph edges.  MXNet's channel-last weight
    layout is (O, spatial..., I)."""
    spatial = {3: "W", 4: "HW", 5: "DHW"}[ndim]
    if layout is None or layout.startswith("NC"):
        s = "NC" + spatial
        return (s, "OI" + spatial, s)
    s = "N" + spatial + "C"
    return (s, "O" + spatial + "I", s)


@register("Convolution")
def _convolution(attrs, data, weight, bias=None):
    """N-D convolution (src/operator/nn/convolution.cc), layout attr selects
    channel-first (default) or channel-last data/weight layouts."""
    lax = _lax()
    nd = data.ndim - 2
    kernel = _pair(attrs["kernel"], nd)
    stride = _pair(attrs.get("stride", (1,) * nd), nd)
    pad = _pair(attrs.get("pad", (0,) * nd), nd)
    dilate = _pair(attrs.get("dilate", (1,) * nd), nd)
    num_group = int(attrs.get("num_group", 1))
    layout = attrs.get("layout")
    channel_last = layout is not None and not layout.startswith("NC")
    dn = lax.conv_dimension_numbers(data.shape, weight.shape,
                                    _conv_dims(data.ndim, layout))
    out = lax.conv_general_dilated(
        data, weight,
        window_strides=stride,
        padding=[(p, p) for p in pad],
        lhs_dilation=(1,) * nd,
        rhs_dilation=dilate,
        dimension_numbers=dn,
        feature_group_count=num_group,
        preferred_element_type=None)
    if not attrs.get("no_bias", False) and bias is not None:
        bshape = ((1,) * (nd + 1) + (-1,)) if channel_last \
            else ((1, -1) + (1,) * nd)
        out = out + bias.reshape(bshape)
    return out


@register("Deconvolution")
def _deconvolution(attrs, data, weight, bias=None):
    """Transposed convolution (src/operator/nn/deconvolution.cc)."""
    lax = _lax()
    jnp = _jnp()
    nd = data.ndim - 2
    kernel = _pair(attrs["kernel"], nd)
    stride = _pair(attrs.get("stride", (1,) * nd), nd)
    pad = _pair(attrs.get("pad", (0,) * nd), nd)
    adj = _pair(attrs.get("adj", (0,) * nd), nd)
    num_group = int(attrs.get("num_group", 1))
    layout = attrs.get("layout")
    if layout is not None and not layout.startswith("NC"):
        raise ValueError("Deconvolution supports channel-first layouts only; "
                         "got layout=%r" % (layout,))
    dilate = _pair(attrs.get("dilate", (1,) * nd), nd)
    # weight layout (in_c, out_c/g, *kernel) per MXNet deconvolution.
    # Output size is (i-1)*s + (k-1)*d + 1 - 2p + adj: the effective
    # (dilated) kernel sets the halo, and adj widens the TRAILING side
    # only (deconvolution-inl.h — adj recovers sizes conv rounded away).
    dn = lax.conv_dimension_numbers(data.shape, weight.shape, _conv_dims(data.ndim))
    ke = [(k - 1) * d + 1 for k, d in zip(kernel, dilate)]
    pads = [(k - 1 - p, k - 1 - p + a) for k, p, a in zip(ke, pad, adj)]
    w = jnp.swapaxes(weight, 0, 1)
    w = jnp.flip(w, axis=tuple(range(2, 2 + nd)))
    if num_group > 1:
        # grouped transposed conv: split along channel groups
        outs = []
        xg = jnp.split(data, num_group, axis=1)
        wg = jnp.split(weight, num_group, axis=0)
        for xi, wi in zip(xg, wg):
            wi = jnp.flip(jnp.swapaxes(wi, 0, 1), axis=tuple(range(2, 2 + nd)))
            outs.append(lax.conv_general_dilated(
                xi, wi, window_strides=(1,) * nd, padding=pads,
                lhs_dilation=stride, rhs_dilation=dilate,
                dimension_numbers=dn))
        out = jnp.concatenate(outs, axis=1)
    else:
        out = lax.conv_general_dilated(
            data, w, window_strides=(1,) * nd, padding=pads,
            lhs_dilation=stride, rhs_dilation=dilate,
            dimension_numbers=dn)
    if not attrs.get("no_bias", True) and bias is not None:
        out = out + bias.reshape((1, -1) + (1,) * nd)
    return out


# ---------------------------------------------------------------------------
# Pooling
# ---------------------------------------------------------------------------

@register("Pooling")
def _pooling(attrs, data):
    """max/avg/sum pooling via lax.reduce_window (src/operator/nn/pooling.cc);
    layout attr selects channel-first (default) or channel-last windows."""
    lax = _lax()
    jnp = _jnp()
    nd = data.ndim - 2
    pool_type = attrs.get("pool_type", "max")
    layout = attrs.get("layout")
    channel_last = layout is not None and not layout.startswith("NC")
    global_pool = bool(attrs.get("global_pool", False))
    if global_pool:
        axes = tuple(range(1, data.ndim - 1)) if channel_last \
            else tuple(range(2, data.ndim))
        if pool_type == "max":
            out = jnp.max(data, axis=axes, keepdims=True)
        elif pool_type in ("avg", "sum"):
            out = jnp.mean(data, axis=axes, keepdims=True) if pool_type == "avg" \
                else jnp.sum(data, axis=axes, keepdims=True)
        else:
            raise ValueError(pool_type)
        return out
    kernel = _pair(attrs["kernel"], nd)
    stride = _pair(attrs.get("stride", (1,) * nd), nd)
    pad = _pair(attrs.get("pad", (0,) * nd), nd)
    pooling_convention = attrs.get("pooling_convention", "valid")
    window = ((1,) + kernel + (1,)) if channel_last else ((1, 1) + kernel)
    strides = ((1,) + stride + (1,)) if channel_last else ((1, 1) + stride)
    spatial0 = 1 if channel_last else 2
    if pooling_convention == "full" or (pooling_convention == "same"
                                        and nd > 1):
        # ceil-mode: pad right edge so ceil((x+2p-k)/s)+1 windows fit.
        # The reference's 2-D/3-D shape inference routes 'same' through
        # the SAME ceil formula as 'full' (pooling.cc:163-181 else-branch
        # covers both kFull and kSame); only the 1-D branch gives 'same'
        # its own formula.
        extra = []
        for i in range(nd):
            x = data.shape[spatial0 + i] + 2 * pad[i] - kernel[i]
            rem = x % stride[i]
            e = 0 if rem == 0 else stride[i] - rem
            extra.append(e)
        spads = [(pad[i], pad[i] + extra[i]) for i in range(nd)]
    elif pooling_convention == "same":
        # 1-D 'same' (pooling.cc:142-145): ceil((x+2p)/s) windows — pad
        # the right edge to (O-1)*s + k total extent
        extra = []
        for i in range(nd):
            x = data.shape[spatial0 + i] + 2 * pad[i]
            n_win = -(-x // stride[i])  # ceil
            e = max((n_win - 1) * stride[i] + kernel[i] - x, 0)
            extra.append(e)
        spads = [(pad[i], pad[i] + extra[i]) for i in range(nd)]
    else:
        spads = [(p, p) for p in pad]
    pads = ([(0, 0)] + spads + [(0, 0)]) if channel_last \
        else ([(0, 0), (0, 0)] + spads)
    if pool_type == "max":
        init = _np.array(-_np.inf if jnp.issubdtype(data.dtype, jnp.floating)
                         else jnp.iinfo(data.dtype).min, data.dtype)
        return lax.reduce_window(data, init, lax.max, window, strides, pads)
    if pool_type in ("avg", "sum"):
        s = lax.reduce_window(data, _np.array(0.0, data.dtype), lax.add,
                              window, strides, pads)
        if pool_type == "sum":
            return s
        if bool(attrs.get("count_include_pad", True)):
            extra = [hi - pad[i] for i, (_, hi) in enumerate(spads)]
            if not any(extra):
                denom = 1.0
                for k in kernel:
                    denom *= k
                return s / denom
            # ceil-mode windows hang past the padded extent; the reference
            # divisor is the window area clipped to [-p, i+p) — padding
            # cells count, the ceil-extra region does not (pool.h:273-275)
            ones = jnp.ones_like(data)
            sym_pads = [(pad[i], pad[i]) for i in range(nd)]
            if channel_last:
                ones_p = jnp.pad(ones, [(0, 0)] + sym_pads + [(0, 0)],
                                 constant_values=1)
                extra_pads = [(0, 0)] + [(0, e) for e in extra] + [(0, 0)]
            else:
                ones_p = jnp.pad(ones, [(0, 0), (0, 0)] + sym_pads,
                                 constant_values=1)
                extra_pads = [(0, 0), (0, 0)] + [(0, e) for e in extra]
            cnt = lax.reduce_window(ones_p, _np.array(0.0, data.dtype),
                                    lax.add, window, strides, extra_pads)
            return s / cnt
        ones = jnp.ones_like(data)
        cnt = lax.reduce_window(ones, _np.array(0.0, data.dtype), lax.add,
                                window, strides, pads)
        return s / cnt
    raise ValueError("unsupported pool_type %s" % pool_type)


@register("UpSampling")
def _upsampling(attrs, *inputs):
    """src/operator/nn/upsampling-inl.h.  nearest accepts num_args inputs:
    each is nearest-upsampled to the FIRST input's scaled extent, then
    channel-concatenated (multi_input_mode='concat', default) or summed
    (:99-115).  bilinear is NOT an interpolation op — it is a grouped
    Deconvolution over a real weight input (kernel 2s - s%2, stride s,
    pad ceil((s-1)/2), num_group = num_filter, no bias; GetDeconvolution-
    Param :170-188), so the kernel is learnable and is only bilinear
    interpolation when initialized with init.Bilinear."""
    jnp = _jnp()
    scale = int(attrs["scale"])
    sample_type = attrs.get("sample_type", "nearest")
    if sample_type == "nearest":
        x0 = inputs[0]
        out_h = x0.shape[2] * scale
        ups = []
        for x in inputs:
            s_i = out_h // x.shape[2]
            ups.append(jnp.repeat(jnp.repeat(x, s_i, axis=2), s_i, axis=3))
        if len(ups) == 1:
            return ups[0]
        if attrs.get("multi_input_mode") == "sum":
            out = ups[0]
            for u in ups[1:]:
                out = out + u
            return out
        return jnp.concatenate(ups, axis=1)
    if sample_type == "bilinear":
        if len(inputs) < 2:
            raise ValueError(
                "UpSampling(sample_type='bilinear') takes (data, weight) — "
                "the reference implements it as a grouped Deconvolution "
                "over a learnable kernel (upsampling-inl.h:200-206)")
        data, weight = inputs[0], inputs[1]
        kernel = 2 * scale - scale % 2
        pad = int(_np.ceil((scale - 1) / 2.0))
        num_filter = int(attrs.get("num_filter", data.shape[1]))
        return _deconvolution(
            {"kernel": (kernel, kernel), "stride": (scale, scale),
             "pad": (pad, pad), "num_group": num_filter,
             "num_filter": num_filter, "no_bias": True},
            data, weight)
    raise ValueError(sample_type)


# ---------------------------------------------------------------------------
# Normalization
# ---------------------------------------------------------------------------

BN_EPS_DEFAULT = 1e-3  # reference batch_norm-inl.h eps default


def bn_invstd_to_var(invstd, eps):
    """Invert the reference's VARIANCE_TO_INVSTD: the op's third output
    is 1/sqrt(var + eps); running averages track the raw variance."""
    return 1.0 / (invstd * invstd) - eps


def _bn_apply(attrs, data, gamma, beta, mean, var):
    """Shared affine-normalize step of BatchNorm/SyncBatchNorm."""
    jnp = _jnp()
    eps = float(attrs.get("eps", BN_EPS_DEFAULT))
    axis = int(attrs.get("axis", 1)) % data.ndim  # -1 = channel-last
    bshape = tuple(data.shape[axis] if i == axis else 1
                   for i in range(data.ndim))
    if bool(attrs.get("fix_gamma", True)):
        gamma = jnp.ones_like(gamma)
    inv = jnp.reshape(gamma, bshape) / jnp.sqrt(jnp.reshape(var, bshape) + eps)
    return (data - jnp.reshape(mean, bshape)) * inv + jnp.reshape(beta, bshape)


def _bn_batch_stats(data, axis, shift, sync=None):
    """Biased batch (mean, var) over every axis but ``axis``, in ONE pass:
    ``m1 = mean(x - c)`` and ``m2 = mean((x - c)^2)`` are sibling reductions
    of one operand, which XLA folds into the epilogue of the convolution
    that produced ``x``; ``mean = c + m1``, ``var = max(m2 - m1^2, 0)``.
    The two-pass ``jnp.var`` cost one more full read of ``x`` forward and,
    under autodiff, a third for the mean's gradient ``sum(x - mean)``, which
    is identically zero.  ``c`` is ``shift`` (the running mean) held
    constant: a vector known before ``x`` is, so it costs no pass, and once
    it tracks the batch mean ``m2 - m1^2`` cancels nothing however far the
    data sits from zero (with ``c = 0``, a fresh layer, it is the textbook
    E[x^2] - E[x]^2).  Both sums accumulate in float32 whatever the data's
    dtype (squares of bfloat16 summed in bfloat16 are no variance), and the
    results are float32 too: the caller casts.  ``sync`` (SyncBatchNorm's
    cross-device ``pmean``) is applied to both moments before the variance."""
    jnp = _jnp()
    axes = tuple(i for i in range(data.ndim) if i != axis)
    acc = jnp.promote_types(data.dtype, jnp.float32)
    c = _lax().stop_gradient(shift).astype(acc)
    d = data.astype(acc) - jnp.reshape(
        c, tuple(-1 if i == axis else 1 for i in range(data.ndim)))
    n = data.size / data.shape[axis]
    m1 = jnp.sum(d, axis=axes) / n
    m2 = jnp.sum(d * d, axis=axes) / n
    if sync is not None:
        m1, m2 = sync(m1), sync(m2)
    var = jnp.maximum(m2 - m1 * m1, 0)
    return c + m1, var


@register("BatchNorm", num_outputs=3, visible_outputs=1, mode_dependent=True)
def _batch_norm(attrs, data, gamma, beta, moving_mean, moving_var):
    """Batch normalization (src/operator/nn/batch_norm.cc).

    Returns (out, mean, invstd) — the reference's second saved output is
    the INVERSE STD 1/sqrt(var + eps), not the variance, in train AND
    use_global modes alike (batch_norm.cc:140-154 VARIANCE_TO_INVSTD;
    the output_mean_var doc promises "data_mean and the inverse of
    data_var").  Consumers that fold running averages (gluon BatchNorm,
    the executor's functional aux update) recover the raw variance as
    1/invstd^2 - eps.

    In training mode the batch variance is one-pass, E[d^2] - E[d]^2 of
    d = x - moving_mean with float32 sums (_bn_batch_stats): the step is
    bound by memory traffic, and the two-pass variance read every
    convolution output twice more."""
    jnp = _jnp()
    axis = int(attrs.get("axis", 1)) % data.ndim  # -1 = channel-last
    eps = float(attrs.get("eps", BN_EPS_DEFAULT))
    use_global = bool(attrs.get("use_global_stats", False)) or not attrs.get("_training", False)
    if use_global:
        mean, var = moving_mean, moving_var
    else:
        mean, var = (s.astype(data.dtype) for s in
                     _bn_batch_stats(data, axis, moving_mean))
    invstd = 1.0 / jnp.sqrt(var + eps)
    return _bn_apply(attrs, data, gamma, beta, mean, var), mean, invstd


@register("LayerNorm")
def _layer_norm(attrs, data, gamma, beta):
    jnp = _jnp()
    axis = int(attrs.get("axis", -1))
    eps = float(attrs.get("eps", 1e-5))
    mean = jnp.mean(data, axis=axis, keepdims=True)
    var = jnp.var(data, axis=axis, keepdims=True)
    out = (data - mean) / jnp.sqrt(var + eps)
    bshape = [1] * data.ndim
    bshape[axis] = data.shape[axis]
    return out * gamma.reshape(bshape) + beta.reshape(bshape)


@register("InstanceNorm")
def _instance_norm(attrs, data, gamma, beta):
    jnp = _jnp()
    eps = float(attrs.get("eps", 1e-3))
    axes = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=axes, keepdims=True)
    var = jnp.var(data, axis=axes, keepdims=True)
    out = (data - mean) / jnp.sqrt(var + eps)
    bshape = (1, -1) + (1,) * (data.ndim - 2)
    return out * gamma.reshape(bshape) + beta.reshape(bshape)


@register("L2Normalization")
def _l2_normalization(attrs, data):
    jnp = _jnp()
    eps = float(attrs.get("eps", 1e-10))
    mode = attrs.get("mode", "instance")
    if mode == "instance":
        axes = tuple(range(1, data.ndim))
    elif mode == "channel":
        axes = (1,)
    elif mode == "spatial":
        axes = tuple(range(2, data.ndim))
    else:
        raise ValueError(mode)
    norm = jnp.sqrt(jnp.sum(jnp.square(data), axis=axes, keepdims=True) + eps)
    return data / norm


@register("LRN")
def _lrn(attrs, data):
    jnp = _jnp()
    alpha = float(attrs.get("alpha", 1e-4))
    beta = float(attrs.get("beta", 0.75))
    knorm = float(attrs.get("knorm", 2.0))
    nsize = int(attrs["nsize"])
    sq = jnp.square(data)
    pad = nsize // 2
    sq_pad = jnp.pad(sq, [(0, 0), (pad, pad), (0, 0), (0, 0)])
    acc = jnp.zeros_like(data)
    for i in range(nsize):
        acc = acc + sq_pad[:, i:i + data.shape[1], :, :]
    return data / jnp.power(knorm + alpha * acc / nsize, beta)


# ---------------------------------------------------------------------------
# Activations / softmax
# ---------------------------------------------------------------------------

@register("Activation")
def _activation(attrs, data):
    import jax
    jnp = _jnp()
    act = attrs.get("act_type", "relu")
    if act == "relu":
        return jnp.maximum(data, 0)
    if act == "sigmoid":
        return jax.nn.sigmoid(data)
    if act == "tanh":
        return jnp.tanh(data)
    if act == "softrelu":
        return jax.nn.softplus(data)
    if act == "softsign":
        return data / (1 + jnp.abs(data))
    raise ValueError("unknown act_type %s" % act)


def _is_rrelu(attrs):
    return attrs.get("act_type", "leaky") == "rrelu"


# flags are attr predicates: only rrelu draws randomness / depends on the
# train-predict mode, so leaky/prelu/elu/selu/gelu keep the zero-overhead
# dispatch (no per-call key split, no train/predict jit-cache doubling)
@register("LeakyReLU", mode_dependent=_is_rrelu, needs_rng=_is_rrelu)
def _leaky_relu(attrs, data, gamma=None):
    """src/operator/leaky_relu-inl.h.  rrelu (:145-176) samples the
    negative-side slope per ELEMENT from U(lower_bound, upper_bound) in
    train mode (the randomized-relu of Xu et al.); eval mode uses the
    deterministic midpoint.  The sampled slope doubles as the backward
    mask, which jax.vjp reproduces for free through the where()."""
    import jax
    jnp = _jnp()
    act = attrs.get("act_type", "leaky")
    slope = float(attrs.get("slope", 0.25))
    if act == "leaky":
        return jnp.where(data >= 0, data, slope * data)
    if act == "elu":
        return jnp.where(data >= 0, data, slope * (jnp.exp(data) - 1))
    if act == "selu":
        alpha, scale = 1.6732632423543772, 1.0507009873554805
        return scale * jnp.where(data >= 0, data, alpha * (jnp.exp(data) - 1))
    if act == "prelu":
        g = gamma.reshape((1, -1) + (1,) * (data.ndim - 2)) if gamma.ndim == 1 else gamma
        return jnp.where(data >= 0, data, g * data)
    if act == "gelu":
        return jax.nn.gelu(data)
    if act == "rrelu":
        lower = float(attrs.get("lower_bound", 0.125))
        upper = float(attrs.get("upper_bound", 0.334))
        if bool(attrs.get("_training", False)):
            key = attrs["_rng_key"]
            sl = jax.random.uniform(key, data.shape, data.dtype,
                                    minval=lower, maxval=upper)
            return jnp.where(data >= 0, data, sl * data)
        return jnp.where(data >= 0, data, (lower + upper) / 2 * data)
    raise ValueError("unknown act_type %s" % act)


@register("softmax")
def _softmax(attrs, data, length=None):
    import jax
    axis = int(attrs.get("axis", -1))
    temperature = attrs.get("temperature")
    if temperature:
        data = data / float(temperature)
    return jax.nn.softmax(data, axis=axis)


@register("log_softmax")
def _log_softmax(attrs, data):
    import jax
    axis = int(attrs.get("axis", -1))
    temperature = attrs.get("temperature")
    if temperature:
        data = data / float(temperature)
    return jax.nn.log_softmax(data, axis=axis)


@register("softmin")
def _softmin(attrs, data):
    import jax
    axis = int(attrs.get("axis", -1))
    return jax.nn.softmax(-data, axis=axis)


@register("SoftmaxActivation")
def _softmax_activation(attrs, data):
    import jax
    mode = attrs.get("mode", "instance")
    if mode == "channel":
        return jax.nn.softmax(data, axis=1)
    return jax.nn.softmax(data.reshape(data.shape[0], -1), axis=-1).reshape(data.shape)


@register("SoftmaxOutput")
def _softmax_output(attrs, data, label):
    """Softmax forward with implicit cross-entropy backward
    (src/operator/softmax_output-inl.h).  Implemented as a jax.custom_vjp so
    the tape's jax.vjp picks up the reference's gradient semantics.

    The reference backward has three branches (softmax_output-inl.h:150-262),
    all reproduced here:
      1. label.shape == out.shape (soft/probability label, :150-161):
         grad = (out - label) * grad_scale, no normalization division.
      2. multi_output (:162-206): softmax along axis 1 over (n, k, s);
         grad = (out - one_hot) * grad_scale / divisor where divisor is
         s (null), s*n (batch), or #non-ignored-labels (valid, clamped >=1
         and counted regardless of use_ignore, exactly like the reference's
         workspace loop at :181-196).
      3. hard label (:207-258): softmax over the flattened class axis;
         smooth_alpha label smoothing (mshadow SmoothSoftmaxGrad: the
         smoothed target is (1-alpha) at the gold class and alpha/(k-1)
         elsewhere), then grad_scale / valid_cnt with valid_cnt = 1 (null),
         #labels (batch), or #non-ignored (valid).
    All branches honor out_grad=True (:156,202,253): multiply elementwise by
    the incoming head gradient.  Forward is shape-preserving — the
    reference's 2-D/3-D flattening is a TBlob *view*, so out.shape always
    equals data.shape; preserve_shape softmaxes the LAST axis (:121-124)."""
    import jax
    jnp = _jnp()
    grad_scale = float(attrs.get("grad_scale", 1.0))
    ignore_label = float(attrs.get("ignore_label", -1.0))
    use_ignore = bool(attrs.get("use_ignore", False))
    multi_output = bool(attrs.get("multi_output", False))
    normalization = attrs.get("normalization", "null")
    preserve_shape = bool(attrs.get("preserve_shape", False))
    use_out_grad = bool(attrs.get("out_grad", False))
    smooth_alpha = float(attrs.get("smooth_alpha", 0.0))

    @jax.custom_vjp
    def f(d, l):
        if multi_output:
            return jax.nn.softmax(d, axis=1)
        if preserve_shape or d.ndim <= 2:
            return jax.nn.softmax(d, axis=-1)
        n = d.shape[0]
        return jax.nn.softmax(d.reshape(n, -1), axis=-1).reshape(d.shape)

    def f_fwd(d, l):
        out = f(d, l)
        return out, (out, l)

    def f_bwd(res, g):
        out, l = res
        dtype = out.dtype

        # branch 1: probability-shaped label (soft targets)
        if l.shape == out.shape:
            grad = (out - l.astype(dtype)) * dtype.type(grad_scale)
            if use_out_grad:
                grad = grad * g
            return grad.astype(dtype), None

        if multi_output:
            # (n, k, s) view: softmax axis 1, one label per spatial position
            n, k = out.shape[0], out.shape[1]
            s = int(_np.prod(out.shape[2:])) if out.ndim > 2 else 1
            out3 = out.reshape(n, k, s)
            l2 = l.reshape(n, s)
            oh = jax.nn.one_hot(l2.astype(jnp.int32), k, axis=1, dtype=dtype)
            grad = out3 - oh
            if use_ignore:
                # reference SoftmaxGrad compares static_cast<int>(label) ==
                # static_cast<int>(ignore_label) — int-cast so the mask and
                # the 'valid' divisor below can never disagree
                keep = (l2.astype(jnp.int32)
                        != int(ignore_label)).astype(dtype)
                grad = grad * keep[:, None, :]
            if normalization == "batch":
                grad = grad * dtype.type(grad_scale / (s * n))
            elif normalization == "valid":
                valid = jnp.maximum(
                    jnp.sum(l2.astype(jnp.int32) != int(ignore_label)), 1)
                grad = grad * (grad_scale / valid.astype(dtype))
            else:  # null
                grad = grad * dtype.type(grad_scale / s)
            if use_out_grad:
                grad = grad * g.reshape(n, k, s)
            return grad.reshape(out.shape).astype(dtype), None

        # branch 3: hard label over the flattened class axis
        if preserve_shape:
            out2 = out.reshape(-1, out.shape[-1])
        else:
            out2 = out.reshape(out.shape[0], -1)
        k = out2.shape[1]
        lf = l.reshape(-1)
        oh = jax.nn.one_hot(lf.astype(jnp.int32), k, dtype=dtype)
        target = oh
        if smooth_alpha > 0.0:
            target = (oh * dtype.type(1.0 - smooth_alpha)
                      + (1.0 - oh) * dtype.type(smooth_alpha / max(k - 1, 1)))
        grad = out2 - target
        if use_ignore:
            keep = (lf.astype(jnp.int32) != int(ignore_label)).astype(dtype)
            grad = grad * keep[:, None]
        if normalization == "batch":
            grad = grad * dtype.type(grad_scale / lf.shape[0])
        elif normalization == "valid":
            valid = jnp.maximum(
                jnp.sum(lf.astype(jnp.int32) != int(ignore_label)), 1)
            grad = grad * (grad_scale / valid.astype(dtype))
        else:  # null
            grad = grad * dtype.type(grad_scale)
        if use_out_grad:
            grad = grad * g.reshape(out2.shape)
        return grad.reshape(out.shape).astype(dtype), None

    f.defvjp(f_fwd, f_bwd)
    return f(data, label)


alias("Softmax", "SoftmaxOutput")


@register("softmax_cross_entropy")
def _softmax_cross_entropy(attrs, data, label):
    import jax
    jnp = _jnp()
    logp = jax.nn.log_softmax(data, axis=-1)
    oh = jax.nn.one_hot(label.astype(jnp.int32), data.shape[-1])
    return -jnp.sum(oh * logp).reshape((1,))


@register("LinearRegressionOutput")
def _linear_regression_output(attrs, data, label):
    import jax
    grad_scale = float(attrs.get("grad_scale", 1.0))

    @jax.custom_vjp
    def f(d, l):
        return d

    def f_fwd(d, l):
        return d, (d, l)

    def f_bwd(res, g):
        d, l = res
        num_out = max(int(_np.prod(d.shape[1:])), 1)
        return (grad_scale * (d - l.reshape(d.shape)) / num_out, None)

    f.defvjp(f_fwd, f_bwd)
    return f(data, label)


@register("MAERegressionOutput")
def _mae_regression_output(attrs, data, label):
    import jax
    jnp = _jnp()
    grad_scale = float(attrs.get("grad_scale", 1.0))

    @jax.custom_vjp
    def f(d, l):
        return d

    def f_fwd(d, l):
        return d, (d, l)

    def f_bwd(res, g):
        d, l = res
        num_out = max(int(_np.prod(d.shape[1:])), 1)
        return (grad_scale * jnp.sign(d - l.reshape(d.shape)) / num_out, None)

    f.defvjp(f_fwd, f_bwd)
    return f(data, label)


@register("LogisticRegressionOutput")
def _logistic_regression_output(attrs, data, label):
    import jax
    grad_scale = float(attrs.get("grad_scale", 1.0))

    @jax.custom_vjp
    def f(d, l):
        return jax.nn.sigmoid(d)

    def f_fwd(d, l):
        out = jax.nn.sigmoid(d)
        return out, (out, l)

    def f_bwd(res, g):
        out, l = res
        num_out = max(int(_np.prod(out.shape[1:])), 1)
        return (grad_scale * (out - l.reshape(out.shape)) / num_out, None)

    f.defvjp(f_fwd, f_bwd)
    return f(data, label)


# ---------------------------------------------------------------------------
# Dropout
# ---------------------------------------------------------------------------

@register("Dropout", mode_dependent=True, needs_rng=True)
def _dropout(attrs, data):
    import jax
    jnp = _jnp()
    p = float(attrs.get("p", 0.5))
    mode = attrs.get("mode", "training")
    training = bool(attrs.get("_training", False))
    axes = attrs.get("axes", ())
    if (not training and mode != "always") or p <= 0:
        return data
    key = attrs["_rng_key"]
    if axes:
        shape = tuple(1 if i in tuple(axes) else s for i, s in enumerate(data.shape))
    else:
        shape = data.shape
    keep = 1.0 - p
    mask = jax.random.bernoulli(key, keep, shape).astype(data.dtype) / keep
    return data * mask


# ---------------------------------------------------------------------------
# Sequence ops (src/operator/sequence_mask.cc, sequence_last.cc, sequence_reverse.cc)
# ---------------------------------------------------------------------------

@register("SequenceMask")
def _sequence_mask(attrs, data, sequence_length=None):
    jnp = _jnp()
    use_len = bool(attrs.get("use_sequence_length", False))
    value = float(attrs.get("value", 0.0))
    axis = int(attrs.get("axis", 0))  # time axis
    if not use_len or sequence_length is None:
        return data
    T = data.shape[axis]
    pos = jnp.arange(T)
    # data layout: (T, B, ...) for axis=0 or (B, T, ...) for axis=1
    if axis == 0:
        mask = pos[:, None] < sequence_length[None, :].astype(jnp.int32)
    else:
        mask = pos[None, :] < sequence_length[:, None].astype(jnp.int32)
    mask = mask.reshape(mask.shape + (1,) * (data.ndim - 2))
    return jnp.where(mask, data, value)


@register("SequenceLast")
def _sequence_last(attrs, data, sequence_length=None):
    jnp = _jnp()
    use_len = bool(attrs.get("use_sequence_length", False))
    axis = int(attrs.get("axis", 0))
    if not use_len or sequence_length is None:
        idx = [slice(None)] * data.ndim
        idx[axis] = -1
        return data[tuple(idx)]
    last = (sequence_length.astype(jnp.int32) - 1)
    if axis == 0:
        return jnp.take_along_axis(
            data, last.reshape((1, -1) + (1,) * (data.ndim - 2)), axis=0)[0]
    return jnp.take_along_axis(
        data, last.reshape((-1, 1) + (1,) * (data.ndim - 2)), axis=1)[:, 0]


@register("SequenceReverse")
def _sequence_reverse(attrs, data, sequence_length=None):
    jnp = _jnp()
    use_len = bool(attrs.get("use_sequence_length", False))
    if not use_len or sequence_length is None:
        return jnp.flip(data, axis=0)
    T = data.shape[0]
    lens = sequence_length.astype(jnp.int32)
    pos = jnp.arange(T)[:, None]
    rev_idx = jnp.where(pos < lens[None, :], lens[None, :] - 1 - pos, pos)
    return jnp.take_along_axis(data, rev_idx.reshape(rev_idx.shape + (1,) * (data.ndim - 2)), axis=0)


# ---------------------------------------------------------------------------
# Fused RNN (src/operator/rnn-inl.h:49) — lax.scan over time
# ---------------------------------------------------------------------------

def _rnn_num_outputs(attrs):
    return 2 if attrs.get("mode") == "lstm" and attrs.get("state_outputs", False) \
        else (2 if attrs.get("state_outputs", False) else 1)


@register("RNN", num_outputs=lambda attrs: (3 if attrs.get("mode", "lstm") == "lstm" else 2)
         if attrs.get("state_outputs", False) else 1,
         mode_dependent=True, needs_rng=True)
def _rnn(attrs, data, parameters, state, state_cell=None):
    """Fused multi-layer RNN/LSTM/GRU (reference src/operator/rnn-inl.h:49;
    cudnn path cudnn_rnn-inl.h).  data: (T, B, I); packed parameters follow the
    cudnn/MXNet canonical order: per layer/direction, i2h weights then h2h
    weights, then all biases (i2h then h2h).  Computed as lax.scan over time;
    each step's gate matmul hits the MXU with weights pinned on-chip."""
    import jax
    jnp = _jnp()
    lax = _lax()
    mode = attrs.get("mode", "lstm")
    state_size = int(attrs["state_size"])
    num_layers = int(attrs.get("num_layers", 1))
    bidirectional = bool(attrs.get("bidirectional", False))
    state_outputs = bool(attrs.get("state_outputs", False))
    p_drop = float(attrs.get("p", 0.0))
    training = bool(attrs.get("_training", False))
    ndir = 2 if bidirectional else 1
    ngates = {"rnn_relu": 1, "rnn_tanh": 1, "lstm": 4, "gru": 3}[mode]

    T, B, I = data.shape
    H = state_size

    # --- unpack parameters ------------------------------------------------
    offset = 0

    def take(n, shape):
        nonlocal offset
        w = lax.dynamic_slice(parameters, (offset,), (n,)).reshape(shape)
        offset += n
        return w

    Wx, Wh = [], []
    for layer in range(num_layers):
        in_size = I if layer == 0 else H * ndir
        for d in range(ndir):
            Wx.append(take(ngates * H * in_size, (ngates * H, in_size)))
            Wh.append(take(ngates * H * H, (ngates * H, H)))
    Bx, Bh = [], []
    for layer in range(num_layers):
        for d in range(ndir):
            Bx.append(take(ngates * H, (ngates * H,)))
            Bh.append(take(ngates * H, (ngates * H,)))

    def cell_step(mode, x_proj, h, c, Whh, bh):
        """One timestep given precomputed input projection."""
        gates = x_proj + jnp.matmul(h, Whh.T) + bh
        if mode == "rnn_relu":
            return jnp.maximum(gates, 0), c
        if mode == "rnn_tanh":
            return jnp.tanh(gates), c
        if mode == "lstm":
            i, f, g, o = jnp.split(gates, 4, axis=-1)
            i, f, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f), jax.nn.sigmoid(o)
            g = jnp.tanh(g)
            c_new = f * c + i * g
            return o * jnp.tanh(c_new), c_new
        if mode == "gru":
            # cudnn GRU: r,z,n gating with separate h2h bias on n
            xr, xz, xn = jnp.split(x_proj, 3, axis=-1)
            hr, hz, hn = jnp.split(jnp.matmul(h, Whh.T), 3, axis=-1)
            br, bz, bn = jnp.split(bh, 3)
            r = jax.nn.sigmoid(xr + hr + br)
            z = jax.nn.sigmoid(xz + hz + bz)
            n = jnp.tanh(xn + r * (hn + bn))
            return (1 - z) * n + z * h, c
        raise ValueError(mode)

    x = data
    h_finals, c_finals = [], []
    key = attrs.get("_rng_key")
    for layer in range(num_layers):
        outs_dir = []
        for d in range(ndir):
            li = layer * ndir + d
            h0 = state[li]
            c0 = state_cell[li] if mode == "lstm" and state_cell is not None \
                else jnp.zeros_like(h0)
            xs = jnp.flip(x, axis=0) if d == 1 else x
            # big batched input projection: (T*B, in) @ (in, G*H) on the MXU
            x_proj = jnp.einsum("tbi,gi->tbg", xs, Wx[li]) + Bx[li]

            def step(carry, xp, _Whh=Wh[li], _bh=Bh[li]):
                h, c = carry
                h2, c2 = cell_step(mode, xp, h, c, _Whh, _bh)
                return (h2, c2), h2

            (hT, cT), ys = lax.scan(step, (h0, c0), x_proj)
            if d == 1:
                ys = jnp.flip(ys, axis=0)
            outs_dir.append(ys)
            h_finals.append(hT)
            c_finals.append(cT)
        x = jnp.concatenate(outs_dir, axis=-1) if ndir == 2 else outs_dir[0]
        if p_drop > 0 and training and layer < num_layers - 1 and key is not None:
            key, sub = jax.random.split(key)
            mask = jax.random.bernoulli(sub, 1 - p_drop, x.shape).astype(x.dtype)
            x = x * mask / (1 - p_drop)

    if not state_outputs:
        return x
    hs = jnp.stack(h_finals, axis=0)
    if mode == "lstm":
        cs = jnp.stack(c_finals, axis=0)
        return x, hs, cs
    return x, hs


# ---------------------------------------------------------------------------
# misc
# ---------------------------------------------------------------------------

@register("Correlation")
def _correlation(attrs, data1, data2):
    """FlowNet correlation layer (src/operator/correlation.cc:40-82).

    For every output position the kernel-window inner product (or abs
    difference) between data1 and data2 displaced by each offset in the
    (2*max_displacement/stride2+1)^2 neighborhood, averaged over
    kernel_size^2 * channels.

    TPU-native: instead of the reference's per-pixel scalar loop, each of the
    D^2 displacements becomes one shifted elementwise product + strided
    window-sum — all static slices, so XLA fuses the whole neighborhood into
    a few vectorized kernels.
    """
    jnp = _jnp()
    K = int(attrs.get("kernel_size", 1))
    md = int(attrs.get("max_displacement", 1))
    s1 = int(attrs.get("stride1", 1))
    s2 = int(attrs.get("stride2", 1))
    pad = int(attrs.get("pad_size", 0))
    is_multiply = bool(attrs.get("is_multiply", True))
    N, C, H, W = data1.shape
    kr = (K - 1) // 2
    border = md + kr
    Hp, Wp = H + 2 * pad, W + 2 * pad
    top_h = -(-(Hp - 2 * border) // s1)   # ceil-div, reference shape math
    top_w = -(-(Wp - 2 * border) // s1)
    grid_r = md // s2
    D = 2 * grid_r + 1
    # padded frames, NHWC; data2 gets an extra max_displacement margin so
    # every displacement is a static in-bounds slice
    y_hi = md + (top_h - 1) * s1 + K      # one past the last row data1 reads
    x_hi = md + (top_w - 1) * s1 + K
    HA, WA = max(Hp, y_hi), max(Wp, x_hi)
    t1 = jnp.zeros((N, HA, WA, C), data1.dtype)
    t1 = t1.at[:, pad:pad + H, pad:pad + W].set(jnp.transpose(data1, (0, 2, 3, 1)))
    t2 = jnp.zeros((N, HA + 2 * md, WA + 2 * md, C), data2.dtype)
    t2 = t2.at[:, md + pad:md + pad + H, md + pad:md + pad + W].set(
        jnp.transpose(data2, (0, 2, 3, 1)))
    scale = 1.0 / (K * K * C)
    channels = []
    for dy in range(-grid_r, grid_r + 1):
        for dx in range(-grid_r, grid_r + 1):
            shifted = t2[:, md + dy * s2:md + dy * s2 + HA,
                         md + dx * s2:md + dx * s2 + WA]
            if is_multiply:
                prod = jnp.sum(t1 * shifted, axis=-1)     # (N, HA, WA)
            else:
                prod = jnp.sum(jnp.abs(t1 - shifted), axis=-1)
            acc = 0.0
            for h in range(K):
                for w in range(K):
                    acc = acc + prod[:, md + h:md + h + (top_h - 1) * s1 + 1:s1,
                                     md + w:md + w + (top_w - 1) * s1 + 1:s1]
            channels.append(acc * scale)
    # channel order: tc = (dy+grid_r)*D + (dx+grid_r) (s2p from tc//D)
    return jnp.stack(channels, axis=1)


@register("CTCLoss")
def _ctc_loss(attrs, data, label, data_lengths=None, label_lengths=None):
    """Connectionist Temporal Classification loss (src/operator/nn/ctc_loss.cc).

    data: (T, N, C) unnormalized activations (softmax applied internally, like
    warp-ctc); label: (N, L) int indices; returns per-example loss (N,).
    blank_label='first' reserves channel 0 (labels are >=1, padding 0);
    'last' reserves channel C-1 (labels 0-indexed, padding -1).

    TPU-native: the alpha recursion runs in the log semiring under one
    ``lax.scan`` over time — a single compiled loop, batched over N, and
    differentiable (the reference ships a hand-written backward; here the
    scan's VJP provides it).
    """
    import jax
    jnp = _jnp()
    lax = _lax()
    T, N, C = data.shape
    blank_first = str(attrs.get("blank_label", "first")) == "first"
    blank = 0 if blank_first else C - 1
    pad_val = 0 if blank_first else -1
    NEG = jnp.asarray(-1e30, jnp.float32)

    logp = jax.nn.log_softmax(data.astype(jnp.float32), axis=-1)
    label = label.astype(jnp.int32)
    L = label.shape[1]
    # optional inputs arrive positionally in (data_lengths, label_lengths)
    # order, but when only use_label_lengths is set the single extra input IS
    # the label lengths (reference CTCLossOpNumInputs, ctc_loss.cc)
    use_dl = bool(attrs.get("use_data_lengths", False))
    use_ll = bool(attrs.get("use_label_lengths", False))
    extras = [x for x in (data_lengths, label_lengths) if x is not None]
    if not attrs:  # direct fcompute call: trust the keyword positions
        use_dl, use_ll = data_lengths is not None, label_lengths is not None
    dl = extras.pop(0) if use_dl and extras else None
    ll = extras.pop(0) if use_ll and extras else None
    if extras:
        raise ValueError(
            "CTCLoss got %d length input(s) not covered by use_data_lengths/"
            "use_label_lengths — set the matching flag(s)" % len(extras))
    if ll is not None:
        lab_len = ll.astype(jnp.int32)
    else:
        lab_len = jnp.sum((label != pad_val).astype(jnp.int32), axis=1)
    if dl is not None:
        seq_len = dl.astype(jnp.int32)
    else:
        seq_len = jnp.full((N,), T, jnp.int32)

    if L == 0:
        # no labels at all: the only path is all-blanks
        t_mask = jnp.arange(T)[:, None] < seq_len[None, :]
        total = jnp.sum(jnp.where(t_mask, logp[:, :, blank], 0.0), axis=0)
        return (-total).astype(data.dtype)

    # extended label sequence: blank, l1, blank, l2, ..., blank  (length S)
    S = 2 * L + 1
    ext = jnp.full((N, S), blank, jnp.int32)
    ext = ext.at[:, 1::2].set(label)
    pos = jnp.arange(S)
    valid_s = pos[None, :] < (2 * lab_len + 1)[:, None]
    # a position may also arrive from s-2 when its label differs from ext[s-2]
    # (and is not blank) — the standard CTC skip transition
    can_skip = jnp.zeros((N, S), bool)
    can_skip = can_skip.at[:, 2:].set(
        (ext[:, 2:] != blank) & (ext[:, 2:] != ext[:, :-2]))

    def emit(t_logp, labels_ext):
        return jnp.take_along_axis(t_logp, labels_ext, axis=1)  # (N, S)

    alpha0 = jnp.full((N, S), NEG)
    alpha0 = alpha0.at[:, 0].set(logp[0, :, blank])
    if L > 0:
        alpha0 = alpha0.at[:, 1].set(
            jnp.where(lab_len > 0, emit(logp[0], ext)[:, 1], NEG))
    alpha0 = jnp.where(valid_s, alpha0, NEG)

    def step(alpha, t_and_logp):
        t, lp = t_and_logp
        stay = alpha
        prev1 = jnp.concatenate([jnp.full((N, 1), NEG), alpha[:, :-1]], axis=1)
        prev2 = jnp.concatenate([jnp.full((N, 2), NEG), alpha[:, :-2]], axis=1)
        prev2 = jnp.where(can_skip, prev2, NEG)
        merged = jnp.logaddexp(jnp.logaddexp(stay, prev1), prev2)
        new = merged + emit(lp, ext)
        new = jnp.where(valid_s, new, NEG)
        # freeze finished sequences (t >= their data length)
        new = jnp.where((t < seq_len)[:, None], new, alpha)
        return new, None

    ts = jnp.arange(1, T)
    alpha, _ = lax.scan(step, alpha0, (ts, logp[1:]))

    end = 2 * lab_len  # index of final blank in the extended sequence
    a_last = jnp.take_along_axis(alpha, end[:, None], axis=1)[:, 0]
    a_prev = jnp.take_along_axis(
        alpha, jnp.maximum(end - 1, 0)[:, None], axis=1)[:, 0]
    a_prev = jnp.where(lab_len > 0, a_prev, NEG)
    loss = -jnp.logaddexp(a_last, a_prev)
    return loss.astype(data.dtype)


alias("ctc_loss", "CTCLoss")
alias("_contrib_CTCLoss", "CTCLoss")
alias("_contrib_ctc_loss", "CTCLoss")


@register("_contrib_SyncBatchNorm", num_outputs=3, visible_outputs=1,
          mode_dependent=True)
def _sync_batch_norm(attrs, data, gamma, beta, moving_mean, moving_var):
    """Synchronized BatchNorm (src/operator/contrib/sync_batch_norm.cc).

    The reference synchronizes batch statistics across ``ndev`` GPU workers
    with a host-side barrier + shared buffer keyed by ``key``.  TPU-native:
    when traced inside pjit/shard_map with a mesh axis named ``axis_name``
    (default 'dp'), the batch mean and mean-of-squares ride one
    ``lax.pmean`` over ICI; outside a mesh it degrades to plain BatchNorm.
    Returns (out, mean, invstd) like BatchNorm — the third output is the
    reference's inverse std (batch_norm.cc:140-154); running-stat folding
    recovers the variance via bn_invstd_to_var.
    """
    jnp = _jnp()
    lax = _lax()
    use_global = (bool(attrs.get("use_global_stats", False))
                  or not attrs.get("_training", False))
    axis_name = attrs.get("axis_name", "dp")
    channel_axis = int(attrs.get("axis", 1)) % data.ndim
    if use_global:
        mean, var = moving_mean, moving_var
    else:
        def sync(moment):
            try:  # inside shard_map/pmap with the axis bound: cross-device
                return lax.pmean(moment, axis_name)
            except NameError:  # axis not bound: single-device semantics
                return moment
        mean, var = (s.astype(data.dtype) for s in
                     _bn_batch_stats(data, channel_axis, moving_mean, sync))
    # invstd third output, matching BatchNorm (batch_norm.cc:140-154)
    eps = float(attrs.get("eps", BN_EPS_DEFAULT))
    invstd = 1.0 / jnp.sqrt(var + eps)
    return _bn_apply(attrs, data, gamma, beta, mean, var), mean, invstd


@register("GridGenerator")
def _grid_generator(attrs, data):
    jnp = _jnp()
    transform_type = attrs.get("transform_type", "affine")
    target_shape = tuple(attrs.get("target_shape", (0, 0)))
    if transform_type == "affine":
        H, W = target_shape
        ys = jnp.linspace(-1, 1, H)
        xs = jnp.linspace(-1, 1, W)
        gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
        ones = jnp.ones_like(gx)
        grid = jnp.stack([gx.reshape(-1), gy.reshape(-1), ones.reshape(-1)], axis=0)
        theta = data.reshape((-1, 2, 3))
        out = jnp.matmul(theta, grid)
        return out.reshape((-1, 2, H, W))
    # warp
    flow = data
    n, _, H, W = flow.shape
    ys = jnp.arange(H, dtype=flow.dtype)
    xs = jnp.arange(W, dtype=flow.dtype)
    gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
    gx2 = (gx[None] + flow[:, 0]) / max((W - 1) / 2.0, 1) - 1
    gy2 = (gy[None] + flow[:, 1]) / max((H - 1) / 2.0, 1) - 1
    return jnp.stack([gx2, gy2], axis=1)


@register("BilinearSampler")
def _bilinear_sampler(attrs, data, grid):
    jnp = _jnp()
    n, c, h, w = data.shape
    gx = (grid[:, 0] + 1) * (w - 1) / 2.0
    gy = (grid[:, 1] + 1) * (h - 1) / 2.0
    x0 = jnp.floor(gx).astype(jnp.int32)
    y0 = jnp.floor(gy).astype(jnp.int32)
    x1, y1 = x0 + 1, y0 + 1
    wx = gx - x0
    wy = gy - y0

    def gather(xi, yi):
        # out-boundary corners contribute ZERO, not a clamped edge value
        # (bilinear_sampler.cc:61-67 guards each corner with between();
        # docstring: "out-boundary points will be padded with zeros")
        inb = ((xi >= 0) & (xi <= w - 1) & (yi >= 0) & (yi <= h - 1))
        xc = jnp.clip(xi, 0, w - 1)
        yc = jnp.clip(yi, 0, h - 1)
        bidx = jnp.arange(n).reshape(n, 1, 1)
        vals = data[bidx, :, yc, xc]  # (n, Ho, Wo, c)
        return vals * inb[..., None].astype(vals.dtype)

    v00 = gather(x0, y0)
    v01 = gather(x1, y0)
    v10 = gather(x0, y1)
    v11 = gather(x1, y1)
    wx_ = wx[..., None]
    wy_ = wy[..., None]
    out = (v00 * (1 - wx_) * (1 - wy_) + v01 * wx_ * (1 - wy_)
           + v10 * (1 - wx_) * wy_ + v11 * wx_ * wy_)
    return jnp.transpose(out, (0, 3, 1, 2))


@register("SpatialTransformer")
def _spatial_transformer(attrs, data, loc):
    jnp = _jnp()
    target_shape = tuple(attrs.get("target_shape", (0, 0)))
    grid = _grid_generator({"transform_type": "affine", "target_shape": target_shape}, loc)
    return _bilinear_sampler({}, data, grid)


@register("IdentityAttachKLSparseReg")
def _identity_attach_kl(attrs, data):
    return data


# ---------------------------------------------------------------------------
# symbolic-API input specs (the FListInputNames analog): ordered input names so
# sym.* calls auto-create missing parameter/aux/label Variables like the
# reference's NNVM binding does.
# ---------------------------------------------------------------------------
from .registry import get_op as _get_op

_get_op("FullyConnected").arg_spec = lambda attrs: (
    ["data", "weight"] + ([] if attrs.get("no_bias") else ["bias"]))
_get_op("Convolution").arg_spec = lambda attrs: (
    ["data", "weight"] + ([] if attrs.get("no_bias") else ["bias"]))
_get_op("Deconvolution").arg_spec = lambda attrs: (
    ["data", "weight"] + ([] if attrs.get("no_bias", True) else ["bias"]))
_get_op("BatchNorm").arg_spec = ["data", "gamma", "beta",
                                 "aux:moving_mean", "aux:moving_var"]
_get_op("_contrib_SyncBatchNorm").arg_spec = ["data", "gamma", "beta",
                                              "aux:moving_mean", "aux:moving_var"]
_get_op("CTCLoss").arg_spec = ["data", "label:label"]
_get_op("LayerNorm").arg_spec = ["data", "gamma", "beta"]
_get_op("InstanceNorm").arg_spec = ["data", "gamma", "beta"]
_get_op("Embedding").arg_spec = ["data", "weight"]
_get_op("LeakyReLU").arg_spec = lambda attrs: (
    ["data", "gamma"] if attrs.get("act_type") == "prelu" else ["data"])
_get_op("SoftmaxOutput").arg_spec = ["data", "label:label"]
_get_op("LinearRegressionOutput").arg_spec = ["data", "label:label"]
_get_op("MAERegressionOutput").arg_spec = ["data", "label:label"]
_get_op("LogisticRegressionOutput").arg_spec = ["data", "label:label"]
_get_op("softmax_cross_entropy").arg_spec = ["data", "label:label"]
_get_op("RNN").arg_spec = lambda attrs: (
    ["data", "parameters", "zero:state"]
    + (["zero:state_cell"] if attrs.get("mode", "lstm") == "lstm" else []))


def _prod(t):
    n = 1
    for s in t:
        n *= s
    return n


# param_shape_fn(attrs, in_shapes) -> {input_name: shape} for inputs whose
# shapes are deducible from the data shape + attrs (the reference's bidirectional
# shape inference, infer_graph_attr_pass.cc, restricted to the param slots).
def _fc_param_shapes(attrs, in_shapes):
    data = in_shapes[0]
    nh = int(attrs["num_hidden"])
    flatten = bool(attrs.get("flatten", True))
    in_dim = _prod(data[1:]) if flatten else data[-1]
    out = {"weight": (nh, in_dim)}
    if not attrs.get("no_bias"):
        out["bias"] = (nh,)
    return out


def _conv_param_shapes(attrs, in_shapes):
    data = in_shapes[0]
    nf = int(attrs["num_filter"])
    ng = int(attrs.get("num_group", 1))
    kernel = tuple(attrs["kernel"]) if not isinstance(attrs["kernel"], int) \
        else (attrs["kernel"],)
    layout = attrs.get("layout")
    if layout is not None and not layout.startswith("NC"):
        out = {"weight": (nf,) + kernel + (data[-1] // ng,)}
    else:
        out = {"weight": (nf, data[1] // ng) + kernel}
    if not attrs.get("no_bias"):
        out["bias"] = (nf,)
    return out


def _deconv_param_shapes(attrs, in_shapes):
    data = in_shapes[0]
    nf = int(attrs["num_filter"])
    ng = int(attrs.get("num_group", 1))
    kernel = tuple(attrs["kernel"]) if not isinstance(attrs["kernel"], int) \
        else (attrs["kernel"],)
    out = {"weight": (data[1], nf // ng) + kernel}
    if not attrs.get("no_bias", True):
        out["bias"] = (nf,)
    return out


def _bn_param_shapes(attrs, in_shapes):
    axis = int(attrs.get("axis", 1))
    c = in_shapes[0][axis]
    return {"gamma": (c,), "beta": (c,), "moving_mean": (c,), "moving_var": (c,)}


def _ln_param_shapes(attrs, in_shapes):
    axis = int(attrs.get("axis", -1))
    c = in_shapes[0][axis]
    return {"gamma": (c,), "beta": (c,)}


def _in_param_shapes(attrs, in_shapes):
    c = in_shapes[0][1]
    return {"gamma": (c,), "beta": (c,)}


def _embedding_param_shapes(attrs, in_shapes):
    return {"weight": (int(attrs["input_dim"]), int(attrs["output_dim"]))}


def _prelu_param_shapes(attrs, in_shapes):
    if attrs.get("act_type") == "prelu":
        return {"gamma": (in_shapes[0][1],)}
    return {}


def _softmax_output_label_shape(attrs, in_shapes):
    data = in_shapes[0]
    if attrs.get("multi_output"):
        return {"label": (data[0],) + tuple(data[2:])}
    if attrs.get("preserve_shape"):
        return {"label": tuple(data[:-1])}
    return {"label": (data[0],)}


def _regression_label_shape(attrs, in_shapes):
    return {"label": tuple(in_shapes[0])}


def _rnn_param_shapes(attrs, in_shapes):
    data = in_shapes[0]
    T, B, I = data
    H = int(attrs["state_size"])
    L = int(attrs.get("num_layers", 1))
    D = 2 if attrs.get("bidirectional") else 1
    G = {"rnn_relu": 1, "rnn_tanh": 1, "lstm": 4, "gru": 3}[attrs.get("mode", "lstm")]
    total = 0
    in_size = I
    for layer in range(L):
        for _ in range(D):
            total += G * H * in_size + G * H * H
        in_size = H * D
    total += 2 * L * D * G * H
    out = {"parameters": (total,), "state": (L * D, B, H)}
    if attrs.get("mode") == "lstm":
        out["state_cell"] = (L * D, B, H)
    return out


_get_op("FullyConnected").param_shape_fn = _fc_param_shapes
_get_op("Convolution").param_shape_fn = _conv_param_shapes
_get_op("Deconvolution").param_shape_fn = _deconv_param_shapes
_get_op("BatchNorm").param_shape_fn = _bn_param_shapes
_get_op("_contrib_SyncBatchNorm").param_shape_fn = _bn_param_shapes
_get_op("LayerNorm").param_shape_fn = _ln_param_shapes
_get_op("InstanceNorm").param_shape_fn = _in_param_shapes
_get_op("Embedding").param_shape_fn = _embedding_param_shapes
_get_op("LeakyReLU").param_shape_fn = _prelu_param_shapes
_get_op("SoftmaxOutput").param_shape_fn = _softmax_output_label_shape
_get_op("LinearRegressionOutput").param_shape_fn = _regression_label_shape
_get_op("MAERegressionOutput").param_shape_fn = _regression_label_shape
_get_op("LogisticRegressionOutput").param_shape_fn = _regression_label_shape
_get_op("softmax_cross_entropy").param_shape_fn = _softmax_output_label_shape
_get_op("RNN").param_shape_fn = _rnn_param_shapes
