"""Neural-network layer operators.

TPU-native equivalents of the reference's legacy `OperatorProperty` layer ops
(`src/operator/*-inl.h`): Convolution (reference builds im2col+dot,
`src/operator/convolution-inl.h:90-288` — here a single
`lax.conv_general_dilated`, which XLA tiles straight onto the MXU),
FullyConnected, Pooling, BatchNorm, Dropout, activations, normalizations,
loss-output heads, sequence ops.

Loss heads (SoftmaxOutput etc.) install ``jax.custom_vjp`` so that executor
backward == plain vjp with ones head-gradient reproduces the reference's
special backward semantics (softmax-minus-label, ignore_label, grad
normalization — `src/operator/softmax_output-inl.h`).
"""
from __future__ import annotations

import numpy as np

from ..attrs import Param, ParamSchema
from ..registry import OpDef, register_op, simple_compute


def _jnp():
    import jax.numpy as jnp

    return jnp


def _pair(v, n=2):
    if isinstance(v, int):
        return (v,) * n
    if len(v) == 1:
        return tuple(v) * n
    return tuple(v)


# ---------------------------------------------------------------------------
# shape inference helpers
# ---------------------------------------------------------------------------

def _fc_shape(attrs, in_shapes, aux_shapes):
    dshape = in_shapes[0]
    nh = attrs["num_hidden"]
    flat = attrs.get("flatten", True)
    if flat:
        d = 1
        for s in dshape[1:]:
            d *= s
        wshape = (nh, d)
        out = (dshape[0], nh)
    else:
        wshape = (nh, dshape[-1])
        out = tuple(dshape[:-1]) + (nh,)
    shapes = [dshape, wshape]
    if not attrs.get("no_bias", False):
        shapes.append((nh,))
    return shapes, [out], []


def _conv_shape(attrs, in_shapes, aux_shapes):
    dshape = in_shapes[0]
    nhwc = attrs.get("layout") == "NHWC"
    if nhwc:
        n, h, w, c = dshape
    else:
        n, c, h, w = dshape
    kh, kw = _pair(attrs["kernel"])
    sh, sw = _pair(attrs.get("stride", (1, 1)))
    ph, pw = _pair(attrs.get("pad", (0, 0)))
    dh, dw = _pair(attrs.get("dilate", (1, 1)))
    nf = attrs["num_filter"]
    ng = attrs.get("num_group", 1)
    wshape = (nf, c // ng, kh, kw)
    oh = (h + 2 * ph - dh * (kh - 1) - 1) // sh + 1
    ow = (w + 2 * pw - dw * (kw - 1) - 1) // sw + 1
    shapes = [dshape, wshape]
    if not attrs.get("no_bias", False):
        shapes.append((nf,))
    oshape = (n, oh, ow, nf) if nhwc else (n, nf, oh, ow)
    return shapes, [oshape], []


def _deconv_pad(attrs, h, w):
    """Resolve Deconvolution pad/adj; ``target_shape`` overrides both so the
    output spatial dims come out exactly as requested (reference:
    deconvolution-inl.h InferPad — pad = ceil(d/2), adj = d%2 where
    d = stride*(in-1)+kernel-target)."""
    kh, kw = _pair(attrs["kernel"])
    sh, sw = _pair(attrs.get("stride", (1, 1)))
    target = tuple(attrs.get("target_shape", ()) or ())
    if target:
        th, tw = _pair(target)
        dh = (h - 1) * sh + kh - th
        dw = (w - 1) * sw + kw - tw
        if dh < 0 or dw < 0:
            raise ValueError(
                "Deconvolution target_shape %s is larger than the maximum "
                "output %s for input %s" % (target, ((h - 1) * sh + kh,
                                                     (w - 1) * sw + kw),
                                            (h, w)))
        return (dh + 1) // 2, (dw + 1) // 2, dh % 2, dw % 2
    ph, pw = _pair(attrs.get("pad", (0, 0)))
    ah, aw = _pair(attrs.get("adj", (0, 0)))
    return ph, pw, ah, aw


def _deconv_shape(attrs, in_shapes, aux_shapes):
    dshape = in_shapes[0]
    n, c, h, w = dshape
    kh, kw = _pair(attrs["kernel"])
    sh, sw = _pair(attrs.get("stride", (1, 1)))
    ph, pw, ah, aw = _deconv_pad(attrs, h, w)
    nf = attrs["num_filter"]
    ng = attrs.get("num_group", 1)
    wshape = (c, nf // ng, kh, kw)
    oh = (h - 1) * sh - 2 * ph + kh + ah
    ow = (w - 1) * sw - 2 * pw + kw + aw
    shapes = [dshape, wshape]
    if not attrs.get("no_bias", True):
        shapes.append((nf,))
    return shapes, [(n, nf, oh, ow)], []


def _bn_type(attrs, in_types, aux_types):
    """Output follows data; statistics (gamma/beta/mean/var + moving aux)
    stay float32 for low-precision training (the cuDNN-BN convention)."""
    f32 = np.dtype(np.float32)
    d = in_types[0] if in_types[0] is not None else f32
    return [d, f32, f32], [d, f32, f32], [f32, f32]


def _bn_shape(attrs, in_shapes, aux_shapes):
    dshape = in_shapes[0]
    axis = attrs.get("axis", 1) if len(dshape) > 1 else 0
    c = dshape[axis]
    return [dshape, (c,), (c,)], [dshape, (c,), (c,)], [(c,), (c,)]


def register_all():
    jnp = _jnp()
    import jax
    from jax import lax

    # ---------------- Activation ----------------
    def _activation(attrs, x):
        act = attrs.get("act_type", "relu")
        if act == "relu":
            return jnp.maximum(x, 0)
        if act == "sigmoid":
            return jax.nn.sigmoid(x)
        if act == "tanh":
            return jnp.tanh(x)
        if act == "softrelu":
            return jnp.logaddexp(x, 0.0)
        if act == "softsign":
            return x / (1 + jnp.abs(x))
        if act == "silu":
            return jax.nn.silu(x)
        raise ValueError("unknown act_type %s" % act)

    register_op(OpDef("Activation", simple_compute(_activation),
                      schema=ParamSchema(Param("act_type", str, required=True,
                                               enum=("relu", "sigmoid", "tanh",
                                                     "softrelu", "softsign",
                                                     "silu"))),
                      num_inputs=1, hint="activation"))

    def _leaky_relu(attrs, x, *rest):
        act = attrs.get("act_type", "leaky")
        slope = attrs.get("slope", 0.25)
        if act == "leaky" or act == "rrelu":
            return jnp.where(x > 0, x, slope * x)
        if act == "elu":
            return jnp.where(x > 0, x, slope * (jnp.exp(x) - 1))
        if act == "prelu":
            gamma = rest[0].reshape((1, -1) + (1,) * (x.ndim - 2))
            return jnp.where(x > 0, x, gamma * x)
        raise ValueError(act)

    def _lrelu_shape(attrs, in_shapes, aux_shapes):
        d = in_shapes[0]
        if attrs.get("act_type", "leaky") == "prelu":
            return [d, (d[1],)], [d], []
        return [d], [d], []

    register_op(OpDef(
        "LeakyReLU", simple_compute(_leaky_relu),
        schema=ParamSchema(
            Param("act_type", str, default="leaky"),
            Param("slope", float, default=0.25),
            Param("lower_bound", float, default=0.125),
            Param("upper_bound", float, default=0.334)),
        num_inputs=lambda a: 2 if a.get("act_type") == "prelu" else 1,
        arguments=lambda a: ["data", "gamma"] if a.get("act_type") == "prelu" else ["data"],
        infer_shape=_lrelu_shape, hint="leakyrelu"))

    def _softmax_act(attrs, x):
        if attrs.get("mode", "instance") == "channel":
            return jax.nn.softmax(x, axis=1)
        return jax.nn.softmax(x.reshape(x.shape[0], -1), axis=-1).reshape(x.shape)

    register_op(OpDef("SoftmaxActivation", simple_compute(_softmax_act),
                      schema=ParamSchema(Param("mode", str, default="instance")),
                      num_inputs=1, hint="softmaxactivation"))

    # ---------------- FullyConnected ----------------
    def _fc(attrs, inputs, aux, octx):
        data, weight, *bias = inputs
        flatten = attrs.get("flatten", True)
        # flatten=False contracts the last dim of a (B, T, K) operand as ONE
        # (B*T, K) matmul: the reshape fuses into the element-wise op that
        # made the operand, and under a mesh the weight gradient is
        # all-reduced once over the axes the rows are sharded on.  Straight
        # after attention the operand is taken as it comes: with that dot
        # merged too XLA:TPU lays the whole residual stream out as rows and
        # the LM step is 9 % slower (PERF.md section 6, PR 32)
        merge = not flatten \
            and "dot_product_attention" not in octx.producers
        if flatten:
            x = data.reshape(data.shape[0], -1)
        elif merge:
            x = data.reshape(-1, data.shape[-1])
        else:
            x = data
        out = jnp.dot(x, weight.T)
        if bias:
            out = out + bias[0]
        if merge:
            out = out.reshape(data.shape[:-1] + out.shape[-1:])
        return [out], list(aux)

    fc_schema = ParamSchema(Param("num_hidden", int, required=True),
                            Param("no_bias", bool, default=False),
                            Param("flatten", bool, default=True))
    register_op(OpDef(
        "FullyConnected", _fc, schema=fc_schema,
        num_inputs=lambda a: 2 if a.get("no_bias") else 3,
        arguments=lambda a: ["data", "weight"] if a.get("no_bias")
        else ["data", "weight", "bias"],
        infer_shape=_fc_shape, hint="fullyconnected"))

    # ---------------- Convolution ----------------
    conv_schema = ParamSchema(
        Param("kernel", "shape", required=True),
        Param("stride", "shape", default=(1, 1)),
        Param("dilate", "shape", default=(1, 1)),
        Param("pad", "shape", default=(0, 0)),
        Param("num_filter", int, required=True),
        Param("num_group", int, default=1),
        Param("workspace", int, default=1024),
        Param("no_bias", bool, default=False),
        Param("cudnn_tune", str, default=None),
        Param("cudnn_off", bool, default=False),
        Param("layout", str, default=None))

    def _conv(attrs, data, weight, *bias):
        sh, sw = _pair(attrs.get("stride", (1, 1)))
        ph, pw = _pair(attrs.get("pad", (0, 0)))
        dh, dw = _pair(attrs.get("dilate", (1, 1)))
        ng = attrs.get("num_group", 1)
        nhwc = attrs.get("layout") == "NHWC"
        # weight stays OIHW in both layouts (checkpoint compatibility);
        # NHWC activations avoid layout churn around the Pallas fused ops
        dims = ("NHWC", "OIHW", "NHWC") if nhwc else ("NCHW", "OIHW", "NCHW")
        out = lax.conv_general_dilated(
            data, weight, window_strides=(sh, sw),
            padding=((ph, ph), (pw, pw)),
            rhs_dilation=(dh, dw),
            dimension_numbers=dims,
            feature_group_count=ng,
            preferred_element_type=jnp.float32 if data.dtype == jnp.float32 else None)
        if bias:
            bshape = (1, 1, 1, -1) if nhwc else (1, -1, 1, 1)
            out = out + bias[0].reshape(bshape)
        return out.astype(data.dtype)

    register_op(OpDef(
        "Convolution", simple_compute(_conv), schema=conv_schema,
        num_inputs=lambda a: 2 if a.get("no_bias") else 3,
        arguments=lambda a: ["data", "weight"] if a.get("no_bias")
        else ["data", "weight", "bias"],
        infer_shape=_conv_shape, hint="convolution"))

    # ---------------- Deconvolution ----------------
    deconv_schema = ParamSchema(
        Param("kernel", "shape", required=True),
        Param("stride", "shape", default=(1, 1)),
        Param("pad", "shape", default=(0, 0)),
        Param("adj", "shape", default=(0, 0)),
        Param("target_shape", "shape", default=()),
        Param("num_filter", int, required=True),
        Param("num_group", int, default=1),
        Param("workspace", int, default=512),
        Param("no_bias", bool, default=True),
        Param("cudnn_tune", str, default=None),
        Param("cudnn_off", bool, default=False),
        Param("layout", str, default=None))

    def _deconv(attrs, data, weight, *bias):
        kh, kw = _pair(attrs["kernel"])
        sh, sw = _pair(attrs.get("stride", (1, 1)))
        ph, pw, ah, aw = _deconv_pad(attrs, data.shape[2], data.shape[3])
        ng = attrs.get("num_group", 1)
        # deconv = gradient of conv: dilate lhs by stride, full-minus-pad padding,
        # kernel flipped spatially and IO-transposed (weight is (C, F/g, kh, kw))
        w = jnp.flip(weight, axis=(-2, -1))
        if ng > 1:
            c, fpg = w.shape[0], w.shape[1]
            w = w.reshape(ng, c // ng, fpg, kh, kw)
            w = jnp.moveaxis(w, 2, 1).reshape(ng * fpg, c // ng, kh, kw)
        else:
            w = jnp.swapaxes(w, 0, 1)
        out = lax.conv_general_dilated(
            data, w, window_strides=(1, 1),
            padding=((kh - 1 - ph, kh - 1 - ph + ah), (kw - 1 - pw, kw - 1 - pw + aw)),
            lhs_dilation=(sh, sw),
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            feature_group_count=ng)
        if bias:
            out = out + bias[0].reshape(1, -1, 1, 1)
        return out.astype(data.dtype)

    register_op(OpDef(
        "Deconvolution", simple_compute(_deconv), schema=deconv_schema,
        num_inputs=lambda a: 2 if a.get("no_bias", True) else 3,
        arguments=lambda a: ["data", "weight"] if a.get("no_bias", True)
        else ["data", "weight", "bias"],
        infer_shape=_deconv_shape, hint="deconvolution"))

    # ---------------- Pooling ----------------
    pool_schema = ParamSchema(
        Param("kernel", "shape", required=True),
        Param("pool_type", str, default="max", enum=("max", "avg", "sum")),
        Param("global_pool", bool, default=False),
        Param("pooling_convention", str, default="valid"),
        Param("stride", "shape", default=(1, 1)),
        Param("pad", "shape", default=(0, 0)),
        Param("layout", str, default=None,
              doc="NCHW (default) or NHWC (match Convolution layout)"))

    def _pool_geometry(attrs, h, w):
        kh, kw = _pair(attrs["kernel"])
        sh, sw = _pair(attrs.get("stride", (1, 1)))
        ph, pw = _pair(attrs.get("pad", (0, 0)))
        if attrs.get("global_pool", False):
            return (h, w), (1, 1), (0, 0, 0, 0), (1, 1)
        if attrs.get("pooling_convention", "valid") == "full":
            oh = int(np.ceil((h + 2 * ph - kh) / sh)) + 1
            ow = int(np.ceil((w + 2 * pw - kw) / sw)) + 1
        else:
            oh = (h + 2 * ph - kh) // sh + 1
            ow = (w + 2 * pw - kw) // sw + 1
        eh = max(0, (oh - 1) * sh + kh - h - 2 * ph)
        ew = max(0, (ow - 1) * sw + kw - w - 2 * pw)
        return (kh, kw), (sh, sw), (ph, ph + eh, pw, pw + ew), (oh, ow)

    def _pooling(attrs, x):
        nhwc = attrs.get("layout") == "NHWC"
        if nhwc:
            n, h, w, c = x.shape
        else:
            n, c, h, w = x.shape
        (kh, kw), (sh, sw), (plo_h, phi_h, plo_w, phi_w), _ = _pool_geometry(attrs, h, w)
        ptype = attrs.get("pool_type", "max")
        if nhwc:
            pads = ((0, 0), (plo_h, phi_h), (plo_w, phi_w), (0, 0))
            window = (1, kh, kw, 1)
            strides = (1, sh, sw, 1)
        else:
            pads = ((0, 0), (0, 0), (plo_h, phi_h), (plo_w, phi_w))
            window = (1, 1, kh, kw)
            strides = (1, 1, sh, sw)
        if ptype == "max":
            init = -np.inf if jnp.issubdtype(x.dtype, jnp.floating) \
                else np.iinfo(np.dtype(x.dtype)).min
            return lax.reduce_window(x, init, lax.max, window, strides, pads)
        out = lax.reduce_window(x, 0.0 if jnp.issubdtype(x.dtype, jnp.floating)
                                else 0, lax.add, window, strides, pads)
        if ptype == "avg":
            out = out / (kh * kw)
        return out

    register_op(OpDef("Pooling", simple_compute(_pooling), schema=pool_schema,
                      num_inputs=1, hint="pooling"))

    # ---------------- BatchNorm ----------------
    bn_schema = ParamSchema(
        Param("eps", float, default=1e-3),
        Param("momentum", float, default=0.9),
        Param("fix_gamma", bool, default=True),
        Param("use_global_stats", bool, default=False),
        Param("output_mean_var", bool, default=False),
        Param("axis", int, default=1,
              doc="channel axis (1 = NCHW default; -1/3 for NHWC data, "
                  "e.g. downstream of Convolution(layout='NHWC'))"))

    def _bn_train_core(eps, caxis):
        """Training-mode BN as an explicit custom_vjp.

        The autodiff-derived backward of the naive formulation saves the
        float32-upcast activation as a residual — at bf16 compute that
        doubles BN's HBM traffic, and this op is memory-bound.  Here the
        residuals are the *compute-dtype* input plus the (C,)-sized fp32
        statistics; both passes do elementwise math in the compute dtype
        with only the channel reductions in fp32.
        """

        def stats(x, center):
            # mean and variance in ONE fused reduction pass: jnp.var's
            # two-pass formulation costs an extra full read of x per BN —
            # measured 9% of the whole ResNet-50 step on the bench chip
            # (benchmarks/ROOFLINE.md).  The shifted-data formulation
            # var = E[(x-c)^2] - (mean-c)^2 centers on c = moving_mean: a
            # CONSTANT, so the subtraction and both reductions fuse into
            # x's producer (a data-dependent center — e.g. a subsample
            # mean — would serialize a second pass over x, giving the
            # two-pass cost back).  Once the moving mean has warmed toward
            # the batch mean the fp32 sums stay O(var).  The cold-start
            # hole (moving_mean at its zero init + |mean| >> std ->
            # catastrophic cancellation, advisor round-4) is closed by a
            # DETECTED fallback: when the recovered variance is within
            # fp32 cancellation noise of the shifted mean square, a
            # lax.cond pays one corrective pass with the exact batch mean
            # as center.  The predicate only fires during those early
            # pathological steps, so the steady-state cost is the fused
            # single pass.
            red = tuple(i for i in range(x.ndim) if i != caxis)
            bshape = tuple(x.shape[caxis] if i == caxis else 1
                           for i in range(x.ndim))
            if not red:
                z = jnp.zeros(x.shape[caxis], jnp.float32)
                return x.astype(jnp.float32).reshape(-1), z
            xc = x.astype(jnp.float32) - center.reshape(bshape)
            mc = jnp.mean(xc, axis=red)
            var_fast = jnp.maximum(jnp.mean(jnp.square(xc), axis=red)
                                   - jnp.square(mc), 0.0)
            mean = mc + center
            # fp32 cancellation noise is ~1e-7 * (mean-c)^2; refine when it
            # could exceed ~1% of the recovered variance AND the variance
            # it may have destroyed matters relative to eps (noise below
            # eps can't move rsqrt(var + eps) meaningfully).  The second
            # term also retires the guard for legitimately-zero-variance
            # channels (dead ReLU features, constant pads): as the moving
            # mean converges onto them, mc^2 falls below eps/1e-7 and the
            # refine stops firing instead of paying the second pass on
            # every step forever.
            mc2 = jnp.square(mc)
            bad = jnp.any((var_fast <= 1e-5 * mc2) & (1e-7 * mc2 > eps))

            def refine(_):
                m = jax.lax.stop_gradient(mean).reshape(bshape)
                return jnp.mean(jnp.square(x.astype(jnp.float32) - m),
                                axis=red)

            var = jax.lax.cond(bad, refine, lambda _: var_fast, None)
            return mean, var

        def apply(x, gamma, beta, mean, inv):
            bshape = tuple(x.shape[caxis] if i == caxis else 1
                           for i in range(x.ndim))
            scale = (inv * gamma.astype(jnp.float32)).astype(x.dtype)
            shift = (beta.astype(jnp.float32)
                     - mean * inv * gamma.astype(jnp.float32)).astype(x.dtype)
            return x * scale.reshape(bshape) + shift.reshape(bshape)

        @jax.custom_vjp
        def bn(x, gamma, beta, center):
            mean, var = stats(x, center)
            inv = jax.lax.rsqrt(var + eps)
            return apply(x, gamma, beta, mean, inv), mean, var

        def bn_fwd(x, gamma, beta, center):
            mean, var = stats(x, center)
            inv = jax.lax.rsqrt(var + eps)
            return (apply(x, gamma, beta, mean, inv), mean, var), \
                (x, gamma, mean, inv)

        def bn_bwd(res, cts):
            x, gamma, mean, inv = res
            dy, dmean_ct, dvar_ct = cts
            red = tuple(i for i in range(x.ndim) if i != caxis)
            bshape = tuple(x.shape[caxis] if i == caxis else 1
                           for i in range(x.ndim))
            n = 1
            for i in red:
                n *= x.shape[i]
            xmu = x.astype(jnp.float32) - mean.reshape(bshape)
            xhat = xmu * inv.reshape(bshape)
            dy32 = dy.astype(jnp.float32)
            dbeta = jnp.sum(dy32, axis=red)
            dgamma = jnp.sum(dy32 * xhat, axis=red)
            g32 = gamma.astype(jnp.float32)
            dx = (inv * g32).reshape(bshape) \
                * (dy32 - (dbeta / n).reshape(bshape)
                   - xhat * (dgamma / n).reshape(bshape))
            # the mean/var outputs are separately consumable (output_mean_var,
            # user head_grads); fold their cotangents in as well
            dx = dx + (dmean_ct / n).reshape(bshape) \
                + (dvar_ct * 2.0 / n).reshape(bshape) * xmu
            return dx.astype(x.dtype), dgamma.astype(gamma.dtype), \
                dbeta.astype(gamma.dtype), jnp.zeros_like(mean)

        bn.defvjp(bn_fwd, bn_bwd)
        return bn

    def _batchnorm(attrs, inputs, aux, octx):
        data, gamma, beta = inputs
        moving_mean, moving_var = aux
        eps = attrs.get("eps", 1e-3)
        momentum = attrs.get("momentum", 0.9)
        caxis = attrs.get("axis", 1) if data.ndim > 1 else 0
        if caxis < 0:
            caxis += data.ndim
        bshape = tuple(data.shape[caxis] if i == caxis else 1 for i in range(data.ndim))
        if attrs.get("fix_gamma", True):
            gamma = jax.lax.stop_gradient(jnp.ones_like(gamma))
        use_global = attrs.get("use_global_stats", False) or not octx.is_train
        if use_global:
            mean, var = moving_mean, moving_var
            new_mm, new_mv = moving_mean, moving_var
            inv = jax.lax.rsqrt(var + eps)
            scale = (inv * gamma.astype(jnp.float32)).astype(data.dtype)
            shift = (beta.astype(jnp.float32)
                     - mean * inv * gamma.astype(jnp.float32)).astype(data.dtype)
            out = data * scale.reshape(bshape) + shift.reshape(bshape)
        else:
            out, mean, var = _bn_train_core(eps, caxis)(
                data, gamma, beta,
                jax.lax.stop_gradient(moving_mean.astype(jnp.float32)))
            new_mm = momentum * moving_mean + (1 - momentum) * jax.lax.stop_gradient(mean)
            new_mv = momentum * moving_var + (1 - momentum) * jax.lax.stop_gradient(var)
        return [out, mean, var], [new_mm, new_mv]

    register_op(OpDef(
        "BatchNorm", _batchnorm, schema=bn_schema,
        num_inputs=3, num_outputs=3,
        num_visible_outputs=lambda a: 3 if a.get("output_mean_var") else 1,
        arguments=["data", "gamma", "beta"],
        outputs=["output", "mean", "var"],
        aux=["moving_mean", "moving_var"],
        infer_shape=_bn_shape, infer_type=_bn_type, needs_train=True,
        hint="batchnorm"))

    # ---------------- Dropout ----------------
    def _dropout(attrs, inputs, aux, octx):
        (x,) = inputs
        p = attrs.get("p", 0.5)
        if not octx.is_train or p <= 0.0:
            return [x, jnp.ones_like(x)], []
        keep = 1.0 - p
        mask = jax.random.bernoulli(octx.rng, keep, x.shape).astype(x.dtype) / keep
        return [x * mask, mask], []

    register_op(OpDef(
        "Dropout", _dropout,
        schema=ParamSchema(Param("p", float, default=0.5),
                           Param("mode", str, default="training")),
        num_inputs=1, num_outputs=2, num_visible_outputs=1,
        outputs=["output", "mask"],
        needs_rng=True, needs_train=True, hint="dropout"))

    # ---------------- LRN ----------------
    def _lrn(attrs, x):
        n = attrs["nsize"]
        alpha = attrs.get("alpha", 1e-4)
        beta = attrs.get("beta", 0.75)
        knorm = attrs.get("knorm", 2.0)
        sq = jnp.square(x)
        half = n // 2
        padded = jnp.pad(sq, ((0, 0), (half, half), (0, 0), (0, 0)))
        win = sum(padded[:, i:i + x.shape[1]] for i in range(n))
        return x / jnp.power(knorm + (alpha / n) * win, beta)

    register_op(OpDef("LRN", simple_compute(_lrn),
                      schema=ParamSchema(Param("nsize", int, required=True),
                                         Param("alpha", float, default=1e-4),
                                         Param("beta", float, default=0.75),
                                         Param("knorm", float, default=2.0)),
                      num_inputs=1, hint="lrn"))

    # ---------------- InstanceNorm ----------------
    def _instance_norm(attrs, x, gamma, beta):
        eps = attrs.get("eps", 1e-3)
        red = tuple(range(2, x.ndim))
        mean = jnp.mean(x, axis=red, keepdims=True)
        var = jnp.var(x, axis=red, keepdims=True)
        g = gamma.reshape((1, -1) + (1,) * (x.ndim - 2))
        b = beta.reshape((1, -1) + (1,) * (x.ndim - 2))
        return (x - mean) / jnp.sqrt(var + eps) * g + b

    def _in_shape(attrs, in_shapes, aux_shapes):
        d = in_shapes[0]
        return [d, (d[1],), (d[1],)], [d], []

    register_op(OpDef("InstanceNorm", simple_compute(_instance_norm),
                      schema=ParamSchema(Param("eps", float, default=1e-3)),
                      num_inputs=3, arguments=["data", "gamma", "beta"],
                      infer_shape=_in_shape, hint="instancenorm"))

    # ---------------- RMSNorm ----------------
    def _rms_norm(attrs, x, gamma):
        # x * rsqrt(mean(x^2) + eps) * gamma over the last axis; the mean
        # is taken in float32 whatever the stream's dtype
        x32 = x.astype(jnp.float32)
        inv = lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                        + attrs.get("eps", 1e-5))
        return (x32 * inv).astype(x.dtype) * gamma

    def _rms_shape(attrs, in_shapes, aux_shapes):
        d = in_shapes[0]
        return [d, (d[-1],)], [d], []

    register_op(OpDef("RMSNorm", simple_compute(_rms_norm),
                      schema=ParamSchema(Param("eps", float, default=1e-5)),
                      num_inputs=2, arguments=["data", "gamma"],
                      infer_shape=_rms_shape, hint="rmsnorm"))

    # ---------------- L2Normalization ----------------
    def _l2norm(attrs, x):
        eps = attrs.get("eps", 1e-10)
        mode = attrs.get("mode", "instance")
        if mode == "instance":
            red, keep = tuple(range(1, x.ndim)), True
        elif mode == "channel":
            red, keep = (1,), True
        else:  # spatial
            red, keep = tuple(range(2, x.ndim)), True
        norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=red, keepdims=keep) + eps)
        return x / norm

    register_op(OpDef("L2Normalization", simple_compute(_l2norm),
                      schema=ParamSchema(Param("eps", float, default=1e-10),
                                         Param("mode", str, default="instance")),
                      num_inputs=1, hint="l2normalization"))

    # ---------------- loss heads ----------------
    _register_loss_heads()

    # ---------------- Pad ----------------
    def _pad(attrs, x):
        pw = attrs["pad_width"]
        pads = [(pw[2 * i], pw[2 * i + 1]) for i in range(x.ndim)]
        mode = attrs.get("mode", "constant")
        if mode == "constant":
            return jnp.pad(x, pads, constant_values=attrs.get("constant_value", 0.0))
        return jnp.pad(x, pads, mode="edge" if mode == "edge" else "reflect")

    register_op(OpDef("Pad", simple_compute(_pad),
                      schema=ParamSchema(Param("mode", str, default="constant"),
                                         Param("pad_width", "shape", required=True),
                                         Param("constant_value", float, default=0.0)),
                      num_inputs=1, hint="pad"),
                aliases=["pad"])

    # ---------------- UpSampling ----------------
    def _upsampling(attrs, *xs):
        scale = attrs["scale"]
        stype = attrs.get("sample_type", "nearest")
        x = xs[0]
        if stype == "nearest":
            return jnp.repeat(jnp.repeat(x, scale, axis=2), scale, axis=3)
        # bilinear: resize (the learnable-deconv variant is Deconvolution-backed)
        import jax.image

        n, c, h, w = x.shape
        return jax.image.resize(x, (n, c, h * scale, w * scale), method="bilinear")

    register_op(OpDef("UpSampling", simple_compute(_upsampling),
                      schema=ParamSchema(Param("scale", int, required=True),
                                         Param("num_filter", int, default=0),
                                         Param("sample_type", str, default="nearest"),
                                         Param("multi_input_mode", str, default="concat"),
                                         Param("num_args", int, default=1),
                                         Param("workspace", int, default=512)),
                      num_inputs=lambda a: a.get("num_args", 1),
                      key_var_num_args="num_args", hint="upsampling"))

    # ---------------- Sequence ops (axis 0 = time, TNC) ----------------
    def _seq_last(attrs, data, *seq_len):
        if attrs.get("use_sequence_length", False) and seq_len:
            idx = (seq_len[0] - 1).astype(jnp.int32)
            return data[idx, jnp.arange(data.shape[1])]
        return data[-1]

    seq_schema = ParamSchema(Param("use_sequence_length", bool, default=False),
                             Param("value", float, default=0.0),
                             Param("axis", int, default=0))

    def _seq_args(a):
        return ["data", "sequence_length"] if a.get("use_sequence_length") else ["data"]

    def _seq_n(a):
        return 2 if a.get("use_sequence_length") else 1

    def _seqlast_shape(attrs, in_shapes, aux_shapes):
        d = in_shapes[0]
        out = tuple(d[1:])
        if attrs.get("use_sequence_length"):
            return [d, (d[1],)], [out], []
        return [d], [out], []

    register_op(OpDef("SequenceLast", simple_compute(_seq_last), schema=seq_schema,
                      num_inputs=_seq_n, arguments=_seq_args,
                      infer_shape=_seqlast_shape, hint="sequencelast"))

    def _seq_mask(attrs, data, *seq_len):
        if not attrs.get("use_sequence_length", False) or not seq_len:
            return data
        T = data.shape[0]
        mask = jnp.arange(T)[:, None] < seq_len[0][None, :].astype(jnp.int32)
        mask = mask.reshape(mask.shape + (1,) * (data.ndim - 2))
        return jnp.where(mask, data, attrs.get("value", 0.0))

    register_op(OpDef("SequenceMask", simple_compute(_seq_mask), schema=seq_schema,
                      num_inputs=_seq_n, arguments=_seq_args, hint="sequencemask"))

    def _seq_reverse(attrs, data, *seq_len):
        if attrs.get("use_sequence_length", False) and seq_len:
            T = data.shape[0]
            sl = seq_len[0].astype(jnp.int32)
            t = jnp.arange(T)[:, None]
            src = jnp.where(t < sl[None, :], sl[None, :] - 1 - t, t)
            return jnp.take_along_axis(
                data, src.reshape(src.shape + (1,) * (data.ndim - 2)), axis=0)
        return jnp.flip(data, axis=0)

    register_op(OpDef("SequenceReverse", simple_compute(_seq_reverse), schema=seq_schema,
                      num_inputs=_seq_n, arguments=_seq_args, hint="sequencereverse"))

    # IdentityAttachKLSparseReg: forward identity (+ sparsity KL penalty on grad)
    register_op(OpDef("IdentityAttachKLSparseReg",
                      simple_compute(lambda attrs, x: x + 0),
                      schema=ParamSchema(Param("sparseness_target", float, default=0.1),
                                         Param("penalty", float, default=0.001),
                                         Param("momentum", float, default=0.9)),
                      num_inputs=1, hint="identityattachklsparsereg"))


def _register_loss_heads():
    import jax
    import jax.numpy as jnp

    # ---- SoftmaxOutput ----
    sm_schema = ParamSchema(
        Param("grad_scale", float, default=1.0),
        Param("ignore_label", float, default=-1.0),
        Param("multi_output", bool, default=False),
        Param("use_ignore", bool, default=False),
        Param("preserve_shape", bool, default=False),
        Param("normalization", str, default="null"),
        Param("out_grad", bool, default=False))

    def _softmax_output(attrs, inputs, aux, octx):
        data, label = inputs
        multi = attrs.get("multi_output", False)
        preserve = attrs.get("preserve_shape", False)

        def fwd_fn(d):
            # normalize in fp32: exp/sum in bf16 would be the one numerically
            # fragile spot in an otherwise-bf16 graph
            d32 = d.astype(jnp.float32)
            if multi:
                out = jax.nn.softmax(d32, axis=1)
            elif preserve:
                out = jax.nn.softmax(d32, axis=-1)
            else:
                out = jax.nn.softmax(d32.reshape(d.shape[0], -1),
                                     axis=-1).reshape(d.shape)
            return out.astype(d.dtype)

        @jax.custom_vjp
        def head(d, l):
            return fwd_fn(d)

        def head_fwd(d, l):
            out = fwd_fn(d)
            return out, (out, l)

        def head_bwd(res, g):
            out, l = res
            scale = attrs.get("grad_scale", 1.0)
            norm = attrs.get("normalization", "null")
            use_ignore = attrs.get("use_ignore", False)
            ignore = attrs.get("ignore_label", -1.0)
            if multi:
                # data (N, C, ...); label (N, ...)
                li = l.astype(jnp.int32)
                onehot = jax.nn.one_hot(li, out.shape[1], dtype=out.dtype, axis=1)
                grad = out - onehot
                mask = (l != ignore) if use_ignore else jnp.ones(l.shape, bool)
                grad = grad * mask[:, None].astype(out.dtype) if use_ignore else grad
                valid = jnp.sum(mask.astype(out.dtype))
            else:
                flat = out.reshape(out.shape[0], -1) if not preserve else out
                lflat = l.reshape(flat.shape[:-1]).astype(jnp.int32)
                onehot = jax.nn.one_hot(lflat, flat.shape[-1], dtype=out.dtype)
                grad = flat - onehot
                mask = (l.reshape(lflat.shape) != ignore) if use_ignore \
                    else jnp.ones(lflat.shape, bool)
                if use_ignore:
                    grad = grad * mask[..., None].astype(out.dtype)
                valid = jnp.sum(mask.astype(out.dtype))
                grad = grad.reshape(out.shape)
            if norm == "batch":
                grad = grad / out.shape[0]
            elif norm == "valid":
                grad = grad / jnp.maximum(valid, 1.0)
            return (grad * scale, jnp.zeros_like(l))

        head.defvjp(head_fwd, head_bwd)
        return [head(data, label)], []

    def _softmax_out_shape(attrs, in_shapes, aux_shapes):
        d = in_shapes[0]
        if attrs.get("multi_output", False):
            lshape = (d[0],) + tuple(d[2:])
        elif attrs.get("preserve_shape", False):
            lshape = tuple(d[:-1])
        else:
            lshape = (d[0],)
        return [d, lshape], [d], []

    register_op(OpDef("SoftmaxOutput", _softmax_output, schema=sm_schema,
                      num_inputs=2, arguments=["data", "label"],
                      infer_shape=_softmax_out_shape, hint="softmaxoutput"),
                aliases=["Softmax"])

    # ---- regression heads ----
    reg_schema = ParamSchema(Param("grad_scale", float, default=1.0))

    def _make_regression(name, fwd, grad):
        def fcompute(attrs, inputs, aux, octx):
            data, label = inputs
            scale = attrs.get("grad_scale", 1.0)

            @jax.custom_vjp
            def head(d, l):
                return fwd(d)

            def head_fwd(d, l):
                return fwd(d), (fwd(d), l)

            def head_bwd(res, g):
                out, l = res
                n = 1
                for s in out.shape[1:]:
                    n *= s
                return (grad(out, l.reshape(out.shape)) * scale / n,
                        jnp.zeros_like(l))

            head.defvjp(head_fwd, head_bwd)
            return [head(data, label)], []

        def _reg_shape(attrs, in_shapes, aux_shapes):
            d = in_shapes[0]
            return [d, d], [d], []

        register_op(OpDef(name, fcompute, schema=reg_schema, num_inputs=2,
                          arguments=["data", "label"], infer_shape=_reg_shape,
                          hint=name.lower()))

    _make_regression("LinearRegressionOutput", lambda d: d, lambda o, l: o - l)
    _make_regression("LogisticRegressionOutput", lambda d: jax.nn.sigmoid(d),
                     lambda o, l: o - l)
    _make_regression("MAERegressionOutput", lambda d: d,
                     lambda o, l: jnp.sign(o - l))

    # ---- MakeLoss ----
    ml_schema = ParamSchema(Param("grad_scale", float, default=1.0),
                            Param("valid_thresh", float, default=0.0),
                            Param("normalization", str, default="null"))

    def _make_loss(attrs, inputs, aux, octx):
        (data,) = inputs
        scale = attrs.get("grad_scale", 1.0)
        norm = attrs.get("normalization", "null")

        @jax.custom_vjp
        def head(d):
            return d

        def head_fwd(d):
            return d, d

        def head_bwd(d, g):
            grad = jnp.full_like(d, scale)
            if norm == "batch":
                grad = grad / d.shape[0]
            elif norm == "valid":
                valid = jnp.sum((d > attrs.get("valid_thresh", 0.0)).astype(d.dtype))
                grad = grad / jnp.maximum(valid, 1.0)
            return (grad,)

        head.defvjp(head_fwd, head_bwd)
        return [head(data)], []

    register_op(OpDef("MakeLoss", _make_loss, schema=ml_schema, num_inputs=1,
                      hint="makeloss"),
                aliases=["make_loss"])

    # ---- SVMOutput ----
    svm_schema = ParamSchema(Param("margin", float, default=1.0),
                             Param("regularization_coefficient", float, default=1.0),
                             Param("use_linear", bool, default=False))

    def _svm_output(attrs, inputs, aux, octx):
        data, label = inputs
        margin = attrs.get("margin", 1.0)
        reg = attrs.get("regularization_coefficient", 1.0)
        linear = attrs.get("use_linear", False)

        @jax.custom_vjp
        def head(d, l):
            return d

        def head_fwd(d, l):
            return d, (d, l)

        def head_bwd(res, g):
            d, l = res
            li = l.astype(jnp.int32)
            onehot = jax.nn.one_hot(li, d.shape[1], dtype=d.dtype)
            score_y = jnp.take_along_axis(d, li[:, None], axis=1)
            if linear:  # L1-SVM subgradient
                viol = ((margin - (2 * onehot - 1) * d) > 0).astype(d.dtype)
                grad = -(2 * onehot - 1) * viol * reg
            else:  # L2-SVM
                m = jnp.maximum(0.0, margin - (2 * onehot - 1) * d)
                grad = -2.0 * (2 * onehot - 1) * m * reg
            del score_y
            return (grad, jnp.zeros_like(l))

        head.defvjp(head_fwd, head_bwd)
        return [head(data, label)], []

    def _svm_shape(attrs, in_shapes, aux_shapes):
        d = in_shapes[0]
        return [d, (d[0],)], [d], []

    register_op(OpDef("SVMOutput", _svm_output, schema=svm_schema, num_inputs=2,
                      arguments=["data", "label"], infer_shape=_svm_shape,
                      hint="svmoutput"))
