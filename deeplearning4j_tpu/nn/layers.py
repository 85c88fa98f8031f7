"""Runtime layers: pure ``init``/``apply`` functions per layer config.

Reference parity:
  * org/deeplearning4j/nn/layers/** — each reference layer hand-implements
    ``activate()`` (forward) and ``backpropGradient()`` (hand-written
    backward) against ND4J ops.
  * TPU-native realization: only the forward is written; the backward comes
    from jax.grad over the whole network (the reference's per-layer
    hand-written backprop dissolves — SURVEY §8.1). Layers are pure:
    ``apply(params, x, state, *, train, rng, mask) -> (y, new_state, mask)``.
    ``state`` carries non-trainable buffers (BatchNormalization running
    stats — the reference stores them as params excluded from updates).

Param naming matches the reference's param keys where they exist
("W", "b", "gamma", "beta", "mean", "var", "RW" for recurrent weights) so
flat-param export (params_flat) lines up for parity checks.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple, Type

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn import conf as C
from deeplearning4j_tpu.ops import nn_ops
from deeplearning4j_tpu.ops.activations import get_activation
from deeplearning4j_tpu.ops.weight_init import init_weights

Params = Dict[str, jax.Array]
State = Dict[str, jax.Array]


class Layer:
    """Runtime twin of one LayerConf (org.deeplearning4j.nn.layers.BaseLayer)."""

    def __init__(self, net_conf: C.MultiLayerConfiguration, lc: C.LayerConf, itype: C.InputType):
        self.net_conf = net_conf
        self.lc = lc
        self.itype = itype  # input type AFTER preprocessor
        self.otype = lc.output_type(itype)
        self.activation = get_activation(net_conf.layer_activation(lc))
        self.winit = net_conf.layer_weight_init(lc)
        from deeplearning4j_tpu.nn.dtype import param_dtype

        self.dtype = param_dtype(net_conf.dtype)

    # -- override points ----------------------------------------------------
    def init(self, key) -> Params:
        return {}

    def init_state(self) -> State:
        return {}

    def apply(self, params: Params, x, state: State, *, train: bool, rng, mask=None):
        raise NotImplementedError

    # -- common helpers -----------------------------------------------------
    def _maybe_dropout(self, x, *, train: bool, rng):
        """Input dropout, reference layer-level `dropOut` semantics (applied
        to the layer INPUT, as in BaseLayer.applyDropOutIfNecessary)."""
        rate = self.lc.dropout
        if not rate or not train:
            return x
        return nn_ops.dropout.fn(x, rng, rate=rate)

    def n_params(self, params: Params) -> int:
        return sum(int(v.size) for v in params.values())


class DenseLayerImpl(Layer):
    """layers/feedforward/dense/DenseLayer.java: out = act(xW + b)."""

    def init(self, key) -> Params:
        lc = self.lc
        p = {"W": init_weights(key, (lc.n_in, lc.n_out), self.winit, dtype=self.dtype)}
        if lc.has_bias:
            p["b"] = jnp.zeros((lc.n_out,), self.dtype)
        return p

    def apply(self, params, x, state, *, train, rng, mask=None):
        x = self._maybe_dropout(x, train=train, rng=rng)
        z = x @ params["W"]
        if "b" in params:
            z = z + params["b"]
        return self.activation(z), state, mask


class OutputLayerImpl(DenseLayerImpl):
    """layers/OutputLayer.java: dense + loss (loss applied by the network)."""


class LossLayerImpl(Layer):
    """layers/LossLayer.java: activation only; loss applied by the network."""

    def apply(self, params, x, state, *, train, rng, mask=None):
        return self.activation(x), state, mask


class EmbeddingLayerImpl(Layer):
    """layers/feedforward/embedding/EmbeddingLayer.java: ids -> rows."""

    def init(self, key) -> Params:
        lc = self.lc
        p = {"W": init_weights(key, (lc.n_in, lc.n_out), self.winit, dtype=self.dtype)}
        if getattr(lc, "has_bias", False):
            p["b"] = jnp.zeros((lc.n_out,), self.dtype)
        return p

    def apply(self, params, x, state, *, train, rng, mask=None):
        ids = x.astype(jnp.int32)
        if ids.ndim == 2 and ids.shape[-1] == 1:
            ids = ids[:, 0]
        out = params["W"][ids]
        if "b" in params:
            out = out + params["b"]
        return self.activation(out), state, mask


class EmbeddingSequenceLayerImpl(EmbeddingLayerImpl):
    """layers/feedforward/embedding/EmbeddingSequenceLayer.java.

    Input (N, T) int ids -> (N, T, F).
    """

    def apply(self, params, x, state, *, train, rng, mask=None):
        ids = x.astype(jnp.int32)
        if ids.ndim == 3 and ids.shape[-1] == 1:
            ids = ids[..., 0]
        out = params["W"][ids]
        return self.activation(out), state, mask


class ConvolutionLayerImpl(Layer):
    """layers/convolution/ConvolutionLayer.java.

    Internal layout NHWC, kernel HWIO (SURVEY §8.3 layout policy; reference is
    NCHW/OIHW from its cuDNN heritage — accepted at the model edge, not here).
    """

    def init(self, key) -> Params:
        lc = self.lc
        kh, kw = C._pair(lc.kernel)
        p = {"W": init_weights(key, (kh, kw, lc.n_in, lc.n_out), self.winit, dtype=self.dtype)}
        if lc.has_bias:
            p["b"] = jnp.zeros((lc.n_out,), self.dtype)
        return p

    def _conv_args(self):
        lc = self.lc
        if lc.convolution_mode == "same":
            padding = "same"
        else:
            ph, pw = C._pair(lc.padding)
            padding = ((ph, ph), (pw, pw))
        return dict(stride=C._pair(lc.stride), padding=padding, dilation=C._pair(lc.dilation))

    def apply(self, params, x, state, *, train, rng, mask=None):
        x = self._maybe_dropout(x, train=train, rng=rng)
        if getattr(self.lc, "s2d_stem", False):
            z = self._s2d_stem_conv(x, params["W"], params.get("b"))
        else:
            z = nn_ops.conv2d.fn(x, params["W"], params.get("b"), **self._conv_args())
        return self.activation(z), state, mask

    def _s2d_stem_conv(self, x, W, b):
        """7×7/2 'same' conv lowered as 4×4/1 over a 2×2 space-to-depth input.

        Exact rewrite (MLPerf ResNet stem trick): pad the kernel to 8×8 with
        zeros on the high edge, regroup Wp[2α+da, 2β+db, c, f] into
        W2[α, β, (da·2+db)·C+c, f] (matching space_to_depth's channel order),
        and the stride-2 'same' conv becomes a stride-1 conv with pad (1,2).
        Gradients flow only into the canonical 7×7 entries (the pad is a
        constant), so training is bit-for-bit the same model.
        """
        lc = self.lc
        if (tuple(C._pair(lc.kernel)) != (7, 7) or tuple(C._pair(lc.stride)) != (2, 2)
                or tuple(C._pair(lc.dilation)) != (1, 1)
                or lc.convolution_mode != "same"
                or x.shape[1] % 2 or x.shape[2] % 2):
            return nn_ops.conv2d.fn(x, W, b, **self._conv_args())
        c_in, f = W.shape[2], W.shape[3]
        Wp = jnp.pad(W, ((0, 1), (0, 1), (0, 0), (0, 0)))
        W2 = (Wp.reshape(4, 2, 4, 2, c_in, f).transpose(0, 2, 1, 3, 4, 5)
              .reshape(4, 4, 4 * c_in, f))
        from deeplearning4j_tpu.ops import exec_op
        x2 = exec_op("space_to_depth", x, block_size=2)
        return nn_ops.conv2d.fn(x2, W2, b, stride=(1, 1), padding=((1, 2), (1, 2)))


class Deconvolution2DImpl(ConvolutionLayerImpl):
    """layers/convolution/Deconvolution2DLayer.java (transposed conv)."""

    def apply(self, params, x, state, *, train, rng, mask=None):
        lc = self.lc
        x = self._maybe_dropout(x, train=train, rng=rng)
        if lc.convolution_mode == "same":
            pad = "same"
        else:
            # explicit pad must match output_type: oh = s*(h-1) + k - 2p
            pad = C._pair(lc.padding)
        z = nn_ops.deconv2d.fn(x, params["W"], params.get("b"), stride=C._pair(lc.stride), padding=pad)
        return self.activation(z), state, mask


class DepthwiseConvolution2DImpl(Layer):
    """layers/convolution/DepthwiseConvolution2DLayer.java."""

    def init(self, key) -> Params:
        lc = self.lc
        kh, kw = C._pair(lc.kernel)
        mult = getattr(lc, "depth_multiplier", 1)
        p = {"W": init_weights(key, (kh, kw, lc.n_in, mult), self.winit, dtype=self.dtype)}
        if lc.has_bias:
            p["b"] = jnp.zeros((lc.n_in * mult,), self.dtype)
        return p

    def apply(self, params, x, state, *, train, rng, mask=None):
        lc = self.lc
        x = self._maybe_dropout(x, train=train, rng=rng)
        pad = "same" if lc.convolution_mode == "same" else "valid"
        z = nn_ops.depthwise_conv2d.fn(
            x, params["W"], params.get("b"), stride=C._pair(lc.stride), padding=pad,
            dilation=C._pair(lc.dilation))
        return self.activation(z), state, mask


class SeparableConvolution2DImpl(Layer):
    """layers/convolution/SeparableConvolution2DLayer.java."""

    def init(self, key) -> Params:
        lc = self.lc
        kh, kw = C._pair(lc.kernel)
        mult = getattr(lc, "depth_multiplier", 1)
        k1, k2 = jax.random.split(key)
        p = {
            "dW": init_weights(k1, (kh, kw, lc.n_in, mult), self.winit, dtype=self.dtype),
            "pW": init_weights(k2, (1, 1, lc.n_in * mult, lc.n_out), self.winit, dtype=self.dtype),
        }
        if lc.has_bias:
            p["b"] = jnp.zeros((lc.n_out,), self.dtype)
        return p

    def apply(self, params, x, state, *, train, rng, mask=None):
        lc = self.lc
        x = self._maybe_dropout(x, train=train, rng=rng)
        pad = "same" if lc.convolution_mode == "same" else "valid"
        z = nn_ops.separable_conv2d.fn(
            x, params["dW"], params["pW"], params.get("b"),
            stride=C._pair(lc.stride), padding=pad)
        return self.activation(z), state, mask


class SubsamplingLayerImpl(Layer):
    """layers/convolution/subsampling/SubsamplingLayer.java."""

    def apply(self, params, x, state, *, train, rng, mask=None):
        lc = self.lc
        if lc.convolution_mode == "same":
            pad = "same"
        else:
            ph, pw = C._pair(lc.padding)
            pad = ((ph, ph), (pw, pw))
        kw = dict(kernel=C._pair(lc.kernel), stride=C._pair(lc.stride), padding=pad)
        if lc.pooling_type == "max":
            y = nn_ops.maxpool2d.fn(x, **kw)
        elif lc.pooling_type == "avg":
            y = nn_ops.avgpool2d.fn(x, **kw)
        elif lc.pooling_type == "pnorm":
            y = nn_ops.pnormpool2d.fn(x, p=lc.pnorm, **kw)
        else:
            raise ValueError(f"unknown pooling type {lc.pooling_type}")
        return y, state, mask


class Upsampling2DImpl(Layer):
    """layers/convolution/upsampling/Upsampling2D.java."""

    def apply(self, params, x, state, *, train, rng, mask=None):
        return nn_ops.upsampling2d.fn(x, size=C._pair(self.lc.size)), state, mask


class GlobalPoolingLayerImpl(Layer):
    """layers/pooling/GlobalPoolingLayer.java — conv NHWC (axes 1,2) or
    recurrent (axis 1 = time, mask-aware)."""

    def apply(self, params, x, state, *, train, rng, mask=None):
        pt = self.lc.pooling_type
        if x.ndim == 5:  # NDHWC
            axes = (1, 2, 3)
            m = None
        elif x.ndim == 4:  # NHWC
            axes = (1, 2)
            m = None
        else:  # (N, T, F)
            axes = (1,)
            m = mask
        if m is not None:
            m3 = m[..., None].astype(x.dtype)
            if pt == "avg":
                y = (x * m3).sum(axes) / jnp.maximum(m3.sum(axes), 1e-8)
            elif pt == "sum":
                y = (x * m3).sum(axes)
            elif pt == "max":
                y = jnp.where(m3 > 0, x, -jnp.inf).max(axes)
            else:
                y = ((jnp.abs(x) ** self.lc_pnorm()) * m3).sum(axes) ** (1.0 / self.lc_pnorm())
        else:
            if pt == "avg":
                y = x.mean(axes)
            elif pt == "sum":
                y = x.sum(axes)
            elif pt == "max":
                y = x.max(axes)
            else:
                y = (jnp.abs(x) ** self.lc_pnorm()).sum(axes) ** (1.0 / self.lc_pnorm())
        return y, state, None

    def lc_pnorm(self):
        return getattr(self.lc, "pnorm", 2)


class DiscretizationLayerImpl(Layer):
    """conf.DiscretizationLayer runtime: bucketize by static boundaries
    (keras semantics: index = number of boundaries <= x)."""

    def apply(self, params, x, state, *, train, rng, mask=None):
        bounds = jnp.asarray(self.lc.bin_boundaries, jnp.float32)
        idx = jnp.searchsorted(bounds, x.astype(jnp.float32), side="right")
        return idx.astype(jnp.int32), state, mask


class CategoryEncodingLayerImpl(Layer):
    """conf.CategoryEncodingLayer runtime: one_hot / multi_hot / count."""

    def apply(self, params, x, state, *, train, rng, mask=None):
        lc = self.lc
        oh = jax.nn.one_hot(x.astype(jnp.int32), lc.num_tokens,
                            dtype=jnp.float32)
        if lc.output_mode == "one_hot":
            # keras requires a trailing size-1 feature axis for one_hot and
            # squeezes it: (N, 1) -> (N, num_tokens)
            if oh.ndim >= 3 and oh.shape[-2] == 1:
                oh = oh.squeeze(-2)
            return oh, state, mask
        agg = jnp.sum(oh, axis=-2) if oh.ndim >= 2 else oh
        if lc.output_mode == "count":
            return agg, state, mask
        return jnp.minimum(agg, 1.0), state, mask  # multi_hot


class EinsumDenseLayerImpl(Layer):
    """conf.EinsumDenseLayer runtime (Keras EinsumDense parity): the
    weight shape is the equation's rhs operand dims; bias broadcasts on
    the declared bias shape."""

    def init(self, key):
        lc = self.lc
        # rhs operand dims come from the equation's second input spec sized
        # by (input feature dims, out_shape); Keras stores the built kernel
        # shape — we derive it the same way from equation + out_shape
        eq = lc.equation.replace(" ", "")
        ins_, out = eq.split("->")
        a_spec, b_spec = ins_.split(",")
        sizes = {}
        for ax, n in zip(reversed(out.replace("...", "")),
                         reversed(lc.out_shape)):
            sizes[ax] = int(n)
        # input labels size from the ACTUAL input dims, right-aligned:
        # recurrent → (timesteps, size), feedforward → (flat,); without
        # '...' the leading a_spec label is the batch axis
        if self.itype.kind == "recurrent":
            in_dims = (self.itype.timesteps, self.itype.size)
        else:
            in_dims = (self.itype.flat_size(),)
        labels_in = a_spec.replace("...", "")
        if "..." not in a_spec:
            labels_in = labels_in[1:]  # drop the explicit batch label
        for ax, n in zip(reversed(labels_in), reversed(in_dims)):
            sizes.setdefault(ax, int(n))
        missing = [ax for ax in b_spec.replace("...", "") if ax not in sizes]
        if missing:
            raise ValueError(
                f"EinsumDenseLayer: cannot size kernel labels {missing} "
                f"from equation '{lc.equation}', out_shape {lc.out_shape} "
                f"and input {self.itype} — give a fully-specified "
                f"out_shape (every kernel-only label must appear in the "
                f"output spec)")
        w_shape = tuple(sizes[ax] for ax in b_spec.replace("...", ""))
        p = {"W": init_weights(key, w_shape, self.winit, dtype=self.dtype)}
        if lc.bias_shape:
            p["b"] = jnp.zeros(tuple(lc.bias_shape), self.dtype)
        return p

    def apply(self, params, x, state, *, train, rng, mask=None):
        x = self._maybe_dropout(x, train=train, rng=rng)
        y = jnp.einsum(self.lc.equation, x, params["W"])
        if "b" in params:
            y = y + params["b"]
        return self.activation(y), state, mask


class DuelingQLayerImpl(Layer):
    """conf.DuelingQLayer runtime: Q = V + A − mean(A) (Wang et al.
    aggregation, the RL4J dueling head)."""

    def init(self, key):
        lc = self.lc
        k1, k2 = jax.random.split(key)
        return {"Wv": init_weights(k1, (lc.n_in, 1), self.winit, dtype=self.dtype),
                "bv": jnp.zeros((1,), self.dtype),
                "Wa": init_weights(k2, (lc.n_in, lc.n_actions), self.winit,
                                   dtype=self.dtype),
                "ba": jnp.zeros((lc.n_actions,), self.dtype)}

    def apply(self, params, x, state, *, train, rng, mask=None):
        x = self._maybe_dropout(x, train=train, rng=rng)
        v = x @ params["Wv"] + params["bv"]
        a = x @ params["Wa"] + params["ba"]
        q = v + a - jnp.mean(a, axis=-1, keepdims=True)
        return self.activation(q), state, mask


class BatchNormalizationImpl(Layer):
    """layers/normalization/BatchNormalization.java.

    gamma/beta trainable; running mean/var live in layer STATE (the reference
    keeps them in the param buffer but excludes them from updates — state is
    the functional equivalent). Reference decay semantics:
    running = decay * running + (1-decay) * batch.
    """

    def init(self, key) -> Params:
        n = self.lc.n_out
        if self.lc.lock_gamma_beta:
            return {}
        return {"gamma": jnp.ones((n,), self.dtype), "beta": jnp.zeros((n,), self.dtype)}

    def init_state(self) -> State:
        n = self.lc.n_out
        return {"mean": jnp.zeros((n,), self.dtype), "var": jnp.ones((n,), self.dtype)}

    def apply(self, params, x, state, *, train, rng, mask=None):
        lc = self.lc
        gamma = params.get("gamma")
        beta = params.get("beta")
        if train:
            axes = tuple(range(x.ndim - 1))  # all but channel/feature
            y, new_mean, new_var = nn_ops.batch_norm_train(
                x, gamma, beta, state["mean"], state["var"],
                axis=axes, eps=lc.eps, momentum=lc.decay)
            return self.activation(y), {"mean": new_mean, "var": new_var}, mask
        y = nn_ops.batchnorm.fn(x, state["mean"], state["var"], gamma, beta, eps=lc.eps)
        return self.activation(y), state, mask


class LocalResponseNormalizationImpl(Layer):
    """layers/normalization/LocalResponseNormalization.java."""

    def apply(self, params, x, state, *, train, rng, mask=None):
        lc = self.lc
        y = nn_ops.local_response_normalization.fn(
            x, depth=lc.n, bias=lc.k, alpha=lc.alpha, beta=lc.beta)
        return y, state, mask


class ActivationLayerImpl(Layer):
    """layers/ActivationLayer.java."""

    def apply(self, params, x, state, *, train, rng, mask=None):
        return self.activation(x), state, mask


class DropoutLayerImpl(Layer):
    """layers/DropoutLayer.java + conf/dropout/{Spatial,Alpha,Gaussian}Dropout.java."""

    def apply(self, params, x, state, *, train, rng, mask=None):
        if not train or self.lc.rate <= 0.0:
            return x, state, mask
        rate = self.lc.rate
        mode = getattr(self.lc, "mode", "elementwise")
        if mode == "elementwise":
            return nn_ops.dropout.fn(x, rng, rate=rate), state, mask
        if mode == "spatial":
            # drop whole feature maps: bernoulli over (N, 1, ..., 1, C)
            keep = 1.0 - rate
            mshape = (x.shape[0],) + (1,) * (x.ndim - 2) + (x.shape[-1],)
            m = jax.random.bernoulli(rng, keep, mshape)
            return jnp.where(m, x / keep, 0.0), state, mask
        if mode == "alpha":
            # Klambauer et al. 2017 §3: keeps SELU self-normalisation
            keep = 1.0 - rate
            alpha_p = -1.7580993408473766
            a = (keep + alpha_p ** 2 * keep * rate) ** -0.5
            b = -a * rate * alpha_p
            m = jax.random.bernoulli(rng, keep, x.shape)
            return a * jnp.where(m, x, alpha_p) + b, state, mask
        if mode == "gaussian":
            std = (rate / (1.0 - rate)) ** 0.5
            noise = 1.0 + std * jax.random.normal(rng, x.shape, x.dtype)
            return x * noise, state, mask
        raise ValueError(f"unknown dropout mode {mode!r}")


# ---------------------------------------------------------------------------
# Recurrent layers — lax.scan over time (layers/recurrent/*)
# ---------------------------------------------------------------------------


def _lstm_scan(params, x0, h0, c0, mask, *, gate_act, cell_act, reverse=False):
    """Scan an LSTM over (N, T, F). Gate math per LSTMHelpers.java:
    gates = x·Wih + h·Whh + b, order [i, f, o, g]; c' = f*c + i*g;
    h = o * cell_act(c') — the layer's configured activation IS the
    cell-output activation (reference default tanh), not a post-transform.

    The whole loop is one lax.scan — XLA unrolls/pipelines it; the per-step
    matmuls hit the MXU batched over N.
    """
    w_ih, w_hh, b = params["W"], params["RW"], params["b"]

    masked = mask is not None

    def step(carry, xm):
        h, c = carry
        xt, mt = xm
        gates = xt @ w_ih + h @ w_hh + b
        i, f, o, g = jnp.split(gates, 4, axis=-1)
        i, f, o = gate_act(i), gate_act(f), gate_act(o)
        g = jnp.tanh(g)
        c_new = f * c + i * g
        h_new = o * cell_act(c_new)
        if masked:
            m = mt[:, None]
            h_new = jnp.where(m > 0, h_new, h)
            c_new = jnp.where(m > 0, c_new, c)
        return (h_new, c_new), h_new

    xs = jnp.swapaxes(x0, 0, 1)  # (T, N, F)
    ms = jnp.swapaxes(mask, 0, 1) if masked else jnp.zeros((xs.shape[0], 0))
    (h_last, c_last), hs = jax.lax.scan(step, (h0, c0), (xs, ms), reverse=reverse)
    return jnp.swapaxes(hs, 0, 1), h_last, c_last


class LSTMImpl(Layer):
    """layers/recurrent/LSTM.java — scan-based, mask-aware, stateful-capable.

    The configured ``activation`` is the cell-output activation inside the
    scan (reference default tanh). Stateful rnnTimeStep() support passes
    ``initial=(h0, c0)`` and consumes the returned last state (wired by the
    network's rnn_time_step path).
    """

    reverse = False

    def init(self, key) -> Params:
        lc = self.lc
        k1, k2 = jax.random.split(key)
        b = jnp.zeros((4 * lc.n_out,), self.dtype)
        # forget-gate bias init (reference forgetGateBiasInit): gate order [i,f,o,g]
        b = b.at[lc.n_out : 2 * lc.n_out].set(lc.forget_gate_bias_init)
        return {
            "W": init_weights(k1, (lc.n_in, 4 * lc.n_out), self.winit, dtype=self.dtype),
            "RW": init_weights(k2, (lc.n_out, 4 * lc.n_out), self.winit, dtype=self.dtype),
            "b": b,
        }

    def apply(self, params, x, state, *, train, rng, mask=None, initial=None):
        lc = self.lc
        x = self._maybe_dropout(x, train=train, rng=rng)
        n = x.shape[0]
        if initial is not None:
            h0, c0 = initial
        else:
            h0 = jnp.zeros((n, lc.n_out), x.dtype)
            c0 = jnp.zeros((n, lc.n_out), x.dtype)
        gate_act = get_activation(lc.gate_activation)
        hs, h_last, c_last = _lstm_scan(
            params, x, h0, c0, mask, gate_act=gate_act, cell_act=self.activation,
            reverse=self.reverse)
        return hs, state, mask

    def zero_state(self, batch: int, dtype=jnp.float32):
        n = self.lc.n_out
        return (jnp.zeros((batch, n), dtype), jnp.zeros((batch, n), dtype))

    def apply_with_state(self, params, x, *, mask=None, initial=None):
        """Stateful forward for rnn_time_step: returns (out, (h_last, c_last))."""
        lc = self.lc
        n = x.shape[0]
        if initial is not None:
            h0, c0 = initial
        else:
            h0 = jnp.zeros((n, lc.n_out), x.dtype)
            c0 = jnp.zeros((n, lc.n_out), x.dtype)
        hs, h_last, c_last = _lstm_scan(
            params, x, h0, c0, mask, gate_act=get_activation(lc.gate_activation),
            cell_act=self.activation, reverse=self.reverse)
        return hs, (h_last, c_last)


class GRUImpl(Layer):
    """GRU over the gru_cell declarable op, scanned across time — the same
    shared-recurrence shape as SimpleRnn/LSTM (training forward, tBPTT, and
    rnn_time_step all route through apply_with_state)."""

    def __init__(self, net_conf, lc, itype):
        super().__init__(net_conf, lc, itype)
        # the gru_cell ABI hardcodes tanh/sigmoid; an EXPLICIT per-layer
        # activation would be silently ignored — refuse instead
        # (LSTM/SimpleRnn honor theirs, so silence here would diverge; the
        # net-wide default activation is not treated as a GRU request)
        if lc.activation not in (None, "tanh"):
            raise ValueError(
                f"GRU uses the gru_cell op's fixed tanh/sigmoid gates; "
                f"activation={lc.activation!r} cannot apply")

    def init(self, key) -> Params:
        lc = self.lc
        k1, k2 = jax.random.split(key)
        return {
            "W": init_weights(k1, (lc.n_in, 3 * lc.n_out), self.winit,
                              dtype=self.dtype),
            "RW": init_weights(k2, (lc.n_out, 3 * lc.n_out), self.winit,
                               dtype=self.dtype),
            "b": jnp.zeros((3 * lc.n_out,), self.dtype),
            "rb": jnp.zeros((3 * lc.n_out,), self.dtype),
        }

    def zero_state(self, batch: int, dtype=jnp.float32):
        return jnp.zeros((batch, self.lc.n_out), dtype)

    def apply(self, params, x, state, *, train, rng, mask=None, initial=None):
        x = self._maybe_dropout(x, train=train, rng=rng)
        hs, _ = self.apply_with_state(params, x, mask=mask, initial=initial)
        return hs, state, mask

    def apply_with_state(self, params, x, *, mask=None, initial=None):
        from deeplearning4j_tpu.ops.registry import registry

        cell = registry().get("gru_cell").fn
        lc = self.lc
        n = x.shape[0]
        h0 = initial if initial is not None else jnp.zeros((n, lc.n_out), x.dtype)
        masked = mask is not None

        def step(h, xm):
            xt, mt = xm
            h_new = cell(xt, h, params["W"], params["RW"], params["b"],
                         params["rb"])
            if masked:
                h_new = jnp.where(mt[:, None] > 0, h_new, h)
            return h_new, h_new

        xs = jnp.swapaxes(x, 0, 1)
        ms = (jnp.swapaxes(mask, 0, 1) if masked
              else jnp.zeros((xs.shape[0], 0), x.dtype))  # unmasked sentinel
        h_last, hs = jax.lax.scan(step, h0, (xs, ms))
        return jnp.swapaxes(hs, 0, 1), h_last


class SimpleRnnImpl(Layer):
    """layers/recurrent/SimpleRnn.java: h' = act(x·W + h·RW + b)."""

    def init(self, key) -> Params:
        lc = self.lc
        k1, k2 = jax.random.split(key)
        return {
            "W": init_weights(k1, (lc.n_in, lc.n_out), self.winit, dtype=self.dtype),
            "RW": init_weights(k2, (lc.n_out, lc.n_out), self.winit, dtype=self.dtype),
            "b": jnp.zeros((lc.n_out,), self.dtype),
        }

    def apply(self, params, x, state, *, train, rng, mask=None, initial=None):
        x = self._maybe_dropout(x, train=train, rng=rng)
        hs, _ = self.apply_with_state(params, x, mask=mask, initial=initial)
        return hs, state, mask

    def zero_state(self, batch: int, dtype=jnp.float32):
        return jnp.zeros((batch, self.lc.n_out), dtype)

    def apply_with_state(self, params, x, *, mask=None, initial=None):
        """Shared scan; returns (out, h_last) — the single recurrence impl
        for both training forward and stateful rnn_time_step."""
        lc = self.lc
        n = x.shape[0]
        h0 = initial if initial is not None else jnp.zeros((n, lc.n_out), x.dtype)
        act = self.activation
        masked = mask is not None

        def step(h, xm):
            xt, mt = xm
            h_new = act(xt @ params["W"] + h @ params["RW"] + params["b"])
            if masked:
                h_new = jnp.where(mt[:, None] > 0, h_new, h)
            return h_new, h_new

        xs = jnp.swapaxes(x, 0, 1)
        ms = jnp.swapaxes(mask, 0, 1) if masked else jnp.zeros((xs.shape[0], 0))
        h_last, hs = jax.lax.scan(step, h0, (xs, ms))
        return jnp.swapaxes(hs, 0, 1), h_last


class BidirectionalImpl(Layer):
    """layers/recurrent/BidirectionalLayer.java: fwd + bwd inner RNN, merged."""

    def __init__(self, net_conf, lc, itype):
        super().__init__(net_conf, lc, itype)
        inner = lc.inner()
        self.fwd_layer = build_layer(net_conf, inner, itype)
        self.bwd_layer = build_layer(net_conf, inner, itype)
        if isinstance(self.bwd_layer, LSTMImpl):
            self.bwd_layer.reverse = True

    def init(self, key) -> Params:
        k1, k2 = jax.random.split(key)
        return {"fwd": self.fwd_layer.init(k1), "bwd": self.bwd_layer.init(k2)}

    def apply(self, params, x, state, *, train, rng, mask=None):
        yf, _, _ = self.fwd_layer.apply(params["fwd"], x, {}, train=train, rng=rng, mask=mask)
        if isinstance(self.bwd_layer, LSTMImpl):
            yb, _, _ = self.bwd_layer.apply(params["bwd"], x, {}, train=train, rng=rng, mask=mask)
        else:
            xr = jnp.flip(x, axis=1)
            mr = None if mask is None else jnp.flip(mask, axis=1)
            yb, _, _ = self.bwd_layer.apply(params["bwd"], xr, {}, train=train, rng=rng, mask=mr)
            if yb.ndim == x.ndim:
                # sequence output: restore original time order. A collapsed
                # output (LastTimeStep-wrapped, keras return_sequences=False)
                # is ALREADY the backward pass's final step — flipping it
                # would scramble the FEATURE axis (round-4 bidirectional
                # regression)
                yb = jnp.flip(yb, axis=1)
        mode = self.lc.mode
        if mode == "concat":
            y = jnp.concatenate([yf, yb], axis=-1)
        elif mode == "add":
            y = yf + yb
        elif mode == "mul":
            y = yf * yb
        elif mode == "average":
            y = 0.5 * (yf + yb)
        else:
            raise ValueError(f"unknown Bidirectional mode {mode}")
        return y, state, mask


class RnnOutputLayerImpl(Layer):
    """layers/recurrent/RnnOutputLayer.java: time-distributed dense + loss."""

    def init(self, key) -> Params:
        lc = self.lc
        p = {"W": init_weights(key, (lc.n_in, lc.n_out), self.winit, dtype=self.dtype)}
        if lc.has_bias:
            p["b"] = jnp.zeros((lc.n_out,), self.dtype)
        return p

    def apply(self, params, x, state, *, train, rng, mask=None):
        z = x @ params["W"]
        if "b" in params:
            z = z + params["b"]
        return self.activation(z), state, mask


class LastTimeStepImpl(Layer):
    """layers/recurrent/LastTimeStepLayer.java: inner RNN -> last unmasked step."""

    def __init__(self, net_conf, lc, itype):
        super().__init__(net_conf, lc, itype)
        self.inner_layer = build_layer(net_conf, lc.inner(), itype)

    def init(self, key) -> Params:
        return {"inner": self.inner_layer.init(key)}

    def apply(self, params, x, state, *, train, rng, mask=None):
        y, _, _ = self.inner_layer.apply(params["inner"], x, {}, train=train, rng=rng, mask=mask)
        if mask is None:
            out = y[:, -1]
        else:
            idx = jnp.maximum(mask.sum(axis=1).astype(jnp.int32) - 1, 0)
            out = y[jnp.arange(y.shape[0]), idx]
        return out, state, None


class SelfAttentionLayerImpl(Layer):
    """layers/SelfAttentionLayer.java — MHA with Q=K=V=input sequence.

    Lowers to the registry's multi_head_dot_product_attention (which the
    platform-helper table may override with a Pallas flash-attention kernel
    on TPU — the cuDNN-helper analog)."""

    def init(self, key) -> Params:
        lc = self.lc
        ks = jax.random.split(key, 4)
        d = lc.n_out
        return {
            "Wq": init_weights(ks[0], (lc.n_in, d), self.winit, dtype=self.dtype),
            "Wk": init_weights(ks[1], (lc.n_in, d), self.winit, dtype=self.dtype),
            "Wv": init_weights(ks[2], (lc.n_in, d), self.winit, dtype=self.dtype),
            "Wo": init_weights(ks[3], (d, d), self.winit, dtype=self.dtype),
        }

    def apply(self, params, x, state, *, train, rng, mask=None):
        h = self.lc.n_heads
        q = x @ params["Wq"]
        k = x @ params["Wk"]
        v = x @ params["Wv"]
        n, t, d = q.shape
        dh = d // h

        def split(a):
            return a.reshape(n, t, h, dh).transpose(0, 2, 1, 3)

        qh, kh, vh = split(q), split(k), split(v)
        scores = (qh @ jnp.swapaxes(kh, -1, -2)) / jnp.sqrt(jnp.asarray(dh, x.dtype))
        if mask is not None:
            am = mask[:, None, None, :]
            scores = jnp.where(am > 0, scores, -1e9)
        attn = jax.nn.softmax(scores, axis=-1)
        out = (attn @ vh).transpose(0, 2, 1, 3).reshape(n, t, d)
        return out @ params["Wo"], state, mask


class LearnedSelfAttentionLayerImpl(Layer):
    """layers/LearnedSelfAttentionLayer.java: learned query matrix attends
    over the input sequence → fixed n_queries output timesteps. Routes the
    attention through the op registry so the Pallas flash helper fires on
    TPU for long sequences."""

    def init(self, key) -> Params:
        lc = self.lc
        ks = jax.random.split(key, 4)
        d = lc.n_out
        return {
            "Q": init_weights(ks[0], (lc.n_queries, d), self.winit, dtype=self.dtype),
            "Wk": init_weights(ks[1], (lc.n_in, d), self.winit, dtype=self.dtype),
            "Wv": init_weights(ks[2], (lc.n_in, d), self.winit, dtype=self.dtype),
            "Wo": init_weights(ks[3], (d, d), self.winit, dtype=self.dtype),
        }

    def apply(self, params, x, state, *, train, rng, mask=None):
        from deeplearning4j_tpu.ops import exec_op

        h = self.lc.n_heads
        n, t, _ = x.shape
        d = self.lc.n_out
        dh = d // h
        q = jnp.broadcast_to(params["Q"][None], (n,) + params["Q"].shape)
        k = x @ params["Wk"]
        v = x @ params["Wv"]

        def split(a):
            return a.reshape(n, a.shape[1], h, dh).transpose(0, 2, 1, 3)

        m = None if mask is None else mask[:, None, None, :]
        out = exec_op("dot_product_attention", split(q), split(k), split(v),
                      m, scaled=True)
        out = out.transpose(0, 2, 1, 3).reshape(n, self.lc.n_queries, d)
        return out @ params["Wo"], state, None  # fixed-length output: no mask


class RecurrentAttentionLayerImpl(Layer):
    """layers/RecurrentAttentionLayer.java: out_t = act(Wx·x_t + Wr·attn_t
    + b) where attn_t attends over the WHOLE input sequence queried by the
    previous output — a lax.scan over timesteps (TPU-compilable; the
    reference loops in Java)."""

    def init(self, key) -> Params:
        lc = self.lc
        ks = jax.random.split(key, 5)
        return {
            "Wx": init_weights(ks[0], (lc.n_in, lc.n_out), self.winit, dtype=self.dtype),
            "Wr": init_weights(ks[1], (lc.n_in, lc.n_out), self.winit, dtype=self.dtype),
            "Wq": init_weights(ks[2], (lc.n_out, lc.n_in), self.winit, dtype=self.dtype),
            "b": jnp.zeros((lc.n_out,), self.dtype),
        }

    def apply(self, params, x, state, *, train, rng, mask=None):
        n, t, d_in = x.shape
        heads = max(1, self.lc.n_heads)
        if d_in % heads:
            raise ValueError(
                f"RecurrentAttentionLayer: n_in={d_in} not divisible by "
                f"n_heads={heads}")
        dh = d_in // heads
        scale = 1.0 / float(dh) ** 0.5
        key_mask = None if mask is None else (mask > 0)
        xh = x.reshape(n, t, heads, dh)  # keys/values per head

        def step(h, x_t):
            q = (h @ params["Wq"]).reshape(n, heads, dh)
            s = jnp.einsum("nhd,nthd->nht", q, xh) * scale
            if key_mask is not None:
                s = jnp.where(key_mask[:, None, :], s, -1e9)
            a = jax.nn.softmax(s, axis=-1)
            attn = jnp.einsum("nht,nthd->nhd", a, xh).reshape(n, d_in)
            h_new = self.activation(x_t @ params["Wx"] + attn @ params["Wr"]
                                    + params["b"])
            return h_new, h_new

        h0 = jnp.zeros((n, self.lc.n_out), x.dtype)
        _, ys = jax.lax.scan(step, h0, jnp.swapaxes(x, 0, 1))
        return jnp.swapaxes(ys, 0, 1), state, mask


class AttentionVertexImpl(Layer):
    """graph/vertex AttentionVertex: parameterized multi-input attention.
    Routed through the op registry → Pallas flash helper on TPU."""

    def init(self, key) -> Params:
        lc = self.lc
        ks = jax.random.split(key, 4)
        d = lc.n_out
        d_out = getattr(lc, "d_out", 0) or d
        nq = lc.n_in_queries or lc.n_in_keys
        nk = lc.n_in_keys or nq
        nv = lc.n_in_values or nk
        p = {
            "Wq": init_weights(ks[0], (nq, d), self.winit, dtype=self.dtype),
            "Wk": init_weights(ks[1], (nk, d), self.winit, dtype=self.dtype),
            "Wv": init_weights(ks[2], (nv, d), self.winit, dtype=self.dtype),
            "Wo": init_weights(ks[3], (d, d_out), self.winit, dtype=self.dtype),
        }
        if getattr(lc, "has_bias", False):
            p.update({"bq": jnp.zeros((d,), self.dtype),
                      "bk": jnp.zeros((d,), self.dtype),
                      "bv": jnp.zeros((d,), self.dtype),
                      "bo": jnp.zeros((d_out,), self.dtype)})
        return p

    def apply_multi(self, params, xs, state, *, train, rng, mask=None):
        from deeplearning4j_tpu.ops import exec_op

        if getattr(self.lc, "keras_order", False) and len(xs) >= 2:
            # Keras MultiHeadAttention call order: (query, VALUE[, key])
            queries = xs[0]
            values = xs[1]
            keys = xs[2] if len(xs) > 2 else values
        else:
            queries = xs[0]
            keys = xs[1] if len(xs) > 1 else xs[0]
            values = xs[2] if len(xs) > 2 else keys
        out = exec_op("multi_head_dot_product_attention",
                      queries, keys, values,
                      params["Wq"], params["Wk"], params["Wv"], params["Wo"],
                      mask, num_heads=self.lc.n_heads,
                      bq=params.get("bq"), bk=params.get("bk"),
                      bv=params.get("bv"), bo=params.get("bo"))
        return out, state, mask

    def apply(self, params, x, state, *, train, rng, mask=None):
        return self.apply_multi(params, [x], state, train=train, rng=rng,
                                mask=mask)


class Convolution1DImpl(Layer):
    """layers/convolution/Convolution1DLayer.java over (N, T, C)."""

    def init(self, key) -> Params:
        lc = self.lc
        p = {"W": init_weights(key, (lc.kernel, lc.n_in, lc.n_out),
                               self.winit, dtype=self.dtype)}
        p["b"] = jnp.zeros((lc.n_out,), self.dtype)
        return p

    def apply(self, params, x, state, *, train, rng, mask=None):
        lc = self.lc
        x = self._maybe_dropout(x, train=train, rng=rng)
        z = nn_ops.conv1d.fn(x, params["W"], params.get("b"),
                             stride=lc.stride,
                             padding=lc.convolution_mode,
                             dilation=lc.dilation)
        if mask is not None and z.shape[1] != mask.shape[1]:
            # subsample the mask with the conv (reference Conv1D semantics:
            # a timestep survives if its window START was valid)
            mask = mask[:, ::lc.stride][:, :z.shape[1]]
        return self.activation(z), state, mask


class Convolution3DImpl(Layer):
    """layers/convolution/Convolution3DLayer.java over (N, D, H, W, C)."""

    def init(self, key) -> Params:
        lc = self.lc
        kd, kh, kw = lc.kernel
        return {
            "W": init_weights(key, (kd, kh, kw, lc.n_in, lc.n_out),
                              self.winit, dtype=self.dtype),
            "b": jnp.zeros((lc.n_out,), self.dtype),
        }

    def apply(self, params, x, state, *, train, rng, mask=None):
        lc = self.lc
        x = self._maybe_dropout(x, train=train, rng=rng)
        z = nn_ops.conv3d.fn(x, params["W"], params.get("b"),
                             stride=lc.stride,
                             padding=lc.convolution_mode)
        return self.activation(z), state, mask


class Subsampling3DLayerImpl(Layer):
    """layers/convolution/Subsampling3DLayer.java (NDHWC pooling)."""

    def apply(self, params, x, state, *, train, rng, mask=None):
        lc = self.lc
        k = (1,) + tuple(lc.kernel) + (1,)
        s = (1,) + tuple(lc.stride) + (1,)
        if lc.pooling_type == "max":
            z = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, k, s, "VALID")
        else:
            z = jax.lax.reduce_window(x, 0.0, jax.lax.add, k, s, "VALID") \
                / float(lc.kernel[0] * lc.kernel[1] * lc.kernel[2])
        return z, state, mask


class LocallyConnected2DImpl(Layer):
    """layers/convolution/LocallyConnected2DLayer.java: per-position
    (unshared) conv weights — patches × per-position kernels as ONE einsum,
    which XLA maps onto the MXU as a batched matmul."""

    def _out_hw(self):
        lc = self.lc
        kh, kw = C._pair(lc.kernel)
        sh, sw = C._pair(lc.stride)
        ih, iw = lc.input_size
        return (ih - kh) // sh + 1, (iw - kw) // sw + 1

    def init(self, key) -> Params:
        lc = self.lc
        kh, kw = C._pair(lc.kernel)
        oh, ow = self._out_hw()
        return {
            "W": init_weights(key, (oh * ow, kh * kw * lc.n_in, lc.n_out),
                              self.winit, dtype=self.dtype),
            "b": jnp.zeros((oh, ow, lc.n_out), self.dtype),
        }

    def apply(self, params, x, state, *, train, rng, mask=None):
        lc = self.lc
        kh, kw = C._pair(lc.kernel)
        sh, sw = C._pair(lc.stride)
        oh, ow = self._out_hw()
        patches = jax.lax.conv_general_dilated_patches(
            x, (kh, kw), (sh, sw), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        # patches feature order is (C, kh, kw); align W accordingly at init?
        # no — keep W in patch order: reshape to (N, oh*ow, feat)
        n = x.shape[0]
        p = patches.reshape(n, oh * ow, -1)
        z = jnp.einsum("npf,pfo->npo", p, params["W"])
        z = z.reshape(n, oh, ow, lc.n_out) + params["b"]
        return self.activation(z), state, mask


class LocallyConnected1DImpl(Layer):
    """layers/convolution/LocallyConnected1DLayer.java over (N, T, C)."""

    def _out_t(self):
        lc = self.lc
        return (lc.input_size - lc.kernel) // lc.stride + 1

    def init(self, key) -> Params:
        lc = self.lc
        ot = self._out_t()
        return {
            "W": init_weights(key, (ot, lc.kernel * lc.n_in, lc.n_out),
                              self.winit, dtype=self.dtype),
            "b": jnp.zeros((ot, lc.n_out), self.dtype),
        }

    def apply(self, params, x, state, *, train, rng, mask=None):
        lc = self.lc
        ot = self._out_t()
        starts = jnp.arange(ot) * lc.stride
        idx = starts[:, None] + jnp.arange(lc.kernel)[None, :]  # (ot, k)
        windows = x[:, idx, :]  # (N, ot, k, C)
        n = x.shape[0]
        p = windows.reshape(n, ot, -1)
        z = jnp.einsum("npf,pfo->npo", p, params["W"]) + params["b"]
        if mask is not None and z.shape[1] != mask.shape[1]:
            mask = None
        return self.activation(z), state, mask


class PReLULayerImpl(Layer):
    """layers/feedforward/PReLULayer.java: learned per-feature slope."""

    def init(self, key) -> Params:
        return {"alpha": jnp.full((self.lc.n_in,), 0.25, self.dtype)}

    def apply(self, params, x, state, *, train, rng, mask=None):
        a = params["alpha"]
        return jnp.maximum(x, 0) + a * jnp.minimum(x, 0), state, mask


class VariationalAutoencoderImpl(Layer):
    """layers/variational/VariationalAutoencoder.java.

    Supervised forward = encoder → latent mean (reference activate()
    semantics). ``elbo_loss(params, x, rng)`` gives the pretrain objective
    (reparameterized ELBO) for unsupervised fit — the reference's
    pretrain-layer role."""

    def init(self, key) -> Params:
        lc = self.lc
        sizes_e = (lc.n_in,) + tuple(lc.encoder_layer_sizes)
        sizes_d = (lc.n_out,) + tuple(lc.decoder_layer_sizes)
        ks = jax.random.split(key, 2 * (len(sizes_e) + len(sizes_d)) + 3)
        ki = iter(range(len(ks)))
        p: Dict[str, Any] = {"enc": [], "dec": []}
        for i in range(len(sizes_e) - 1):
            p["enc"].append({
                "W": init_weights(ks[next(ki)], (sizes_e[i], sizes_e[i + 1]),
                                  self.winit, dtype=self.dtype),
                "b": jnp.zeros((sizes_e[i + 1],), self.dtype)})
        h = sizes_e[-1]
        p["mean"] = {"W": init_weights(ks[next(ki)], (h, lc.n_out),
                                       self.winit, dtype=self.dtype),
                     "b": jnp.zeros((lc.n_out,), self.dtype)}
        p["logvar"] = {"W": init_weights(ks[next(ki)], (h, lc.n_out),
                                         self.winit, dtype=self.dtype),
                       "b": jnp.zeros((lc.n_out,), self.dtype)}
        for i in range(len(sizes_d) - 1):
            p["dec"].append({
                "W": init_weights(ks[next(ki)], (sizes_d[i], sizes_d[i + 1]),
                                  self.winit, dtype=self.dtype),
                "b": jnp.zeros((sizes_d[i + 1],), self.dtype)})
        p["recon"] = {"W": init_weights(ks[next(ki)],
                                        (sizes_d[-1], lc.n_in),
                                        self.winit, dtype=self.dtype),
                      "b": jnp.zeros((lc.n_in,), self.dtype)}
        return p

    def _encode(self, params, x):
        h = x
        for lp in params["enc"]:
            h = self.activation(h @ lp["W"] + lp["b"])
        mean = h @ params["mean"]["W"] + params["mean"]["b"]
        logvar = h @ params["logvar"]["W"] + params["logvar"]["b"]
        return mean, logvar

    def _decode(self, params, z):
        h = z
        for lp in params["dec"]:
            h = self.activation(h @ lp["W"] + lp["b"])
        return h @ params["recon"]["W"] + params["recon"]["b"]

    def apply(self, params, x, state, *, train, rng, mask=None):
        mean, _ = self._encode(params, x)
        return mean, state, mask

    def elbo_loss(self, params, x, rng):
        mean, logvar = self._encode(params, x)
        eps = jax.random.normal(rng, mean.shape, mean.dtype)
        z = mean + jnp.exp(0.5 * logvar) * eps
        recon = self._decode(params, z)
        if self.lc.reconstruction_distribution == "bernoulli":
            p = jax.nn.sigmoid(recon)
            rec = -jnp.sum(x * jnp.log(p + 1e-8)
                           + (1 - x) * jnp.log(1 - p + 1e-8), axis=-1)
        else:
            rec = 0.5 * jnp.sum((x - recon) ** 2, axis=-1)
        kl = -0.5 * jnp.sum(1 + logvar - mean ** 2 - jnp.exp(logvar), axis=-1)
        return jnp.mean(rec + kl)


class ZeroPadding1DLayerImpl(Layer):
    """layers/convolution/ZeroPadding1DLayer.java: pad time axis of (N,T,C)."""

    def apply(self, params, x, state, *, train, rng, mask=None):
        a, b = C._pair(self.lc.padding)
        y = jnp.pad(x, ((0, 0), (a, b), (0, 0)))
        if mask is not None:
            mask = jnp.pad(mask, ((0, 0), (a, b)))
        return y, state, mask


class ZeroPaddingLayerImpl(Layer):
    """layers/convolution/ZeroPaddingLayer.java: NHWC spatial pad."""

    def apply(self, params, x, state, *, train, rng, mask=None):
        t, b, l, r = self.lc.padding
        return jnp.pad(x, ((0, 0), (t, b), (l, r), (0, 0))), state, mask


class ZeroPadding3DLayerImpl(Layer):
    """layers/convolution/ZeroPadding3DLayer.java: NDHWC pad."""

    def apply(self, params, x, state, *, train, rng, mask=None):
        p = self.lc.padding
        return jnp.pad(x, ((0, 0), (p[0], p[1]), (p[2], p[3]),
                           (p[4], p[5]), (0, 0))), state, mask


class Cropping1DImpl(Layer):
    """layers/convolution/Cropping1DLayer.java."""

    def apply(self, params, x, state, *, train, rng, mask=None):
        a, b = C._pair(self.lc.cropping)
        t = x.shape[1]
        y = x[:, a:t - b, :]
        if mask is not None:
            mask = mask[:, a:t - b]
        return y, state, mask


class Cropping2DImpl(Layer):
    """layers/convolution/Cropping2DLayer.java."""

    def apply(self, params, x, state, *, train, rng, mask=None):
        t, b, l, r = self.lc.cropping
        h, w = x.shape[1], x.shape[2]
        return x[:, t:h - b, l:w - r, :], state, mask


class Cropping3DImpl(Layer):
    """layers/convolution/Cropping3DLayer.java."""

    def apply(self, params, x, state, *, train, rng, mask=None):
        c = self.lc.cropping
        d, h, w = x.shape[1], x.shape[2], x.shape[3]
        return x[:, c[0]:d - c[1], c[2]:h - c[3], c[4]:w - c[5], :], state, mask


class Upsampling1DImpl(Layer):
    """layers/convolution/upsampling/Upsampling1D.java: repeat timesteps."""

    def apply(self, params, x, state, *, train, rng, mask=None):
        y = jnp.repeat(x, self.lc.size, axis=1)
        if mask is not None:
            mask = jnp.repeat(mask, self.lc.size, axis=1)
        return y, state, mask


class Upsampling3DImpl(Layer):
    """layers/convolution/upsampling/Upsampling3D.java: NN-upsample NDHWC."""

    def apply(self, params, x, state, *, train, rng, mask=None):
        s = self.lc.size
        y = jnp.repeat(jnp.repeat(jnp.repeat(x, s[0], axis=1), s[1], axis=2),
                       s[2], axis=3)
        return y, state, mask


class Subsampling1DLayerImpl(Layer):
    """layers/convolution/subsampling/Subsampling1DLayer.java: temporal pool."""

    def apply(self, params, x, state, *, train, rng, mask=None):
        lc = self.lc
        k, s = int(lc.kernel), int(lc.stride)
        pad = "SAME" if lc.convolution_mode == "same" else "VALID"
        if lc.pooling_type == "max":
            y = jax.lax.reduce_window(
                x, -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min,
                jax.lax.max, (1, k, 1), (1, s, 1), pad)
        else:
            ones = jnp.ones_like(x)
            tot = jax.lax.reduce_window(x, 0.0, jax.lax.add, (1, k, 1), (1, s, 1), pad)
            cnt = jax.lax.reduce_window(ones, 0.0, jax.lax.add, (1, k, 1), (1, s, 1), pad)
            y = tot / cnt
        if mask is not None:
            mask = jax.lax.reduce_window(
                mask.astype(x.dtype), 0.0, jax.lax.max, (1, k), (1, s), pad)
        return y, state, mask


class Deconvolution3DImpl(Layer):
    """layers/convolution/Deconvolution3DLayer.java: transposed 3-D conv."""

    def init(self, key) -> Params:
        lc = self.lc
        kd, kh, kw = lc.kernel
        p = {"W": init_weights(key, (kd, kh, kw, lc.n_in, lc.n_out),
                               self.winit, dtype=self.dtype)}
        p["b"] = jnp.zeros((lc.n_out,), self.dtype)
        return p

    def apply(self, params, x, state, *, train, rng, mask=None):
        lc = self.lc
        pad = "SAME" if lc.convolution_mode == "same" else "VALID"
        y = jax.lax.conv_transpose(
            x, params["W"], strides=tuple(lc.stride), padding=pad,
            dimension_numbers=("NDHWC", "DHWIO", "NDHWC"))
        y = y + params["b"]
        return self.activation(y), state, mask


class CnnLossLayerImpl(Layer):
    """layers/convolution/CnnLossLayer.java: activation only — per-position
    loss applied by the network against (N, H, W, C) labels."""

    def apply(self, params, x, state, *, train, rng, mask=None):
        return self.activation(x), state, mask


class RnnLossLayerImpl(CnnLossLayerImpl):
    """layers/recurrent/RnnLossLayer.java: per-timestep loss (N, T, C)."""


class MaskLayerImpl(Layer):
    """layers/util/MaskLayer.java: zero masked timesteps explicitly."""

    def apply(self, params, x, state, *, train, rng, mask=None):
        if mask is not None:
            m = mask.astype(x.dtype)
            while m.ndim < x.ndim:
                m = m[..., None]
            x = x * m
        return x, state, mask


class MaskZeroLayerImpl(Layer):
    """layers/recurrent/MaskZeroLayer.java: derive the timestep mask from
    the input values, then run the wrapped layer under it."""

    def __init__(self, net_conf, lc, itype):
        super().__init__(net_conf, lc, itype)
        self.inner_layer = build_layer(net_conf, lc.inner(), itype)

    def init(self, key) -> Params:
        return {"inner": self.inner_layer.init(key)}

    def init_state(self) -> State:
        return self.inner_layer.init_state()

    def apply(self, params, x, state, *, train, rng, mask=None):
        derived = jnp.any(x != self.lc.mask_value, axis=-1).astype(x.dtype)
        if mask is not None:
            derived = derived * mask.astype(x.dtype)
        x = x * derived[..., None]
        y, st, _ = self.inner_layer.apply(params["inner"], x, state,
                                          train=train, rng=rng, mask=derived)
        return y, st, derived


class RepeatVectorImpl(Layer):
    """layers/RepeatVector.java: (N, F) -> (N, n, F)."""

    def apply(self, params, x, state, *, train, rng, mask=None):
        return jnp.broadcast_to(x[:, None, :],
                                (x.shape[0], self.lc.n, x.shape[-1])), state, None


class ElementWiseMultiplicationLayerImpl(Layer):
    """layers/feedforward/elementwise/ElementWiseMultiplicationLayer.java."""

    def init(self, key) -> Params:
        n = self.lc.n_out or self.lc.n_in
        return {"W": jnp.ones((n,), self.dtype), "b": jnp.zeros((n,), self.dtype)}

    def apply(self, params, x, state, *, train, rng, mask=None):
        x = self._maybe_dropout(x, train=train, rng=rng)
        return self.activation(x * params["W"] + params["b"]), state, mask


class FrozenLayerWithBackpropImpl(Layer):
    """layers/FrozenLayerWithBackprop.java: stop-gradient on the wrapped
    layer's PARAMS (they never update) while activations and upstream
    gradients flow normally."""

    def __init__(self, net_conf, lc, itype):
        super().__init__(net_conf, lc, itype)
        self.inner_layer = build_layer(net_conf, lc.inner(), itype)

    def init(self, key) -> Params:
        return {"inner": self.inner_layer.init(key)}

    def init_state(self) -> State:
        return self.inner_layer.init_state()

    def apply(self, params, x, state, *, train, rng, mask=None):
        frozen = jax.tree.map(jax.lax.stop_gradient, params["inner"])
        return self.inner_layer.apply(frozen, x, state, train=train, rng=rng,
                                      mask=mask)


class CenterLossOutputLayerImpl(DenseLayerImpl):
    """layers/training/CenterLossOutputLayer.java: dense+softmax forward;
    per-class centers live in params["centers"] and enter through the loss
    (the network adds λ·½‖features − c_y‖² — see MultiLayerNetwork)."""

    def init(self, key) -> Params:
        p = super().init(key)
        p["centers"] = jnp.zeros((self.lc.n_out, self.lc.n_in), self.dtype)
        return p


class Yolo2OutputLayerImpl(Layer):
    """layers/objdetect/Yolo2OutputLayer.java: identity forward — the raw
    head output is decoded inside the 'yolo2' loss."""

    def apply(self, params, x, state, *, train, rng, mask=None):
        return x, state, mask


def _squash(v, axis=-1, eps=1e-8):
    """CapsNet squash: (‖v‖²/(1+‖v‖²)) · v/‖v‖ (Sabour et al. 2017)."""
    sq = jnp.sum(jnp.square(v), axis=axis, keepdims=True)
    return (sq / (1.0 + sq)) * v * jax.lax.rsqrt(sq + eps)


class PrimaryCapsulesImpl(Layer):
    """layers/PrimaryCapsules.java: conv → capsule channels → squash."""

    def init(self, key) -> Params:
        lc = self.lc
        kh, kw = lc.kernel
        out_ch = lc.capsules * lc.capsule_dim
        p = {"W": init_weights(key, (kh, kw, self.itype.channels, out_ch),
                               self.winit, dtype=self.dtype),
             "b": jnp.zeros((out_ch,), self.dtype)}
        return p

    def apply(self, params, x, state, *, train, rng, mask=None):
        lc = self.lc
        y = jax.lax.conv_general_dilated(
            x, params["W"], tuple(lc.stride), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC")) + params["b"]
        n = y.shape[0]
        y = y.reshape(n, -1, lc.capsule_dim)
        return _squash(y), state, None


class CapsuleLayerImpl(Layer):
    """layers/CapsuleLayer.java: dynamic routing between capsule layers."""

    def init(self, key) -> Params:
        lc = self.lc
        in_caps, in_dim = self.itype.timesteps, self.itype.size
        return {"W": init_weights(key, (in_caps, lc.capsules,
                                        lc.capsule_dim, in_dim),
                                  self.winit, dtype=self.dtype)}

    def apply(self, params, x, state, *, train, rng, mask=None):
        lc = self.lc
        # u_hat[n,i,j,k] = W[i,j,k,:] · x[n,i,:]
        u_hat = jnp.einsum("nid,ijkd->nijk", x, params["W"])
        b = jnp.zeros(u_hat.shape[:3], u_hat.dtype)
        v = None
        for it in range(max(int(lc.routings), 1)):
            c = jax.nn.softmax(b, axis=2)
            s = jnp.sum(c[..., None] * u_hat, axis=1)
            v = _squash(s)
            if it + 1 < lc.routings:
                # routing agreement uses detached predictions (standard
                # CapsNet practice: gradients flow only through the last pass)
                b = b + jnp.einsum("njk,nijk->nij",
                                   jax.lax.stop_gradient(v), u_hat)
        return v, state, None


class CapsuleStrengthLayerImpl(Layer):
    """layers/CapsuleStrengthLayer.java: per-capsule L2 norm."""

    def apply(self, params, x, state, *, train, rng, mask=None):
        return jnp.sqrt(jnp.sum(jnp.square(x), axis=-1) + 1e-12), state, mask



class PermuteLayerImpl(Layer):
    """Keras Permute parity: reorder non-batch axes (dims are 1-indexed)."""

    def apply(self, params, x, state, *, train, rng, mask=None):
        perm = (0,) + tuple(int(d) for d in self.lc.dims)
        return jnp.transpose(x, perm), state, mask


class ReshapeLayerImpl(Layer):
    """Keras Reshape parity: batch-preserving reshape with -1 inference."""

    def apply(self, params, x, state, *, train, rng, mask=None):
        return x.reshape((x.shape[0],) + tuple(int(s) for s in
                                               self.lc.target_shape)), \
            state, mask


class LayerNormalizationImpl(Layer):
    """Trailing-axis layer norm with learned gain/bias (layer_norm op)."""

    def init(self, key) -> Params:
        n = self.lc.n_out
        return {"gain": jnp.ones((n,), self.dtype),
                "b": jnp.zeros((n,), self.dtype)}

    def apply(self, params, x, state, *, train, rng, mask=None):
        y = nn_ops.layer_norm.fn(x, params["gain"], params["b"],
                                 axis=-1, eps=self.lc.eps)
        return self.activation(y), state, mask


class GroupNormalizationImpl(Layer):
    """Group norm: normalize per (sample, group) over spatial dims +
    in-group channels, then per-channel scale/shift."""

    def init(self, key) -> Params:
        n = self.lc.n_out
        return {"gamma": jnp.ones((n,), self.dtype),
                "beta": jnp.zeros((n,), self.dtype)}

    def apply(self, params, x, state, *, train, rng, mask=None):
        lc = self.lc
        c = x.shape[-1]
        g = lc.groups if lc.groups > 0 else c
        xg = x.reshape(x.shape[:-1] + (g, c // g))
        # per (sample, group): reduce spatial dims + in-group channels,
        # NOT across groups (keras GroupNormalization semantics)
        axes = tuple(i for i in range(1, xg.ndim) if i != xg.ndim - 2)
        mean = jnp.mean(xg, axis=axes, keepdims=True)
        var = jnp.var(xg, axis=axes, keepdims=True)
        y = ((xg - mean) * jax.lax.rsqrt(var + lc.eps)).reshape(x.shape)
        y = y * params["gamma"] + params["beta"]
        return self.activation(y), state, mask


class RescaleLayerImpl(Layer):
    """out = x * scale + offset (Keras Rescaling / adapted Normalization)."""

    def apply(self, params, x, state, *, train, rng, mask=None):
        scale = jnp.asarray(self.lc.scale, x.dtype)
        offset = jnp.asarray(self.lc.offset, x.dtype)
        return x * scale + offset, state, mask


class UnitNormLayerImpl(Layer):
    """L2-normalize along the trailing axis (Keras UnitNormalization)."""

    def apply(self, params, x, state, *, train, rng, mask=None):
        norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True))
        return x / jnp.maximum(norm, self.lc.eps), state, mask


class ConvLSTM2DImpl(Layer):
    """Convolutional LSTM over (N, T, H, W, C): gate pre-activations are
    conv2d(x_t, W) + conv2d(h, RW) + b, one lax.scan over time so each step
    is a batched MXU conv (KerasConvLSTM2D parity; gate order i, f, o, g
    after import re-packing)."""

    def init(self, key) -> Params:
        lc = self.lc
        kh, kw = lc.kernel
        k1, k2 = jax.random.split(key)
        return {
            "W": init_weights(k1, (kh, kw, lc.n_in, 4 * lc.filters),
                              self.winit, dtype=self.dtype),
            "RW": init_weights(k2, (kh, kw, lc.filters, 4 * lc.filters),
                               self.winit, dtype=self.dtype),
            "b": jnp.zeros((4 * lc.filters,), self.dtype),
        }

    def apply(self, params, x, state, *, train, rng, mask=None):
        lc = self.lc
        gate_act = get_activation(lc.gate_activation)
        pad = "same" if lc.padding == "same" else "valid"

        def conv(a, w, p):
            return jax.lax.conv_general_dilated(
                a, w, (1, 1), p,
                dimension_numbers=("NHWC", "HWIO", "NHWC"))

        # input convs for ALL timesteps in one batched conv: (N*T, H, W, C)
        n, t = x.shape[0], x.shape[1]
        zx = conv(x.reshape((n * t,) + x.shape[2:]), params["W"], pad.upper())
        zx = zx.reshape((n, t) + zx.shape[1:]) + params["b"]
        h0 = jnp.zeros((n,) + zx.shape[2:-1] + (lc.filters,), x.dtype)

        def step(carry, zt):
            h, c = carry
            # the recurrent conv is ALWAYS 'same' — the carried state must
            # keep its spatial shape (keras ConvLSTM2D semantics)
            gates = zt + conv(h, params["RW"], "SAME")
            i, f, o, g = jnp.split(gates, 4, axis=-1)
            # keras applies `activation` to BOTH candidate and cell output
            c_new = gate_act(f) * c + gate_act(i) * self.activation(g)
            h_new = gate_act(o) * self.activation(c_new)
            return (h_new, c_new), h_new

        (h_last, _), hs = jax.lax.scan(step, (h0, h0),
                                       jnp.swapaxes(zx, 0, 1))
        if lc.return_sequences:
            return jnp.swapaxes(hs, 0, 1), state, mask
        return h_last, state, None



class DotAttentionLayerImpl(Layer):
    """Param-free Keras Attention / AdditiveAttention: inputs in KERAS
    order (query, value[, key]); key defaults to value."""

    def apply_multi(self, params, xs, state, *, train, rng, mask=None):
        q = xs[0]
        v = xs[1] if len(xs) > 1 else xs[0]
        k = xs[2] if len(xs) > 2 else v
        lc = self.lc
        if lc.additive:
            # Bahdanau: score[b,i,j] = sum(scale * tanh(q_i + k_j))
            t = jnp.tanh(q[:, :, None, :] + k[:, None, :, :])
            if lc.use_scale and lc.scale is not None:
                t = t * jnp.asarray(lc.scale, t.dtype)
            scores = jnp.sum(t, axis=-1)
        else:
            scores = jnp.einsum("bqd,bkd->bqk", q, k)
            if lc.use_scale and lc.scale is not None:
                scores = scores * jnp.asarray(lc.scale, scores.dtype)
        if mask is not None and mask.shape[-1] == k.shape[1]:
            # key-padding mask: padded positions get no attention weight
            scores = jnp.where(mask[:, None, :] > 0, scores,
                               jnp.asarray(-1e9, scores.dtype))
        w = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bqk,bkd->bqd", w, v), state, mask

    def apply(self, params, x, state, *, train, rng, mask=None):
        return self.apply_multi(params, [x], state, train=train, rng=rng,
                                mask=mask)



class SeparableConvolution1DImpl(Layer):
    """Depthwise (grouped) + pointwise conv over (N, T, C)."""

    def init(self, key) -> Params:
        lc = self.lc
        k1, k2 = jax.random.split(key)
        mult = lc.depth_multiplier
        p = {"dW": init_weights(k1, (lc.kernel, 1, lc.n_in * mult),
                                self.winit, dtype=self.dtype),
             "pW": init_weights(k2, (1, lc.n_in * mult, lc.n_out),
                                self.winit, dtype=self.dtype)}
        if lc.has_bias:
            p["b"] = jnp.zeros((lc.n_out,), self.dtype)
        return p

    def apply(self, params, x, state, *, train, rng, mask=None):
        lc = self.lc
        x = self._maybe_dropout(x, train=train, rng=rng)
        pad = "SAME" if lc.convolution_mode == "same" else "VALID"
        dn = ("NWC", "WIO", "NWC")
        z = jax.lax.conv_general_dilated(
            x, params["dW"], (lc.stride,), pad, dimension_numbers=dn,
            feature_group_count=lc.n_in)
        z = jax.lax.conv_general_dilated(
            z, params["pW"], (1,), "VALID", dimension_numbers=dn)
        if "b" in params:
            z = z + params["b"]
        if mask is not None and z.shape[1] != mask.shape[1]:
            mask = mask[:, ::lc.stride][:, :z.shape[1]]
        return self.activation(z), state, mask



class Deconvolution1DImpl(Layer):
    """Transposed temporal conv over (N, T, C)."""

    def init(self, key) -> Params:
        lc = self.lc
        p = {"W": init_weights(key, (lc.kernel, lc.n_in, lc.n_out),
                               self.winit, dtype=self.dtype)}
        if lc.has_bias:
            p["b"] = jnp.zeros((lc.n_out,), self.dtype)
        return p

    def apply(self, params, x, state, *, train, rng, mask=None):
        lc = self.lc
        x = self._maybe_dropout(x, train=train, rng=rng)
        pad = "SAME" if lc.convolution_mode == "same" else "VALID"
        # transpose_kernel=True = TF conv1d_transpose semantics (exact at
        # every stride); W stored (k, in, out) like the 2D convention
        z = jax.lax.conv_transpose(
            x, jnp.swapaxes(params["W"], 1, 2), (lc.stride,), pad,
            dimension_numbers=("NWC", "WIO", "NWC"), transpose_kernel=True)
        if "b" in params:
            z = z + params["b"]
        return self.activation(z), state, None



class SpaceToDepthLayerImpl(Layer):
    """layers/convolution/SpaceToDepthLayer.java (YOLOv2 reorg)."""

    def apply(self, params, x, state, *, train, rng, mask=None):
        from deeplearning4j_tpu.ops import exec_op

        return exec_op("space_to_depth", x,
                       block_size=self.lc.block_size), state, mask



class SameDiffLayerImpl(Layer):
    """layers/samediff/SameDiffLayer.java runtime: the user's define()
    records into a private SameDiff once; apply interprets that graph with
    the live params/input under the outer trace, so jax.grad of the whole
    network differentiates straight through the block."""

    def _graph(self):
        if not hasattr(self, "_sd"):
            from deeplearning4j_tpu.autodiff.samediff import SameDiff

            sd = SameDiff.create()
            x = sd.placeholder("sdl_x", shape=None)
            pvars = {name: sd.placeholder(f"sdl_p_{name}", shape=tuple(shape))
                     for name, shape in (self.lc.param_shapes or {}).items()}
            out = self.lc.define(sd, x, pvars)
            self._sd = sd
            self._out_name = out.name
        return self._sd, self._out_name

    def init(self, key) -> Params:
        shapes = self.lc.param_shapes or {}
        ks = jax.random.split(key, max(len(shapes), 1))
        params = {}
        for k_, (name, shape) in zip(ks, sorted(shapes.items())):
            if len(shape) >= 2:
                params[name] = init_weights(k_, tuple(shape), self.winit,
                                            dtype=self.dtype)
            else:
                params[name] = jnp.zeros(tuple(shape), self.dtype)
        return params

    def apply(self, params, x, state, *, train, rng, mask=None):
        x = self._maybe_dropout(x, train=train, rng=rng)
        sd, out_name = self._graph()
        env = dict(sd._arrays)
        env["sdl_x"] = x
        for name, arr in params.items():
            env[f"sdl_p_{name}"] = arr
        out = sd._interpret(env, [out_name])[out_name]
        # the block's output IS define()'s result — the net-wide default
        # activation must NOT double-activate it (reference SameDiffLayer
        # semantics); an explicit per-layer activation still applies
        if self.lc.activation is not None:
            out = self.activation(out)
        return out, state, mask



class ResizeLayerImpl(Layer):
    """Keras Resizing: NHWC resize via the registry resize ops (half-pixel
    centers — the TF2/keras convention)."""

    def apply(self, params, x, state, *, train, rng, mask=None):
        from deeplearning4j_tpu.ops import exec_op

        op = {"bilinear": "resize_bilinear",
              "nearest": "resize_nearest_neighbor",
              "bicubic": "resize_bicubic"}[self.lc.method]
        return exec_op(op, x, size=(self.lc.height, self.lc.width)), \
            state, mask


class CenterCropLayerImpl(Layer):
    """Keras CenterCrop: static center window (keras floor convention:
    start = (in - out) // 2)."""

    def apply(self, params, x, state, *, train, rng, mask=None):
        h, w = x.shape[1], x.shape[2]
        th, tw = self.lc.height, self.lc.width
        if h < th or w < tw:
            # keras falls back to smart_resize here; our declared output
            # shape cannot flex, so fail loudly rather than mis-shape
            raise ValueError(
                f"CenterCropLayer: input {h}x{w} smaller than target "
                f"{th}x{tw} (keras would resize; use ResizeLayer instead)")
        y0, x0 = (h - th) // 2, (w - tw) // 2
        return x[:, y0:y0 + th, x0:x0 + tw, :], state, mask


LAYER_IMPLS: Dict[Type[C.LayerConf], Type[Layer]] = {
    C.DenseLayer: DenseLayerImpl,
    C.OutputLayer: OutputLayerImpl,
    C.LossLayer: LossLayerImpl,
    C.EmbeddingLayer: EmbeddingLayerImpl,
    C.EmbeddingSequenceLayer: EmbeddingSequenceLayerImpl,
    C.ConvolutionLayer: ConvolutionLayerImpl,
    C.Deconvolution2D: Deconvolution2DImpl,
    C.DepthwiseConvolution2D: DepthwiseConvolution2DImpl,
    C.SeparableConvolution2D: SeparableConvolution2DImpl,
    C.SubsamplingLayer: SubsamplingLayerImpl,
    C.Upsampling2D: Upsampling2DImpl,
    C.GlobalPoolingLayer: GlobalPoolingLayerImpl,
    C.BatchNormalization: BatchNormalizationImpl,
    C.DuelingQLayer: DuelingQLayerImpl,
    C.EinsumDenseLayer: EinsumDenseLayerImpl,
    C.DiscretizationLayer: DiscretizationLayerImpl,
    C.CategoryEncodingLayer: CategoryEncodingLayerImpl,
    C.LocalResponseNormalization: LocalResponseNormalizationImpl,
    C.ActivationLayer: ActivationLayerImpl,
    C.DropoutLayer: DropoutLayerImpl,
    C.LSTM: LSTMImpl,
    C.GravesLSTM: LSTMImpl,
    C.GRU: GRUImpl,
    C.SimpleRnn: SimpleRnnImpl,
    C.Bidirectional: BidirectionalImpl,
    C.RnnOutputLayer: RnnOutputLayerImpl,
    C.LastTimeStep: LastTimeStepImpl,
    C.SelfAttentionLayer: SelfAttentionLayerImpl,
    C.AttentionVertex: AttentionVertexImpl,
    C.LearnedSelfAttentionLayer: LearnedSelfAttentionLayerImpl,
    C.RecurrentAttentionLayer: RecurrentAttentionLayerImpl,
    C.Convolution1D: Convolution1DImpl,
    C.Convolution3D: Convolution3DImpl,
    C.Subsampling3DLayer: Subsampling3DLayerImpl,
    C.LocallyConnected2D: LocallyConnected2DImpl,
    C.LocallyConnected1D: LocallyConnected1DImpl,
    C.PReLULayer: PReLULayerImpl,
    C.VariationalAutoencoder: VariationalAutoencoderImpl,
    C.ZeroPadding1DLayer: ZeroPadding1DLayerImpl,
    C.ZeroPaddingLayer: ZeroPaddingLayerImpl,
    C.ZeroPadding3DLayer: ZeroPadding3DLayerImpl,
    C.Cropping1D: Cropping1DImpl,
    C.Cropping2D: Cropping2DImpl,
    C.Cropping3D: Cropping3DImpl,
    C.Upsampling1D: Upsampling1DImpl,
    C.Upsampling3D: Upsampling3DImpl,
    C.Subsampling1DLayer: Subsampling1DLayerImpl,
    C.Deconvolution3D: Deconvolution3DImpl,
    C.CnnLossLayer: CnnLossLayerImpl,
    C.RnnLossLayer: RnnLossLayerImpl,
    C.MaskLayer: MaskLayerImpl,
    C.MaskZeroLayer: MaskZeroLayerImpl,
    C.RepeatVector: RepeatVectorImpl,
    C.ResizeLayer: ResizeLayerImpl,
    C.CenterCropLayer: CenterCropLayerImpl,
    C.SameDiffLayer: SameDiffLayerImpl,
    C.SpaceToDepthLayer: SpaceToDepthLayerImpl,
    C.Deconvolution1D: Deconvolution1DImpl,
    C.SeparableConvolution1D: SeparableConvolution1DImpl,
    C.DotAttentionLayer: DotAttentionLayerImpl,
    C.PermuteLayer: PermuteLayerImpl,
    C.ReshapeLayer: ReshapeLayerImpl,
    C.LayerNormalization: LayerNormalizationImpl,
    C.GroupNormalization: GroupNormalizationImpl,
    C.RescaleLayer: RescaleLayerImpl,
    C.UnitNormLayer: UnitNormLayerImpl,
    C.ConvLSTM2D: ConvLSTM2DImpl,
    C.ElementWiseMultiplicationLayer: ElementWiseMultiplicationLayerImpl,
    C.FrozenLayerWithBackprop: FrozenLayerWithBackpropImpl,
    C.CenterLossOutputLayer: CenterLossOutputLayerImpl,
    C.Yolo2OutputLayer: Yolo2OutputLayerImpl,
    C.PrimaryCapsules: PrimaryCapsulesImpl,
    C.CapsuleLayer: CapsuleLayerImpl,
    C.CapsuleStrengthLayer: CapsuleStrengthLayerImpl,
}


def build_layer(net_conf: C.MultiLayerConfiguration, lc: C.LayerConf, itype: C.InputType) -> Layer:
    impl = LAYER_IMPLS.get(type(lc))
    if impl is None and type(lc) is C.MoELayer:
        from deeplearning4j_tpu.nn.moe_layer import MoELayerImpl
        LAYER_IMPLS[C.MoELayer] = MoELayerImpl
        impl = MoELayerImpl
    if impl is None:
        raise ValueError(f"no runtime impl for layer config {type(lc).__name__}")
    return impl(net_conf, lc, itype)


def apply_preprocessor(p: Optional[C.InputPreProcessor], x):
    """conf/preprocessor/* forward application."""
    if p is None:
        return x
    if isinstance(p, C.FeedForwardToCnnPreProcessor):
        # reference flattening is NCHW C-major; our runtime layout is NHWC
        return x.reshape(x.shape[0], p.channels, p.height, p.width).transpose(0, 2, 3, 1)
    if isinstance(p, C.CnnToFeedForwardPreProcessor):
        # inverse: NHWC -> NCHW-major flatten to match reference flat ordering
        return x.transpose(0, 3, 1, 2).reshape(x.shape[0], -1)
    if isinstance(p, C.Cnn3DToFeedForwardPreProcessor):
        # NDHWC -> channel-major flatten (reference NCDHW ordering)
        return x.transpose(0, 4, 1, 2, 3).reshape(x.shape[0], -1)
    if isinstance(p, C.RnnToFeedForwardPreProcessor):
        return x.reshape(-1, x.shape[-1])
    if isinstance(p, C.FeedForwardToRnnPreProcessor):
        raise ValueError("FeedForwardToRnnPreProcessor needs batch size context; unsupported standalone")
    return x
