"""Define-by-run parameter store — the zoo's graph-builder kernel.

Each architecture is written ONCE as a pure build function over a
``Store``; the same code path (a) initializes a param pytree, (b) applies
the model in inference mode, and (c) applies it in train mode collecting
batch-norm moving-stat updates. This replaces the reference's frozen-
GraphDef composition kernel (ref: sparkdl graph/builder.py —
IsolatedSession/GraphFunction ~L40-L200): where the reference splices
protobufs, we compose pure functions that jit into one XLA program.

Param pytrees are keyed by **canonical Keras layer names** (the names a
freshly-built keras.applications model has in a clean process; the
``Namer`` reproduces Keras's per-type auto-numbering). That makes Keras
weight conversion a mechanical per-layer copy (SURVEY.md §7.3 mitigation)
with no transliteration table.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from tpudl.obs import metrics as _metrics
from tpudl.zoo import nn

__all__ = ["Namer", "Store", "glorot_uniform"]


class Namer:
    """Reproduces Keras auto-naming: first unnamed Conv2D in a fresh process
    is ``conv2d``, then ``conv2d_1``, ... Per-type counters."""

    def __init__(self):
        self._counts: dict[str, int] = {}

    def __call__(self, base: str, explicit: str | None = None) -> str:
        if explicit is not None:
            return explicit
        i = self._counts.get(base, 0)
        self._counts[base] = i + 1
        return base if i == 0 else f"{base}_{i}"


def glorot_uniform(rng, shape, dtype=jnp.float32):
    """Keras's default kernel initializer.

    Accepts a jax PRNG key (traceable, device-backed) or a
    ``np.random.Generator`` (host fast path: init of a 20M-param net is
    milliseconds of numpy instead of hundreds of tiny device dispatches —
    the round-1 bench spent ~60s here before the first batch ran).
    """
    if len(shape) == 2:
        fan_in, fan_out = shape
    else:  # conv HWIO: receptive field × channels
        rf = int(np.prod(shape[:-2]))
        fan_in, fan_out = shape[-2] * rf, shape[-1] * rf
    limit = float(np.sqrt(6.0 / (fan_in + fan_out)))
    if isinstance(rng, np.random.Generator):
        return rng.uniform(-limit, limit, size=shape).astype(dtype)
    return jax.random.uniform(rng, shape, dtype, -limit, limit)


class Store:
    """One object, three modes:

    - init:  ``Store(rng=key)`` — layer calls create params, inputs flow
      through so shapes are inferred from the trace.
    - apply: ``Store(params=p)`` — layer calls consume params.
    - train: ``Store(params=p, train=True)`` — BN uses batch stats and
      updated moving averages accumulate in ``store.bn_updates``.
    """

    def __init__(self, params=None, rng=None, *, train: bool = False,
                 param_dtype=jnp.float32):
        if (params is None) == (rng is None):
            raise ValueError("pass exactly one of params= (apply) or rng= (init)")
        self.params = params
        self.initializing = params is None
        if self.initializing:
            self.params = {}
        self._rng = rng
        self.train = train and not self.initializing
        self.param_dtype = param_dtype
        self.name = Namer()
        self.bn_updates: dict[str, dict] = {}

    def _next_rng(self):
        if isinstance(self._rng, np.random.Generator):
            return self._rng  # host fast path: sequential draws
        self._rng, sub = jax.random.split(self._rng)
        return sub

    def _zeros(self, shape):
        if isinstance(self._rng, np.random.Generator):
            return np.zeros(shape, self.param_dtype)
        return jnp.zeros(shape, self.param_dtype)

    def _ones(self, shape):
        if isinstance(self._rng, np.random.Generator):
            return np.ones(shape, self.param_dtype)
        return jnp.ones(shape, self.param_dtype)

    def _get(self, name: str, make) -> dict:
        if self.initializing:
            if name in self.params:
                raise ValueError(f"duplicate layer name {name!r}")
            self.params[name] = make()
        if name not in self.params:
            raise KeyError(f"missing params for layer {name!r}")
        return self.params[name]

    # -- layers (each mirrors the matching Keras layer's weight layout) ----
    def conv(self, x, filters, kernel_size, *, strides=(1, 1), padding="SAME",
             use_bias=True, name=None):
        kh, kw = (kernel_size, kernel_size) if isinstance(kernel_size, int) else kernel_size
        lname = self.name("conv2d", name)
        cin = x.shape[-1]

        def make():
            p = {"kernel": glorot_uniform(self._next_rng(), (kh, kw, cin, filters),
                                          self.param_dtype)}
            if use_bias:
                p["bias"] = self._zeros((filters,))
            return p

        p = self._get(lname, make)
        return nn.conv2d(x, p["kernel"], p.get("bias"), strides=strides,
                         padding=padding)

    def sep_conv(self, x, filters, kernel_size, *, strides=(1, 1),
                 padding="SAME", use_bias=True, name=None):
        kh, kw = (kernel_size, kernel_size) if isinstance(kernel_size, int) else kernel_size
        lname = self.name("separable_conv2d", name)
        cin = x.shape[-1]

        def make():
            p = {
                "depthwise_kernel": glorot_uniform(
                    self._next_rng(), (kh, kw, cin, 1), self.param_dtype),
                "pointwise_kernel": glorot_uniform(
                    self._next_rng(), (1, 1, cin, filters), self.param_dtype),
            }
            if use_bias:
                p["bias"] = self._zeros((filters,))
            return p

        p = self._get(lname, make)
        return nn.separable_conv2d(x, p["depthwise_kernel"], p["pointwise_kernel"],
                                   p.get("bias"), strides=strides, padding=padding)

    def depthwise_conv(self, x, kernel_size, *, strides=(1, 1),
                       padding="SAME", use_bias=True, name=None):
        """Keras DepthwiseConv2D (depth multiplier 1): param key
        ``depthwise_kernel`` (kh, kw, cin, 1), matching the Keras weight
        layout so conversion stays mechanical."""
        kh, kw = ((kernel_size, kernel_size)
                  if isinstance(kernel_size, int) else kernel_size)
        lname = self.name("depthwise_conv2d", name)
        cin = x.shape[-1]

        def make():
            p = {"depthwise_kernel": glorot_uniform(
                self._next_rng(), (kh, kw, cin, 1), self.param_dtype)}
            if use_bias:
                p["bias"] = self._zeros((cin,))
            return p

        p = self._get(lname, make)
        return nn.depthwise_conv2d(x, p["depthwise_kernel"], p.get("bias"),
                                   strides=strides, padding=padding)

    def bn(self, x, *, scale=True, epsilon=1e-3, momentum=0.99, name=None):
        lname = self.name("batch_normalization", name)
        c = x.shape[-1]

        def make():
            p = {
                "beta": self._zeros((c,)),
                "moving_mean": self._zeros((c,)),
                "moving_var": self._ones((c,)),
            }
            if scale:
                p["gamma"] = self._ones((c,))
            return p

        p = self._get(lname, make)
        if self.train:
            y, new_stats = nn.batch_norm(x, p, train=True, epsilon=epsilon,
                                         momentum=momentum)
            self.bn_updates[lname] = new_stats
            return y
        return nn.batch_norm(x, p, train=False, epsilon=epsilon)

    def conv_bn(self, x, filters, kernel_size, *, strides=(1, 1),
                padding="SAME", epsilon=1e-3, conv_name=None, bn_name=None):
        """A biased convolution followed directly by batch norm: the same
        two parameter dictionaries, in the same order of draws, as ``conv``
        then ``bn``. On moving statistics the norm is a constant
        per-channel scale and shift, so it is folded into the kernel and
        the bias inside the traced program (``nn.conv2d_bn_folded``): all
        six leaves keep their gradients, and the backward pass no longer
        needs the raw convolution output. On batch statistics
        (``train=True``) the scale depends on the data and the pair runs
        as it is written. The store's mode decides; callers set nothing.

        The two counters are bumped while a program is TRACED, once per
        pair, on purpose: they say whether the fold engaged in it."""
        if self.initializing or self.train:
            if self.train:
                _metrics.counter("zoo.conv_bn.unfolded").inc()
            x = self.conv(x, filters, kernel_size, strides=strides,
                          padding=padding, name=conv_name)
            return self.bn(x, epsilon=epsilon, name=bn_name)
        _metrics.counter("zoo.conv_bn.folded").inc()
        pc = self._get(self.name("conv2d", conv_name), None)
        pb = self._get(self.name("batch_normalization", bn_name), None)
        return nn.conv2d_bn_folded(x, pc["kernel"], pc["bias"], pb,
                                   epsilon=epsilon, strides=strides,
                                   padding=padding)

    def norm_stats(self, x, *, name=None):
        """Keras ``Normalization`` layer: (x - mean) / sqrt(variance)
        with mean/variance as (non-trainable) WEIGHTS — EfficientNet
        normalizes inside the model this way. Fresh init is the
        identity (mean 0, variance 1), matching a weights=None keras
        build; pretrained stats arrive via conversion (which also folds
        the imagenet graph's extra 1/sqrt(stddev) rescale into the
        variance — convert.params_from_keras)."""
        lname = self.name("normalization", name)
        c = x.shape[-1]

        def make():
            return {"mean": self._zeros((c,)), "variance": self._ones((c,))}

        p = self._get(lname, make)
        # keras Normalization clamps: maximum(sqrt(var), epsilon) — a
        # zero-variance channel must match the oracle, not produce inf
        return ((x - jnp.asarray(p["mean"], x.dtype))
                / jnp.maximum(jnp.sqrt(jnp.asarray(p["variance"],
                                                   x.dtype)), 1e-7))

    def dense(self, x, units, *, use_bias=True, name=None):
        lname = self.name("dense", name)
        cin = x.shape[-1]

        def make():
            p = {"kernel": glorot_uniform(self._next_rng(), (cin, units),
                                          self.param_dtype)}
            if use_bias:
                p["bias"] = self._zeros((units,))
            return p

        p = self._get(lname, make)
        return nn.dense(x, p["kernel"], p.get("bias"))

    def merged_params(self) -> dict:
        """Params with train-mode BN moving stats folded back in."""
        out = dict(self.params)
        for lname, stats in self.bn_updates.items():
            out[lname] = {**out[lname], **stats}
        return out
