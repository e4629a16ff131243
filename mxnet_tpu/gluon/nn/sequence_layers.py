"""Layers of hybrid sequence models: RMSNorm, a Mamba-2 mixer with its
state-space scan, grouped-query attention, a squared-ReLU MLP, a
mixture of experts that computes its chip's share, and the stack that
applies such mixers by a pattern.

The reference has none of these (its sequence story is ``gluon.rnn``).
Every part that a profile should tell apart is a block of its own, so
that the compiled step's operations carry its name: the mixer and its
scan, the router, the routed bank, the shared expert, the attention
block.  Inputs are ``(batch, length, width)``; nothing here has a bias
but the mixer's convolution.  Each block's mathematics is a pure
function of jax arrays (``_op``): it runs eagerly under autograd and
inside a traced step alike, through ``nd.invoke``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ... import ndarray as nd
from ... import profiler as _profiler
from ...ops import flash_attention as _fa
from ...ops import routed_experts as _re
from ...ops import ssd as _ssd
from ...ops.registry import OpDef
from ..block import HybridBlock, _collect_all_params, _swap_param_values
from .basic_layers import Dense, Embedding

__all__ = ["RMSNorm", "SquaredReLUMLP", "SSDScan", "Mamba2Mixer",
           "GQAttention", "MoERouter", "RoutedExperts", "SparseMoE",
           "HybridStack"]


def _op(fn=None, *, num_outputs=1):
    """``fn`` as an operator that ``nd.invoke`` takes, kept out of the
    registry: these are blocks' bodies, not ``mx.nd`` functions."""
    if fn is None:
        return lambda f: _op(f, num_outputs=num_outputs)
    return OpDef(fn.__name__.lstrip("_"), fn, num_outputs=num_outputs)


def _rms(x, gamma, eps, groups=1):
    """``x * rsqrt(mean(x^2) + eps) * gamma`` over the last axis (over
    each of its ``groups`` equal parts), statistics in float32."""
    x32 = x.astype(jnp.float32)
    parts = x32.reshape(x.shape[:-1] + (groups, x.shape[-1] // groups))
    parts = parts * jax.lax.rsqrt(
        jnp.mean(jnp.square(parts), axis=-1, keepdims=True) + eps)
    return (parts.reshape(x.shape) * gamma.astype(jnp.float32)).astype(
        x.dtype)


@_op
def _rms_norm(x, gamma, *, eps, groups=1):
    return _rms(x, gamma, eps, groups)


@_op
def _gated_rms_norm(y, z, gamma, *, eps, groups):
    """Mamba-2's output norm: ``groupRMSNorm(y * silu(z)) * gamma``."""
    gated = y.reshape(z.shape).astype(jnp.float32) \
        * jax.nn.silu(z.astype(jnp.float32))
    return _rms(gated, gamma, eps, groups).astype(y.dtype)


class RMSNorm(HybridBlock):
    """``x * rsqrt(mean(x^2) + epsilon) * gamma`` over the last axis."""

    def __init__(self, epsilon=1e-5, in_channels=0, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        self._epsilon = epsilon
        with self.name_scope():
            self.gamma = self.params.get(
                "gamma", shape=(in_channels,), init="ones",
                allow_deferred_init=True)

    def _infer_param_shapes(self, x, *args):
        self.gamma.shape = (x.shape[-1],)

    def hybrid_forward(self, F, x, gamma):
        return nd.invoke(_rms_norm, [x, gamma], eps=self._epsilon)


@_op
def _relu2(x):
    return jnp.square(jax.nn.relu(x))


class SquaredReLUMLP(HybridBlock):
    """``down(relu(up x)^2)``: no gate, no bias."""

    def __init__(self, units, hidden, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.up = Dense(hidden, use_bias=False, flatten=False,
                            in_units=units)
            self.down = Dense(units, use_bias=False, flatten=False,
                              in_units=hidden)

    def hybrid_forward(self, F, x):
        return self.down(nd.invoke(_relu2, [self.up(x)]))


# ------------------------------------------------------------- Mamba-2
@_op
def _ssd_scan(x, dt, a_log, b, c, d, dt_bias, *, chunk):
    """The scan over ``x`` (batch, length, heads, head_dim) with ``dt =
    softplus(dt + dt_bias)`` and ``A = -exp(A_log)``, both float32."""
    dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
    return _ssd.ssd_chunked_scan(
        x, dt, -jnp.exp(a_log.astype(jnp.float32)), b, c, d, chunk=chunk)


class SSDScan(HybridBlock):
    """Mamba-2's selective state-space scan by chunks
    (``ops/ssd.py``).  It owns the per-head ``A_log``, ``D`` and
    ``dt_bias``, which stay float32 under mixed precision."""

    def __init__(self, heads, chunk, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._chunk = chunk
        with self.name_scope():
            self.dt_bias = self.params.get("dt_bias", shape=(heads,),
                                           init="zeros")
            self.A_log = self.params.get("A_log", shape=(heads,),
                                         init="zeros")
            self.D = self.params.get("D", shape=(heads,), init="ones")

    def hybrid_forward(self, F, x, dt, b, c, dt_bias, A_log, D):
        return nd.invoke(_ssd_scan, [x, dt, A_log, b, c, D, dt_bias],
                         chunk=self._chunk)


@_op(num_outputs=5)
def _split_conv_silu(proj, weight, bias, *, heads, head_dim, groups, state):
    """``(z, x, B, C, dt)`` of ``[z | xBC | dt] = proj``: ``xBC`` goes
    through ``silu`` of the causal depthwise convolution along the
    sequence, ``y_t = sum_j weight[:, j] xBC_{t - (K - 1) + j} + bias``,
    and is cut into ``x`` (batch, length, heads, head_dim) and ``B``,
    ``C`` (batch, length, groups, state)."""
    inner, bc = heads * head_dim, groups * state
    bsz, length = proj.shape[0], proj.shape[1]
    z, xbc, dt = jnp.split(proj, [inner, 2 * inner + 2 * bc], axis=-1)
    k = weight.shape[1]
    padded = jnp.pad(xbc, [(0, 0), (k - 1, 0), (0, 0)])
    y = sum(padded[:, j:j + length] * weight[:, j].astype(xbc.dtype)
            for j in range(k))
    xbc = jax.nn.silu(y + bias.astype(xbc.dtype))
    x, b, c = jnp.split(xbc, [inner, inner + bc], axis=-1)
    return (z, x.reshape(bsz, length, heads, head_dim),
            b.reshape(bsz, length, groups, state),
            c.reshape(bsz, length, groups, state), dt)


class Mamba2Mixer(HybridBlock):
    """Mamba-2 (arXiv:2405.21060): ``[z | xBC | dt] = in_proj(u)``,
    ``xBC = silu(causal depthwise conv(xBC) + b)``, ``[x | B | C]``, the
    scan, ``groupRMSNorm(y * silu(z))``, ``out_proj``."""

    def __init__(self, units, heads, head_dim, groups, state, conv_kernel=4,
                 chunk=128, epsilon=1e-5, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        inner = heads * head_dim
        conv_width = inner + 2 * groups * state
        self._shape = (heads, head_dim, groups, state)
        self._epsilon = epsilon
        with self.name_scope():
            self.in_proj = Dense(inner + conv_width + heads, use_bias=False,
                                 flatten=False, in_units=units)
            self.conv_weight = self.params.get(
                "conv_weight", shape=(conv_width, conv_kernel))
            self.conv_bias = self.params.get(
                "conv_bias", shape=(conv_width,), init="zeros")
            self.scan = SSDScan(heads, chunk)
            self.norm_gamma = self.params.get(
                "norm_gamma", shape=(inner,), init="ones")
            self.out_proj = Dense(units, use_bias=False, flatten=False,
                                  in_units=inner)

    def hybrid_forward(self, F, u, conv_weight, conv_bias, norm_gamma):
        heads, head_dim, groups, state = self._shape
        z, x, b, c, dt = nd.invoke(
            _split_conv_silu, [self.in_proj(u), conv_weight, conv_bias],
            heads=heads, head_dim=head_dim, groups=groups, state=state)
        y = nd.invoke(_gated_rms_norm, [self.scan(x, dt, b, c), z, norm_gamma],
                      eps=self._epsilon, groups=groups)
        return self.out_proj(y)


# ----------------------------------------------------------- attention
@_op
def _gq_attention(q, k, v, *, heads, kv_heads, scope=None):
    """Causal attention of ``heads`` query heads over ``kv_heads``
    key/value heads (each serves ``heads / kv_heads`` of them), through
    ``ops/flash_attention.py``, which reads each key/value head once for
    its group; no positional embedding.  ``scope`` names the backward
    kernels."""
    bsz, length = q.shape[0], q.shape[1]
    dim = q.shape[2] // heads

    def split(t, n):
        return t.reshape(bsz, length, n, dim).transpose(0, 2, 1, 3)

    out = _fa.flash_attention(split(q, heads), split(k, kv_heads),
                              split(v, kv_heads), causal=True, scope=scope)
    return out.transpose(0, 2, 1, 3).reshape(bsz, length, heads * dim)


class GQAttention(HybridBlock):
    """Grouped-query causal self-attention: ``o_proj(attention(q_proj x,
    k_proj x, v_proj x))`` with ``kv_heads`` key/value heads."""

    def __init__(self, units, heads, kv_heads, head_dim, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        self._heads, self._kv_heads = heads, kv_heads
        with self.name_scope():
            self.q_proj, self.k_proj, self.v_proj = (
                Dense(n * head_dim, use_bias=False, flatten=False,
                      in_units=units) for n in (heads, kv_heads, kv_heads))
            self.o_proj = Dense(units, use_bias=False, flatten=False,
                                in_units=heads * head_dim)

    def hybrid_forward(self, F, x):
        out = nd.invoke(
            _gq_attention, [self.q_proj(x), self.k_proj(x), self.v_proj(x)],
            heads=self._heads, kv_heads=self._kv_heads, scope=self.name)
        return self.o_proj(out)


# -------------------------------------------------- mixture of experts
@_op(num_outputs=2)
def _route(u, router, bias, *, k, scale):
    tokens = u.reshape(-1, u.shape[-1])
    return _re.sigmoid_topk_route(tokens, router, bias, k=k, scale=scale)


@_op
def _routed(u, ids, weights, up, down, *, held, experts, scope):
    tokens = u.reshape(-1, u.shape[-1])
    return _re.routed_experts(tokens, ids, weights, up, down, held=held,
                              experts=experts, scope=scope).reshape(u.shape)


class MoERouter(HybridBlock):
    """Scores every token against all ``experts`` (``sigmoid`` of a
    float32 product), chooses the ``k`` largest of score plus
    ``correction_bias`` and weighs them ``scale * s / sum of the chosen
    s``.  Both leaves stay float32 under mixed precision; the bias enters
    the choice alone (the balancing rule that moves it is a training
    recipe's, not this block's)."""

    def __init__(self, units, experts, k, scale, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._k, self._scale = k, scale
        with self.name_scope():
            self.router_weight = self.params.get(
                "router_weight", shape=(experts, units))
            self.correction_bias = self.params.get(
                "correction_bias", shape=(experts,), init="zeros")

    def hybrid_forward(self, F, u, router_weight, correction_bias):
        return nd.invoke(_route, [u, router_weight, correction_bias],
                         k=self._k, scale=self._scale)


class RoutedExperts(HybridBlock):
    """The experts ``experts_held`` (a ``range`` of ids) of a bank of
    ``experts`` (all are held where it is not given) of
    ``down_e(relu(up_e u)^2)``: each bank one 2-D leaf, an expert's rows
    together.  Computes every assignment to a held expert, whatever the
    imbalance: a grouped product over the routed rows alone, or, in a
    step whose rows exceed the buffer that shapes fix, every held expert
    over every token (``ops/routed_experts.py``); and counts them."""

    #: what a step's forward pass counts here (``profiler.count``)
    step_counters = {"moe_assignments": "sum", "moe_assignments_held": "sum",
                     "moe_rows_max": "max", "moe_dropped": "sum",
                     "moe_layers": "sum", "moe_layers_grouped": "sum"}

    def __init__(self, units, hidden, experts_held, experts=None,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        held = experts_held
        if not isinstance(held, range) or held.step != 1 or not len(held):
            raise ValueError(f"experts_held must be a run of ids: {held}")
        self._held = (held.start, len(held))
        self._experts = experts
        with self.name_scope():
            self.up_weight = self.params.get(
                "up_weight", shape=(len(held) * hidden, units))
            self.down_weight = self.params.get(
                "down_weight", shape=(len(held) * units, hidden))

    def hybrid_forward(self, F, u, ids, weights, up_weight, down_weight):
        return nd.invoke(_routed, [u, ids, weights, up_weight, down_weight],
                         held=self._held, experts=self._experts,
                         scope=self.name)


class SparseMoE(HybridBlock):
    """A sigmoid-routed mixture of experts beside a shared expert, as one
    chip of an expert-parallel deployment computes it: ``sum over the
    chosen experts THAT ARE HELD HERE + shared(u)``.  The chosen experts
    that other chips hold add nothing here."""

    def __init__(self, units, experts, k, hidden, shared_hidden,
                 experts_held=None, scale=1.0, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.router = MoERouter(units, experts, k, scale)
            self.experts = RoutedExperts(
                units, hidden, experts_held or range(experts), experts)
            self.shared = SquaredReLUMLP(units, shared_hidden)

    def hybrid_forward(self, F, u):
        ids, weights = self.router(u)
        return self.experts(u, ids, weights) + self.shared(u)


# --------------------------------------------------------------- stack
class ResidualLayer(HybridBlock):
    """``h + mixer(RMSNorm(h))``."""

    def __init__(self, units, mixer_fn, epsilon, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.norm = RMSNorm(epsilon, in_channels=units)
            self.mixer = mixer_fn()

    def hybrid_forward(self, F, h):
        return h + self.mixer(self.norm(h))


class HybridStack(HybridBlock):
    """``h = Embedding(ids)``; for each letter of ``pattern`` ``h = h +
    mixer(RMSNorm(h))`` with the mixer that ``mixers[letter]()`` builds;
    ``head(RMSNorm(h))``, the head untied and without bias.  With
    ``remat`` each layer is rematerialised in the backward pass
    (``jax.checkpoint`` round the layer's call while a step is traced)."""

    def __init__(self, vocab, units, pattern, mixers, epsilon=1e-5,
                 remat=False, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._remat = remat
        with self.name_scope():
            self.embed = Embedding(vocab, units)
            self.layers = []
            for letter in pattern:
                layer = ResidualLayer(units, mixers[letter], epsilon)
                self.register_child(layer)
                self.layers.append(layer)
            self.norm = RMSNorm(epsilon, in_channels=units)
            self.head = Dense(vocab, use_bias=False, flatten=False,
                              in_units=units)

    def _rematerialised(self, layer, h):
        """``layer(h)`` under ``jax.checkpoint``.  The layer's parameters
        are arguments of the rematerialised function, and what its blocks
        count leaves it as an output."""
        leaves = _collect_all_params(layer)
        hows = {}

        @jax.checkpoint
        def run(values, x):
            saved = _swap_param_values(layer, values)
            try:
                with _profiler.counting() as counted:
                    out = layer(nd.NDArray(x))._data
            finally:
                _swap_param_values(layer, saved)
            hows.update((k, how) for k, (how, _) in counted.items())
            return out, {k: v for k, (_, v) in counted.items()}

        out, counted = run([p.data()._data for p in leaves], h._data)
        _profiler.count_all({k: (hows[k], v) for k, v in counted.items()})
        return nd.NDArray(out)

    def hybrid_forward(self, F, ids):
        h = self.embed(ids)
        traced = isinstance(h._data, jax.core.Tracer)
        for layer in self.layers:
            h = self._rematerialised(layer, h) if self._remat and traced \
                else layer(h)
        return self.head(self.norm(h))
