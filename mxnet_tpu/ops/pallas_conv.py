"""Fused BN+ReLU+1x1-conv block with a one-pass Pallas backward (TPU).

The cuDNN-tier custom-kernel layer of the framework (reference analog:
src/operator/nn/cudnn/cudnn_convolution-inl.h + the fused
CuDNNBatchNorm/activation kernels): ResNet bottlenecks chain
``y = conv1x1(relu(batchnorm(u)))`` where the relu activation is private
to the conv.  XLA's conv emitters run this backward as two passes over
the big tensors (a dx fusion with the BN/relu epilogue + a separate dW
fusion).  The Pallas kernel below computes, in ONE stream over
(dy, u):

    d_act   = dy @ W^T
    d_bnout = d_act * (bnout > 0)      (streamed out, bf16)
    dW      = relu(bnout)^T @ dy       (f32 accumulator)
    s1      = sum_rows d_bnout         (BN backward reduction)
    s2      = sum_rows d_bnout * xhat  (BN backward reduction)

so the weight gradient and both BatchNorm backward reductions ride the
same HBM read the data gradient needs.  The BN input gradient
``du = g*inv-scale * (d_bnout - s1/n - xhat*s2/n)`` is pass-2
elementwise work that XLA fuses into the upstream conv's backward, the
same way it fuses the eager path today.

Channel-last only (NHWC: the [N*H*W, C] matmul views are free);
off-TPU the same math runs as plain jnp, so CPU-mesh tests exercise
identical numerics.
"""
from __future__ import annotations

import os
import threading
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kernel_target

_INTERPRET = False  # tests may flip for kernel-path coverage on CPU


def enabled():
    """Is the fused block used by model code for the program being
    traced?  Decision order: the ``pallas_bnreluconv`` autotune
    variant (``stock`` = unfused layer path, ``jnp``/``pallas`` = the
    fused op with that backward) — a tuner ``force`` scope, the
    MXNET_BNRELUCONV_VARIANT hand override, or a cached per-shape
    winner applied by the jit entry points' ``program_scope`` — then
    the legacy MXNET_FUSED_BNRELUCONV env (1 = fused), default OFF.

    The r05 isolation-win/in-step-loss gap (the kernel won the 0.48 vs
    1.18 ms microbench yet lost the step 54.8 vs 46.3 ms to relayout
    copies at the custom-call boundary) is exactly why all THREE arms
    — stock, fused-jnp, fused-pallas — are separate in-step autotune
    entries now: the per-shape call is whatever autotune.json's
    measured winner says for this program signature, not a docstring.

    Read at TRACE time: a hybridized block bakes the choice into its
    cached program, so flipping the env var after the first call does
    not retrace (same as every env-config knob read inside traced
    code).  Toggle before building/hybridizing the net."""
    from ..autotune import variant_choice

    choice = variant_choice("pallas_bnreluconv")
    if choice in ("jnp", "pallas", True):
        return True
    if choice in ("stock", False):
        return False
    env = os.environ.get("MXNET_FUSED_BNRELUCONV")
    if env is not None:
        return env == "1"
    return False


# ------------------------------------------------------------------ bwd
def _bwd_kernel(dy_ref, u_ref, w_ref, g_ref, b_ref, mu_ref, inv_ref,
                dbn_ref, dw_ref, s1_ref, s2_ref,
                accw_ref, acc1_ref, acc2_ref, *, rows_total, block_m):
    i = pl.program_id(0)
    dy = dy_ref[:]                                  # [BM, Co] bf16
    u32 = u_ref[:].astype(jnp.float32)              # [BM, Ci]
    bnout = u32 * g_ref[:] + b_ref[:]
    act = bnout.astype(dy.dtype)                    # matches stored act
    # mask on the CAST value (the layer path casts BN output to the
    # activation dtype before relu); compare in f32 — the v5e VPU has
    # no bf16 compare, and half->f32 is exact so the kink is identical
    mask = act.astype(jnp.float32) > 0.0
    # tail guard: the last block may run past M; masked rows must not
    # contribute to dW/s1/s2 (their dbn writes are masked by pallas)
    row0 = i * block_m
    rows = jax.lax.broadcasted_iota(jnp.int32, (block_m, 1), 0) + row0
    live = rows < rows_total
    mask = jnp.logical_and(mask, live)
    # padded tail rows hold UNSPECIFIED bits: zero every operand that
    # enters a contraction, not just one side — 0 * NaN is NaN and one
    # poisoned row would corrupt dW/s2 for the whole call
    dy = jnp.where(live, dy, jnp.zeros_like(dy))
    d_act = jax.lax.dot_general(
        dy, w_ref[:], dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    d_bnout32 = jnp.where(mask, d_act, 0.0)
    dbn_ref[:] = d_bnout32.astype(dbn_ref.dtype)
    relu_act = jnp.where(mask, act, jnp.zeros_like(act))
    partw = jax.lax.dot_general(
        relu_act, dy, dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)         # [Ci, Co]
    xhat = jnp.where(live, (u32 - mu_ref[:]) * inv_ref[:], 0.0)
    p1 = jnp.sum(d_bnout32, axis=0, keepdims=True)
    p2 = jnp.sum(d_bnout32 * xhat, axis=0, keepdims=True)

    @pl.when(i == 0)
    def _():
        accw_ref[:] = partw
        acc1_ref[:] = p1
        acc2_ref[:] = p2

    @pl.when(i > 0)
    def _():
        accw_ref[:] = accw_ref[:] + partw
        acc1_ref[:] = acc1_ref[:] + p1
        acc2_ref[:] = acc2_ref[:] + p2

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        dw_ref[:] = accw_ref[:]
        s1_ref[:] = acc1_ref[:]
        s2_ref[:] = acc2_ref[:]


def _pick_block_m(M, Ci, Co, esize):
    """Largest block whose full VMEM plan (double-buffered dy/u inputs
    and dbn output, W input, dW output + accumulator) fits the 16MB/core
    budget with headroom; None = no block fits (the resident W, dW
    and accumulator alone fill it at ResNet-50's stage-4 512<->2048
    widths) and the caller declines to the jnp pass, counted.
    ``esize`` is the activation element size (2 for bf16/f16, 4 f32)."""
    budget = 13 * 1024 * 1024
    fixed = (2 * Ci * Co * esize  # W input (double-buffered)
             + 2 * Ci * Co * 4    # dW output buffers
             + Ci * Co * 4        # f32 accumulator scratch
             + 16 * 4 * (Ci + Co))
    for bm in (4096, 2048, 1024, 512, 256):
        need = (fixed
                + 2 * bm * (Co + Ci) * esize  # dy,u in (double-buffered)
                + 2 * bm * Ci * esize)        # dbn out (double-buffered)
        if need <= budget:
            return bm
    return None


def _bwd_pass1_pallas(dy, u, w2, g, b, mu, inv, interpret=None):
    M, Co = dy.shape
    Ci = u.shape[1]
    bm = _pick_block_m(M, Ci, Co, dy.dtype.itemsize)
    if bm is None:
        kernel_target.declined(
            "pallas_bnreluconv", f"no row block fits the VMEM plan at "
            f"{Ci}->{Co} (W, dW and the f32 accumulator stay resident)",
            "jnp", shape=(M, Ci, Co))
        return _bwd_pass1_jnp(dy, u, w2, g, b, mu, inv)
    if interpret is None:
        # an explicitly chosen kernel arm off-TPU (the autotune race on
        # a CPU host) runs in interpret mode — honest, just slow
        interpret = _INTERPRET or not _target_is_tpu(dy)
    grid = ((M + bm - 1) // bm,)
    vec = lambda: pl.BlockSpec((1, Ci), lambda i: (0, 0))
    kern = partial(_bwd_kernel, rows_total=M, block_m=bm)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, Co), lambda i: (i, 0)),
            pl.BlockSpec((bm, Ci), lambda i: (i, 0)),
            pl.BlockSpec((Ci, Co), lambda i: (0, 0)),
            vec(), vec(), vec(), vec(),
        ],
        out_specs=[
            pl.BlockSpec((bm, Ci), lambda i: (i, 0)),
            pl.BlockSpec((Ci, Co), lambda i: (0, 0)),
            vec(), vec(),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, Ci), dy.dtype),
            jax.ShapeDtypeStruct((Ci, Co), jnp.float32),
            jax.ShapeDtypeStruct((1, Ci), jnp.float32),
            jax.ShapeDtypeStruct((1, Ci), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((Ci, Co), jnp.float32),
                        pltpu.VMEM((1, Ci), jnp.float32),
                        pltpu.VMEM((1, Ci), jnp.float32)],
        interpret=interpret,
        # the name a trace and chipbench/conv_kernels.json know it by
        name="bnreluconv_bwd",
    )(dy, u, w2, g, b, mu, inv)


def _bwd_pass1_jnp(dy, u, w2, g, b, mu, inv):
    """Same math, plain jnp (non-TPU backends and the parity tests)."""
    u32 = u.astype(jnp.float32)
    bnout = u32 * g + b
    act = bnout.astype(dy.dtype)
    mask = act.astype(jnp.float32) > 0.0
    d_act = jax.lax.dot_general(
        dy, w2, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    d_bnout32 = jnp.where(mask, d_act, 0.0)
    relu_act = jnp.where(mask, act, jnp.zeros_like(act))
    dw = jax.lax.dot_general(
        relu_act, dy, dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    xhat = (u32 - mu) * inv
    s1 = jnp.sum(d_bnout32, axis=0, keepdims=True)
    s2 = jnp.sum(d_bnout32 * xhat, axis=0, keepdims=True)
    return d_bnout32.astype(dy.dtype), dw, s1, s2


_hint = threading.local()


def _concrete(a):
    return isinstance(a, jax.Array) and not isinstance(a, jax.core.Tracer)


def platform_of(arrs):
    """Platform of the first concrete array in ``arrs`` (None if all
    are tracers/None) — the single-sourced probe the jit entry points
    feed into ``set_trace_platform``."""
    for a in arrs:
        if _concrete(a):
            return next(iter(a.devices())).platform
    return None


def set_trace_platform(platform):
    """Trace-time hint: the platform the program being traced will run
    on ('tpu'/'cpu'/None).  jax traces are platform-agnostic, so a
    kernel-or-jnp choice inside a custom_vjp cannot see the target; the
    jit entry points (gluon's _call_cached) set this from their concrete
    argument devices before tracing."""
    prev = getattr(_hint, "platform", None)
    _hint.platform = platform
    return prev


def _target_is_tpu(x):
    """Best-effort: does the program containing ``x`` run on TPU?
    Order: concrete device of x (eager) -> trace hint (jit cache) ->
    process default device (make_train_step, bench)."""
    if _concrete(x):
        return all(d.platform == "tpu" for d in x.devices())
    hint = getattr(_hint, "platform", None)
    if hint is not None:
        return hint == "tpu"
    return kernel_target.on_tpu()


def _use_pallas(x):
    env = os.environ.get("MXNET_PALLAS")  # None = unset (default on)
    if env == "0":
        return False
    feasible = _target_is_tpu(x) or _INTERPRET
    if env == "1":
        # EXPLICITLY set: the user's hand override beats any cached
        # autotune winner (the same precedence MXNET_CONV_1X1_DOT gets)
        return feasible
    # autotune variant "pallas_bnreluconv": a tuner race or a cached
    # per-program winner overrides the platform heuristic (the r05
    # lesson — isolated kernel wins can be in-step losses, so the
    # kernel-vs-XLA call is owned by in-step timing where available).
    # "pallas" picks the kernel backward, "jnp"/"stock" the jnp math
    # (inside a "stock" program this vjp should never trace, but the
    # jnp pass is the right conservative answer if it does).
    from ..autotune import variant_choice

    choice = variant_choice("pallas_bnreluconv")
    if choice is not None:
        # an explicit kernel choice is feasible ANYWHERE: off-TPU the
        # pallas_call runs in interpret mode (keys carry the platform,
        # so a TPU-recorded winner never leaks onto a CPU program)
        return choice in ("pallas", True)
    return feasible


# ------------------------------------------------------------ composite
def _stats(u2):
    """fp32 batch stats over rows — delegates to ops/nn.py _bn_stats
    (axis=1 on the [M, Ci] view) so the fused path can never diverge
    from the BatchNorm layer's numerics policy."""
    from .nn import _bn_stats

    return _bn_stats(u2, 1)


def _fwd_math(u2, gamma, beta, w2, eps, fix_gamma):
    mean, var = _stats(u2)
    inv = jax.lax.rsqrt(var + eps)
    g32 = jnp.ones_like(inv) if fix_gamma else gamma.astype(jnp.float32)
    scale = inv * g32
    shift = beta.astype(jnp.float32) - mean * scale
    u32 = u2.astype(jnp.float32)
    # cast THEN relu, matching the BatchNorm-layer + Activation path
    act = jnp.maximum((u32 * scale + shift).astype(u2.dtype),
                      jnp.zeros((), u2.dtype))
    # w2 arrives as [Ci, Co]: contract act's channel dim with dim 0
    y = jax.lax.dot_general(
        act, w2, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=u2.dtype)
    return y, mean, var, inv, scale, shift


@partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _bn_relu_conv1x1_flat(u2, gamma, beta, w2, eps, fix_gamma):
    y, mean, var, _, _, _ = _fwd_math(u2, gamma, beta, w2, eps, fix_gamma)
    return y, mean, var


def _brc_fwd(u2, gamma, beta, w2, eps, fix_gamma):
    y, mean, var, inv, scale, shift = _fwd_math(
        u2, gamma, beta, w2, eps, fix_gamma)
    return (y, mean, var), (u2, gamma, w2, mean, inv, scale, shift)


def _brc_bwd(eps, fix_gamma, res, cts):
    u2, gamma, w2, mean, inv, scale, shift = res
    dy, dmean_ct, dvar_ct = cts
    M = u2.shape[0]
    g = scale.reshape(1, -1)
    b = shift.reshape(1, -1)
    mu = mean.reshape(1, -1)
    iv = inv.reshape(1, -1)
    pass1 = _bwd_pass1_pallas if _use_pallas(dy) else _bwd_pass1_jnp
    d_bnout, dw, s1, s2 = pass1(dy, u2, w2, g, b, mu, iv)
    s1 = s1.reshape(-1)
    s2 = s2.reshape(-1)
    # pass 2: elementwise BN input gradient (XLA fuses this into the
    # upstream backward, same as the eager _bn_train_bwd path)
    u32 = u2.astype(jnp.float32)
    xhat = (u32 - mu) * iv
    du32 = g * (d_bnout.astype(jnp.float32)
                - (s1 / M).reshape(1, -1)
                - xhat * (s2 / M).reshape(1, -1))
    if dmean_ct is not None:
        du32 = du32 + (dmean_ct / M).reshape(1, -1)
    if dvar_ct is not None:
        du32 = du32 + (dvar_ct * 2.0 / M).reshape(1, -1) * (u32 - mu)
    dgamma = jnp.zeros_like(gamma) if fix_gamma \
        else (s2 * 1.0).astype(gamma.dtype)
    dbeta = s1.astype(gamma.dtype)
    # dw computed on bf16 act/dy with f32 accumulate; cast to the
    # weight's dtype (f32 master weights keep the f32 value)
    return du32.astype(u2.dtype), dgamma, dbeta, dw.astype(w2.dtype)


_bn_relu_conv1x1_flat.defvjp(_brc_fwd, _brc_bwd)


def fused_bn_relu_conv1x1(u, gamma, beta, weight, *, eps=1e-5,
                          fix_gamma=False):
    """``conv1x1(relu(batchnorm(u)))`` with batch stats, channel-last.

    u: [N, *spatial, Ci]; weight: [Co, *(1,)*nd, Ci] (the channel-last
    O*kI convention of ops/conv.py).  Returns (y [N, *sp, Co],
    batch_mean [Ci], batch_var [Ci]) — the caller folds the batch stats
    into its running averages exactly like the plain BatchNorm layer.
    """
    ci = u.shape[-1]
    co = weight.shape[0]
    lead = u.shape[:-1]
    u2 = u.reshape(-1, ci)
    w2 = weight.reshape(co, ci)
    # kernel contracts over dim 1 of BOTH sides: pass W as [Ci, Co]
    y2, mean, var = _bn_relu_conv1x1_flat(
        u2, gamma, beta, w2.T, float(eps), bool(fix_gamma))
    return y2.reshape(lead + (co,)), mean, var


from .registry import register_op  # noqa: E402


@register_op("_contrib_BNReluConv", num_outputs=3,
             platform_sensitive=True)
def _bn_relu_conv_op(u, gamma, beta, weight, *, eps=1e-5,
                     fix_gamma=False):
    """Registry wrapper so the fused block is reachable as
    ``F._contrib_BNReluConv`` from eager, jit-cached, and symbolic
    paths alike (reference analog: the fused cuDNN norm-activation-conv
    ops registered as contrib operators)."""
    return fused_bn_relu_conv1x1(u, gamma, beta, weight, eps=eps,
                                 fix_gamma=fix_gamma)
