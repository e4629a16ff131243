"""Mamba-2's state-space scan, computed by chunks (arXiv:2405.21060,
section 6: the state-space dual form).

Per head ``p`` (its group is ``p // (heads / groups)``) the recurrence is

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T      y_t = S_t C_t + D x_t

with the state nought at a sequence's start.  Over a chunk of ``L``
positions it splits into a product within the chunk, the masked
``(C B^T) * decay`` applied to ``dt x``, and a state carried between
chunks: every chunk's own contribution to the state at its end, one
small product over the chunks that decays and adds those of the chunks
before (the chunks are few), and the carried state read out by ``C``.  The big products run in the operands' type
on the matrix unit; every decay (``exp`` of a running sum of ``dt A``,
never above 1) and the carried state stay float32.  The backward pass is
the transpose of these products; the caller rematerialises the block
(``gluon.nn.SSDScan`` under a stack's ``jax.checkpoint``) rather than
keep the ``(chunks, heads, L, L)`` masks.

The products are ``lax.dot_general`` calls, each under a comment with its
``einsum`` spelling: ``jnp.einsum`` and ``jnp.cumsum`` wrap their work in
a scope of their own name, under which a profile would file the scan's
time instead of under the block that called it.  The running sum within
a chunk is a product too, with a triangle of ones (float32 operands at
``Precision.HIGHEST``, so every term enters whole): a v5e runs
``lax.cumsum`` over a chunk's positions as a window sum, some 1.9 ms a
pass at Nemotron-3-Nano's shape, where the product takes under 0.03 ms.
The running sum over the chunks (a few dozen) stays ``lax.cumsum``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _dot(a, b, contract, batch, precision=None):
    """``lax.dot_general`` with a float32 result: the batch dimensions
    first, then ``a``'s free ones, then ``b``'s."""
    return jax.lax.dot_general(a, b, (contract, batch), precision=precision,
                               preferred_element_type=jnp.float32)


def ssd_chunked_scan(x, dt, a, b, c, d, *, chunk):
    """``y`` (batch, length, heads, head_dim) of the recurrence above.

    x: (batch, length, heads, head_dim); dt: (batch, length, heads),
    already positive, float32; a: (heads,), negative, float32; b, c:
    (batch, length, groups, state); d: (heads,).  A length that is no
    multiple of ``chunk`` is padded with steps of ``dt`` 0, which leave
    the state as it is, and cut again.
    """
    bsz, length, heads, hdim = x.shape
    groups, state = b.shape[2], b.shape[3]
    rep = heads // groups
    cdt = x.dtype
    pad = (-length) % chunk
    if pad:
        x, dt, b, c = (jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
                       for t in (x, dt, b, c))
    n = (length + pad) // chunk
    f32 = jnp.float32
    # (batch b, chunks n, positions l or s, groups g, heads a group r, ...)
    xs = x.reshape(bsz, n, chunk, groups, rep, hdim)
    dts = dt.astype(f32).reshape(bsz, n, chunk, groups, rep)
    bs = b.reshape(bsz, n, chunk, groups, state)
    cs = c.reshape(bsz, n, chunk, groups, state)
    # "bnsgr,sl->bngrl": the running sum, positions last
    upper = jnp.triu(jnp.ones((chunk, chunk), f32))  # [s, l]: s <= l
    cum = _dot(dts * a.astype(f32).reshape(groups, rep), upper,
               ((2,), (0,)), ((), ()), precision=jax.lax.Precision.HIGHEST)
    xdt = xs.astype(f32) * dts[..., None]

    # within a chunk: y_t += sum_{s<=t} exp(cum_t - cum_s) (C_t . B_s) dt_s x_s
    # "bnlgk,bnsgk->bngls"
    scores = _dot(cs, bs, ((4,), (4,)), ((0, 1, 3), (0, 1, 3)))
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(
        lower, cum[..., :, None] - cum[..., None, :], -jnp.inf))
    mask = scores[:, :, :, None] * decay
    # "bngrls,bnsgrp->bngrlp"
    y = _dot(mask.astype(cdt), xdt.astype(cdt), ((5,), (2,)),
             ((0, 1, 2, 3), (0, 1, 3, 4)))

    # each chunk's own contribution to the state at its end
    # "bnsgk,bnsgrp->bngkrp"
    to_end = jnp.moveaxis(jnp.exp(cum[..., -1:] - cum), -1, 2)
    own = _dot(bs, (xdt * to_end[..., None]).astype(cdt), ((2,), (2,)),
               ((0, 1, 3), (0, 1, 3)))
    # the state chunk n starts from: what the chunks m < n left, each
    # decayed by the chunks between ("bgrnm,bmgkrp->bgrnkp", float32)
    whole = jnp.moveaxis(cum[..., -1], 1, -1)  # (b, g, r, n): a chunk's sum
    upto = jax.lax.cumsum(whole, axis=3)
    earlier = jnp.tril(jnp.ones((n, n), bool), -1)
    between = jnp.exp(jnp.where(
        earlier, (upto - whole)[..., :, None] - upto[..., None, :], -jnp.inf))
    before = _dot(between, own, ((4,), (1,)), ((0, 1, 2), (0, 2, 4)),
                  precision=jax.lax.Precision.HIGHEST)
    # "bnlgk,bgrnkp->bnglrp", then decayed down to each position
    carried = _dot(cs, before.astype(cdt), ((4,), (4,)),
                   ((0, 1, 3), (0, 3, 1)))
    y = y + carried.transpose(0, 1, 2, 4, 3, 5) * jnp.exp(cum)[..., None]
    y = y.transpose(0, 1, 4, 2, 3, 5) \
        + xs.astype(f32) * d.astype(f32).reshape(groups, rep, 1)
    y = y.reshape(bsz, length + pad, heads, hdim)[:, :length]
    return y.astype(cdt)
