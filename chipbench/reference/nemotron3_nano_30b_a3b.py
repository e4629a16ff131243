"""NVIDIA-Nemotron-3-Nano-30B-A3B (``nemotron_h``), training mode, plain
float32 ``jax.numpy`` at ``HIGHEST``: one chip's share of a stated
deployment (``chipbench/configs/nemotron3_nano_30b_a3b.json``).

Written from the equations, not from the program (Nemotron-H,
arXiv:2504.03624; Mamba-2, arXiv:2405.21060; the family's public
``config.json``):

- *Stack.*  ``h = Embedding(ids)``; for each letter of ``arch["pattern"]``
  ``h = h + mixer(RMSNorm(h))``; ``logits = head(RMSNorm(h))``, the head
  untied; no bias anywhere but the convolution.  ``RMSNorm(x) = x *
  rsqrt(mean(x^2) + eps) * w``.
- *M, Mamba-2.*  ``[z | xBC | dt] = in_proj(u)``; ``xBC = silu(conv(xBC) +
  b)``, the convolution causal and depthwise along the sequence, ``y_t =
  sum_j w[:, j] xBC_{t - (K - 1) + j}``; ``[x | B | C] = xBC``; ``dt =
  softplus(dt + dt_bias)``, ``A = -exp(A_log)`` one a head; per head ``p``
  (its group is ``p // (heads / groups)``) ``S_t = exp(dt_t A) S_{t-1} +
  dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t``, the state nought at a
  sequence's start; ``y = groupRMSNorm(y * silu(z)) * w`` over ``groups``
  equal parts of the channels; ``out_proj(y)``.  The scan is the
  recurrence itself, a ``lax.scan`` over positions.
- *E, mixture of experts.*  ``s = sigmoid(W_r u)`` over ALL the layer's
  experts; the ``k`` largest of ``s + b`` (the correction bias enters the
  choice alone); weights ``scale * s_e / (sum of the chosen s + 1e-20)``;
  an expert is ``down_e(relu(up_e u)^2)``; ``out = sum over the chosen
  experts that this chip HOLDS (``arch["experts_held"]``, a run of ids) +
  shared(u)``: what the absent experts would add is left out.  The
  experts are a loop over the held ids, each on all tokens, times the
  token's weight for that expert (nought where it was not chosen).
- *\\*, attention.*  ``q, k, v`` projections; each key/value head serves
  ``heads / kv_heads`` query heads; causal softmax of ``q k^T /
  sqrt(head_dim)``; ``o_proj``.  No positional embedding (the config's
  ``assumed`` says why).

So that it fits beside the optimizer's copies (``kinds/train_closed.py``
``reference_steps``), and with the same mathematics: each layer is
recomputed in the backward pass (``jax.checkpoint``), the scan is recomputed
by blocks of ``SCAN_BLOCK`` positions, each held expert's product too, and
attention goes by blocks of ``QUERY_BLOCK`` queries, one after another.  Under the ``float8`` control the operands of
every dense product, of attention's two products and of the experts are
snapped (``refmath``); the router, the scan and the norms stay float32, as
the configuration states them.

The state is a flat list in the order the program's net holds it: see
:func:`param_specs`.  The forward pass moves none of it.
"""
import jax
import jax.numpy as jnp
from jax import lax

from chipbench import refmath as rm

_HIGHEST = lax.Precision.HIGHEST
SCAN_BLOCK = 64
QUERY_BLOCK = 512

#: leaves of one layer by its letter, beside its norm's weight
_LEAVES = {"M": 8, "E": 6, "*": 4}


def _sizes(arch):
    inner = arch["mamba_num_heads"] * arch["mamba_head_dim"]
    bc = arch["n_groups"] * arch["ssm_state_size"]
    return inner, bc


def param_specs(arch, vocab, classes):
    """[(kind, shape)] of every array of the net's state."""
    width = arch["hidden_size"]
    inner, bc = _sizes(arch)
    heads, taps = arch["mamba_num_heads"], arch["conv_kernel"]
    first, end = arch["experts_held"]
    held, hidden = end - first, arch["moe_intermediate_size"]
    shared = arch["moe_shared_expert_intermediate_size"]
    q = arch["num_attention_heads"] * arch["head_dim"]
    kv = arch["num_key_value_heads"] * arch["head_dim"]
    specs = [("embedding", (vocab, width))]
    for letter in arch["pattern"]:
        specs.append(("gamma", (width,)))
        if letter == "M":
            specs += [("conv", (inner + 2 * bc, taps)),
                      ("bias", (inner + 2 * bc,)), ("gamma", (inner,)),
                      ("dense", (2 * inner + 2 * bc + heads, width)),
                      ("beta", (heads,)),   # dt_bias
                      ("beta", (heads,)),   # A_log
                      ("gamma", (heads,)),  # D
                      ("dense", (width, inner))]
        elif letter == "E":
            specs += [("dense", (arch["n_routed_experts_published"], width)),
                      ("bias", (arch["n_routed_experts_published"],)),
                      ("dense", (held * hidden, width)),
                      ("dense", (held * width, hidden)),
                      ("dense", (shared, width)), ("dense", (width, shared))]
        elif letter == "*":
            specs += [("dense", (q, width)), ("dense", (kv, width)),
                      ("dense", (kv, width)), ("dense", (width, q))]
        else:
            raise ValueError(f"no layer for the letter {letter!r}")
    return specs + [("gamma", (width,)), ("dense", (classes, width))]


def rms_norm(x, w, eps, groups=1):
    parts = x.reshape(x.shape[:-1] + (groups, x.shape[-1] // groups))
    parts = parts * lax.rsqrt(
        jnp.mean(jnp.square(parts), axis=-1, keepdims=True) + eps)
    return parts.reshape(x.shape) * w


def relu2_mlp(x, up, down, precision="float32"):
    return rm.dense(jnp.square(jax.nn.relu(rm.dense(x, up, None, precision))),
                    down, None, precision)


# --------------------------------------------------------------- Mamba-2
def scan(x, dt, a, b, c, d):
    """The recurrence, position by position.  x (batch, length, heads,
    head_dim), dt (batch, length, heads) positive, a (heads,) negative,
    b and c (batch, length, groups, state), d (heads,)."""
    bsz, length, heads, hdim = x.shape
    rep = heads // b.shape[2]

    def step(s, at):
        x_t, dt_t, b_t, c_t = at
        b_h, c_h = (jnp.repeat(t, rep, axis=1) for t in (b_t, c_t))
        s = jnp.exp(dt_t * a)[..., None, None] * s \
            + (dt_t[..., None] * x_t)[..., None] * b_h[:, :, None, :]
        y = jnp.einsum("bhpn,bhn->bhp", s, c_h, precision=_HIGHEST)
        return s, y + d[:, None] * x_t

    pad = (-length) % SCAN_BLOCK  # steps of dt 0 leave the state alone
    blocks = [jnp.moveaxis(jnp.pad(t, [(0, 0), (0, pad)]
                                   + [(0, 0)] * (t.ndim - 2)), 1, 0)
              for t in (x, dt, b, c)]
    blocks = [t.reshape((-1, SCAN_BLOCK) + t.shape[1:]) for t in blocks]
    s0 = jnp.zeros((bsz, heads, hdim, b.shape[3]), jnp.float32)
    _, ys = lax.scan(jax.checkpoint(lambda s, blk: lax.scan(step, s, blk)),
                     s0, tuple(blocks))
    ys = ys.reshape((length + pad,) + ys.shape[2:])[:length]
    return jnp.moveaxis(ys, 0, 1)


def mamba_layer(p, u, arch, precision="float32"):
    """One sequence after another (``lax.map``): the float32 temporaries
    of two at once do not fit beside the optimizer's copies."""
    return lax.map(jax.checkpoint(
        lambda row: _mamba_rows(p, row[None], arch, precision)[0]), u)


def _mamba_rows(p, u, arch, precision):
    conv_w, conv_b, norm_w, in_w, dt_bias, a_log, d, out_w = p
    inner, bc = _sizes(arch)
    heads, groups = arch["mamba_num_heads"], arch["n_groups"]
    bsz, length = u.shape[0], u.shape[1]
    z, xbc, dt = jnp.split(rm.dense(u, in_w, None, precision),
                           [inner, 2 * inner + 2 * bc], axis=-1)
    taps = conv_w.shape[1]
    padded = jnp.pad(xbc, [(0, 0), (taps - 1, 0), (0, 0)])
    xbc = jax.nn.silu(sum(padded[:, j:j + length] * conv_w[:, j]
                          for j in range(taps)) + conv_b)
    x, b, c = jnp.split(xbc, [inner, inner + bc], axis=-1)
    y = scan(x.reshape(bsz, length, heads, -1),
             jax.nn.softplus(dt + dt_bias), -jnp.exp(a_log),
             b.reshape(bsz, length, groups, -1),
             c.reshape(bsz, length, groups, -1), d)
    y = rms_norm(y.reshape(bsz, length, inner) * jax.nn.silu(z), norm_w,
                 arch["norm_eps"], groups)
    return rm.dense(y, out_w, None, precision)


# ---------------------------------------------------- mixture of experts
def route(u, router, bias, arch):
    """``(ids, weights)`` of every token's chosen experts, over all."""
    s = jax.nn.sigmoid(jnp.matmul(u, router.T, precision=_HIGHEST))
    _, ids = lax.top_k(s + bias, arch["num_experts_per_tok"])
    chosen = jnp.take_along_axis(s, ids, axis=-1)
    return ids, arch["routed_scaling_factor"] * chosen / (
        jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)


def routed_part(u, ids, weights, up, down, arch, precision="float32"):
    """What the held experts give: each on all tokens, times the token's
    weight for it."""
    first, end = arch["experts_held"]
    hidden, width = arch["moe_intermediate_size"], u.shape[-1]
    def one(u, ids, weights, up_e, down_e, e):
        w_e = jnp.sum(jnp.where(ids == e, weights, 0.0), axis=-1)
        return w_e[..., None] * relu2_mlp(u, up_e, down_e, precision)

    out = jnp.zeros_like(u)
    for j, e in enumerate(range(first, end)):
        out = out + jax.checkpoint(one, static_argnums=5)(
            u, ids, weights, up[j * hidden:(j + 1) * hidden],
            down[j * width:(j + 1) * width], e)
    return out


def moe_layer(p, u, arch, precision="float32"):
    router, bias, up, down, shared_up, shared_down = p
    ids, weights = route(u, router, bias, arch)
    return routed_part(u, ids, weights, up, down, arch, precision) \
        + relu2_mlp(u, shared_up, shared_down, precision)


# ------------------------------------------------------------- attention
def attention_layer(p, u, arch, precision="float32"):
    wq, wk, wv, wo = p
    heads, kv_heads = arch["num_attention_heads"], arch["num_key_value_heads"]
    dim = arch["head_dim"]
    bsz, length = u.shape[0], u.shape[1]
    q = rm.dense(u, wq, None, precision).reshape(bsz, length, heads, dim)
    k, v = (jnp.repeat(
        rm.dense(u, w, None, precision).reshape(bsz, length, kv_heads, dim),
        heads // kv_heads, axis=2) for w in (wk, wv))

    @jax.checkpoint
    def block(at):
        q_blk, start = at
        q_op, k_op = rm._operands(precision, q_blk, k)
        s = rm._result(precision, jnp.einsum(
            "bqhd,bkhd->bhqk", q_op, k_op, precision=_HIGHEST)) / dim ** 0.5
        seen = jnp.arange(length)[None, :] \
            <= (start + jnp.arange(q_blk.shape[1]))[:, None]
        prob = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        p_op, v_op = rm._operands(precision, prob, v)
        return rm._result(precision, jnp.einsum(
            "bhqk,bkhd->bqhd", p_op, v_op, precision=_HIGHEST))

    # one block of queries after another (a ``lax.map``, so that no two
    # blocks' scores are alive at once); a short sequence is one block
    size = min(QUERY_BLOCK, length)
    pad = (-length) % size
    blocks = jnp.pad(q, [(0, 0), (0, pad), (0, 0), (0, 0)]).reshape(
        bsz, -1, size, heads, dim)
    out = lax.map(block, (jnp.moveaxis(blocks, 1, 0),
                          jnp.arange(0, length + pad, size)))
    out = jnp.moveaxis(out, 0, 1).reshape(bsz, length + pad, heads * dim)
    out = out[:, :length]
    return rm.dense(out, wo, None, precision)


_LAYERS = {"M": mamba_layer, "E": moe_layer, "*": attention_layer}


def forward(params, x, arch, precision="float32"):
    """``(logits, moved)`` of a batch of token ids ``x`` (batch, length);
    ``moved`` is empty: no layer of this net keeps statistics."""
    it = iter(params)
    h = jnp.take(next(it), x.astype(jnp.int32), axis=0)
    for letter in arch["pattern"]:
        def layer(h, norm_w, *p, fn=_LAYERS[letter]):
            return h + fn(p, rms_norm(h, norm_w, arch["norm_eps"]), arch,
                          precision)
        h = jax.checkpoint(layer)(
            h, next(it), *[next(it) for _ in range(_LEAVES[letter])])
    h = rms_norm(h, next(it), arch["norm_eps"])
    return rm.dense(h, next(it), None, precision), {}
