"""Mixture-of-experts with expert parallelism over the device mesh.

The reference has no MoE (its sparse scaling story is row_sparse
embeddings over the parameter server); on TPU the equivalent
capability-scaling axis is expert parallelism: E experts' weights live
stacked on a leading axis sharded over an 'expert' mesh axis, tokens
are routed with capacity-bounded dense dispatch/combine einsums (the
GShard/Switch formulation — fixed shapes, so XLA can tile it onto the
MXU and insert the all-to-all-style collectives itself), and dropped
tokens fall through a residual path.

What this is beside ``gluon.nn.SparseMoE`` (``ops/routed_experts.py``):
this module is a capacity-bounded dispatch that DROPS what exceeds an
expert's capacity, over a mesh axis, reached by no gluon block; the
gluon block is one chip's share of an expert-parallel layer, told which
experts it holds, with no capacity and no drop (the held experts as one
wide MLP gated by the routing weights), and is what the zoo's language
models train through.

Public API:
  top_k_gating(logits, k, capacity)       — dispatch/combine tensors
  moe_apply(expert_fn, stacked_params, gate_w, x, ...)
      — full MoE layer; with ``mesh`` the expert axis is sharded and
        the dispatch/combine contractions ride the mesh collectives.

Note: ``expert_fn`` (and pipeline ``stage_fn``) are compile-cache keys —
pass a *stable* callable (module-level function or a lambda created
once), not a fresh lambda per call, or every invocation recompiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = ["top_k_gating", "moe_apply", "expert_capacity"]


def expert_capacity(n_tokens, n_experts, k=1, capacity_factor=1.25):
    """Per-expert token capacity (GShard: k * T/E * factor, >=1)."""
    return max(1, int(n_tokens * k * capacity_factor / n_experts))


def top_k_gating(logits, k, capacity):
    """Capacity-bounded top-k gating.

    logits: (T, E) router scores.  Returns
      dispatch: (T, E, C) 0/1 — token t goes to expert e at slot c
      combine:  (T, E, C) float — gate-probability weights for the
                return path (rows of dropped tokens are all-zero).
    Fixed shapes throughout: position-in-expert comes from a cumsum
    over the one-hot assignment, tokens past ``capacity`` are dropped
    (standard Switch/GShard semantics).
    """
    t_, e_ = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    dispatch = jnp.zeros((t_, e_, capacity), jnp.float32)
    combine = jnp.zeros((t_, e_, capacity), jnp.float32)
    # iterate the (small, static) k choices; mask out used experts
    masked = probs
    # running per-expert fill count carried across the k rounds
    fill = jnp.zeros((e_,), jnp.int32)
    for _ in range(k):
        idx = jnp.argmax(masked, axis=-1)                    # (T,)
        gate = jnp.take_along_axis(probs, idx[:, None], 1)[:, 0]
        onehot = jax.nn.one_hot(idx, e_, dtype=jnp.int32)    # (T,E)
        # slot of each token within its expert, offset by prior rounds
        pos = jnp.cumsum(onehot, axis=0) - 1 + fill[None, :]  # (T,E)
        pos_t = (pos * onehot).sum(-1)                        # (T,)
        keep = pos_t < capacity
        slot = jax.nn.one_hot(jnp.clip(pos_t, 0, capacity - 1),
                              capacity, dtype=jnp.float32)    # (T,C)
        d = (onehot.astype(jnp.float32)[:, :, None] * slot[:, None, :]
             ) * keep[:, None, None]
        dispatch = dispatch + d
        combine = combine + d * gate[:, None, None]
        fill = fill + (onehot * keep[:, None].astype(jnp.int32)).sum(0)
        masked = jnp.where(onehot.astype(bool), -jnp.inf, masked)
    return dispatch, combine


@functools.partial(jax.jit, static_argnums=(0, 4, 5))
def _moe_core(expert_fn, stacked_params, gate_w, x, k, capacity):
    t_, d_ = x.shape
    logits = x.astype(jnp.float32) @ gate_w.astype(jnp.float32)
    dispatch, combine = top_k_gating(logits, k, capacity)
    # route: (T,E,C),(T,D) -> (E,C,D)
    expert_in = jnp.einsum("tec,td->ecd", dispatch,
                           x.astype(jnp.float32)).astype(x.dtype)
    expert_out = jax.vmap(expert_fn)(stacked_params, expert_in)
    out = jnp.einsum("tec,ecd->td", combine,
                     expert_out.astype(jnp.float32))
    # capacity-dropped tokens pass through unchanged (identity
    # residual, the Switch/GShard overflow semantics)
    routed = jnp.clip(dispatch.sum(axis=(1, 2)), 0.0, 1.0)  # (T,)
    out = out + (1.0 - routed)[:, None] * x.astype(jnp.float32)
    return out.astype(x.dtype)


def moe_apply(expert_fn, stacked_params, gate_w, x, k=1,
              capacity_factor=1.25, mesh=None, axis_name="expert"):
    """Apply a mixture-of-experts layer.

    expert_fn(params_e, tokens) -> tokens : one expert on its (C, D)
    slice.  stacked_params: pytree with leading axis E.  gate_w:
    (D, E) router weights.  x: (T, D) tokens.

    With ``mesh``, expert weights are placed sharded over
    ``mesh[axis_name]`` and the dispatched (E, C, D) tensor inherits
    the expert sharding — XLA turns the routing einsums into the
    cross-device token exchange (all-to-all over ICI).
    """
    e_ = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    capacity = expert_capacity(x.shape[0], e_, k, capacity_factor)
    if mesh is not None:
        stacked_params = jax.device_put(
            stacked_params,
            jax.tree_util.tree_map(
                lambda leaf: NamedSharding(
                    mesh, P(axis_name, *([None] * (leaf.ndim - 1)))),
                stacked_params))
        x = jax.device_put(x, NamedSharding(mesh, P()))
    return _moe_core(expert_fn, stacked_params, gate_w, x, int(k),
                     int(capacity))
