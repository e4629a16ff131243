"""A chip's share of a routed bank of experts, with no assignment dropped.

The router scores every token against ALL the experts of the layer; a
chip holds a range of them (``held = (first, count)``) and computes, for
every token, the part of the result that its own experts give.  What the
absent experts would add is left out; under expert parallelism the peers
add theirs (``parallel/moe.py`` is the other design: a dense
``(tokens, experts, capacity)`` dispatch over a mesh axis that drops what
exceeds the capacity).

No capacity and nothing the data decides: the held experts are one wide
gated MLP.  ``gate[t, e]`` is token ``t``'s weight for held expert ``e``,
nought where it did not choose it; every held expert runs over every
token, ``relu(up_e u)^2`` is scaled by its gate and one product over
``(expert, inner)`` sums the experts' parts.  Every assignment to a held
expert is computed whatever the imbalance, and a step's time does not
follow the routing: the products' shapes are the batch's.  It multiplies
rows that no token sent (a held expert sees ``tokens x k / experts`` of
them on average): a grouped product over the routed rows alone
(``lax.ragged_dot`` over a sorted buffer) does a sixteenth of the work at
8 of 128 experts held, and its time follows the routing (PERF.md, PR 33:
3% between seeds on a v5e, more than the benchmark's bounds admit).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import profiler as _profiler


def sigmoid_topk_route(u, router, bias, *, k, scale):
    """``(ids, weights)``, each (tokens, k): the ``k`` experts with the
    largest ``sigmoid(router u) + bias`` and their weights ``scale * s /
    (sum of the chosen s + 1e-20)``.  The scores are float32 from the
    float32 router at full precision; ``bias`` enters the choice alone
    and so gets no gradient."""
    s = jax.nn.sigmoid(jnp.matmul(
        u.astype(jnp.float32), router.astype(jnp.float32).T,
        precision=jax.lax.Precision.HIGHEST))
    _, ids = jax.lax.top_k(s + jax.lax.stop_gradient(bias), k)
    chosen = jnp.take_along_axis(s, ids, axis=-1)
    return ids, scale * chosen / (
        jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)


def routed_experts(u, ids, weights, up, down, *, held):
    """The held experts' part of ``sum_e weight_e down_e(relu(up_e u)^2)``.

    u: (tokens, width); ids, weights: (tokens, k) over all the layer's
    experts; up: (count x inner, width) and down: (count x width, inner),
    each expert's rows together; ``held = (first, count)``.  Counts the
    step's ``moe_*`` counters where a step collects them."""
    first, count = held
    tokens, width = u.shape
    inner = up.shape[0] // count
    chosen = ids[:, :, None] == first + jnp.arange(count)  # (tokens, k, count)
    gate = jnp.sum(jnp.where(chosen, weights[:, :, None], 0.0), axis=1)
    h = jax.lax.dot_general(u, up, (((1,), (1,)), ((), ())))
    act = jnp.square(jax.nn.relu(h.astype(jnp.float32))).reshape(
        tokens, count, inner) * gate[:, :, None]
    out = jax.lax.dot_general(
        act.astype(u.dtype), down.reshape(count, width, inner),
        (((1, 2), (0, 2)), ((), ())))
    rows = jnp.sum(chosen, axis=(0, 1))  # assignments to each held expert
    n_here = jnp.sum(jnp.any(chosen, axis=-1))
    _profiler.count("moe_assignments", ids.size)
    _profiler.count("moe_assignments_held", n_here)
    _profiler.count("moe_rows_max", jnp.max(rows), how="max")
    # every assignment that fell on a held expert has its gate: by
    # construction none is left over
    _profiler.count("moe_dropped", n_here - jnp.sum(rows))
    return out
