"""A chip's share of a routed bank of experts, with no assignment dropped.

The router scores every token against ALL the experts of the layer; a
chip holds a range of them (``held = (first, count)``) and computes, for
every token, the part of the result that its own experts give.  What the
absent experts would add is left out; under expert parallelism the peers
add theirs (``parallel/moe.py`` is the other design: a dense
``(tokens, experts, capacity)`` dispatch over a mesh axis that drops what
exceeds the capacity).  Still missing here: the all-to-all that would
bring the peers' tokens in and take their parts back, and a balancing
rule that moves the router's correction bias.

**The routed rows alone.**  A held expert is sent ``tokens x k /
experts`` rows on average, so the bank multiplies a compact buffer of the
routed rows and not every token by every held expert:

* *Compaction without a sort.*  Each held expert's mask over the tokens
  gives its row count and, by a running sum, each row's rank.  Expert
  ``e``'s rows start at the sum of the earlier experts' counts, each
  rounded up to the row tile (``ROW_TILE``; an expert without a row still
  takes one tile).  The buffer has ``budget + count x ROW_TILE`` rows, a
  number that shapes fix.  Rows past an expert's last one up to its next
  tile boundary, and the tiles past the last expert (which read its
  weights again), are padding: gate nought, some valid token.  Which
  token a row holds is read by comparing every token's slot with the
  row's number (``_rows``): dense arithmetic, no scatter of indices.
* *One grouped product, whose tile count the routing cannot change.*
  Every tile of ``ROW_TILE`` rows belongs to one expert, named by a
  prefetched table, and every tile is visited every step.  Three Pallas
  kernels, named in ``KERNELS``: rows times a bank's expert (the bank laid
  out ``(expert, out, in)`` as the leaves are, no transpose), the same
  contracting the bank's middle axis (the rows' gradient), and the banks'
  gradients summed over each expert's tiles.  bf16 operands, float32
  accumulation; what ``relu(.)^2`` and the gate read and their gradient
  leave the kernels in float32 and are worked on outside them.  Off
  the TPU the same buffer goes through plain ``lax.dot_general`` by tiles.
* *The budget comes from shapes, and an overflow is computed.*  ``budget
  = tokens x min(k, count, max(1, ceil(2 k count / experts)))``: twice
  the expected rows and more (8192 for an expected 3072 at 8 of 128
  experts and k = 6), and every possible row where all experts are held.
  A step whose held rows exceed it takes the dense bank for that layer
  (``lax.cond``): every held expert over every token, gated by the
  routing weights, which is also the tests' oracle.  Nothing is dropped
  either way; ``moe_layers_grouped`` of ``moe_layers`` says how often the
  grouped path ran.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import profiler as _profiler
from . import kernel_target

#: rows of one tile of the compact buffer; a held expert's rows start at
#: a multiple of it
ROW_TILE = 128

#: the ``pl.pallas_call(name=...)`` of the three grouped products; a
#: traced event goes under its kernel's name as its block, and the
#: benchmark's readers find the mixture's and the bank's blocks by the
#: two parts these names hold
GMM, GMM_ROWS_GRAD, GMM_BANK_GRAD = KERNELS = (
    "sparsemoe_routedexperts_gmm", "sparsemoe_routedexperts_gmm_dx",
    "sparsemoe_routedexperts_gmm_dw")

#: the longest block along an axis that a product does not contract: of
#: a bank's expert in ``gmm``, of the accumulator in ``tgmm``
_GMM_MOST = 896
_TGMM_MOST = 2048
#: what a kernel may plan of a v5e's 128 MiB of VMEM
_VMEM_MOST = 96 * 1024 * 1024


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


# ------------------------------------------------- the grouped products
def _split(n, most):
    """The block length along an axis of ``n`` that no product contracts:
    ``n`` whole where it is within ``most``, else the least multiple of
    128 that covers ``n`` in ``ceil(n / most)`` blocks.  The last block
    may hang over the edge: what is read there is written there, and
    dropped."""
    if n <= most:
        return n
    parts = -(-n // most)
    return -(-n // (parts * 128)) * 128


def _gmm_kernel(group_ref, lhs_ref, bank_ref, out_ref, *, contract):
    del group_ref  # the index maps read it
    out_ref[...] = jax.lax.dot_general(
        lhs_ref[...], bank_ref[...], (((1,), (contract,)), ((), ())),
        preferred_element_type=jnp.float32).astype(out_ref.dtype)


def _vmem_limit(*blocks):
    """Bytes of VMEM for a kernel over these blocks: twice each (Pallas
    double-buffers) and as much again for what the compiler keeps
    between them, 32 MiB at the least."""
    return max(32 << 20, 4 * sum(rows * cols * jnp.dtype(dtype).itemsize
                                 for rows, cols, dtype in blocks))


def gmm(lhs, bank, tile_group, *, transposed, out_dtype=None,
        interpret=False):
    """``lhs[tile] @ bank[tile_group[tile]]`` for every tile of
    ``ROW_TILE`` rows of ``lhs`` (M, K): ``bank`` is (G, K, N), or
    (G, N, K) with ``transposed``; (M, N) in ``out_dtype`` (``lhs``'s
    where not given)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = lhs.shape
    n = bank.shape[1] if transposed else bank.shape[2]
    tn = _split(n, _GMM_MOST)
    if transposed:
        bank_spec = pl.BlockSpec((None, tn, k), lambda j, i, g: (g[i], j, 0))
    else:
        bank_spec = pl.BlockSpec((None, k, tn), lambda j, i, g: (g[i], 0, j))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, contract=1 if transposed else 0),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(pl.cdiv(n, tn), m // ROW_TILE),
            in_specs=[pl.BlockSpec((ROW_TILE, k), lambda j, i, g: (i, 0)),
                      bank_spec],
            out_specs=pl.BlockSpec((ROW_TILE, tn), lambda j, i, g: (i, j))),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype or lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(
                (ROW_TILE, k, lhs.dtype), (tn, k, bank.dtype),
                (ROW_TILE, tn, jnp.float32))),
        interpret=interpret,
        name=GMM if transposed else GMM_ROWS_GRAD,
    )(tile_group, lhs, bank)


def _tgmm_kernel(group_ref, lhs_ref, rhs_ref, out_ref, acc_ref):
    from jax.experimental import pallas as pl

    i, last = pl.program_id(2), pl.num_programs(2) - 1
    group = group_ref[i]

    @pl.when((i == 0) | (group_ref[jnp.maximum(i - 1, 0)] != group))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        lhs_ref[...], rhs_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when((i == last) | (group_ref[jnp.minimum(i + 1, last)] != group))
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def tgmm(lhs, rhs, tile_group, groups, *, interpret=False):
    """``sum over the tiles of group g of lhs[tile].T @ rhs[tile]``:
    ``lhs`` (M, N), ``rhs`` (M, K), (groups, N, K) in ``lhs``'s type.
    Every group owns a run of one tile or more."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, n = lhs.shape
    k = rhs.shape[1]
    tn, tk = _split(n, _TGMM_MOST), _split(k, _TGMM_MOST)
    return pl.pallas_call(
        _tgmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(pl.cdiv(n, tn), pl.cdiv(k, tk), m // ROW_TILE),
            in_specs=[
                pl.BlockSpec((ROW_TILE, tn), lambda a, b, i, g: (i, a)),
                pl.BlockSpec((ROW_TILE, tk), lambda a, b, i, g: (i, b))],
            out_specs=pl.BlockSpec((None, tn, tk),
                                   lambda a, b, i, g: (g[i], a, b)),
            scratch_shapes=[pltpu.VMEM((tn, tk), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((groups, n, k), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(
                (ROW_TILE, tn + tk, lhs.dtype), (tn, tk, lhs.dtype),
                (tn, tk, jnp.float32))),
        interpret=interpret,
        name=GMM_BANK_GRAD,
    )(tile_group, lhs, rhs)


def _by_tiles(rows):
    return rows.reshape(-1, ROW_TILE, rows.shape[-1])


def gmm_plain(lhs, bank, tile_group, *, transposed, out_dtype=None):
    """:func:`gmm` as one batched ``dot_general`` over the tiles."""
    out = jax.lax.dot_general(
        _by_tiles(lhs), bank[tile_group],
        (((2,), (2 if transposed else 1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    return out.reshape(lhs.shape[0], -1).astype(out_dtype or lhs.dtype)


def tgmm_plain(lhs, rhs, tile_group, groups):
    """:func:`tgmm` as one batched ``dot_general`` over the tiles, summed
    by group."""
    by_tile = jax.lax.dot_general(
        _by_tiles(lhs), _by_tiles(rhs), (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    of_group = (tile_group[None, :] == jnp.arange(groups)[:, None])
    return jax.lax.dot_general(
        of_group.astype(jnp.float32), by_tile, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST).astype(lhs.dtype)


def _holds(width, inner, dtype):
    """Do the kernels' blocks fit their VMEM plan at these widths?  The
    contracted axis enters whole.  Decided here, before lowering."""
    widest = max(width, inner)
    plan = _vmem_limit((ROW_TILE + _GMM_MOST, widest, dtype),
                       (_TGMM_MOST, _TGMM_MOST, jnp.float32))
    if plan <= _VMEM_MOST:
        return True
    kernel_target.declined(
        "routed_experts", f"blocks of {plan} bytes exceed the VMEM plan "
        f"of {_VMEM_MOST}", "plain", shape=(width, inner))
    return False


def _products(width, inner, dtype):
    """``(gmm, tgmm)``: the kernels on a TPU, plain products elsewhere."""
    if kernel_target.on_tpu() and _holds(width, inner, dtype):
        return gmm, tgmm
    return gmm_plain, tgmm_plain


# ---------------------------------------------------------- compaction
def row_budget(tokens, k, count, experts=None):
    """Rows the compact buffer holds before its padding: ``tokens x
    min(k, count, max(1, ceil(2 k count / experts)))``, every possible
    row where ``experts`` is not given or all are held."""
    each = min(k, count)
    if experts:
        each = min(each, max(1, -(-2 * k * count // experts)))
    return tokens * each


def buffer_tiles(budget, count):
    """Tiles of the compact buffer: the budget's, and one for each held
    expert's rounding (or its emptiness)."""
    return -(-budget // ROW_TILE) + count


def plan_slots(sent, tiles):
    """``(slot, tile_group)`` from ``sent`` (tokens, count), the mask of
    the tokens each held expert is sent: ``slot[e, t]`` is the buffer row
    of token ``t`` for expert ``e`` (-1 where not sent), expert ``e``'s
    rows starting at the earlier experts' tiles; ``tile_group[i]`` is the
    expert whose weights tile ``i`` reads (the last expert's past the
    last row).  Where the rows exceed the buffer the slots run past it:
    the caller takes the dense bank then."""
    count = sent.shape[1]
    sent = sent.T.astype(jnp.int32)  # (count, tokens): tokens on lanes
    rank = jax.lax.cumsum(sent, axis=1) - 1
    takes = jnp.maximum(1, (rank[:, -1] + ROW_TILE) // ROW_TILE)  # tiles
    ends = jax.lax.cumsum(takes, axis=0)
    slot = jnp.where(sent > 0,
                     ((ends - takes) * ROW_TILE)[:, None] + rank, -1)
    tile_group = jnp.minimum(
        jnp.sum(jnp.arange(tiles)[:, None] >= ends[None, :], axis=1),
        count - 1).astype(jnp.int32)
    return slot, tile_group


def _rows(slot, gate, tile_group):
    """``(token, gate)`` of every buffer row, each (rows,): the token
    whose slot is the row and its gate for the tile's expert; for a row
    that is padding some valid token and nought.  A comparison of every
    row with every token's slot in the tile's expert, reduced over the
    tokens: no scatter, and the gate's gradient is the same comparison."""
    tiles, tokens = tile_group.shape[0], slot.shape[1]
    row = (jnp.arange(tiles)[:, None] * ROW_TILE
           + jnp.arange(ROW_TILE)[None, :])
    hit = slot[tile_group][:, None, :] == row[:, :, None]
    token = jnp.sum(jnp.where(hit, jnp.arange(1, tokens + 1), 0), axis=-1)
    token = jnp.where(token > 0, token - 1, row % tokens)  # none hit: padding
    row_gate = jnp.sum(
        jnp.where(hit, gate.T[tile_group][:, None, :], 0.0), axis=-1)
    return token.reshape(-1), row_gate.reshape(-1)


# ------------------------------------------------------- the two banks
def _dense(u, gate, up, down):
    """Every held expert over every token, as one wide MLP gated by the
    routing weights: the oracle, and the branch of an overflow."""
    tokens, width = u.shape
    count = gate.shape[1]
    h = jax.lax.dot_general(u, up, (((1,), (1,)), ((), ())))
    act = jnp.square(jax.nn.relu(h.astype(jnp.float32))).reshape(
        tokens, count, -1) * gate[:, :, None]
    return jax.lax.dot_general(
        act.astype(u.dtype), down.reshape(count, width, -1),
        (((1, 2), (0, 2)), ((), ())))


def _dense_of(u, gate, up, down, slot, tile_group):
    """:func:`_dense` of the grouped bank's arguments."""
    del slot, tile_group
    return _dense(u, gate, up, down)


def _gated(h, row_gate):
    """``(relu(h), relu(h)^2 * gate)`` of a float32 ``h``."""
    r = jax.nn.relu(h)
    return r, jnp.square(r) * row_gate[:, None]


def _banks(up, down, count):
    return (up.reshape(count, -1, up.shape[1]),
            down.reshape(count, down.shape[0] // count, -1))


def _combine(rows, token, like):
    """The buffer's ``rows`` summed into their tokens' rows in float32,
    in the shape and type of ``like``."""
    out = jnp.zeros(like.shape, jnp.float32).at[token].add(
        rows.astype(jnp.float32), mode="promise_in_bounds")
    return out.astype(like.dtype)


def _grouped_fwd(u, gate, up, down, slot, tile_group):
    """The held experts' part over the compact buffer, and what its
    backward pass keeps."""
    up3, down3 = _banks(up, down, gate.shape[1])
    mm, _ = _products(u.shape[1], up3.shape[1], u.dtype)
    token, row_gate = _rows(slot, gate, tile_group)
    x = u.at[token].get(mode="promise_in_bounds")
    h = mm(x, up3, tile_group, transposed=True, out_dtype=jnp.float32)
    y = mm(_gated(h, row_gate)[1].astype(u.dtype), down3, tile_group,
           transposed=True)
    return _combine(y, token, u), (token, row_gate, x, h)


def _grouped_bwd(kept, u, gate, up, down, slot, tile_group, ct):
    token, row_gate, x, h = kept
    count = gate.shape[1]
    up3, down3 = _banks(up, down, count)
    mm, tmm = _products(u.shape[1], up3.shape[1], u.dtype)
    dy = ct.at[token].get(mode="promise_in_bounds")
    r, act = _gated(h, row_gate)
    d_down = tmm(dy, act.astype(u.dtype), tile_group, count)
    d_act = mm(dy, down3, tile_group, transposed=False,
               out_dtype=jnp.float32)
    d_row_gate = jnp.sum(d_act * jnp.square(r), axis=-1)
    dh = (d_act * (2.0 * row_gate)[:, None] * r).astype(u.dtype)
    d_up = tmm(dh, x, tile_group, count)
    dx = mm(dh, up3, tile_group, transposed=False)
    du = _combine(dx, token, u)
    _, of_gate = jax.vjp(lambda g: _rows(slot, g, tile_group)[1], gate)
    return (du, of_gate(d_row_gate)[0],
            d_up.reshape(up.shape).astype(up.dtype),
            d_down.reshape(down.shape).astype(down.dtype))


def _either(scope, fits, grouped, dense, *args):
    """``grouped(*args)`` where the rows ``fits`` the buffer, else
    ``dense(*args)``; ``grouped`` alone where ``fits`` is None (no routing
    overflows the budget).  A branch of a ``cond`` is traced under the
    ``cond``'s own names, which would hide the block: each runs under
    ``scope`` again."""
    def scoped(fn):
        def run(*args):
            with jax.named_scope(scope):
                return fn(*args)
        return run

    if fits is None:
        return grouped(*args)
    return jax.lax.cond(fits, scoped(grouped), scoped(dense), *args)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _held_part(scope, fits, u, gate, up, down, slot, tile_group):
    """The grouped bank where the routed rows fit the buffer, the dense
    one where they do not."""
    return _either(scope, fits, lambda *a: _grouped_fwd(*a)[0], _dense_of,
                   u, gate, up, down, slot, tile_group)


def _held_part_fwd(scope, fits, u, gate, up, down, slot, tile_group):
    args = (u, gate, up, down, slot, tile_group)

    def dense(*a):
        kept_like = jax.eval_shape(_grouped_fwd, *a)[1]
        return _dense_of(*a), jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), kept_like)

    out, kept = _either(scope, fits, _grouped_fwd, dense, *args)
    return out, (fits, kept) + args


def _held_part_bwd(scope, res, ct):
    fits, kept, u, gate, up, down, slot, tile_group = res

    def grouped(ct):
        return _grouped_bwd(kept, u, gate, up, down, slot, tile_group, ct)

    def dense(ct):
        return jax.vjp(_dense, u, gate, up, down)[1](ct)

    grads = _either(scope, fits, grouped, dense, ct)
    return (None,) + tuple(grads) + (None, None)


_held_part.defvjp(_held_part_fwd, _held_part_bwd)


def routed_experts(u, ids, weights, up, down, *, held, experts=None,
                   scope="sparsemoe_routedexperts"):
    """The held experts' part of ``sum_e weight_e down_e(relu(up_e u)^2)``.

    u: (tokens, width); ids, weights: (tokens, k) over all ``experts`` of
    the layer; up: (count x inner, width) and down: (count x width,
    inner), each expert's rows together; ``held = (first, count)``.
    ``scope`` names the operations inside the ``cond``'s branches (the
    caller's block).  Counts the step's ``moe_*`` counters where a step
    collects them."""
    first, count = held
    tokens, k = ids.shape
    chosen = ids[:, :, None] == first + jnp.arange(count)  # (tokens, k, count)
    gate = jnp.sum(jnp.where(chosen, weights[:, :, None], 0.0), axis=1)
    sent = jnp.any(chosen, axis=1)
    budget = row_budget(tokens, k, count, experts)
    slot, tile_group = plan_slots(sent, buffer_tiles(budget, count))
    # a budget of every possible row never overflows: no second branch
    fits = None if budget == row_budget(tokens, k, count) \
        else jnp.sum(sent) <= budget
    out = _held_part(scope, fits, u, gate.astype(jnp.float32), up, down,
                     slot, tile_group)
    rows = jnp.sum(chosen, axis=(0, 1))  # assignments to each held expert
    n_here = jnp.sum(jnp.any(chosen, axis=-1))
    _profiler.count("moe_assignments", ids.size)
    _profiler.count("moe_assignments_held", n_here)
    _profiler.count("moe_rows_max", jnp.max(rows), how="max")
    # every assignment that fell on a held expert has its gate, in the
    # buffer or in the dense bank: by construction none is left over
    _profiler.count("moe_dropped", n_here - jnp.sum(rows))
    _profiler.count("moe_layers", 1)
    _profiler.count("moe_layers_grouped",
                    1 if fits is None else fits.astype(jnp.int32))
    return out
