"""``ops/routed_experts.py``: the grouped bank over the routed rows alone
against the dense bank (every held expert over every token), in value
and in every gradient; the compaction's plan; the three grouped products
in interpret mode against plain ``dot_general``; the budget; the names
the benchmark finds the kernels by."""
import json
import os
import sys

import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import trace_reduce  # noqa: E402
from mxnet_tpu import profiler  # noqa: E402
from mxnet_tpu.ops import routed_experts as rex  # noqa: E402

TILE = rex.ROW_TILE
WIDTH, INNER = 32, 24


def _routing(tokens, experts, k, seed, favour=()):
    """``(ids, weights)`` of a random routing; the experts ``favour`` are
    chosen by every token."""
    scores = jax.random.uniform(jax.random.key(seed), (tokens, experts))
    if favour:
        scores = scores.at[:, jnp.array(favour)].add(2.0)
    chosen, ids = jax.lax.top_k(scores, k)
    return ids, chosen / jnp.sum(chosen, axis=-1, keepdims=True)


def _leaves(tokens, count, seed):
    ku, kup, kdown, kct = jax.random.split(jax.random.key(100 + seed), 4)
    return (0.5 * jax.random.normal(ku, (tokens, WIDTH)),
            0.2 * jax.random.normal(kup, (count * INNER, WIDTH)),
            0.2 * jax.random.normal(kdown, (count * WIDTH, INNER)),
            jax.random.normal(kct, (tokens, WIDTH)))


def _dense(u, ids, weights, up, down, held):
    first, count = held
    chosen = ids[:, :, None] == first + jnp.arange(count)
    gate = jnp.sum(jnp.where(chosen, weights[:, :, None], 0.0), axis=1)
    return rex._dense(u, gate, up, down)


def _both(tokens, experts, k, held, seed, favour=()):
    """Value and gradients of the bank as built and of the dense bank,
    and what the bank counted."""
    ids, weights = _routing(tokens, experts, k, seed, favour)
    u, up, down, ct = _leaves(tokens, held[1], seed)
    with profiler.counting() as counted:
        ours, ours_vjp = jax.vjp(
            lambda u, w, up, down: rex.routed_experts(
                u, ids, w, up, down, held=held, experts=experts),
            u, weights, up, down)
    theirs, theirs_vjp = jax.vjp(
        lambda u, w, up, down: _dense(u, ids, w, up, down, held),
        u, weights, up, down)
    counted = {name: float(v) for name, (_, v) in counted.items()}
    return ([ours, *ours_vjp(ct)], [theirs, *theirs_vjp(ct)], counted,
            ids)


def _sent_to(ids, held):
    first, count = held
    return [int(jnp.sum(jnp.any(ids == first + e, axis=1)))
            for e in range(count)]


#: name -> (tokens, experts, k, held, seed, favoured experts)
GROUPED = {
    "random_routing": (300, 16, 6, (0, 8), 0, ()),
    "held_not_from_nought": (300, 64, 6, (8, 4), 1, ()),
    "an_expert_over_two_tiles": (700, 8, 2, (2, 4), 2, (3,)),
    "every_expert_held": (64, 8, 2, (0, 8), 3, ()),
    "every_expert_held_k6": (150, 8, 6, (0, 8), 4, ()),
    "one_held_expert": (200, 16, 3, (5, 1), 5, ()),
}


@pytest.mark.parametrize("case", sorted(GROUPED))
def test_grouped_bank_is_the_dense_bank(case):
    tokens, experts, k, held, seed, favour = GROUPED[case]
    ours, theirs, counted, ids = _both(tokens, experts, k, held, seed,
                                       favour)
    assert counted["moe_layers"] == 1.0
    assert counted["moe_layers_grouped"] == 1.0
    assert counted["moe_dropped"] == 0.0
    assert counted["moe_rows_max"] == max(_sent_to(ids, held))
    if case == "an_expert_over_two_tiles":
        assert counted["moe_rows_max"] > 2 * TILE
    for name, a, b in zip(("out", "u", "weights", "up", "down"), ours,
                          theirs):
        top = float(jnp.max(jnp.abs(b)))
        assert top > 0.0, name
        assert float(jnp.max(jnp.abs(a - b))) < 2e-5 * top, name


def test_a_held_expert_without_a_row_takes_a_tile_of_padding():
    """No token chooses expert 2: its tile is all padding, its banks'
    gradients are nought, and the others' are the dense bank's."""
    tokens, experts, k, held = 200, 16, 3, (0, 4)
    scores = jax.random.uniform(jax.random.key(6), (tokens, experts))
    _, ids = jax.lax.top_k(scores.at[:, 2].set(-1.0), k)
    weights = jnp.full((tokens, k), 1.0 / k)
    assert _sent_to(ids, held)[2] == 0
    u, up, down, ct = _leaves(tokens, 4, 6)

    def grads(fn):
        return jax.grad(lambda up, down: jnp.sum(ct * fn(up, down)),
                        argnums=(0, 1))(up, down)

    ours = grads(lambda up, down: rex.routed_experts(
        u, ids, weights, up, down, held=held, experts=experts))
    theirs = grads(lambda up, down: _dense(u, ids, weights, up, down, held))
    for a, b, rows in zip(ours, theirs, (INNER, WIDTH)):
        assert float(jnp.max(jnp.abs(a[2 * rows:3 * rows]))) == 0.0
        assert float(jnp.max(jnp.abs(a - b))) \
            < 2e-5 * float(jnp.max(jnp.abs(b)))
    sent = jnp.any(ids[:, :, None] == jnp.arange(4), axis=1)
    _, tile_group = rex.plan_slots(sent, rex.buffer_tiles(
        rex.row_budget(tokens, k, 4, experts), 4))
    assert int(jnp.sum(tile_group == 2)) == 1


def test_an_overflow_takes_the_dense_bank_and_drops_nothing():
    """Every token chooses two held experts of four held of 64: twice the
    budget of one row a token.  The layer takes the dense bank: the same
    value and gradients, nothing dropped, and the counter says so."""
    ours, theirs, counted, ids = _both(300, 64, 6, (8, 4), 7,
                                       favour=(8, 9))
    assert sum(_sent_to(ids, (8, 4))) > rex.row_budget(300, 6, 4, 64)
    assert counted["moe_layers"] == 1.0
    assert counted["moe_layers_grouped"] == 0.0
    assert counted["moe_dropped"] == 0.0
    for a, b in zip(ours, theirs):
        assert float(jnp.max(jnp.abs(a - b))) \
            <= 1e-6 * float(jnp.max(jnp.abs(b)))


def test_grouped_bank_under_jit_and_checkpoint():
    """As the stack runs it: rematerialised and compiled."""
    tokens, experts, k, held = 300, 16, 6, (0, 8)
    ids, weights = _routing(tokens, experts, k, 8)
    u, up, down, ct = _leaves(tokens, 8, 8)

    def loss(fn):
        return lambda u, up, down: jnp.sum(ct * jax.checkpoint(fn)(
            u, up, down))

    ours = jax.jit(jax.grad(loss(lambda u, up, down: rex.routed_experts(
        u, ids, weights, up, down, held=held, experts=experts)),
        argnums=(0, 1, 2)))(u, up, down)
    theirs = jax.grad(loss(lambda u, up, down: _dense(
        u, ids, weights, up, down, held)), argnums=(0, 1, 2))(u, up, down)
    for a, b in zip(ours, theirs):
        assert float(jnp.max(jnp.abs(a - b))) \
            < 2e-5 * float(jnp.max(jnp.abs(b)))


# ------------------------------------------------------------- the plan
@pytest.mark.parametrize("tokens,k,count,experts,rows", [
    (8192, 6, 8, 128, 8192),       # the cell: 2.7 times the expected 3072
    (8192, 6, 16, 128, 2 * 8192),
    (8192, 6, 8, 8, 6 * 8192),     # every expert held: every possible row
    (8192, 6, 8, None, 6 * 8192),
    (100, 6, 4, 4, 400),           # a token has no more rows than experts
    (100, 2, 8, 128, 100),
])
def test_row_budget_comes_from_shapes(tokens, k, count, experts, rows):
    assert rex.row_budget(tokens, k, count, experts) == rows


def test_the_cells_buffer_is_72_tiles():
    assert rex.buffer_tiles(rex.row_budget(8192, 6, 8, 128), 8) == 72


def _plan(tokens, experts, k, count, seed, favour=()):
    ids, _ = _routing(tokens, experts, k, seed, favour)
    sent = jnp.any(ids[:, :, None] == jnp.arange(count), axis=1)
    tiles = rex.buffer_tiles(rex.row_budget(tokens, k, count, experts),
                             count)
    return sent, tiles, rex.plan_slots(sent, tiles)


@pytest.mark.parametrize("seed,favour", [(0, ()), (1, (3,)), (2, (0, 7))])
def test_tile_count_does_not_follow_the_routing(seed, favour):
    """Two routings, one buffer: the same number of tiles, each expert's
    rows from a tile's first row on, in the tokens' order, each tile one
    expert's, the tiles past the last row the last expert's."""
    sent, tiles, (slot, tile_group) = _plan(600, 32, 4, 8, seed, favour)
    assert tiles == rex.buffer_tiles(rex.row_budget(600, 4, 8, 32), 8)
    assert slot.shape == (8, 600) and tile_group.shape == (tiles,)
    assert bool(jnp.all(jnp.diff(tile_group) >= 0))
    taken = set()
    for e in range(8):
        rows = [int(r) for r in slot[e] if r >= 0]
        assert len(rows) == int(jnp.sum(sent[:, e]))
        assert rows == list(range(rows[0], rows[0] + len(rows)))
        assert rows[0] % TILE == 0 and rows[-1] < tiles * TILE
        assert all(int(tile_group[r // TILE]) == e for r in rows)
        assert not taken & set(rows)
        taken |= set(rows)
    assert int(tile_group[-1]) == 7
    token, gate = rex._rows(slot, jnp.where(sent, 0.5, 0.0), tile_group)
    assert token.shape == gate.shape == (tiles * TILE,)
    assert bool(jnp.all((token >= 0) & (token < 600)))
    assert float(jnp.sum(gate)) == 0.5 * len(taken)
    for e in range(8):
        for t in (0, 299, 599):
            if sent[t, e]:
                assert int(token[slot[e, t]]) == t
                assert float(gate[slot[e, t]]) == 0.5


# --------------------------------------------------- the three products
def _operands(m, k, n, groups, seed):
    a, b, c, d = jax.random.split(jax.random.key(seed), 4)
    tiles = m // TILE
    tile_group = jnp.sort(jnp.concatenate([
        jnp.arange(groups),
        jax.random.randint(d, (tiles - groups,), 0, groups)]))
    return (jax.random.normal(a, (m, k)), jax.random.normal(b, (m, n)),
            jax.random.normal(c, (groups, n, k)),
            tile_group.astype(jnp.int32))


@pytest.mark.parametrize("m,k,n,groups", [(1024, 256, 128, 4),
                                          (768, 1024, 960, 3)])
@pytest.mark.parametrize("product", sorted(rex.KERNELS))
def test_kernel_is_the_plain_product(product, m, k, n, groups):
    """Interpret mode, float32; at 960 and 1024 the blocks are cut and
    the last one hangs over the edge."""
    x, dy, bank, tile_group = _operands(m, k, n, groups, m + n)
    if product == rex.GMM:
        ours = rex.gmm(x, bank, tile_group, transposed=True, interpret=True)
        theirs = rex.gmm_plain(x, bank, tile_group, transposed=True)
    elif product == rex.GMM_ROWS_GRAD:
        ours = rex.gmm(dy, bank, tile_group, transposed=False,
                       interpret=True)
        theirs = rex.gmm_plain(dy, bank, tile_group, transposed=False)
    else:
        ours = rex.tgmm(dy, x, tile_group, groups, interpret=True)
        theirs = rex.tgmm_plain(dy, x, tile_group, groups)
    assert ours.shape == theirs.shape
    assert float(jnp.max(jnp.abs(ours - theirs))) \
        < 1e-5 * float(jnp.max(jnp.abs(theirs)))


def test_plain_products_are_the_products_by_rows():
    x, dy, bank, tile_group = _operands(512, 64, 48, 3, 9)
    group = jnp.repeat(tile_group, TILE)
    by_rows = jnp.stack([bank[g] @ x[r] for r, g in enumerate(group)])
    assert float(jnp.max(jnp.abs(rex.gmm_plain(
        x, bank, tile_group, transposed=True) - by_rows))) < 1e-4
    sums = jnp.stack([(dy * (group == g)[:, None]).T @ x for g in range(3)])
    assert float(jnp.max(jnp.abs(rex.tgmm_plain(
        dy, x, tile_group, 3) - sums))) < 1e-3


# ------------------------------------------- what the benchmark reads
@pytest.mark.parametrize("kernel", sorted(rex.KERNELS))
def test_kernel_names_hold_both_blocks_and_count_as_products(kernel):
    """``moe_ms.train`` and ``expert_roofline.train`` find their blocks by
    ``sparsemoe`` and ``routedexperts``; a Pallas call's events go under
    its own name as their block, and ``chipbench/kernels/`` makes them
    ``conv_dot`` events."""
    assert "sparsemoe" in kernel and "routedexperts" in kernel
    with open(os.path.join(ROOT, "chipbench", "kernels",
                           "moe_kernels.json")) as f:
        assert kernel in json.load(f)["kernels"]
    assert kernel in trace_reduce.conv_kernels()
    scope = ("jit(step)/mx_forward/net0_sparsemoe0_routedexperts0/cond/"
             f"branch_1_fun/net0_sparsemoe0_routedexperts0/{kernel}/"
             "pallas_call")
    assert trace_reduce.phase_and_block(scope) == ("forward", kernel)
    line = (f"  %{kernel}.3 = bf16[9216,1856]{{1,0}} custom-call(...), "
            'custom_call_target="tpu_custom_call"')
    assert trace_reduce.classify(line, {}) == "conv_dot"


def test_widths_beyond_the_vmem_plan_are_declined_before_lowering():
    """The contracted axis enters a kernel whole: at a width whose blocks
    exceed the plan the bank takes the plain products, and says so."""
    from mxnet_tpu.ops import kernel_target

    before = kernel_target.declined_counts().get("routed_experts", 0)
    assert rex._holds(2688, 1856, jnp.bfloat16)
    assert kernel_target.declined_counts().get("routed_experts", 0) == before
    assert not rex._holds(65536, 1856, jnp.bfloat16)
    assert kernel_target.declined_counts()["routed_experts"] == before + 1
