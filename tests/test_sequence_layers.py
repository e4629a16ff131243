"""gluon.nn's sequence layers (RMSNorm, Mamba-2 mixer and scan,
grouped-query attention, squared-ReLU MLP, the mixture of experts' share,
the stack) against the benchmark's plain reference of the same equations
(``chipbench/reference/nemotron3_nano_30b_a3b.py``), float32, seeded, at
a tiny size; and what the train step does for them: whole token ids under
a bfloat16 compute type, float32 leaves by name, counters out of the
compiled step."""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import mxnet_tpu as mx  # noqa: E402
from chipbench import run as cb  # noqa: E402
from mxnet_tpu import gluon, parallel, profiler  # noqa: E402
from mxnet_tpu.gluon import nn  # noqa: E402
from mxnet_tpu.ops import routed_experts as rex  # noqa: E402
from mxnet_tpu.ops import ssd  # noqa: E402

REF = cb.load_module("reference", "nemotron3_nano_30b_a3b")
ARCH = dict(hidden_size=32, pattern="MEMEM*EME", mamba_num_heads=4,
            mamba_head_dim=8, n_groups=2, ssm_state_size=16, conv_kernel=4,
            chunk_size=8, num_attention_heads=4, num_key_value_heads=2,
            head_dim=8, n_routed_experts_published=16, experts_held=[0, 8],
            num_experts_per_tok=6, moe_intermediate_size=24,
            moe_shared_expert_intermediate_size=48,
            routed_scaling_factor=2.5, norm_eps=1e-5)


def _rand(key, *shape, scale=1.0):
    return scale * jax.random.normal(jax.random.key(key), shape, jnp.float32)


def _block_fn(block, leaves):
    """``f(leaves, *inputs)`` of an initialised block, pure."""
    block.initialize()
    params, apply_fn = parallel.functionalize(block, train=True)
    assert [tuple(v.shape) for v in params.values()] \
        == [tuple(a.shape) for a in leaves], list(params)
    names = list(params)
    return lambda ws, *xs: apply_fn(dict(zip(names, ws)), *xs)


def _agree(ours, theirs, leaves, *inputs, tol=2e-4):
    """Forward, and the gradients of a scalar of it by every leaf and
    every float input."""
    def scalar(f):
        return lambda ws, *xs: jnp.sum(jnp.sin(f(ws, *xs)))
    with jax.default_matmul_precision("highest"):
        a, b = ours(leaves, *inputs), theirs(leaves, *inputs)
        scale = float(jnp.max(jnp.abs(b)))
        assert float(jnp.max(jnp.abs(a - b))) < tol * scale
        wrt = (0,) + tuple(i + 1 for i, x in enumerate(inputs)
                           if jnp.issubdtype(x.dtype, jnp.floating))
        ga = jax.grad(scalar(ours), argnums=wrt)(leaves, *inputs)
        gb = jax.grad(scalar(theirs), argnums=wrt)(leaves, *inputs)
    for x, y in zip(jax.tree_util.tree_leaves(ga),
                    jax.tree_util.tree_leaves(gb)):
        top = max(float(jnp.max(jnp.abs(y))), 1e-6)
        assert float(jnp.max(jnp.abs(x - y))) < tol * 10 * top


# ------------------------------------------------ blocks, one at a time
def test_rms_norm_and_squared_relu_mlp_are_the_reference_s():
    u = _rand(0, 2, 5, 32)
    gamma = [1.0 + _rand(1, 32, scale=0.1)]
    _agree(_block_fn(nn.RMSNorm(1e-5, in_channels=32), gamma),
           lambda ws, x: REF.rms_norm(x, ws[0], 1e-5), gamma, u)
    w = [_rand(2, 48, 32, scale=0.2), _rand(3, 32, 48, scale=0.2)]
    _agree(_block_fn(nn.SquaredReLUMLP(32, 48), w),
           lambda ws, x: REF.relu2_mlp(x, *ws), w, u)


#: (heads, head_dim, groups, state, chunk): the tiny one, and the
#: cell's head_dim, state and chunk with four heads a group
_SCAN_SHAPES = {"tiny": (4, 8, 2, 16, 8), "wide": (8, 64, 2, 128, 128)}


@pytest.mark.parametrize("length,shape,dtype", [
    pytest.param(24, "tiny", "float32", id="24"),
    pytest.param(29, "tiny", "float32", id="29"),
    pytest.param(5, "tiny", "float32", id="5"),
    pytest.param(300, "wide", "float32", id="wide-300"),
    pytest.param(300, "wide", "bfloat16", id="wide-300-bf16"),
    pytest.param(77, "wide", "bfloat16", id="wide-77-bf16")])
def test_chunked_scan_is_the_recurrence(length, shape, dtype):
    """Several chunks, a length that is no multiple of the chunk, and one
    shorter than a chunk; forward and every gradient; in bf16 (``x``,
    ``B``, ``C`` and the cotangent, as the cell runs them) within a bf16
    rounding of the float32 recurrence."""
    heads, head_dim, groups, state, chunk = _SCAN_SHAPES[shape]
    x = _rand(0, 2, length, heads, head_dim)
    dt = jax.nn.softplus(_rand(1, 2, length, heads))
    a = -jnp.exp(_rand(2, heads, scale=0.3))
    scale = 16 ** 0.5 / state ** 0.5  # C . B of one size at any state
    b = _rand(3, 2, length, groups, state, scale=scale)
    c = _rand(4, 2, length, groups, state, scale=scale)
    d = 1.0 + _rand(5, heads, scale=0.1)
    ours = (lambda ws, *xs: ssd.ssd_chunked_scan(*xs, *ws, chunk=chunk))
    if dtype == "float32":
        _agree(ours, lambda ws, *xs: REF.scan(*xs, *ws), [d], x, dt, a, b, c)
        return
    bf = jnp.bfloat16
    low = (x.astype(bf), dt, a, b.astype(bf), c.astype(bf))
    cot = _rand(6, *x.shape).astype(bf)

    def values(f, xs, cot):
        y, vjp = jax.vjp(f, [d], *xs)
        assert y.dtype == xs[0].dtype
        return [y] + jax.tree_util.tree_leaves(vjp(cot))
    with jax.default_matmul_precision("highest"):
        exact = values(lambda ws, *xs: REF.scan(*xs, *ws),
                       tuple(t.astype(jnp.float32) for t in low),
                       cot.astype(jnp.float32))
    for got, want in zip(values(ours, low, cot), exact):
        got, want = (np.asarray(t, np.float32) for t in (got, want))
        # y, dD, dx, ddt, dA, dB, dC: at most 0.0032 on the CPU
        assert np.linalg.norm(got - want) < 0.01 * np.linalg.norm(want)


def _mixer_leaves():
    return [_rand(0, 96, 4, scale=0.5), _rand(1, 96, scale=0.01),
            1.0 + _rand(2, 32, scale=0.1), _rand(3, 132, 32, scale=0.18),
            _rand(4, 4, scale=0.1), _rand(5, 4, scale=0.1),
            1.0 + _rand(6, 4, scale=0.1), _rand(7, 32, 32, scale=0.18)]


@pytest.mark.parametrize("length", [24, 13])
def test_mamba2_mixer_is_the_reference_s_layer(length):
    leaves = _mixer_leaves()
    mixer = nn.Mamba2Mixer(32, 4, 8, 2, 16, 4, 8, 1e-5)
    _agree(_block_fn(mixer, leaves),
           lambda ws, x: REF.mamba_layer(ws, x, ARCH), leaves,
           _rand(9, 2, length, 32))


def test_grouped_query_attention_is_the_reference_s_layer():
    leaves = [_rand(0, 32, 32, scale=0.3), _rand(1, 16, 32, scale=0.3),
              _rand(2, 16, 32, scale=0.3), _rand(3, 32, 32, scale=0.2)]
    _agree(_block_fn(nn.GQAttention(32, 4, 2, 8), leaves),
           lambda ws, x: REF.attention_layer(ws, x, ARCH), leaves,
           _rand(9, 2, 24, 32))


def _moe_leaves(experts, held):
    return [_rand(0, experts, 32, scale=0.3), _rand(1, experts, scale=0.01),
            _rand(2, held * 24, 32, scale=0.2),
            _rand(3, held * 32, 24, scale=0.2),
            _rand(4, 48, 32, scale=0.2), _rand(5, 32, 48, scale=0.2)]


def test_mixture_of_experts_is_the_reference_s_layer():
    leaves = _moe_leaves(16, 8)
    moe = nn.SparseMoE(32, 16, 6, 24, 48, range(0, 8), 2.5)
    _agree(_block_fn(moe, leaves),
           lambda ws, x: REF.moe_layer(ws, x, ARCH), leaves,
           _rand(9, 2, 24, 32))


def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips hold 8 of 32 experts each: their routed parts, with the
    shared expert counted once, are the uncut reference's layer."""
    full = _moe_leaves(32, 32)
    u = _rand(9, 2, 24, 32)
    arch = dict(ARCH, n_routed_experts_published=32, experts_held=[0, 32])
    with jax.default_matmul_precision("highest"):
        whole = REF.moe_layer(full, u, arch)
        shared = REF.relu2_mlp(u, full[4], full[5])
        total = shared
        for first in range(0, 32, 8):
            mine = full[:2] + [full[2][first * 24:(first + 8) * 24],
                               full[3][first * 32:(first + 8) * 32]] \
                + full[4:]
            share = _block_fn(nn.SparseMoE(32, 32, 6, 24, 48,
                                           range(first, first + 8), 2.5),
                              mine)
            total = total + share(mine, u) - shared
    assert float(jnp.max(jnp.abs(total - whole))) \
        < 2e-4 * float(jnp.max(jnp.abs(whole)))


def test_no_assignment_is_dropped_when_every_token_chooses_one_expert():
    """A correction bias that makes every token choose expert 3: all the
    tokens land in one held group, every one is computed, none dropped."""
    leaves = _moe_leaves(16, 8)
    leaves[1] = leaves[1].at[3].set(10.0)
    u = _rand(9, 2, 24, 32)
    moe = _block_fn(nn.SparseMoE(32, 16, 6, 24, 48, range(0, 8), 2.5),
                    leaves)
    with profiler.counting() as counted, \
            jax.default_matmul_precision("highest"):
        out = moe(leaves, u)
        ref = REF.moe_layer(leaves, u, ARCH)
    counted = {k: float(v) for k, (_, v) in counted.items()}
    assert counted["moe_dropped"] == 0.0
    assert counted["moe_rows_max"] == 48.0  # every token, in one group
    assert counted["moe_assignments"] == 48 * 6
    assert counted["moe_assignments_held"] >= 48
    assert float(jnp.max(jnp.abs(out - ref))) \
        < 2e-4 * float(jnp.max(jnp.abs(ref)))
    # and by the op itself: every token's only held choice is expert 3
    ids = jnp.tile(jnp.array([[3, 9, 10, 11, 12, 13]]), (48, 1))
    weights = jnp.full((48, 6), 0.4)
    with profiler.counting() as counted:
        got = rex.routed_experts(u.reshape(48, 32), ids, weights, leaves[2],
                                 leaves[3], held=(0, 8))
    assert float(counted["moe_dropped"][1]) == 0.0
    assert float(counted["moe_assignments_held"][1]) == 48.0
    want = 0.4 * REF.relu2_mlp(u.reshape(48, 32), leaves[2][72:96],
                               leaves[3][96:128])
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4


# ------------------------------------------------------------ the stack
def _tiny_net(**kwargs):
    from mxnet_tpu.gluon.model_zoo import language

    net = language.nemotron_h(
        vocab_size=600, hidden_size=32, pattern="MEMEM*EME",
        mamba_num_heads=4, mamba_head_dim=8, n_groups=2, ssm_state_size=16,
        chunk_size=8, num_attention_heads=4, num_key_value_heads=2,
        head_dim=8, n_routed_experts=16, moe_intermediate_size=24,
        moe_shared_expert_intermediate_size=48, experts_held=(0, 8),
        **kwargs)
    net.initialize()
    return net


def test_rematerialised_stack_has_the_plain_stack_s_gradients():
    np.random.seed(3)
    mx.random.seed(3)
    net = _tiny_net(remat=True)
    params, apply_fn = parallel.functionalize(net, train=True)
    x = jnp.asarray(np.random.randint(0, 600, (2, 16)), jnp.int32)

    def loss(p):
        return jnp.sum(jnp.sin(apply_fn(p, x)))

    with_remat = jax.jit(jax.grad(loss))(params)
    net._remat = False
    plain = jax.jit(jax.grad(loss))(params)
    assert "remat2" not in str(jax.make_jaxpr(jax.grad(loss))(params))
    net._remat = True
    assert "remat2" in str(jax.make_jaxpr(jax.grad(loss))(params))
    for n in params:
        top = max(float(jnp.max(jnp.abs(plain[n]))), 1e-6)
        assert float(jnp.max(jnp.abs(with_remat[n] - plain[n]))) \
            < 1e-4 * top, n


def test_float32_leaves_under_mixed_precision_are_chosen_by_name():
    net = _tiny_net()
    params, _ = parallel.functionalize(net, train=True)
    cast = parallel.amp_cast_params(params, "bfloat16")
    kept = {n for n, v in cast.items() if v.dtype == jnp.float32}
    for part in ("_gamma", "_A_log", "_dt_bias", "_D", "router_weight",
                 "correction_bias"):
        assert any(n.endswith(part) for n in kept), part
    assert all(n.endswith(("gamma", "A_log", "dt_bias", "_D",
                           "router_weight", "correction_bias"))
               for n in kept)
    # 10 norms + 4 x (gated norm, A_log, dt_bias, D) + 4 x (router, bias)
    assert len(kept) == 10 + 16 + 8


def test_token_ids_reach_the_embedding_whole_under_bfloat16():
    """Ids above 256 are no bfloat16 numbers: the step casts a float batch
    to the compute type and leaves an integer one alone."""
    from mxnet_tpu.parallel import train_step as ts

    table = nn.Embedding(600, 600)
    table.initialize()
    params, apply_fn = parallel.functionalize(table, train=True)
    params = {n: jnp.eye(600, dtype=jnp.float32) for n in params}
    ids = jnp.array([[255, 256, 257, 511, 513, 599]], jnp.int32)

    def row_found(out, y):
        return mx.nd.NDArray(jnp.argmax(out._data, axis=-1).astype(
            jnp.float32) - y._data)

    loss_of, _ = ts.make_loss_of(apply_fn, row_found, "bfloat16")
    off, counted = loss_of(params, ids, ids.astype(jnp.float32),
                           jax.random.key(0))
    assert float(off) == 0.0 and counted == {}
    assert float(jnp.max(jnp.abs(ids.astype(jnp.bfloat16).astype(
        jnp.int32) - ids))) > 0  # what the cast would have done


def test_counters_leave_the_compiled_step_through_its_state():
    np.random.seed(4)
    mx.random.seed(4)
    net = _tiny_net()
    before = [p.data()._data for p in net.collect_params().values()]
    step, params, opt_state = parallel.make_train_step(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), optimizer="sgd",
        learning_rate=0.01, momentum=0.9, compute_dtype="bfloat16")
    names = {"moe_assignments", "moe_assignments_held", "moe_rows_max",
             "moe_dropped", "moe_layers", "moe_layers_grouped"}
    assert set(opt_state["_counters"]) == names
    # on a CPU the block's own arrays are where they were
    assert all(a is p.data()._data for a, p in
               zip(before, net.collect_params().values()))
    x = jnp.asarray(np.random.randint(0, 600, (2, 24)), jnp.int32)
    y = jnp.asarray(np.random.randint(0, 600, (2, 24)), jnp.float32)
    trees = set()
    for t in range(1, 4):
        trees.add(jax.tree_util.tree_structure(opt_state))
        loss, params, opt_state = step(params, opt_state, x, y,
                                       jax.random.key(0), float(t))
        got = profiler.step_counters()
        assert set(got) == names and got["moe_dropped"] == 0.0
        assert got["moe_assignments"] == 4 * 48 * 6  # four mixtures
        # 8 of 16 held, 6 chosen: the budget is every possible row
        assert got["moe_layers"] == got["moe_layers_grouped"] == 4
        assert 0 < got["moe_assignments_held"] < got["moe_assignments"]
        assert got["moe_rows_max"] >= got["moe_assignments_held"] / 32
    assert len(trees) == 1 and np.isfinite(float(loss))
    assert len(profiler.step_counters(last=2)) == 2
    # a net that counts nothing keeps its state as it was
    dense = nn.Dense(4, in_units=3)
    dense.initialize()
    _, _, plain = parallel.make_train_step(
        dense, gluon.loss.L2Loss(), optimizer="sgd")
    assert "_counters" not in plain


def test_dense_without_a_bias_runs():
    dense = nn.Dense(4, use_bias=False, flatten=False, in_units=3)
    dense.initialize()
    out = dense(mx.nd.NDArray(jnp.ones((2, 5, 3))))
    assert out.shape == (2, 5, 4)
