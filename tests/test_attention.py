"""Flash attention + ring context parallelism tests (§5.7 mandate).

The Pallas kernel runs in interpreter mode on the CPU test mesh; the
ring runs over the 8-device shard_map mesh — both are checked against
the fp32 reference math.
"""
import numpy as onp
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.ops.flash_attention import (_naive_attention,
                                           flash_attention)
from mxnet_tpu.parallel import get_mesh
from mxnet_tpu.parallel import ring as ring_mod

onp.random.seed(13)


def _qkv(b=2, h=2, s=256, d=64, dtype="float32"):
    q = onp.random.randn(b, h, s, d).astype(dtype) * 0.3
    k = onp.random.randn(b, h, s, d).astype(dtype) * 0.3
    v = onp.random.randn(b, h, s, d).astype(dtype) * 0.3
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_kernel_matches_naive(causal):
    q, k, v = _qkv()
    ref = _naive_attention(q, k, v, causal, 1.0 / 8.0)
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                rtol=2e-4, atol=2e-5)


def test_flash_kernel_bf16():
    q, k, v = _qkv(s=128)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    out = flash_attention(qb, kb, vb, interpret=True)
    ref = _naive_attention(q, k, v, False, 1.0 / 8.0)
    assert out.dtype == jnp.bfloat16
    onp.testing.assert_allclose(onp.asarray(out, dtype="float32"),
                                onp.asarray(ref), rtol=5e-2, atol=5e-2)


def test_flash_gradient_matches_naive():
    q, k, v = _qkv(b=1, h=1, s=128, d=64)

    def loss_flash(q_, k_, v_):
        return (flash_attention(q_, k_, v_, causal=True,
                                interpret=True) ** 2).sum()

    def loss_naive(q_, k_, v_):
        return (_naive_attention(q_, k_, v_, True, 1.0 / 8.0) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gn = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gn):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=1e-3, atol=1e-4)


def test_flash_fallback_odd_shapes():
    # 100 % 128 != 0 -> naive fallback, still correct
    q, k, v = _qkv(s=100)
    out = flash_attention(q, k, v)
    ref = _naive_attention(q, k, v, False, 1.0 / 8.0)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                rtol=1e-5, atol=1e-6)


def test_dot_product_attention_op():
    b, s, nh, d = 2, 64, 4, 16
    q = mx.nd.array(onp.random.randn(b, s, nh * d).astype("float32"))
    k = mx.nd.array(onp.random.randn(b, s, nh * d).astype("float32"))
    v = mx.nd.array(onp.random.randn(b, s, nh * d).astype("float32"))
    out = mx.nd.invoke("_contrib_dot_product_attention", [q, k, v],
                       num_heads=nh)
    assert out.shape == (b, s, nh * d)
    # gradient flows through the custom vjp
    q.attach_grad()
    from mxnet_tpu import autograd

    with autograd.record():
        o = mx.nd.invoke("_contrib_dot_product_attention", [q, k, v],
                         num_heads=nh)
        loss = (o * o).sum()
    loss.backward()
    assert onp.abs(q.grad.asnumpy()).max() > 0


def test_div_sqrt_dim():
    x = mx.nd.ones((2, 16))
    out = mx.nd.invoke("_contrib_div_sqrt_dim", [x])
    onp.testing.assert_allclose(out.asnumpy(), onp.ones((2, 16)) / 4.0)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(causal):
    """Ring CP over the 8-device mesh == full attention (SURVEY.md
    §5.7: 'correctness test vs naive attention on the CPU mesh')."""
    mesh = get_mesh((8,), ("seq",))
    b, h, s, d = 2, 2, 128, 32  # 16 tokens per device
    q, k, v = _qkv(b, h, s, d)
    out = ring_mod.ring_attention(q, k, v, mesh, axis_name="seq",
                                  causal=causal)
    ref = _naive_attention(q, k, v, causal, 1.0 / (d ** 0.5))
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                rtol=2e-4, atol=2e-5)


def test_ring_attention_memory_contract():
    """Each device's shard is seq/n — the point of the ring."""
    mesh = get_mesh((8,), ("seq",))
    q, k, v = _qkv(1, 1, 64, 16)
    out = ring_mod.ring_attention(q, k, v, mesh)
    shard_shapes = {tuple(s.data.shape)
                    for s in out.addressable_shards}
    assert shard_shapes == {(1, 1, 8, 16)}


def test_ring_attention_gradients():
    mesh = get_mesh((8,), ("seq",))
    b, h, s, d = 1, 1, 64, 16
    q, k, v = _qkv(b, h, s, d)

    def loss_ring(q_, k_, v_):
        return (ring_mod.ring_attention(q_, k_, v_, mesh) ** 2).sum()

    def loss_naive(q_, k_, v_):
        return (_naive_attention(q_, k_, v_, False,
                                 1.0 / (d ** 0.5)) ** 2).sum()

    gr = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    gn = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gr, gn):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b_),
                                    rtol=1e-3, atol=1e-4)


# ---------------------------------------- round 14: variants + pad shim
@pytest.mark.parametrize("causal", [False, True])
def test_pallas_pad_variant_matches_naive_nonaligned(causal):
    """The padding shim: non-tile-aligned, NON-SQUARE seq lens run the
    kernel padded with masked keys; fwd and bwd match the reference
    (bottom-right causal alignment computed against the VALID key
    length, not the padded one)."""
    rng = onp.random.RandomState(3)
    q = jnp.asarray(rng.randn(2, 2, 70, 16).astype("float32") * 0.3)
    k = jnp.asarray(rng.randn(2, 2, 90, 16).astype("float32") * 0.3)
    v = jnp.asarray(rng.randn(2, 2, 90, 16).astype("float32") * 0.3)
    ref = _naive_attention(q, k, v, causal, 0.25)
    out = flash_attention(q, k, v, causal=causal, sm_scale=0.25,
                          variant="pallas_pad", interpret=True)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                rtol=1e-5, atol=1e-6)

    def loss_pad(q_, k_, v_):
        return (flash_attention(q_, k_, v_, causal=causal,
                                sm_scale=0.25, variant="pallas_pad",
                                interpret=True) ** 2).sum()

    def loss_naive(q_, k_, v_):
        return (_naive_attention(q_, k_, v_, causal, 0.25) ** 2).sum()

    gp = jax.grad(loss_pad, argnums=(0, 1, 2))(q, k, v)
    gn = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gp, gn):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b_),
                                    rtol=1e-4, atol=1e-5)


def test_block_size_subvariant_matches_naive():
    q, k, v = _qkv(b=1, h=2, s=256, d=16)
    ref = _naive_attention(q, k, v, True, 0.25)
    out = flash_attention(q, k, v, causal=True, sm_scale=0.25,
                          variant="pallas_b256", interpret=True)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                rtol=1e-5, atol=1e-6)


def test_variant_registry_consult(tmp_path, monkeypatch):
    """flash_attention with no explicit variant consults the autotune
    registry: a force scope pins the lowering, and a cached winner
    applies through program_scope."""
    from mxnet_tpu import autotune as at

    monkeypatch.setenv("MXNET_AUTOTUNE_CACHE_DIR",
                       str(tmp_path / "atc"))
    at.cache_clear()
    q, k, v = _qkv(b=1, h=1, s=64, d=8)
    ref = _naive_attention(q, k, v, False, 1.0 / (8 ** 0.5))
    with at.force(flash_attention="pallas_pad"):
        out = flash_attention(q, k, v, interpret=True)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                rtol=1e-5, atol=1e-6)
    # cached winner path: record + program_scope -> same answer
    at.record("flash_attention", tuple(q.shape), "float32",
              winner="naive", platform="cpu", mesh="none")
    with at.program_scope(q.shape, "float32", platform="cpu",
                          mesh="none"):
        out2 = flash_attention(q, k, v)
    onp.testing.assert_allclose(onp.asarray(out2), onp.asarray(ref),
                                rtol=1e-6, atol=1e-7)
    at.cache_clear()


def test_fallback_emits_autotune_event(tmp_path):
    """_can_use_pallas' silent fallback is gone: a non-tile-aligned
    shape that consulted the default heuristic leaves an ``autotune``
    event naming the reason in the armed run log."""
    import json

    from mxnet_tpu import telemetry

    path = str(tmp_path / "run.jsonl")
    rl = telemetry.reset(path)
    try:
        q, k, v = _qkv(b=1, h=1, s=100, d=8)
        _ = flash_attention(q, k, v)  # 100 % 128 -> fallback
    finally:
        telemetry.close()
    events = []
    with open(path) as f:
        for line in f:
            rec = json.loads(line)
            if rec.get("type") == "event" and \
                    rec.get("kind") == "autotune":
                events.append(rec)
    assert events, "fallback must leave an attributed autotune event"
    ev = events[-1]
    assert ev["op"] == "flash_attention"
    assert ev["winner"] == "naive"
    assert "tile-aligned" in ev["reason"]
    assert "pallas_pad" in ev["reason"]


# ------------------------------------- round 17: ragged-tail exactness
_ALL_VARIANTS = ("naive", "pallas", "pallas_b256", "pallas_pad")


@pytest.mark.parametrize("variant", _ALL_VARIANTS)
@pytest.mark.parametrize("causal", [False, True])
def test_ragged_tail_matches_reference_all_variants(causal, variant):
    """Every registered flash_attention variant agrees with the fp32
    reference on a RAGGED prompt shape (the generative prefill case:
    s=10 inside a padded bucket).  Forced kernel variants that cannot
    tile fall back to naive — the answer must still be exact."""
    from mxnet_tpu.autotune import VARIANT_OPS

    assert set(_ALL_VARIANTS) == set(VARIANT_OPS["flash_attention"]), \
        "a new registered variant must join this exactness matrix"
    rng = onp.random.RandomState(17)
    q = jnp.asarray(rng.randn(1, 2, 10, 8).astype("float32") * 0.3)
    k = jnp.asarray(rng.randn(1, 2, 10, 8).astype("float32") * 0.3)
    v = jnp.asarray(rng.randn(1, 2, 10, 8).astype("float32") * 0.3)
    ref = _naive_attention(q, k, v, causal, 8 ** -0.5)
    out = flash_attention(q, k, v, causal=causal, variant=variant,
                          interpret=True)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("variant", _ALL_VARIANTS)
@pytest.mark.parametrize("causal", [False, True])
def test_padded_rows_contribute_exactly_zero(causal, variant):
    """The padding-mask proof: blocks-aligned inputs whose tail keys
    hold 1e9 GARBAGE must reproduce the valid-slice reference — any
    nonzero softmax mass on a padded row would swamp the output by
    ~1e9, so agreement at 1e-5 means the tail's normalization weight
    is exactly zero in every variant."""
    from mxnet_tpu.ops.flash_attention import _flash

    rng = onp.random.RandomState(23)
    valid = 10
    q = jnp.asarray(rng.randn(1, 2, valid, 8).astype("float32") * 0.3)
    k = jnp.asarray(rng.randn(1, 2, valid, 8).astype("float32") * 0.3)
    v = jnp.asarray(rng.randn(1, 2, valid, 8).astype("float32") * 0.3)
    ref = _naive_attention(q, k, v, causal, 8 ** -0.5)
    pad = 128 - valid
    widths = ((0, 0), (0, 0), (0, pad), (0, 0))
    qp = jnp.pad(q, widths)
    kp = jnp.pad(k, widths, constant_values=1e9)
    vp = jnp.pad(v, widths, constant_values=1e9)
    out = _flash(qp, kp, vp, causal, 8 ** -0.5, True, variant,
                 valid, valid)
    got = onp.asarray(out[:, :, :valid, :])
    assert onp.isfinite(got).all(), \
        f"{variant}: padded garbage leaked into the output"
    onp.testing.assert_allclose(got, onp.asarray(ref),
                                rtol=1e-5, atol=1e-6)


# ------------------- the training pair: grouped heads, the operands' dtype
def _grouped(rng, heads, kv_heads, sq, sk, d=32, dtype="float32"):
    def draw(h, s):
        return jnp.asarray(rng.randn(2, h, s, d).astype("float32") * 0.5)

    q, k, v, ct = draw(heads, sq), draw(kv_heads, sk), draw(kv_heads, sk), \
        draw(heads, sq)
    return tuple(x.astype(dtype) for x in (q, k, v)) + (ct,)


def _fwd_bwd(fn, q, k, v, ct):
    out, vjp = jax.vjp(fn, q, k, v)
    return (out,) + vjp(ct.astype(out.dtype))


@pytest.mark.parametrize("seqs,variant", [((256, 256), None),
                                          ((70, 90), "pallas_pad")],
                         ids=["aligned", "ragged"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("heads,kv_heads", [(4, 2), (8, 1), (2, 2)])
def test_kernel_pair_matches_reference(heads, kv_heads, causal, dtype,
                                       seqs, variant):
    """The forward kernel and the two backward kernels, interpreted,
    against the float32 reference over the same operands (grouped key
    /value heads repeated for it): bf16 operands enter the MXU as bf16
    with float32 sums, so their tolerance is bf16's; float32 keeps
    today's."""
    q, k, v, ct = _grouped(onp.random.RandomState(37), heads, kv_heads,
                           *seqs, dtype=dtype)
    scale = 32 ** -0.5
    got = _fwd_bwd(lambda q_, k_, v_: flash_attention(
        q_, k_, v_, causal=causal, interpret=True, variant=variant),
        q, k, v, ct)
    want = _fwd_bwd(lambda q_, k_, v_: _naive_attention(
        q_, k_, v_, causal, scale), *(x.astype(jnp.float32)
                                      for x in (q, k, v)), ct)
    for name, a, b, x in zip(("out", "dq", "dk", "dv"), got, want,
                             (q, q, k, v)):
        assert a.dtype == x.dtype and a.shape == x.shape, name
        a, b = onp.asarray(a, dtype="float32"), onp.asarray(b)
        if dtype == "float32":
            onp.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-4,
                                        err_msg=name)
        else:
            err = onp.abs(a - b).max() / onp.abs(b).max()
            assert err < 2e-2, (name, err)


def test_kernel_pair_is_the_kernels_on_both_passes(monkeypatch):
    """Where the forward ran the kernel the backward runs the two
    kernels (the chunked jnp backward is not reached), and where the
    fused jnp math ran forward the jnp backward follows it."""
    from mxnet_tpu.ops import flash_attention as fa

    ran = []
    for name in ("_flash_backward_pallas", "_chunked_bwd"):
        real = getattr(fa, name)
        monkeypatch.setattr(fa, name, lambda *a, _r=real, _n=name, **kw:
                            ran.append(_n) or _r(*a, **kw))
    q, k, v, ct = _grouped(onp.random.RandomState(5), 4, 2, 128, 128)
    for variant in ("pallas", "naive"):
        _fwd_bwd(lambda q_, k_, v_: flash_attention(
            q_, k_, v_, causal=True, variant=variant), q, k, v, ct)
    assert ran == ["_flash_backward_pallas", "_chunked_bwd"]


def test_backward_declines_what_it_cannot_hold(monkeypatch):
    """A query sequence longer than ``dkdv`` holds whole, beside keys the
    forward holds: the backward's decline is counted and the chunked jnp
    backward gives the same gradients."""
    from mxnet_tpu.ops import flash_attention as fa
    from mxnet_tpu.ops import kernel_target

    q, k, v, ct = _grouped(onp.random.RandomState(9), 4, 2, 256, 128)
    monkeypatch.setattr(fa, "_KV_VMEM_BUDGET", 128 * 4 * 128 * 4)
    assert fa.max_seq_k(32, "float32") == 128
    before = kernel_target.declined_counts().get("flash_attention", 0)
    got = _fwd_bwd(lambda q_, k_, v_: flash_attention(
        q_, k_, v_, variant="pallas"), q, k, v, ct)
    assert kernel_target.declined_counts()["flash_attention"] == before + 1
    want = _fwd_bwd(lambda q_, k_, v_: _naive_attention(
        q_, k_, v_, False, 32 ** -0.5), q, k, v, ct)
    for a, b in zip(got, want):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("variant", ["naive", "pallas"])
def test_gq_attention_serves_groups_without_repeating(variant):
    """``GQAttention``'s op hands the kernels its 2 key/value heads
    unrepeated: output and gradients are those of the 8 query heads
    over key/value heads repeated to 8, the way it ran before."""
    from mxnet_tpu import autotune as at
    from mxnet_tpu.gluon.nn.sequence_layers import _gq_attention

    rng = onp.random.RandomState(11)
    heads, kv_heads, dim, length = 8, 2, 32, 128
    q = jnp.asarray(rng.randn(2, length, heads * dim).astype("float32"))
    k, v = (jnp.asarray(rng.randn(2, length, kv_heads * dim)
                        .astype("float32")) for _ in range(2))
    ct = jnp.asarray(rng.randn(2, length, heads * dim).astype("float32"))

    def repeated(q_, k_, v_):
        def split(t, n):
            return t.reshape(2, length, n, dim).transpose(0, 2, 1, 3)

        def serve(t):
            return jnp.repeat(split(t, kv_heads), heads // kv_heads, axis=1)

        out = flash_attention(split(q_, heads), serve(k_), serve(v_),
                              causal=True)
        return out.transpose(0, 2, 1, 3).reshape(2, length, heads * dim)

    with at.force(flash_attention=variant):
        got = _fwd_bwd(lambda q_, k_, v_: _gq_attention.fn(
            q_, k_, v_, heads=heads, kv_heads=kv_heads,
            scope="gqattention0"), q, k, v, ct)
        want = _fwd_bwd(repeated, q, k, v, ct)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=1e-5, atol=1e-6)
