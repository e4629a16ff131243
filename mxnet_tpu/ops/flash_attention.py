"""Flash attention — Pallas TPU kernel with online softmax.

SURVEY.md §5.7 mandate: the reference has no fused attention (only
bucketing + contrib div_sqrt_dim, src/operator/contrib/transformer.cc);
long-context on TPU requires an O(seq) -memory attention kernel.  This
is the single-chip building block; ring context parallelism composes it
across chips (mxnet_tpu.parallel.ring).

Design (standard flash-attention-2 schedule on the MXU):
  grid = (batch*heads, q_blocks); the kernel walks k/v blocks in VMEM,
  keeping the running max m, normalizer l and accumulator acc in f32;
  one rescale per block keeps everything numerically exact, and each
  query row's log-sum-exp leaves as the backward's residual.  Only the
  key blocks that cross the diagonal (or the valid length) are masked;
  those wholly above it are skipped.
Grouped-query heads: ``k``/``v`` may hold ``kv_heads`` heads for ``q``'s
  ``group * kv_heads``; the kernels' index maps send query head h to
  key/value head h // group, so nothing is repeated in memory.
Operand dtype: every product takes the operands in their own dtype
  (bf16 on the MXU as bf16; float32 stays float32) with float32
  accumulation; the softmax statistics, ``delta`` and the gradient sums
  stay float32 on the vector unit.
Backward, where the forward ran the kernel: two kernels.  ``dq`` walks
  a query block over its key blocks; ``dkdv`` holds a key block of one
  key/value head and walks the group's query heads (the innermost grid
  axis) and their query blocks, so each key/value head is read once for
  its group and its gradient written once, summed over the group.
  ``delta = rowsum(dO * O)`` is made once before them.  Where the fused
  jnp math ran forward, or ``dkdv`` cannot hold a query head whole, the
  backward recomputes in query chunks (O(chunk * seq) live memory).
Causal masking uses bottom-right alignment (query i attends keys
j <= i + seq_k - seq_q), identical across kernel/fallback/backward.

Round 14 — the kernel is an in-step autotune variant: the
``flash_attention`` op in ``autotune.VARIANT_OPS`` races the naive
fused-jnp math against the Pallas schedule (block-size sub-variants
included) inside the caller's real jitted step, and the winner applies
per (shape, dtype, platform, mesh) at trace time.  Variants:

* ``naive``       — the fused jnp math (XLA's own fusion);
* ``pallas``      — the kernel at the default 128/128 q/k blocks;
* ``pallas_b256`` — 256/256 blocks (wins on long-seq shapes where the
  larger q tile amortizes the k/v stream);
* ``pallas_pad``  — tile-align by PADDING: non-aligned seq lens pad up
  to the block size, padded keys are masked out of the softmax
  (``kv_valid``), padded query rows are sliced off — so shapes that
  used to silently fall back to jnp can still race the kernel.

With no variant decision the kernels run on a TPU at the largest blocks
up to 256/512 (forward) and 512 (backward) that tile the shape.  Falls
back to the fused jnp implementation off-TPU or for shapes that
don't tile (seq % block != 0) — same math, same vjp.  The silent part
of that fallback is gone: a shape that WANTED the kernel but could not
tile emits an ``autotune`` telemetry event naming the reason, so a
run log shows exactly which attention shapes never raced.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import kernel_target
from .registry import register_op

_BLOCK_Q = 128
_BLOCK_K = 128
#: the default path's largest blocks: the forward's (query, key), and
#: every block of the backward.  The largest of these, halved down to
#: 128, that tile the sequence are taken.  On a v5e at 2 x 32 query
#: heads over 2 x 2 key/value heads of 4096 x 128 bf16, causal, the
#: forward takes 14.0 ms at 128/128, 4.25 at 256/512 and 512/512; the
#: backward 23.7 ms at 128, 9.5 at 256 and 7.9 at 512 (a forward of
#: 512/512 does not compile at ``max_seq_k`` in float32)
_FWD_MOST = (256, 512)
_BWD_MOST = 512

#: forced-value -> (block_q, block_k) for the kernel sub-variants
_VARIANT_BLOCKS = {
    "pallas": (_BLOCK_Q, _BLOCK_K),
    "pallas_b256": (256, 256),
    "pallas_pad": (_BLOCK_Q, _BLOCK_K),
}


def _expand_kv(t, q):
    """``t`` (batch, kv_heads, seq, d) repeated to ``q``'s heads: the
    fused jnp math's own way to serve a group of query heads."""
    group = q.shape[1] // t.shape[1]
    return t if group == 1 else jnp.repeat(t, group, axis=1)


def _naive_attention(q, k, v, causal, sm_scale, kv_valid=None,
                     q_valid=None):
    """Reference math in fp32: softmax(q k^T * scale [+ mask]) v.
    ``kv_valid``/``q_valid`` are the padding-shim contract: keys at
    positions >= kv_valid are masked out, and the causal alignment is
    computed against the VALID lengths so padding never shifts which
    real keys a real query sees.  ``k``/``v`` may hold fewer heads than
    ``q`` (a group of query heads a key/value head)."""
    k, v = _expand_kv(k, q), _expand_kv(v, q)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    qlen, klen = s.shape[-2], s.shape[-1]
    if causal:
        eff_k = klen if kv_valid is None else kv_valid
        eff_q = qlen if q_valid is None else q_valid
        mask = jnp.tril(jnp.ones((qlen, klen), bool), eff_k - eff_q)
        s = jnp.where(mask, s, -jnp.inf)
    if kv_valid is not None and kv_valid < klen:
        kmask = (jnp.arange(klen) < kv_valid)[None, None, None, :]
        s = jnp.where(kmask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    if kv_valid is not None and kv_valid < klen:
        # a fully-masked row softmaxes to uniform garbage; zero it the
        # way the kernel's l=0 guard does
        p = jnp.where(jnp.isfinite(s).any(-1, keepdims=True), p, 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


#: ``dot_general`` dimension numbers: ``a @ b`` and ``a @ b.T``
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))


def _mxu(a, b, dims):
    """A product on the MXU in the operands' own dtype, accumulated in
    float32."""
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _shift(seq_q, seq_k, kv_valid, q_valid):
    """Bottom-right causal alignment: query row i sees key j <= i +
    shift, computed against the VALID lengths where the padding shim
    appended masked keys / sliced-off queries."""
    return ((seq_k if kv_valid is None else kv_valid)
            - (seq_q if q_valid is None else q_valid))


def _key_blocks(q_lo, block_q, block_k, seq_k, causal, kv_valid):
    """``(whole, last)`` for a query block whose first row's shifted
    position is ``q_lo``: key blocks [0, whole) score for every row of
    it; [whole, last) cross the diagonal or the valid length and are
    masked; the blocks after them are skipped."""
    kv_end = seq_k if kv_valid is None else kv_valid
    whole, last = kv_end // block_k, -(-kv_end // block_k)
    if causal:
        whole = jnp.maximum(jnp.minimum(whole, (q_lo + 1) // block_k), 0)
        last = jnp.minimum(last, (q_lo + block_q - 1) // block_k + 1)
    return whole, last


def _mask(s, q_first, k_first, causal, kv_valid, keys_down=False):
    """``s`` with the pairs that do not score at -inf: queries from the
    shifted position ``q_first`` run along its rows and keys from
    ``k_first`` along its columns, or the other way round with
    ``keys_down``."""
    q_axis, k_axis = (1, 0) if keys_down else (0, 1)
    qpos = q_first + jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
    kpos = k_first + jax.lax.broadcasted_iota(jnp.int32, s.shape, k_axis)
    ok = qpos >= kpos if causal else None
    if kv_valid is not None:
        ok = kpos < kv_valid if ok is None else ok & (kpos < kv_valid)
    return jnp.where(ok, s, -jnp.inf)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, causal,
                  sm_scale, block_k, seq_k, shift, kv_valid):
    from jax.experimental import pallas as pl

    q = q_ref[0]  # (block_q, d), the operands' dtype
    block_q = q.shape[0]
    q_lo = pl.program_id(1) * block_q + shift  # first row's shifted pos
    whole, last = _key_blocks(q_lo, block_q, block_k, seq_k, causal,
                              kv_valid)

    def body(kb, carry, masked):
        m, l, acc = carry
        start = pl.multiple_of(kb * block_k, block_k)
        k_blk = k_ref[0, pl.ds(start, block_k), :]
        v_blk = v_ref[0, pl.ds(start, block_k), :]
        s = _mxu(q, k_blk, _NT) * sm_scale  # (block_q, block_k) f32
        if masked:
            s = _mask(s, q_lo, start, causal, kv_valid)
        m_new = jnp.maximum(m, s.max(axis=-1))
        # a row with no key scored yet keeps m = -inf: exp against 0
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0) if masked \
            else m_new
        p = jnp.exp(s - m_safe[:, None])
        alpha = jnp.exp(m - m_safe)
        l = alpha * l + p.sum(axis=-1)
        acc = alpha[:, None] * acc + _mxu(p.astype(v_blk.dtype), v_blk,
                                          _NN)
        return m_new, l, acc

    carry = (jnp.full((block_q,), -jnp.inf, jnp.float32),
             jnp.zeros((block_q,), jnp.float32),
             jnp.zeros((block_q, q.shape[1]), jnp.float32))
    carry = jax.lax.fori_loop(0, whole, functools.partial(
        body, masked=False), carry)
    if causal or kv_valid is not None:
        carry = jax.lax.fori_loop(whole, last, functools.partial(
            body, masked=True), carry)
    m, l, acc = carry
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)
    # a row that saw no key keeps +inf: the backward's exp gives it 0
    lse_ref[0, 0, :] = jnp.where(
        l > 0, m + jnp.log(jnp.maximum(l, 1e-30)), jnp.inf)


def _kv_row(group):
    """Index map of a key/value head's whole sequence: query row ``r`` of
    ``batch * heads`` reads key/value row ``r // group``."""
    return lambda r, i: (r // group, 0, 0)


def _flash_forward_pallas(q, k, v, causal, sm_scale, block_q=_BLOCK_Q,
                          block_k=_BLOCK_K, kv_valid=None,
                          q_valid=None, interpret=False):
    """``(out, lse)``: the attention and each query row's log-sum-exp
    of its scaled scores, (batch * heads, 1, seq_q) float32."""
    from jax.experimental import pallas as pl

    b, h, sq, d = q.shape
    kvh, sk = k.shape[1], k.shape[2]
    kernel = functools.partial(
        _flash_kernel, causal=causal, sm_scale=sm_scale, block_k=block_k,
        seq_k=sk, shift=_shift(sq, sk, kv_valid, q_valid),
        kv_valid=kv_valid if kv_valid is not None and kv_valid < sk
        else None)
    out, lse = pl.pallas_call(
        kernel,
        grid=(b * h, sq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda r, i: (r, i, 0)),
            pl.BlockSpec((1, sk, d), _kv_row(h // kvh)),
            pl.BlockSpec((1, sk, d), _kv_row(h // kvh)),
        ],
        out_specs=[pl.BlockSpec((1, block_q, d), lambda r, i: (r, i, 0)),
                   pl.BlockSpec((1, 1, block_q), lambda r, i: (r, 0, i))],
        out_shape=[jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
                   jax.ShapeDtypeStruct((b * h, 1, sq), jnp.float32)],
        interpret=interpret,
        name="flash_attention_fwd",
    )(q.reshape(b * h, sq, d), k.reshape(b * kvh, sk, d),
      v.reshape(b * kvh, sk, d))
    return out.reshape(b, h, sq, d), lse


# ------------------------------------------------------------- backward
def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               *, causal, sm_scale, block_k, seq_k, shift, kv_valid):
    """``dq`` of one query block: the forward's walk over the key
    blocks, ``p`` rebuilt from the row's log-sum-exp."""
    from jax.experimental import pallas as pl

    q, do = q_ref[0], do_ref[0]
    block_q = q.shape[0]
    lse = jnp.expand_dims(lse_ref[0, 0], -1)  # (block_q, 1)
    delta = jnp.expand_dims(delta_ref[0, 0], -1)
    q_lo = pl.program_id(1) * block_q + shift
    whole, last = _key_blocks(q_lo, block_q, block_k, seq_k, causal,
                              kv_valid)

    def body(kb, dq, masked):
        start = pl.multiple_of(kb * block_k, block_k)
        k_blk = k_ref[0, pl.ds(start, block_k), :]
        v_blk = v_ref[0, pl.ds(start, block_k), :]
        s = _mxu(q, k_blk, _NT) * sm_scale
        if masked:
            s = _mask(s, q_lo, start, causal, kv_valid)
        p = jnp.exp(s - lse)
        ds = p * (_mxu(do, v_blk, _NT) - delta)
        return dq + _mxu(ds.astype(k_blk.dtype), k_blk, _NN)

    dq = jnp.zeros(q.shape, jnp.float32)
    dq = jax.lax.fori_loop(0, whole, functools.partial(
        body, masked=False), dq)
    if causal or kv_valid is not None:
        dq = jax.lax.fori_loop(whole, last, functools.partial(
            body, masked=True), dq)
    dq_ref[0] = (dq * sm_scale).astype(dq_ref.dtype)


def _dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                 dv_ref, dk_acc, dv_acc, *, causal, sm_scale, block_q,
                 shift, kv_valid):
    """``dk``, ``dv`` of one key block of one key/value head: the grid's
    innermost axis walks the group's query heads, each over its query
    blocks at and below the diagonal, in the transposed orientation
    (keys down the sublanes) so that the log-sum-exp and ``delta`` enter
    as rows; both sums stay in float32 scratch until the group's last
    head."""
    from jax.experimental import pallas as pl

    k, v = k_ref[0], v_ref[0]  # (block_k, d)
    block_k = k.shape[0]
    k_lo = pl.program_id(1) * block_k
    g, group = pl.program_id(2), pl.num_programs(2)
    nq = q_ref.shape[1] // block_q

    @pl.when(g == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    # query blocks [first, whole) cross the diagonal or the valid length
    # and are masked; [whole, nq) see every key of this block
    first, whole = 0, 0
    if causal:
        first = jnp.clip((k_lo - shift) // block_q, 0, nq)
        whole = jnp.clip(
            (k_lo + block_k - 1 - shift + block_q - 1) // block_q, 0, nq)
    if kv_valid is not None:
        whole = jnp.where(k_lo + block_k > kv_valid, nq, whole)
        first = jnp.where(k_lo >= kv_valid, nq, first)

    def body(qb, carry, masked):
        start = pl.multiple_of(qb * block_q, block_q)
        q_blk = q_ref[0, pl.ds(start, block_q), :]
        do_blk = do_ref[0, pl.ds(start, block_q), :]
        lse = lse_ref[0, :, pl.ds(start, block_q)]  # (1, block_q)
        delta = delta_ref[0, :, pl.ds(start, block_q)]
        s = _mxu(k, q_blk, _NT) * sm_scale  # (block_k, block_q)
        if masked:
            s = _mask(s, start + shift, k_lo, causal, kv_valid,
                      keys_down=True)
        p = jnp.exp(s - lse)
        dv_acc[...] += _mxu(p.astype(do_blk.dtype), do_blk, _NN)
        ds = p * (_mxu(v, do_blk, _NT) - delta)
        dk_acc[...] += _mxu(ds.astype(q_blk.dtype), q_blk, _NN)
        return carry

    if causal or kv_valid is not None:
        jax.lax.fori_loop(first, whole, functools.partial(
            body, masked=True), 0)
    jax.lax.fori_loop(whole, nq, functools.partial(body, masked=False), 0)

    @pl.when(g == group - 1)
    def _():
        dk_ref[0] = (dk_acc[...] * sm_scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_names(scope):
    """The backward kernels' ``pl.pallas_call(name=...)``: a trace files
    a kernel's events under its name, so a caller's ``scope`` (a block's
    name) puts them under that block's readers."""
    prefix = f"{scope}_" if scope else ""
    return (f"{prefix}flash_attention_bwd_dq",
            f"{prefix}flash_attention_bwd_dkdv")


def _fit(n, most):
    """The largest of ``most``, ``most / 2``, ... 128 that divides
    ``n`` (a multiple of 128)."""
    while n % most:
        most //= 2
    return most


def _flash_backward_pallas(q, k, v, out, lse, g, causal, sm_scale,
                           kv_valid=None, q_valid=None, scope=None,
                           interpret=False):
    """``(dq, dk, dv)`` by two kernels (FA-2): ``dq`` a query block at a
    time over the key blocks, ``dk`` and ``dv`` a key block at a time
    over the group's query heads, each key/value head read once for
    them and its gradient written once."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, sq, d = q.shape
    kvh, sk = k.shape[1], k.shape[2]
    group = h // kvh
    block_q, block_k = _fit(sq, _BWD_MOST), _fit(sk, _BWD_MOST)
    delta = jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32),
                    axis=-1).reshape(b * h, 1, sq)
    fixed = dict(causal=causal, sm_scale=sm_scale,
                 shift=_shift(sq, sk, kv_valid, q_valid),
                 kv_valid=kv_valid if kv_valid is not None and kv_valid < sk
                 else None)
    q3, g3 = q.reshape(b * h, sq, d), g.reshape(b * h, sq, d)
    k3, v3 = k.reshape(b * kvh, sk, d), v.reshape(b * kvh, sk, d)
    dq_name, dkdv_name = _bwd_names(scope)

    rows = pl.BlockSpec((1, block_q, d), lambda r, i: (r, i, 0))
    stats = pl.BlockSpec((1, 1, block_q), lambda r, i: (r, 0, i))
    whole_kv = pl.BlockSpec((1, sk, d), _kv_row(group))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, block_k=block_k, seq_k=sk, **fixed),
        grid=(b * h, sq // block_q),
        in_specs=[rows, whole_kv, whole_kv, rows, stats, stats],
        out_specs=rows,
        out_shape=jax.ShapeDtypeStruct(q3.shape, q.dtype),
        interpret=interpret,
        name=dq_name,
    )(q3, k3, v3, g3, lse, delta)

    def head(r, j, i):
        return (r * group + i, 0, 0)

    whole_q = pl.BlockSpec((1, sq, d), head)
    whole_stats = pl.BlockSpec((1, 1, sq), head)
    keys = pl.BlockSpec((1, block_k, d), lambda r, j, i: (r, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkdv_kernel, block_q=block_q, **fixed),
        grid=(b * kvh, sk // block_k, group),
        in_specs=[whole_q, keys, keys, whole_q, whole_stats, whole_stats],
        out_specs=[keys, keys],
        out_shape=[jax.ShapeDtypeStruct(k3.shape, k.dtype),
                   jax.ShapeDtypeStruct(v3.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=dkdv_name,
    )(q3, k3, v3, g3, lse, delta)
    return (dq.reshape(q.shape), dk.reshape(k.shape),
            dv.reshape(v.shape))


#: VMEM the kernels may plan for the operands they hold whole.  The
#: forward and ``dq`` hold K and V as one block of the whole key
#: sequence, ``dkdv`` holds q and dO of one query head whole; Pallas
#: double-buffers each, so the plan is 4 * seq * head_dim (padded to the
#: 128 lanes) * itemsize; the rest of the v5e compiler's 16 MiB scoped
#: limit is left to the other blocks and the f32 score and accumulator
#: tiles.  Read off the chip's compiler (tests/test_tpu_compile.py): at
#: d=128 bf16 holds seq_k 14336 and is refused at 16384 (16.12M of
#: 16.00M), f32 holds 6144 and is refused at 8192.
_KV_VMEM_BUDGET = 12 * 1024 * 1024


def max_seq_k(head_dim, dtype):
    """Longest key sequence the kernel holds for this head size and
    dtype (a multiple of the 128 block), from the VMEM plan above."""
    lanes = -(-int(head_dim) // 128) * 128
    per_key = 4 * lanes * jnp.dtype(dtype).itemsize
    return _KV_VMEM_BUDGET // per_key // _BLOCK_K * _BLOCK_K


def _fallback_event(reason, q, k, block_q, block_k, ran="naive"):
    """A shape that wanted the kernel but runs the fused jnp math:
    counted, and named in the run log (ops/kernel_target.declined)."""
    kernel_target.declined(
        "flash_attention", reason, ran,
        shape=(tuple(q.shape), tuple(k.shape)),
        blocks=f"{block_q}x{block_k}")


def _kernel_holds(q, k, block_q, block_k):
    """Can the kernel hold this shape at these blocks?  Decided here,
    before lowering: a miss is a counted event naming the reason, not
    a compiler error (and the ``pallas_pad`` variant exists so that
    unaligned shapes can still race padded)."""
    sq, sk = q.shape[2], k.shape[2]
    if sq % block_q or sk % block_k:
        _fallback_event(
            f"seq not tile-aligned (seq_q {sq} % {block_q} = "
            f"{sq % block_q}, seq_k {sk} % {block_k} = {sk % block_k});"
            " the pallas_pad variant can race this shape padded",
            q, k, block_q, block_k)
        return False
    limit = max_seq_k(k.shape[3], k.dtype)
    if sk > limit:
        _fallback_event(
            f"seq_k {sk} exceeds the kernel's VMEM plan (K and V enter "
            f"as whole-sequence blocks; {limit} keys fit at head_dim "
            f"{k.shape[3]} {jnp.dtype(k.dtype).name})",
            q, k, block_q, block_k)
        return False
    return True


def _backward_holds(q, k):
    """Can ``dkdv`` hold one query head's q and dO whole (the forward
    and ``dq`` hold what the forward's check allowed)?  A miss is
    counted and the chunked jnp backward runs."""
    limit = max_seq_k(q.shape[3], q.dtype)
    if q.shape[2] <= limit:
        return True
    _fallback_event(
        f"seq_q {q.shape[2]} exceeds the backward's VMEM plan (q and dO "
        f"enter dkdv as whole-sequence blocks; {limit} rows fit at "
        f"head_dim {q.shape[3]} {jnp.dtype(q.dtype).name})",
        q, k, _BWD_MOST, _BWD_MOST, ran="jnp backward")
    return False


def _blocks(q, k, interpret, variant):
    """``(block_q, block_k, interpret)`` where this call runs the
    kernel, None where it runs the fused jnp math."""
    if variant == "naive":
        return None
    if variant in _VARIANT_BLOCKS:
        bq, bk = _VARIANT_BLOCKS[variant]
        # an explicitly chosen kernel variant runs the kernel even
        # off-TPU (interpret mode): the race stays honest on any host
        if _kernel_holds(q, k, bq, bk):
            return bq, bk, interpret or not kernel_target.on_tpu()
        return None
    # default heuristic (no variant decision): the kernel on a TPU (or
    # where a test asked for interpret mode) when it holds the shape,
    # the fused jnp math otherwise
    if _kernel_holds(q, k, _BLOCK_Q, _BLOCK_K) and \
            (interpret or kernel_target.on_tpu()):
        return (_fit(q.shape[2], _FWD_MOST[0]),
                _fit(k.shape[2], _FWD_MOST[1]), interpret)
    return None


def _attend(q, k, v, causal, sm_scale, interpret, variant, kv_valid,
            q_valid):
    """``(out, lse)``; ``lse`` None where the fused jnp math ran."""
    chosen = _blocks(q, k, interpret, variant)
    if chosen is None:
        return _naive_attention(q, k, v, causal, sm_scale,
                                kv_valid=kv_valid, q_valid=q_valid), None
    bq, bk, interpret = chosen
    return _flash_forward_pallas(
        q, k, v, causal, sm_scale, block_q=bq, block_k=bk,
        kv_valid=kv_valid, q_valid=q_valid, interpret=interpret)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash(q, k, v, causal, sm_scale, interpret, variant, kv_valid,
           q_valid, scope=None):
    return _attend(q, k, v, causal, sm_scale, interpret, variant,
                   kv_valid, q_valid)[0]


def _flash_fwd(q, k, v, causal, sm_scale, interpret, variant, kv_valid,
               q_valid, scope=None):
    out, lse = _attend(q, k, v, causal, sm_scale, interpret, variant,
                       kv_valid, q_valid)
    return out, (q, k, v, None if lse is None else out, lse)


_BWD_CHUNK = 512


def _flash_bwd(causal, sm_scale, interpret, variant, kv_valid, q_valid,
               scope, res, g):
    q, k, v, out, lse = res
    if lse is not None and _backward_holds(q, k):
        # the kernels ran forward: on a TPU, or interpreted where asked
        # for or where an explicit variant ran them off-TPU
        return _flash_backward_pallas(
            q, k, v, out, lse, g, causal, sm_scale, kv_valid=kv_valid,
            q_valid=q_valid, scope=scope,
            interpret=interpret or not kernel_target.on_tpu())
    return _chunked_bwd(causal, sm_scale, kv_valid, q_valid, q, k, v, g)


def _chunked_bwd(causal, sm_scale, kv_valid, q_valid, q, k, v, g):
    # recompute in query chunks: O(chunk * seq_k) live attention rows
    # instead of the full O(seq^2) matrix
    sq = q.shape[2]
    chunk = min(_BWD_CHUNK, sq)
    if sq % chunk:
        chunk = sq  # ragged: single chunk (still correct)
    nchunks = sq // chunk
    sk = k.shape[2]

    def chunk_attn(q_c, k_, v_, off):
        k_, v_ = _expand_kv(k_, q_c), _expand_kv(v_, q_c)
        s = jnp.einsum("bhqd,bhkd->bhqk", q_c.astype(jnp.float32),
                       k_.astype(jnp.float32)) * sm_scale
        kpos = jnp.arange(sk)
        if causal:
            qpos = off + jnp.arange(chunk) + _shift(sq, sk, kv_valid,
                                                    q_valid)
            s = jnp.where((qpos[:, None] >= kpos[None, :])[None, None],
                          s, -jnp.inf)
        if kv_valid is not None and kv_valid < sk:
            s = jnp.where((kpos < kv_valid)[None, None, None], s,
                          -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if kv_valid is not None and kv_valid < sk:
            p = jnp.where(jnp.isfinite(s).any(-1, keepdims=True), p,
                          0.0)
        return jnp.einsum("bhqk,bhkd->bhqd", p,
                          v_.astype(jnp.float32)).astype(q_c.dtype)

    dq = jnp.zeros_like(q)
    dk = jnp.zeros(k.shape, jnp.float32)
    dv = jnp.zeros(v.shape, jnp.float32)
    for ci in range(nchunks):
        off = ci * chunk
        q_c = jax.lax.dynamic_slice_in_dim(q, off, chunk, axis=2)
        g_c = jax.lax.dynamic_slice_in_dim(g, off, chunk, axis=2)
        _, vjp = jax.vjp(
            lambda q_, k_, v_, off=off: chunk_attn(q_, k_, v_, off),
            q_c, k, v)
        dq_c, dk_c, dv_c = vjp(g_c)
        dq = jax.lax.dynamic_update_slice_in_dim(dq, dq_c, off, axis=2)
        dk = dk + dk_c.astype(jnp.float32)
        dv = dv + dv_c.astype(jnp.float32)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _pad_to(x, axis, mult):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _resolve_variant(variant):
    """The trace-time variant decision: explicit arg > the autotune
    registry's ``flash_attention`` choice (force > env > cached
    winner) > None (the platform heuristic)."""
    if variant is not None:
        return variant
    from ..autotune import variant_choice

    return variant_choice("flash_attention")


def flash_attention(q, k, v, causal=False, sm_scale=None,
                    interpret=False, variant=None, scope=None):
    """Fused attention over (batch, heads, seq, head_dim) operands.
    ``k`` and ``v`` may hold fewer heads than ``q``: each key/value head
    serves ``heads / kv_heads`` query heads (grouped-query attention).

    ``variant`` picks the lowering explicitly (``naive`` / ``pallas``
    / ``pallas_b256`` / ``pallas_pad``); None consults the autotune
    registry (``VARIANT_OPS['flash_attention']``) and falls back to
    the platform heuristic.  ``scope`` (a block's name) begins the
    backward kernels' names, so that a trace files them under it."""
    if q.shape[1] % k.shape[1] or k.shape != v.shape:
        raise ValueError(f"{q.shape[1]} query heads cannot be served by "
                         f"keys {k.shape} and values {v.shape}")
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    variant = _resolve_variant(variant)
    if variant == "pallas_pad":
        bq, bk = _VARIANT_BLOCKS["pallas_pad"]
        sq, sk = q.shape[2], k.shape[2]
        if sq % bq == 0 and sk % bk == 0:
            variant = "pallas"  # already aligned: no shim needed
        else:
            # pad q AND k/v up to the blocks; the kernel computes the
            # causal alignment against the VALID lengths (q_valid/
            # kv_valid), padded keys are masked out of the softmax,
            # and padded query rows are sliced off below
            qp = _pad_to(q, 2, bq)
            kp = _pad_to(k, 2, bk)
            vp = _pad_to(v, 2, bk)
            out = _flash(qp, kp, vp, causal, float(sm_scale),
                         interpret, "pallas",
                         sk if kp.shape[2] != sk else None,
                         sq if qp.shape[2] != sq else None, scope)
            return out[:, :, :sq, :]
    return _flash(q, k, v, causal, float(sm_scale), interpret, variant,
                  None, None, scope)


def _resolve_paged_variant(variant):
    """Trace-time decision for the decode-cache attention: explicit
    arg > the autotune registry's ``paged_decode_attention`` choice
    (force > MXNET_PAGED_ATTENTION > cached winner) > gather."""
    if variant is not None:
        return variant
    from ..autotune import variant_choice

    return variant_choice("paged_decode_attention", default="gather")


def _dequant_block(blk, scale):
    """fp32 view of a gathered KV block; ``scale`` is the int8 cache's
    per-(token, head) factor (quantization.kv contract), None = the
    block is already a float dtype."""
    if scale is None:
        return blk.astype(jnp.float32)
    return blk.astype(jnp.float32) * scale.astype(jnp.float32)[..., None]


def paged_decode_attention(q, k_pages, v_pages, page_table, seq_lens,
                           sm_scale=None, k_scale=None, v_scale=None,
                           variant=None):
    """Single-token decode attention over a PAGED KV cache (round 17).

    The generative server's decode step calls this once per layer per
    token: each decode slot's query attends to the keys its page-table
    row maps into the physical page pool — never to another sequence's
    pages, never to unwritten tail positions.

    Operands::

      q          (slots, heads, head_dim)   one query token per slot
      k_pages    (pages, page_tokens, heads, head_dim)  physical pool
      v_pages    (pages, page_tokens, heads, head_dim)
      page_table (slots, max_pages) int32   logical -> physical pages
      seq_lens   (slots,) int32             valid tokens per slot

    ``k_scale``/``v_scale`` (pages, page_tokens, heads) mark an int8
    pool: blocks dequantize AFTER the gather (per block in the paged
    walk), so HBM holds int8 + scales only.  A slot with seq_len 0 is
    inactive: every key masks out and the output row is exactly zero —
    the same fully-masked-row guard as the flash kernel's l=0 path.

    Variants (autotune op ``paged_decode_attention``): ``gather``
    materializes the slot's K/V with one fancy-index gather then runs
    a dense masked softmax; ``paged`` walks the page list with an
    online-softmax accumulator (m/l/acc carry, one page live at a
    time) — flash-attention's schedule transposed onto the page table.
    Both are exact (no approximation), so the race is purely a speed
    decision.
    """
    slots, heads, head_dim = q.shape
    page_tokens = k_pages.shape[1]
    max_pages = page_table.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / (head_dim ** 0.5)
    variant = _resolve_paged_variant(variant)
    qf = q.astype(jnp.float32)

    if variant == "paged":
        def body(i, carry):
            m, l, acc = carry
            phys = page_table[:, i]  # (slots,)
            k_blk = _dequant_block(
                k_pages[phys],
                None if k_scale is None else k_scale[phys])
            v_blk = _dequant_block(
                v_pages[phys],
                None if v_scale is None else v_scale[phys])
            s = jnp.einsum("shd,sthd->sht", qf, k_blk) * sm_scale
            pos = i * page_tokens + jnp.arange(page_tokens)
            s = jnp.where(pos[None, None, :] < seq_lens[:, None, None],
                          s, -jnp.inf)
            m_new = jnp.maximum(m, s.max(axis=-1))
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - m_safe[..., None])
            p = jnp.where(jnp.isfinite(s), p, 0.0)
            alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
            l = l * alpha + p.sum(axis=-1)
            acc = acc * alpha[..., None] + \
                jnp.einsum("sht,sthd->shd", p, v_blk)
            return m_new, l, acc

        m0 = jnp.full((slots, heads), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((slots, heads), jnp.float32)
        acc0 = jnp.zeros((slots, heads, head_dim), jnp.float32)
        m, l, acc = jax.lax.fori_loop(0, max_pages, body, (m0, l0, acc0))
        return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)

    # gather: one fancy-index gather materializes (slots, total, H, D)
    k = _dequant_block(
        k_pages[page_table],
        None if k_scale is None else k_scale[page_table])
    v = _dequant_block(
        v_pages[page_table],
        None if v_scale is None else v_scale[page_table])
    total = max_pages * page_tokens
    k = k.reshape(slots, total, heads, head_dim)
    v = v.reshape(slots, total, heads, head_dim)
    s = jnp.einsum("shd,sthd->sht", qf, k) * sm_scale
    pos = jnp.arange(total)
    s = jnp.where(pos[None, None, :] < seq_lens[:, None, None], s,
                  -jnp.inf)
    m = s.max(axis=-1, keepdims=True)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(s - m_safe)
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    l = p.sum(axis=-1, keepdims=True)
    out = jnp.einsum("sht,sthd->shd", p, v) / jnp.maximum(l[..., 0],
                                                          1e-30)[..., None]
    return out.astype(q.dtype)


@register_op("_contrib_dot_product_attention",
             aliases=("dot_product_attention",))
def dot_product_attention(q, k, v, *, num_heads=1, causal=False,
                          sm_scale=None, interpret=False, variant=None):
    """Multi-head attention over (batch, seq, num_heads*head_dim)
    inputs, flash-backed (the modern replacement for the reference's
    contrib attention helpers)."""
    b, sq, hd = q.shape
    sk = k.shape[1]
    d = hd // num_heads

    def split(x, s):
        return x.reshape(b, s, num_heads, d).transpose(0, 2, 1, 3)

    out = flash_attention(split(q, sq), split(k, sk), split(v, sk),
                          causal=causal, sm_scale=sm_scale,
                          interpret=interpret, variant=variant)
    return out.transpose(0, 2, 1, 3).reshape(b, sq, hd)


@register_op("_contrib_div_sqrt_dim")
def div_sqrt_dim(data):
    """Reference: src/operator/contrib/transformer.cc:33-40."""
    return data / (data.shape[-1] ** 0.5)
