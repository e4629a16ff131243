"""Flash attention — Pallas TPU kernel with online softmax.

SURVEY.md §5.7 mandate: the reference has no fused attention (only
bucketing + contrib div_sqrt_dim, src/operator/contrib/transformer.cc);
long-context on TPU requires an O(seq) -memory attention kernel.  This
is the single-chip building block; ring context parallelism composes it
across chips (mxnet_tpu.parallel.ring).

Design (standard flash-attention-2 schedule on the MXU):
  grid = (batch*heads, q_blocks); the kernel walks k/v blocks in VMEM,
  keeping the running max m, normalizer l and accumulator acc in f32
  scratch; one rescale per block keeps everything numerically exact.
Backward: recomputation in query chunks — each chunk re-derives its
attention rows (O(chunk * seq) live memory, not O(seq^2)) and
contributes dq directly while dk/dv accumulate across chunks.
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

Falls back to the fused jnp implementation off-TPU or for shapes that
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

#: forced-value -> (block_q, block_k) for the kernel sub-variants
_VARIANT_BLOCKS = {
    "pallas": (_BLOCK_Q, _BLOCK_K),
    "pallas_b256": (256, 256),
    "pallas_pad": (_BLOCK_Q, _BLOCK_K),
}


def _naive_attention(q, k, v, causal, sm_scale, kv_valid=None,
                     q_valid=None):
    """Reference math in fp32: softmax(q k^T * scale [+ mask]) v.
    ``kv_valid``/``q_valid`` are the padding-shim contract: keys at
    positions >= kv_valid are masked out, and the causal alignment is
    computed against the VALID lengths so padding never shifts which
    real keys a real query sees."""
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


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, causal, sm_scale,
                  block_k, seq_k, kv_valid, q_valid):
    from jax.experimental import pallas as pl

    q = q_ref[0].astype(jnp.float32)  # (block_q, d)
    block_q = q.shape[0]
    qi = pl.program_id(1)
    seq_q = pl.num_programs(1) * block_q
    # bottom-right causal alignment: shift query positions by sk - sq
    # computed against the VALID lengths when the padding shim
    # appended masked keys / sliced-off queries
    eff_k = seq_k if kv_valid is None else kv_valid
    eff_q = seq_q if q_valid is None else q_valid
    q_off = qi * block_q + (eff_k - eff_q)

    m = jnp.full((block_q,), -jnp.inf, jnp.float32)
    l = jnp.zeros((block_q,), jnp.float32)
    acc = jnp.zeros((block_q, q.shape[1]), jnp.float32)

    num_kb = seq_k // block_k

    def body(kb, carry):
        m, l, acc = carry
        k_blk = k_ref[0, pl.dslice(kb * block_k, block_k),
                      :].astype(jnp.float32)
        v_blk = v_ref[0, pl.dslice(kb * block_k, block_k),
                      :].astype(jnp.float32)
        s = q @ k_blk.T * sm_scale  # (block_q, block_k)
        kpos = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        if causal:
            qpos = q_off + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            s = jnp.where(qpos >= kpos, s, -jnp.inf)
        if kv_valid is not None and kv_valid < seq_k:
            # padding shim: keys past the true length never score
            s = jnp.where(kpos < kv_valid, s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(axis=-1))
        # guard fully-masked rows: exp(-inf - -inf) -> use safe max
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[:, None])
        p = jnp.where(jnp.isfinite(s), p, 0.0)
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l = l * alpha + p.sum(axis=-1)
        acc = acc * alpha[:, None] + p @ v_blk
        return m_new, l, acc

    last_kb = num_kb
    if kv_valid is not None and kv_valid < seq_k:
        # the tail blocks past the true key length are fully masked
        last_kb = (kv_valid + block_k - 1) // block_k
    if causal:
        # skip key blocks entirely above the diagonal
        last_kb = jnp.minimum((q_off + block_q + block_k - 1) // block_k,
                              last_kb)
    m, l, acc = jax.lax.fori_loop(0, last_kb, body, (m, l, acc))
    out = acc / jnp.maximum(l, 1e-30)[:, None]
    o_ref[0] = out.astype(o_ref.dtype)


def _flash_forward_pallas(q, k, v, causal, sm_scale, block_q=_BLOCK_Q,
                          block_k=_BLOCK_K, kv_valid=None,
                          q_valid=None, interpret=False):
    from jax.experimental import pallas as pl

    b, h, sq, d = q.shape
    sk = k.shape[2]
    bh = b * h
    q3 = q.reshape(bh, sq, d)
    k3 = k.reshape(bh, sk, d)
    v3 = v.reshape(bh, sk, d)
    grid = (bh, sq // block_q)
    kernel = functools.partial(_flash_kernel, causal=causal,
                               sm_scale=sm_scale, block_k=block_k,
                               seq_k=sk, kv_valid=kv_valid,
                               q_valid=q_valid)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b_, i: (b_, i, 0)),
            pl.BlockSpec((1, sk, d), lambda b_, i: (b_, 0, 0)),
            pl.BlockSpec((1, sk, d), lambda b_, i: (b_, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b_, i: (b_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        interpret=interpret,
        name="flash_attention_fwd",
    )(q3, k3, v3)
    return out.reshape(b, h, sq, d)


#: VMEM the kernel may plan for its K and V blocks.  Both enter as one
#: block of the whole key sequence and Pallas double-buffers each, so
#: the plan is 4 * seq_k * head_dim (padded to the 128 lanes) *
#: itemsize; the rest of the v5e compiler's 16 MiB scoped limit is left
#: to the q/o blocks and the f32 score and accumulator tiles.  Read off
#: the chip's compiler (tests/test_tpu_compile.py): at d=128 bf16 holds
#: seq_k 14336 and is refused at 16384 (16.12M of 16.00M), f32 holds
#: 6144 and is refused at 8192.
_KV_VMEM_BUDGET = 12 * 1024 * 1024


def max_seq_k(head_dim, dtype):
    """Longest key sequence the kernel holds for this head size and
    dtype (a multiple of the 128 block), from the VMEM plan above."""
    lanes = -(-int(head_dim) // 128) * 128
    per_key = 4 * lanes * jnp.dtype(dtype).itemsize
    return _KV_VMEM_BUDGET // per_key // _BLOCK_K * _BLOCK_K


def _fallback_event(reason, q, k, block_q, block_k):
    """A shape that wanted the kernel but runs the fused jnp math:
    counted, and named in the run log (ops/kernel_target.declined)."""
    kernel_target.declined(
        "flash_attention", reason, "naive",
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


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, sm_scale, interpret, variant, kv_valid,
           q_valid):
    if variant == "naive":
        return _naive_attention(q, k, v, causal, sm_scale,
                                kv_valid=kv_valid, q_valid=q_valid)
    if variant in _VARIANT_BLOCKS:
        bq, bk = _VARIANT_BLOCKS[variant]
        if not _kernel_holds(q, k, bq, bk):
            return _naive_attention(q, k, v, causal, sm_scale,
                                    kv_valid=kv_valid, q_valid=q_valid)
        # an explicitly chosen kernel variant runs the kernel even
        # off-TPU (interpret mode): the race stays honest on any host
        return _flash_forward_pallas(
            q, k, v, causal, sm_scale, block_q=bq, block_k=bk,
            kv_valid=kv_valid, q_valid=q_valid,
            interpret=interpret or not kernel_target.on_tpu())
    # default heuristic (no variant decision): the kernel on a TPU (or
    # where a test asked for interpret mode) when it holds the shape,
    # the fused jnp math otherwise
    if _kernel_holds(q, k, _BLOCK_Q, _BLOCK_K) and \
            (interpret or kernel_target.on_tpu()):
        return _flash_forward_pallas(q, k, v, causal, sm_scale,
                                     kv_valid=kv_valid,
                                     q_valid=q_valid,
                                     interpret=interpret)
    return _naive_attention(q, k, v, causal, sm_scale,
                            kv_valid=kv_valid, q_valid=q_valid)


def _flash_fwd(q, k, v, causal, sm_scale, interpret, variant, kv_valid,
               q_valid):
    return (_flash(q, k, v, causal, sm_scale, interpret, variant,
                   kv_valid, q_valid), (q, k, v))


_BWD_CHUNK = 512


def _flash_bwd(causal, sm_scale, interpret, variant, kv_valid, q_valid,
               res, g):
    # recompute in query chunks: O(chunk * seq_k) live attention rows
    # instead of the full O(seq^2) matrix
    q, k, v = res
    sq = q.shape[2]
    chunk = min(_BWD_CHUNK, sq)
    if sq % chunk:
        chunk = sq  # ragged: single chunk (still correct)
    nchunks = sq // chunk
    sk = k.shape[2]

    def chunk_attn(q_c, k_, v_, off):
        s = jnp.einsum("bhqd,bhkd->bhqk", q_c.astype(jnp.float32),
                       k_.astype(jnp.float32)) * sm_scale
        kpos = jnp.arange(sk)
        if causal:
            eff_k = sk if kv_valid is None else kv_valid
            eff_q = sq if q_valid is None else q_valid
            qpos = off + jnp.arange(chunk) + (eff_k - eff_q)
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
    dk = jnp.zeros_like(k, shape=k.shape).astype(jnp.float32)
    dv = jnp.zeros_like(v, shape=v.shape).astype(jnp.float32)
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
                    interpret=False, variant=None):
    """Fused attention over (batch, heads, seq, head_dim) operands.

    ``variant`` picks the lowering explicitly (``naive`` / ``pallas``
    / ``pallas_b256`` / ``pallas_pad``); None consults the autotune
    registry (``VARIANT_OPS['flash_attention']``) and falls back to
    the platform heuristic."""
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
                         sq if qp.shape[2] != sq else None)
            return out[:, :, :sq, :]
    return _flash(q, k, v, causal, float(sm_scale), interpret, variant,
                  None, None)


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
