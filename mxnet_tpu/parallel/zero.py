"""ZeRO-1 sharded-server gradient exchange (the TPU-native parameter
server).

Reference parity: ps-lite slices every big array across servers
(``MXNET_KVSTORE_BIGARRAY_BOUND``, kvstore_dist.h EncodeDefaultKey),
each server owns a key shard and runs the SERVER-SIDE optimizer on it
(kvstore_dist_server.h:346), and workers pull back only the updated
slices — the partitioning Rajbhandari et al. rediscovered as ZeRO-1
(SC'20) with the bucketed-collective overlap of PyTorch DDP (Li et
al., VLDB'20; both in PAPERS.md).

TPU-native redesign: instead of one XLA all-reduce per parameter
tensor (54 launches for the r05 dp(16) ResNet-18 dryrun — pure launch
overhead on small tensors) the gradients go into a few
dtype-homogeneous BUCKETS, each bucket is summed and scattered over
the data axis (``psum_scatter``), the registry optimizer's fused rule
runs ONLY on the locally-owned shard (optimizer state lives sharded —
memory and FLOPs scale with params/N), and the updated shards
``all_gather`` back.  A bucket has one of two layouts, which the plan
picks from shapes alone (:func:`_leaf_shaped`):

* **flat** — several small leaves (and every 1-D one) packed into one
  padded 1-D array: one launch for many tensors.  What the chip runs
  for it (v5e, jax 0.9): the compiler does NOT keep a
  ``reduce-scatter`` of a 1-D operand; it all-reduces the bucket whole
  (neighbouring buckets combined into one launch) and the update's
  fusion slices the owned part, so a flat bucket moves its bytes twice
  on the gradients' way.  That is the price of packing and is paid
  only where packing buys launches (VGG-16: 4.2% of the elements).
* **leaf** — ONE leaf over the bound (or left alone by its
  neighbours) whose rows divide over the shards: packing it would pack
  nothing, so it is never reshaped.  The gradient goes into
  scatter as the backward pass left it, the owned shard of the weights
  is a ``dynamic_slice`` of rows that fuses into the update, the
  optimizer's state is kept as those rows, and the gather returns the
  leaf as the forward pass reads it; no 1-D form of the leaf anywhere.
  Who scatters and gathers it (:func:`rides_ring`):

  - the train step at stages 1 and 2 sends it **round the ring**
    (:func:`ring_reduce_scatter`, :func:`ring_gather`): n-1 hops of
    ``ppermute`` each, in halves both ways round, over physical
    neighbours (:func:`ring_order`).  On the chip every hop is an
    asynchronous ``collective-permute`` that depends on its leaf's
    gradient alone, so the scheduler runs the backward pass of the
    layers below under it; the gathered rows are written into the
    step's own (donated) weights, so no whole-leaf copy is left.
  - stage 3 (its gather is the forward pass's, its scatter that
    gather's transpose) and the Module-side updater (whose program has
    no backward pass to hide a hop under) keep the native collectives:
    ``psum_scatter(scatter_dimension=0, tiled=True)`` and
    ``all_gather(tiled=True)``.  What the chip runs for those: a native
    ``reduce-scatter``, synchronous, for the largest leaves and for
    convolution kernels, the compiler's fused ``all-reduce-scatter``
    for mid-sized 2-D leaves, and two whole-leaf copies (a donated
    leaf at the program's start, a gathered result into the output).

This module owns the pieces shared by ``make_train_step``'s
``optimizer_sharding="ps"`` path and the Module-side
:class:`ShardedBucketUpdater` (the ``kvstore='dist_sync'`` mapping):

* :func:`plan_buckets` — greedy dtype-homogeneous packing honoring the
  authentic ``MXNET_KVSTORE_BIGARRAY_BOUND`` split threshold, padded
  so every bucket divides the shard count, each bucket marked with its
  layout; :func:`bucket_layout` / :func:`leaf_share` report it;
* :func:`flatten_bucket` / :func:`unflatten_bucket` /
  :func:`shard_slice` / :func:`gather_bucket` — the ONE copy of either
  layout, which every stage and both callers go through;
* :func:`ring_order` / :func:`ring_reduce_scatter` / :func:`ring_gather`
  — a leaf-shaped bucket's exchange in the train step, hop by hop;
  :func:`ring_share` reports how much of a plan takes it;
* :func:`adopt_layout` — a by-bucket tree saved flat, taken into the
  plan's layout (a reshape) or refused;
* :func:`collective_bytes` — the HLO collective counter (moved here
  from ``__graft_entry__`` so bench.py and tests share it);
* :class:`ShardedBucketUpdater` — Module's drop-in Updater with
  bucket-sharded optimizer state (gathers to the LEGACY per-param
  ``.states`` layout on save, re-shards on load, so checkpoint files
  stay interchangeable with replicated runs and between layouts).
"""
from __future__ import annotations

import dataclasses
import re

import jax
import numpy as onp

from ..base import MXNetError

__all__ = ["Bucket", "plan_buckets", "flatten_bucket", "unflatten_bucket",
           "bucket_segments", "shard_slice", "collective_bytes",
           "resolve_stage",
           "plan_fingerprint", "flat_variant_key",
           "resolve_bucket_variant", "analytic_exchange_bytes",
           "bucket_layout", "leaf_share", "ring_share", "adopt_layout",
           "ring_order", "ring_reduce_scatter", "ring_gather",
           "stage3_param_keys", "shard_stage3_params",
           "gather_stage3_params", "overlap_report",
           "ShardedBucketUpdater"]


# ------------------------------------------------------------ bucket plan
@dataclasses.dataclass(frozen=True)
class Bucket:
    """One dtype-homogeneous bucket of whole parameters: a flat pack of
    several leaves, or ONE leaf kept in its own shape (``leaf``)."""

    dtype: str
    names: tuple          # parameter names, in packing order
    shapes: tuple         # per-name shapes
    offsets: tuple        # per-name start offset in the flat layout
    size: int             # total elements (unpadded)
    padded: int           # size rounded up to a multiple of n_shards
    #: opaque partition key (e.g. effective (lr, wd) of the bucket's
    #: params); params with different groups never share a bucket
    group: object = None
    #: the bucket is one leaf whose rows divide over the shards: it is
    #: scattered, updated, kept and gathered as rows of the leaf's own
    #: shape, never repacked 1-D (:func:`_leaf_shaped` is the rule)
    leaf: bool = False

    @property
    def pad(self):
        return self.padded - self.size

    @property
    def shape(self):
        """Shape of the bucket's array (and of its optimizer state):
        the leaf's own, or ``(padded,)``; sharded on dimension 0."""
        return self.shapes[0] if self.leaf else (self.padded,)

    @property
    def layout(self):
        return "leaf" if self.leaf else "flat"


def _capacity(capacity=None):
    if capacity is not None:
        return max(1, int(capacity))
    from ..config import get_env

    return max(1, int(get_env("MXNET_KVSTORE_BIGARRAY_BOUND")))


#: rows of one float32 tile on the chip (``T(8,128)``; narrower dtypes
#: pack 16 or 32 rows a tile)
_SUBLANES = 8


def _leaf_shaped(shapes, dtype, n_shards):
    """The layout rule, from what the plan can observe: a bucket that
    holds ONE leaf of two or more dimensions whose leading dimension
    divides by the shard count into shards of whole tile rows keeps the
    leaf's shape.  Flattening such a bucket packs nothing: it costs a
    relayout of the leaf each way (a 2-D array tiled ``T(8,128)``
    rewritten 1-D tiled ``T(1024)`` and back), and the chip's compiler
    keeps a ``reduce-scatter`` only for the leaf-shaped operand (it
    all-reduces the flat one whole and slices).  A shard that is not
    whole tile rows (``[1000, 4096]`` over 4: 250 rows) would be cut
    across a tile, so such a leaf stays flat, as does every 1-D leaf
    and every bucket that really packs."""
    if len(shapes) != 1 or len(shapes[0]) < 2:
        return False
    rows = int(shapes[0][0])
    tile = _SUBLANES * max(1, 4 // onp.dtype(dtype).itemsize)
    return rows % (int(n_shards) * tile) == 0


def rides_ring(bucket, stage):
    """Whether the train step exchanges this bucket hop by hop round
    the ring (:func:`ring_reduce_scatter`, :func:`ring_gather`): a
    leaf-shaped bucket at stages 1 and 2.  A flat bucket keeps the
    native collectives (it packs small leaves: one launch for many),
    and stage 3 gathers in the forward pass and scatters by that
    gather's transpose."""
    return bucket.leaf and stage in (1, 2)


def bucket_layout(plan, stage=None):
    """``[(bucket key, "leaf" | "flat", elements, "ring" | "native")]``
    of a plan exchanged at ``stage``: what ``step_fn.zero_layout``
    reports."""
    return [(k, b.layout, b.padded,
             "ring" if rides_ring(b, stage) else "native")
            for k, b in zip(stage3_param_keys(plan), plan)]


def _share(plan, which):
    total = sum(b.padded for b in plan)
    picked = [b.padded for b in plan if which(b)]
    return len(picked), len(plan), sum(picked) / total if total else 0.0


def leaf_share(plan):
    """``(leaf-shaped buckets, buckets, share of the elements that are
    exchanged leaf-shaped)`` of a plan."""
    return _share(plan, lambda b: b.leaf)


def ring_share(plan, stage):
    """``(buckets exchanged by the ring, buckets, their share of the
    elements)`` of a plan exchanged at ``stage``."""
    return _share(plan, lambda b: rides_ring(b, stage))


def plan_buckets(params, n_shards, capacity=None, group_key=None):
    """Pack ``{name: array}`` into dtype-homogeneous buckets.

    The split threshold is the authentic reference knob: a bucket is
    closed once adding the next parameter would push it past
    ``MXNET_KVSTORE_BIGARRAY_BOUND`` elements (``capacity`` overrides
    the env) — the ps-lite bound above which arrays are sliced across
    servers.  Whole parameters are never split across buckets; a
    single parameter larger than the bound gets a bucket of its own.
    Each bucket is padded to a multiple of ``n_shards`` so
    reduce-scatter/all-gather tile evenly.  A bucket of one leaf whose
    rows divide over the shards is marked ``leaf`` and keeps the
    leaf's shape (:func:`_leaf_shaped`; its padding is 0).

    ``group_key`` ({name: hashable}, optional) further partitions
    buckets: params with different keys never share one.  The Module
    updater uses it for effective (lr, wd) hyper-parameter groups so
    per-param ``lr_mult``/``wd_mult`` stay exact under sharding.
    """
    cap = _capacity(capacity)
    n_shards = max(1, int(n_shards))
    per_part = {}
    order = []
    for name, v in params.items():
        dt = str(onp.dtype(getattr(v, "dtype", onp.float32)))
        part = (dt, None if group_key is None else group_key.get(name))
        if part not in per_part:
            per_part[part] = []
            order.append(part)
        per_part[part].append((name, tuple(v.shape)))
    buckets = []
    for part in order:
        dt, grp = part
        cur_names, cur_shapes, cur_offsets, cur_size = [], [], [], 0

        def close():
            nonlocal cur_names, cur_shapes, cur_offsets, cur_size
            if not cur_names:
                return
            padded = -(-cur_size // n_shards) * n_shards
            buckets.append(Bucket(dt, tuple(cur_names), tuple(cur_shapes),
                                  tuple(cur_offsets), cur_size, padded,
                                  grp,
                                  _leaf_shaped(cur_shapes, dt, n_shards)))
            cur_names, cur_shapes, cur_offsets, cur_size = [], [], [], 0

        for name, shape in per_part[part]:
            n = 1
            for d in shape:
                n *= int(d)
            if cur_names and cur_size + n > cap:
                close()
            cur_names.append(name)
            cur_shapes.append(shape)
            cur_offsets.append(cur_size)
            cur_size += n
        close()
    return buckets


def flatten_bucket(bucket, tree):
    """The bucket's array (``bucket.shape``) from a ``{name: array}``
    tree: the bucket's parameters concatenated (in plan order) into one
    flat padded array, or the one leaf of a leaf-shaped bucket as it
    is."""
    import jax.numpy as jnp

    if bucket.leaf:
        return tree[bucket.names[0]]
    parts = [jnp.reshape(tree[n], (-1,)) for n in bucket.names]
    if bucket.pad:
        parts.append(jnp.zeros((bucket.pad,), dtype=parts[0].dtype))
    return jnp.concatenate(parts) if len(parts) > 1 else parts[0]


def unflatten_bucket(bucket, flat):
    """Inverse of :func:`flatten_bucket` (padding dropped).  A
    leaf-shaped bucket's row-major content IS the flat layout's (one
    leaf, padding 0), so either form of its array is taken."""
    if bucket.leaf:
        return {bucket.names[0]: flat.reshape(bucket.shapes[0])}
    out = {}
    for name, shape, off in zip(bucket.names, bucket.shapes,
                                bucket.offsets):
        n = 1
        for d in shape:
            n *= int(d)
        out[name] = flat[off:off + n].reshape(shape)
    return out


def bucket_segments(bucket):
    """Static per-element segment ids (param index within the bucket;
    padding gets an inert extra segment) for norm-based rules (LARS)
    that need per-parameter reductions over the flat layout.

    Returns (ids int32 ndarray of ``bucket.shape``, num_segments); a
    leaf-shaped bucket is one segment (a zero-stride view: no memory).
    """
    if bucket.leaf:
        return onp.broadcast_to(onp.int32(0), bucket.shape), 2
    ids = onp.empty((bucket.padded,), onp.int32)
    for i, (shape, off) in enumerate(zip(bucket.shapes, bucket.offsets)):
        n = 1
        for d in shape:
            n *= int(d)
        ids[off:off + n] = i
    ids[bucket.size:] = len(bucket.names)
    return ids, len(bucket.names) + 1


def shard_slice(flat, n_shards, idx):
    """This shard's slice of a bucket's array (inside shard_map:
    ``idx`` is the traced ``lax.axis_index``): a ``n_shards``-th of a
    flat padded bucket, or that share of a leaf-shaped bucket's rows —
    contiguous in the major dimension, so nothing is laid out anew."""
    if flat.ndim > 1:
        rows = flat.shape[0] // n_shards
        return jax.lax.dynamic_slice_in_dim(flat, idx * rows, rows, 0)
    return flat.reshape(n_shards, -1)[idx]


@jax.named_scope("mx_optimizer")
def bucket_shard_update(bucket, opt, params, g_sh, state, t, *, n_shards,
                        idx, axis, seg=None, key=None, pallas=None,
                        want_finite=False, w_sh=None):
    """The per-bucket owned-shard update core, shared by
    :meth:`ShardedBucketUpdater._build` and ``make_train_step``'s ps
    step — ONE copy, so the two arms' seg-id slicing and shard layout
    cannot drift apart (their parity IS the checkpoint-interchange
    contract).  Slices this device's shard of the param bucket (a
    stretch of the flat pack, or rows of a leaf-shaped bucket's leaf)
    and runs the fused rule on it against the already-scattered
    gradient shard ``g_sh`` of the same shape.  Returns
    ``(w_sh, new_w_sh, new_state)`` un-gathered, so the caller can
    finite-gate the update before :func:`gather_bucket`.

    ``pallas``: which lowering runs the update — True for the fused
    Pallas bucket kernels (ops/pallas_opt.py: prep + rule + the
    loss-scale finiteness check in ONE VMEM pass), False for the jnp
    ``fused_bucket_update``, None to consult the ``fused_bucket_opt``
    autotune variant at trace time (force > MXNET_PALLAS_OPT > cached
    per-program winner > jnp).  An infeasible kernel (unsupported
    rule/dtype, or a leaf-shaped shard: the kernels stream 1-D shards)
    declines and keeps the jnp arm — in a race that just means the jnp
    arm wins.

    ``want_finite=True`` returns a 4th element: the loss-scale verdict
    ``isfinite(g_sh).all()`` of the RAW (pre-dtype-cast) gradient —
    fused into the kernel's pass on the pallas arm, or None on the
    jnp arm (the caller keeps its own jnp check, bit-identical to
    today's).

    Traced under the ``mx_optimizer`` scope (the collectives of a
    non-elementwise rule, LARS' norms, under ``mx_exchange`` inside
    it)."""
    import jax.numpy as jnp

    if w_sh is None:
        # stages 1/2: params arrive replicated as the named tree and
        # the owned shard is sliced here; stage 3 already HOLDS the
        # shard (params live sharded by bucket) and passes it in
        # directly via ``w_sh=`` — same update math either way
        w_sh = shard_slice(flatten_bucket(bucket, params), n_shards, idx)
    seg_sh = None
    if seg is not None:
        ids, nseg = seg
        # a leaf-shaped bucket is one segment: nothing to slice
        seg_sh = (jnp.zeros(w_sh.shape, jnp.int32) if bucket.leaf else
                  shard_slice(jnp.asarray(ids), n_shards, idx), nseg)
    use_pallas = pallas
    if use_pallas is None:
        from ..autotune import variant_choice

        use_pallas = bool(variant_choice("fused_bucket_opt"))
    finite = None
    if use_pallas:
        from ..ops import pallas_opt

        res = pallas_opt.bucket_update(
            opt, w_sh, g_sh, state, t, seg=seg_sh, axis_name=axis,
            with_finite=want_finite)
        if res is not None:
            uw, us, finite = res
            if want_finite:
                return w_sh, uw, us, finite
            return w_sh, uw, us
    # the gradient may arrive in a wider dtype than the bucket (the ps
    # step's f32 unscale): cast here so both arms and both callers
    # share one rule (a no-op when dtypes already match)
    gq = g_sh.astype(w_sh.dtype)
    kwargs = {}
    if seg_sh is not None:
        kwargs = dict(seg_ids=seg_sh[0], num_segments=seg_sh[1],
                      axis_name=axis)
    uw, us = opt.fused_bucket_update(w_sh, gq, state, t, key=key,
                                     **kwargs)
    if want_finite:
        return w_sh, uw, us, None
    return w_sh, uw, us


# ------------------------------------------------ the ring over the axis
def ring_order(mesh, axis):
    """The data axis' indices in an order in which each device and the
    next (and the last and the first) are physical neighbours: the way
    round that the hops of :func:`ring_reduce_scatter` and
    :func:`ring_gather` take.  Read from the devices' ``coords``: a
    2x2 of v5e chips listed (0,0) (1,0) (0,1) (1,1) along the axis has
    a diagonal between its second and third, so its ring is 0, 1, 3, 2.
    Devices without coordinates (the CPU's), or among which no such way
    round exists, keep the axis' own order."""
    devs = onp.moveaxis(mesh.devices,
                        mesh.axis_names.index(axis), 0)
    devs = devs.reshape(devs.shape[0], -1)[:, 0]
    n = len(devs)
    coords = [getattr(d, "coords", None) for d in devs]
    if n < 4 or any(c is None for c in coords):
        return tuple(range(n))

    def near(i, j):
        return sum(abs(a - b) for a, b in zip(coords[i], coords[j])) <= 1

    budget = 10000    # a search, not a proof: the axis' order else

    def walk(path, left):
        nonlocal budget
        if not left:
            return path if near(path[-1], path[0]) else None
        for j in sorted(left):
            if budget > 0 and near(path[-1], j):
                budget -= 1
                found = walk(path + [j], left - {j})
                if found:
                    return found
        return None

    return tuple(walk([0], set(range(1, n))) or range(n))


def _ring_ways(ring, rows, dtype):
    """``[(permutation, step, first row, rows)]``: the ways round a
    chunk of ``rows`` rows travels.  Both ways in halves where each
    half is whole tile rows (a link carries both directions at once,
    so a hop takes half the time); one way else."""
    n = len(ring)
    tile = _SUBLANES * max(1, 4 // onp.dtype(dtype).itemsize)

    def perm(step):
        return [(ring[p], ring[(p + step) % n]) for p in range(n)]

    if n > 2 and rows % (2 * tile) == 0:
        return [(perm(1), 1, 0, rows // 2),
                (perm(-1), -1, rows // 2, rows // 2)]
    return [(perm(1), 1, 0, rows)]


def _ring_place(ring, idx):
    """(the ring's order as an array, this device's place in it)."""
    import jax.numpy as jnp

    return (jnp.asarray(ring, jnp.int32),
            jnp.asarray(onp.argsort(ring), jnp.int32)[idx])


@jax.named_scope("mx_exchange")
def ring_reduce_scatter(leaf, axis, ring, idx):
    """This device's rows of the sum of ``leaf`` over the axis (what
    ``psum_scatter(scatter_dimension=0, tiled=True)`` returns), as
    ``len(ring) - 1`` hops round ``ring``: each hop ``ppermute``s the
    running chunk to the next device and adds this device's rows of
    that chunk.  A chunk ends at its owner having passed every other
    device in the ring's order, so the order of addition is fixed by
    the devices' places and a run repeats to the bit (it is not the
    native collective's order: equal to it within float rounding).
    Every hop is an asynchronous ``collective-permute`` that depends on
    this leaf's gradient alone, so the compiler's scheduler runs the
    backward pass of the layers below under it; the native
    ``reduce-scatter`` is synchronous on the chip (v5e, jax 0.9)."""
    import jax.numpy as jnp

    n = len(ring)
    rows = leaf.shape[0] // n
    order, place = _ring_place(ring, idx)
    parts = []
    for perm, step, lo, cnt in _ring_ways(ring, rows, leaf.dtype):
        def mine(k):
            # this device's rows of the chunk owned ``k`` places back
            owner = order[(place - step * k) % n]
            return jax.lax.dynamic_slice_in_dim(
                leaf, owner * rows + lo, cnt, 0)

        acc = mine(1)
        for hop in range(n - 1):
            acc = jax.lax.ppermute(acc, axis, perm) + mine(hop + 2)
        parts.append(acc)
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


@jax.named_scope("mx_exchange")
def ring_gather(into, w_sh, axis, ring, idx):
    """``into`` (an array of the leaf's shape: the weights as the step
    got them) with every device's rows ``w_sh`` written over its own
    (what ``all_gather(tiled=True)`` returns), as ``len(ring) - 1``
    hops round ``ring``, each written with ``dynamic_update_slice``:
    the forward pass gets the leaf with nothing to unpack, and the
    compiler has no gathered temporary to copy into the output (where
    the step's params are donated the rows land in their buffer)."""
    n = len(ring)
    rows = w_sh.shape[0]
    order, place = _ring_place(ring, idx)
    out = jax.lax.dynamic_update_slice_in_dim(into, w_sh, idx * rows, 0)
    for perm, step, lo, cnt in _ring_ways(ring, rows, w_sh.dtype):
        cur = jax.lax.slice_in_dim(w_sh, lo, lo + cnt, axis=0)
        for hop in range(n - 1):
            cur = jax.lax.ppermute(cur, axis, perm)
            owner = order[(place - step * (hop + 1)) % n]
            out = jax.lax.dynamic_update_slice_in_dim(
                out, cur, owner * rows + lo, 0)
    return out


@jax.named_scope("mx_exchange")
def gather_bucket(bucket, w_sh, axis):
    """All-gather an updated shard back to the replicated bucket and
    split it per param (tiled on dimension 0, matching
    :func:`shard_slice`: a leaf-shaped bucket comes back as the leaf
    the forward pass wants, with nothing to unpack)."""
    return unflatten_bucket(
        bucket, jax.lax.all_gather(w_sh, axis, tiled=True))


def flat_variant_key(plan, stage=None):
    """The ``fused_bucket_opt`` autotune key for a bucket plan: the
    total padded element count + lead dtype — what the kernels
    actually stream, shared by the ps train step, the Module updater
    and the bench bucket race so a winner measured by one reaches the
    others on the same plan.

    ``stage`` (MXNET_ZERO_STAGE): stages None/2 share the legacy key —
    stage 2 IS the program every winner so far was measured on, so the
    Module updater's winner still reaches the default train step.
    Stages 1 and 3 wrap the kernel in a different exchange (all-reduce
    + slice / persistently-sharded params), so they get their own key
    dimension rather than inheriting a winner measured elsewhere.  So
    does a plan with leaf-shaped buckets, whose elements the kernels do
    not stream (they decline rows): a winner measured over the whole
    plan flat says nothing of it.  A plan that is all flat keeps the
    legacy key."""
    shape = (sum(b.padded for b in plan),)
    if stage not in (None, 2):
        shape = shape + (int(stage),)
    if any(b.leaf for b in plan):
        shape = shape + ("leaf", sum(b.padded for b in plan if b.leaf))
    return (shape, plan[0].dtype if plan else "float32")


def resolve_bucket_variant(optimizer, plan, mesh=None, stage=None):
    """Resolve the ``fused_bucket_opt`` lowering for a bucket plan at
    BUILD time: a force scope / MXNET_PALLAS_OPT override first, then
    kernel feasibility, then the cached winner under the flat-layout
    key (stage-distinguished for ZeRO stages 1/3).  Returns True
    (Pallas), False (jnp), or None — undecided, so the trace-time
    ``variant_choice`` consult still applies (force scopes entered
    around a later trace keep working)."""
    from .. import autotune as _at
    from ..ops import pallas_opt

    choice = _at.variant_choice("fused_bucket_opt")
    if choice is not None:
        return bool(choice)
    if not _at.enabled():
        return False
    shape, dtype = flat_variant_key(plan, stage)
    if pallas_opt.supported(optimizer, dtype) is not None:
        return False
    cached = _at.lookup("fused_bucket_opt", shape, dtype,
                        mesh=_at.mesh_desc(mesh))
    if cached is not None:
        return bool(_at.VARIANT_OPS["fused_bucket_opt"].get(cached,
                                                            False))
    return None


def plan_fingerprint(plan, n_shards, stage=None):
    """Stable fingerprint of a bucket plan AT a shard count — the
    checkpoint manifest's ``topology.plan_fingerprint`` (resilience.
    elastic).  Two runs share a fingerprint iff their bucket layouts
    are interchangeable: same buckets in the same order with the same
    member names/shapes/dtypes/padding and the same layout (a
    leaf-shaped bucket is tagged; an all-flat plan hashes as it always
    did), sharded the same number of ways.  A resume whose
    fingerprint differs must re-plan + re-shard;
    one whose fingerprint matches is a same-topology no-op.

    ``stage``: ZeRO stages None/1/2 hash identically — their params
    (and so their checkpoint payloads) are the replicated named tree,
    interchangeable across stages, and existing stamped checkpoints
    must keep verifying.  Stage 3 persists PARAMETER shards in the
    flat-bucket layout, a different on-disk world: its fingerprint is
    stage-tagged so a cross-stage resume is flagged for re-shard
    instead of silently misreading flat buckets as named tensors."""
    import hashlib

    h = hashlib.sha256()
    h.update(f"shards={int(n_shards)}".encode())
    if stage == 3:
        h.update(b"stage=3")
    for b in plan:
        h.update(repr((b.dtype, b.names, b.shapes, b.offsets,
                       b.size, b.padded, b.group)).encode())
        if b.leaf:
            h.update(b"leaf")
    return h.hexdigest()[:16]


def _env_stage():
    """MXNET_ZERO_STAGE: None unset, 0 forced off, 1/2/3 the stage.
    Unknown values raise — a typo'd stage silently training the wrong
    exchange is the silent-green failure mode the dryrun case filter
    also rejects."""
    from ..config import get_env

    raw = str(get_env("MXNET_ZERO_STAGE")).strip()
    if not raw:
        return None
    if raw in ("0", "1", "2", "3"):
        return int(raw)
    raise MXNetError(
        f"MXNET_ZERO_STAGE={raw!r} is not a recognized stage (use 1, "
        "2 or 3, 0 to force the replicated step, or unset)")


def resolve_stage(optimizer_sharding=None, zero_stage=None, mesh=None,
                  param_spec=None):
    """The ZeRO ladder's one spelling: ``None`` (replicated) or the
    stage 1/2/3 of the sharded exchange.  ``optimizer_sharding="ps"`` is
    stage 2 (that program bit for bit) unless ``zero_stage`` names
    another; MXNET_ZERO_STAGE's 1/2/3 override the caller and opt in,
    its 0 overrides every opt-in.  No mesh: warns and stays replicated."""
    if optimizer_sharding not in (None, False, "", "ps"):
        raise MXNetError(
            f"unknown optimizer_sharding {optimizer_sharding!r} (only "
            "'ps')")
    if zero_stage not in (None, 1, 2, 3):
        raise MXNetError(
            f"unknown zero_stage {zero_stage!r} (use 1, 2 or 3)")
    env = _env_stage()
    stage = zero_stage if env is None else env
    if stage is None and optimizer_sharding == "ps":
        stage = 2  # the default exchange: reduce-scattered gradients
    if not stage:
        return None
    if mesh is None:
        import warnings

        warnings.warn(
            "optimizer_sharding='ps' needs a mesh (nothing to shard "
            "over on one device) — step stays replicated", stacklevel=3)
        return None
    if param_spec:
        raise MXNetError(
            "optimizer_sharding='ps' does not compose with param_spec "
            "(tensor parallelism) yet")
    return stage


# ------------------------------------------------- stage-3 param layout
def stage3_param_keys(plan):
    """The pytree keys of the by-bucket layout (stage-3 parameters,
    every stage's optimizer state): one array of ``bucket.shape`` per
    plan entry, sharded over the data axis on dimension 0."""
    return [f"_bucket{i}" for i in range(len(plan))]


def shard_stage3_params(plan, named, mesh=None, data_axis="data"):
    """Named ``{name: array}`` params -> the stage-3 persistent layout
    ``{"_bucket<i>": the bucket's array}`` (flat padded, or the leaf
    itself), placed sharded over the data axis when a mesh is given
    (per-chip param bytes ~ total/N)."""
    import jax
    import jax.numpy as jnp

    out = {k: flatten_bucket(b, {n: jnp.asarray(named[n])
                                 for n in b.names})
           for k, b in zip(stage3_param_keys(plan), plan)}
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        out = jax.device_put(out, NamedSharding(mesh, P(data_axis)))
    return out


def gather_stage3_params(plan, pshards):
    """Inverse of :func:`shard_stage3_params`: reassemble the named
    ``{name: array}`` tree from the buckets' arrays (host-side; for a
    multi-process world pass buckets through
    ``resilience.elastic.host_gather`` first)."""
    named = {}
    for k, b in zip(stage3_param_keys(plan), plan):
        named.update(unflatten_bucket(b, onp.asarray(pshards[k])))
    return named


def adopt_layout(plan, tree):
    """A by-bucket tree (a ``make_train_step`` ``opt_state``, stage-3
    params) as the plan lays it out.  Trees saved before leaf-shaped
    buckets hold every ``_bucket<i>`` / ``_residual<i>`` 1-D; a
    leaf-shaped bucket's row-major content equals that flat content
    element for element, so such an entry is reshaped.  Any other shape
    is refused: it was laid out under another plan.  Returns ``tree``
    itself when nothing differs."""
    out = tree
    for i, (k, b) in enumerate(zip(stage3_param_keys(plan), plan)):
        if not b.leaf:
            continue

        def fit(a, key):
            if not getattr(a, "ndim", 0) or tuple(a.shape) == b.shape:
                return a
            if tuple(a.shape) == (b.padded,):
                return a.reshape(b.shape)
            raise MXNetError(
                f"{key} holds an array of shape {tuple(a.shape)} where "
                f"the bucket plan keeps {b.names[0]!r} leaf-shaped as "
                f"{b.shape} (or flat as ({b.padded},), which is taken): "
                "it was saved under another bucket plan; gather it to "
                "named parameters and re-plan")

        for key in (k, f"_residual{i}"):
            leaves = jax.tree_util.tree_leaves(tree.get(key))
            if any(fit(a, key) is not a for a in leaves):
                out = dict(out) if out is tree else out
                out[key] = jax.tree_util.tree_map(
                    lambda a: fit(a, key), tree[key])
    return out


# ---------------------------------------------- analytic exchange bytes
def analytic_exchange_bytes(plan, n_shards, stage):
    """The analytic per-step minimum wire bytes of a bucket plan's
    exchange, in the same accounting :func:`collective_bytes` reads
    off compiled HLO (per-device OUTPUT bytes of each launch):

    * stage 1 — one all-reduce per bucket (``padded`` elements out)
      plus the gather-back all-gather of the updated params;
    * stage 2 — one reduce-scatter per bucket (``padded/N`` out) plus
      the gather-back all-gather (``padded`` out);
    * stage 3 — the forward's per-bucket param all-gather plus the
      backward's reduce-scatter; nothing gathers back.

    The bench/benchdiff collectives-bytes budget gates the measured
    RS+AG bytes at <= 1.05x this floor — anything above it is
    duplicated traffic (a re-gather, an unfused pad) the schedule
    snuck in."""
    rs = ag = ar = 0
    for b in plan:
        item = onp.dtype(b.dtype).itemsize
        full = b.padded * item
        if stage == 1:
            ar += full
            ag += full
        else:
            rs += full // int(n_shards)
            ag += full
    return {"reduce-scatter": rs, "all-gather": ag, "all-reduce": ar}


# ------------------------------------------- compiled-HLO collective lines
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                "collective-permute", "all-to-all")

_DT_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
             "f64": 8, "s64": 8, "u64": 8, "s16": 2, "u16": 2,
             "s8": 1, "u8": 1, "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

# ``%name = <result shape> <kind>[-start](operands...``: the result
# shape is whatever sits between the first " = " and the op kind, so
# nothing in it has to be spelled out — XLA combines collectives into
# one tuple-shaped op whose shape text carries ``/*index=5*/`` comments,
# and the TPU compiler adds tiling such as ``{0:T(1024)S(1)}``.  An op
# kind is preceded by a blank; a *reference* to a collective
# (``get-tuple-element(%all-reduce.3)``) by ``%``, and ``-done`` ops
# do not match.
_COLLECTIVE_LINE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.*?)\s("
    + "|".join(_COLLECTIVES) + r")(-start)?\(")
_COLLECTIVE_DONE = re.compile(
    r"\s(" + "|".join(_COLLECTIVES) + r")-done\(")
_SHAPE = re.compile(
    "(" + "|".join(sorted(_DT_BYTES, key=len, reverse=True))
    + r")\[([\d,]*)\]")


def _collective_shapes(line):
    """``(kind, is_async_start, [(dtype, elements), ...])`` for an HLO
    instruction line that launches a collective, else None.  The
    shapes are the launch's results: an async ``all-gather-start`` or
    ``collective-permute-start`` returns (operands..., results...,
    scalar contexts), of which only the results are kept; an
    ``all-reduce-start`` returns its results alone."""
    m = _COLLECTIVE_LINE.match(line)
    if not m:
        return None
    shapes_text, kind, start = m.groups()
    shapes = []
    for sm in _SHAPE.finditer(shapes_text):
        n = 1
        for d in sm.group(2).split(","):
            if d:
                n *= int(d)
        shapes.append((sm.group(1), n))
    if start and kind != "all-reduce":
        shapes = [sh for sh in shapes if sh[1] > 1] or shapes
        shapes = shapes[len(shapes) // 2:]
    return kind, bool(start), shapes


# -------------------------------------------- overlap proof (Perfetto)
def overlap_report(hlo_text, plan, n_shards):
    """Structural overlap evidence for the stage-3 prefetch, read off
    the compiled step's HLO schedule: every per-bucket parameter
    all-gather is located (matched by its per-device output element
    count = the bucket's ``padded`` total), and for each launch the
    report records how much non-collective compute the schedule placed
    between it and the next bucket's gather (sync schedules, e.g. the
    CPU dryrun) or between its ``-start``/``-done`` pair (async
    schedules, the TPU latency-hiding scheduler).  Overlap is REAL
    when that count is nonzero: bucket k+1's gather is in flight while
    bucket k's consumers run, instead of all collectives serializing
    at the step head.

    Returns ``{"gathers": [{bucket, pos, done_pos, compute_between,
    async}], "total_instructions": int, "overlapped": bool}``."""
    sizes = {}
    for i, b in enumerate(plan):
        sizes.setdefault(b.padded, []).append(i)
    lines = [ln for ln in hlo_text.splitlines() if " = " in ln]
    launches = [_collective_shapes(ln) for ln in lines]
    dones = [_COLLECTIVE_DONE.search(ln) for ln in lines]
    gathers = []
    for pos, parsed in enumerate(launches):
        if parsed is None or parsed[0] != "all-gather":
            continue
        _, is_async, shapes = parsed
        # a combined gather carries several buckets in one tuple
        for _, n in shapes:
            bucket = sizes.get(n)
            if not bucket:
                continue
            gathers.append({"bucket": bucket[0], "pos": pos,
                            "async": is_async, "done_pos": None,
                            "compute_between": 0})
    is_collective = [a is not None or d is not None
                     for a, d in zip(launches, dones)]
    for gi, g in enumerate(gathers):
        if g["async"]:
            for pos in range(g["pos"] + 1, len(lines)):
                if dones[pos] and dones[pos].group(1) == "all-gather":
                    g["done_pos"] = pos
                    break
            end = g["done_pos"] if g["done_pos"] is not None \
                else g["pos"] + 1
        else:
            end = gathers[gi + 1]["pos"] if gi + 1 < len(gathers) \
                else len(lines)
        g["compute_between"] = sum(
            1 for pos in range(g["pos"] + 1, end)
            if not is_collective[pos])
    return {"gathers": gathers, "total_instructions": len(lines),
            "overlapped": any(g["compute_between"] > 0
                              for g in gathers[:-1] or gathers)}


def check_bucket_rule(optimizer):
    """A bucket shard slices through many parameters, so the rule must
    either be elementwise or provide its own bucket-aware form."""
    from ..optimizer.optimizer import Optimizer

    if getattr(optimizer, "fused_elementwise", True):
        return
    if type(optimizer).fused_bucket_update is Optimizer.fused_bucket_update:
        raise MXNetError(
            f"optimizer {type(optimizer).__name__} is not elementwise and "
            "provides no fused_bucket_update — it cannot run on flat "
            "bucket shards (optimizer_sharding='ps')")


def sharding_rule_reasons(optimizer):
    """Semantics the flat-bucket sharded updater cannot reproduce, as
    human-readable reasons (empty list = eligible).  Module uses this
    at init_optimizer time to fall back to the eager updater with a
    logged reason; :meth:`ShardedBucketUpdater.set_states` uses it to
    REFUSE a resumed pickle that smuggles in such an optimizer (e.g.
    an eager dump carrying an lr_scheduler) instead of silently
    running different math."""
    reasons = []
    try:
        check_bucket_rule(optimizer)
    except MXNetError as e:
        reasons.append(str(e))
    if getattr(optimizer, "needs_key", False):
        reasons.append("stochastic rule (needs per-step PRNG keys)")
    if getattr(optimizer, "multi_precision", False):
        reasons.append("multi_precision master weights")
    if getattr(optimizer, "lr_scheduler", None) is not None:
        reasons.append("lr_scheduler (evaluated per update only in "
                       "the eager path)")
    if not reasons:
        # legacy .states interchange needs identical fused/eager state
        # layouts (Nadam's fused rule carries an extra schedule
        # scalar) — probed HERE so set_states' resume gate refuses the
        # same optimizers Module's init gate does
        import jax.numpy as jnp

        from .. import ndarray as nd

        probe = jnp.zeros((2,), jnp.float32)
        try:
            if len(optimizer.fused_state(probe)) != \
                    len(optimizer.create_state(0, nd.NDArray(probe))):
                reasons.append("fused/eager state layouts differ")
        except Exception as e:
            reasons.append(f"state probe failed: {e!r}")
    return reasons


# ------------------------------------------------- HLO collective counter
def collective_bytes(hlo_text):
    """Per-collective output bytes, launch counts and tensor counts in
    a compiled HLO — the per-step cross-chip traffic the sharded
    program will put on ICI/DCN.  ``counts`` are launches; XLA combines
    neighbouring collectives into one tuple-shaped launch, so
    ``tensors`` counts the arrays those launches carry (seven gradient
    all-reduces combined into one op: 1 launch, 7 tensors).  (Moved
    from ``__graft_entry__._collective_bytes`` so bench.py's
    collectives phase and the tier-1 budget tests share one parser.)"""
    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    tensors = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        # async collectives lower to -start/-done pairs: count starts
        parsed = _collective_shapes(line)
        if parsed is None:
            continue
        kind, _, shapes = parsed
        out[kind] += sum(n * _DT_BYTES[dt] for dt, n in shapes)
        counts[kind] += 1
        tensors[kind] += len(shapes)
    return {"bytes": out, "counts": counts, "tensors": tensors,
            "total_bytes": sum(out.values())}


# hyper-params NOT fingerprinted for live-mutation re-trace: lr/wd ride
# the bucket group key, multipliers/param_dict feed _get_lr/_get_wd,
# schedulers force the eager fallback, and the counters advance
# mechanically without changing the update rule
_HYPER_SIG_SKIP = frozenset((
    "lr", "wd", "lr_mult", "wd_mult", "param_dict", "idx2name",
    "lr_scheduler", "num_update", "begin_num_update",
    "_index_update_count", "_all_index_update_counts",
))


# --------------------------------------------- Module-side sharded updater
class ShardedBucketUpdater:
    """Module's ZeRO-1 updater: the optimizer state of every trainable
    parameter lives SHARDED over the data mesh in flat buckets; each
    device runs the fused rule only on its shard (the server-side
    optimizer, kvstore_dist_server.h:346) and the updated param buckets
    all-gather back to the replicated executor weights.

    Gradients arriving here are already fully reduced (the executor's
    backward all-reduces under the data mesh), so the win is optimizer
    MEMORY and update FLOPs at params/N per chip — plus one all-gather
    per bucket instead of nothing, which is the ZeRO-1 trade.

    Checkpoint contract (``get_states``/``set_states``): shards GATHER
    into the legacy per-param ``{name: state-tuple}`` pickle on save
    and RE-SHARD on load, so ``.states`` files are bit-interchangeable
    with the replicated :class:`~mxnet_tpu.optimizer.Updater`.
    """

    def __init__(self, optimizer, mesh, params, data_axis="data",
                 capacity=None):
        from jax.sharding import NamedSharding, PartitionSpec as P

        check_bucket_rule(optimizer)
        self.optimizer = optimizer
        self.mesh = mesh
        self.axis = data_axis
        self.n_shards = int(mesh.shape[data_axis])
        self._capacity = capacity
        self._shapes = {n: tuple(v.shape) for n, v in params.items()}
        self._dtypes = {n: onp.dtype(getattr(v, "dtype", onp.float32))
                        for n, v in params.items()}
        # effective (lr, wd) per param — lr_mult/wd_mult applied the
        # way the eager Updater would — partition the buckets, so each
        # bucket carries ONE hyper-parameter setting and per-param
        # multipliers survive sharding exactly
        self._groups = self._current_groups(params)
        self.plan = plan_buckets(params, self.n_shards, capacity=capacity,
                                 group_key=self._groups)
        self._rebuild_bucket_opts()
        self._hyper_sig = self._current_hyper_sig()
        self._repl = NamedSharding(mesh, P())
        self._state_sh = NamedSharding(mesh, P(data_axis))
        # the step clock continues the optimizer's (begin_num_update
        # seeds resumed runs; adam/ftml bias correction uses t = _t+1
        # exactly as eager's _update_count would produce)
        self._t = int(getattr(optimizer, "num_update", 0) or 0)
        self._fn = None
        #: which lowering runs the per-shard update: True = the fused
        #: Pallas bucket kernels (ops/pallas_opt), False = jnp; None =
        #: not decided yet (resolved at first _build via the
        #: "fused_bucket_opt" autotune registry — see _decide_variant)
        self._pallas = None
        states = []
        for b in self.plan:
            st = optimizer.fused_state(flatten_bucket(
                b, {n: params[n] for n in b.names}))
            states.append(self._place_state(st))
        self._states = states

    def _current_groups(self, names):
        return {n: (float(self.optimizer._get_lr(n)),
                    float(self.optimizer._get_wd(n))) for n in names}

    def _current_hyper_sig(self):
        """Every scalar hyper-param the fused rules bake in at trace
        time besides lr/wd (momentum, beta1/beta2, rescale_grad,
        clip_gradient, ...).  The eager updater reads these live on
        every update, so a mid-run mutation must re-bake + re-trace
        here too, not silently keep the stale traced values."""
        return tuple(sorted(
            (k, v) for k, v in vars(self.optimizer).items()
            if k not in _HYPER_SIG_SKIP
            and isinstance(v, (int, float, bool, str, bytes, type(None)))
        ))

    def _rebuild_bucket_opts(self):
        """One shallow optimizer copy per bucket with that bucket's
        effective lr/wd baked in (the fused rules read self.lr/self.wd
        at trace time; multipliers live in the group key)."""
        import copy

        self._bucket_opts = []
        for b in self.plan:
            o = copy.copy(self.optimizer)
            o.lr_mult, o.wd_mult, o.param_dict = {}, {}, {}
            o.lr_scheduler = None
            if b.group is not None:
                o.lr, o.wd = b.group
            self._bucket_opts.append(o)

    def _sync_hyper_params(self):
        """The eager updater reads lr/wd on EVERY update; the fused
        path bakes them in at trace time.  Re-deriving the effective
        groups per call keeps the two in sync when the caller mutates
        ``optimizer.lr``/``wd`` mid-training (the epoch-decay recipe):
        a value change re-traces the jitted update, and a change that
        re-partitions the params gathers the states, replans the
        buckets and re-shards.  Non-(lr, wd) scalars (momentum,
        beta1/beta2, rescale_grad, clip_gradient, ...) never affect
        the partition, so a mutation there only re-bakes + re-traces."""
        sig = self._current_hyper_sig()
        if sig != self._hyper_sig:
            self._hyper_sig = sig
            self._rebuild_bucket_opts()
            self._fn = None
        groups = self._current_groups(self._shapes)
        if groups == self._groups:
            return
        if all(len({groups[n] for n in b.names}) == 1
               for b in self.plan):
            # same partition, new values: swap the baked hyper-params
            self._groups = groups
            self.plan = [dataclasses.replace(b, group=groups[b.names[0]])
                         for b in self.plan]
            self._rebuild_bucket_opts()
            self._fn = None
            return
        per_param = self._gather_per_param()
        self._groups = groups

        class _Spec:
            def __init__(self, shape, dtype):
                self.shape, self.dtype = shape, dtype

        self.plan = plan_buckets(
            {n: _Spec(self._shapes[n], self._dtypes[n])
             for n in self._shapes},
            self.n_shards, capacity=self._capacity, group_key=groups)
        self._rebuild_bucket_opts()
        self._states = self._flatten_to_plan(per_param)
        self._fn = None
        self._pallas = None  # new plan = new variant key: re-decide

    def _place_state(self, st):
        import jax

        return tuple(
            jax.device_put(s, self._state_sh if getattr(s, "ndim", 0)
                           else self._repl) for s in st)

    # ----------------------------------------------------------- update
    def _variant_key(self):
        """The autotune cache key for this updater's program — the
        shared flat-layout key (:func:`flat_variant_key`), plus the
        mesh component."""
        from .. import autotune as _at

        shape, dtype = flat_variant_key(self.plan)
        return shape, dtype, _at.mesh_desc(self.mesh)

    def _decide_variant(self):
        """Resolve the "fused_bucket_opt" lowering for this updater —
        the eager-Module analog of make_train_step's in-step race.
        :func:`resolve_bucket_variant` handles the shared precedence
        (force/env override, feasibility, cached flat-key winner);
        undecided on TPU triggers an in-step race of the updater's
        OWN jitted exchange — jnp vs Pallas over the real bucket plan
        with synthetic gradients — whose winner persists under the
        same flat key the ps train step consults.  Off-TPU with no
        override and no cache: jnp (the interpret-mode kernel can only
        lose; racing it would cost minutes to learn that)."""
        from .. import autotune as _at
        from ..ops import kernel_target

        decided = resolve_bucket_variant(self.optimizer, self.plan,
                                         self.mesh)
        if decided is not None:
            return decided
        if not kernel_target.on_tpu():
            return False
        shape, dtype, mesh_d = self._variant_key()

        def measure(value):
            return self._time_update(pallas=bool(value))

        winner, _ = _at.tune("fused_bucket_opt", shape, dtype,
                             _at.VARIANT_OPS["fused_bucket_opt"],
                             measure, mesh=mesh_d)
        return bool(_at.VARIANT_OPS["fused_bucket_opt"].get(
            winner, False))

    def _time_update(self, pallas):
        """Marginal sec/update of THIS updater's exchange under the
        given lowering: the shared :func:`autotune.chain_time`
        two-K-slope over a non-donating jit of the real mapped update
        on synthetic small gradients — the program that actually runs
        per Module.update."""
        import jax
        import jax.numpy as jnp

        from .. import autotune as _at

        mapped = self._make_mapped(pallas)
        p_shardings, _ = self._shardings()
        params = {n: jnp.zeros(self._shapes[n],
                               dtype=self._dtypes[n].name)
                  for b in self.plan for n in b.names}
        grads = {n: jnp.full(self._shapes[n], 1e-3,
                             dtype=self._dtypes[n].name)
                 for n in params}
        params = jax.device_put(params,
                                {n: p_shardings[n] for n in params})

        def body(carry, i):
            p_, s_ = carry
            return mapped(p_, grads, s_, (i + 1).astype(jnp.float32))

        return _at.chain_time(body, (params, self._states))

    def _make_mapped(self, pallas):
        import jax
        from jax.sharding import PartitionSpec as P

        from . import compat_shard_map

        plan = self.plan
        opts = self._bucket_opts
        n_sh = self.n_shards
        axis = self.axis
        needs_seg = not getattr(self.optimizer, "fused_elementwise",
                                True)
        segs = [bucket_segments(b) for b in plan] if needs_seg else None

        def local_update(params_, grads_, states_, t):
            idx = jax.lax.axis_index(axis)
            new_p, new_states = {}, []
            for i, b in enumerate(plan):
                # grads arrive fully reduced from the executor's
                # backward; the owned shard is just a slice
                g_sh = shard_slice(flatten_bucket(b, grads_), n_sh, idx)
                _, uw, us = bucket_shard_update(
                    b, opts[i], params_, g_sh, states_[i], t,
                    n_shards=n_sh, idx=idx, axis=axis,
                    seg=segs[i] if needs_seg else None, pallas=pallas)
                new_p.update(gather_bucket(b, uw, axis))
                new_states.append(us)
            return new_p, new_states

        p_specs = {n: P() for b in plan for n in b.names}
        s_specs = [tuple(P(axis) if getattr(s, "ndim", 0) else P()
                         for s in st) for st in self._states]
        return compat_shard_map(
            local_update, self.mesh,
            in_specs=(p_specs, p_specs, s_specs, P()),
            out_specs=(p_specs, s_specs))

    def _shardings(self):
        p_shardings = {n: self._repl for b in self.plan
                       for n in b.names}
        s_shardings = [tuple(self._state_sh if getattr(s, "ndim", 0)
                             else self._repl for s in st)
                       for st in self._states]
        return p_shardings, s_shardings

    def _build(self):
        import jax

        if self._pallas is None:
            self._pallas = self._decide_variant()
        mapped = self._make_mapped(self._pallas)
        p_shardings, s_shardings = self._shardings()
        # donate only the states (we own them between calls); the
        # params/grads buffers stay live in the executor's NDArrays
        self._fn = jax.jit(
            mapped,
            in_shardings=(p_shardings, p_shardings, s_shardings, None),
            out_shardings=(p_shardings, s_shardings),
            donate_argnums=(2,))

    def update_all(self, triplets):
        """Apply one step to every ``(name, grad, weight)`` NDArray
        triplet at once (Module.update collects them; per-name calls
        would defeat the bucketing)."""
        import jax.numpy as jnp

        if self._states is None:
            self._gather_per_param()  # raises the state-lost error
        self._sync_hyper_params()
        if self._fn is None:
            self._build()
        trip = {n: (g, w) for n, g, w in triplets}
        plan_names = [n for b in self.plan for n in b.names]
        planned = set(plan_names)
        missing = [n for n in plan_names if n not in trip]
        extra = [n for n in trip if n not in planned]
        if missing or extra:
            raise MXNetError(
                "sharded update param set diverged from the bucket plan "
                f"(missing {missing[:4]}, unplanned {extra[:4]})")
        grads = {n: trip[n][0]._data for n in plan_names}
        weights = {n: trip[n][1] for n in plan_names}
        params = {n: weights[n]._data for n in plan_names}
        # mid-step collective loss (resilience.faultsim dist.collective):
        # fires BEFORE the jitted exchange, so an armed raise surfaces
        # as a failed step with the donated state buffers still intact
        # — the drain checkpoint that follows stays writable
        from ..resilience import faultsim

        faultsim.inject("dist.collective")
        try:
            new_p, self._states = self._fn(params, grads,
                                           self._states,
                                           jnp.float32(self._t + 1))
        except Exception:
            # the jitted call donates the state buffers; if it died
            # mid-execution they are gone and any later get_states
            # (e.g. the preemption drain's final checkpoint) would
            # crash on deleted arrays — mark the loss so it raises a
            # clear error instead.  _t is untouched: the step did not
            # happen.
            if any(getattr(s, "is_deleted", lambda: False)()
                   for st in self._states for s in st):
                self._states = None
            raise
        self._t += 1
        # the eager Updater advances optimizer.num_update on every call
        # (_update_count); callbacks reading module._optimizer.num_update
        # — the classic decay-every-K-updates recipe — must see the same
        # clock here (num_update is in _HYPER_SIG_SKIP, so this never
        # triggers a re-trace)
        self.optimizer.num_update = max(
            self._t, int(getattr(self.optimizer, "num_update", 0)))
        for n, w in weights.items():
            w._adopt(new_p[n])

    def topology(self):
        """This updater's contribution to the checkpoint ``topology``
        block: shard count, bucket-plan fingerprint, bucket count."""
        return {"world_size": self.n_shards,
                "plan_fingerprint": plan_fingerprint(self.plan,
                                                     self.n_shards),
                "n_buckets": len(self.plan)}

    # --------------------------------------- checkpoint (legacy layout)
    def _gather_per_param(self):
        """Gather the sharded bucket states to host, re-split per
        param: ``{name: tuple of onp leaves}``."""
        if self._states is None:
            raise MXNetError(
                "sharded optimizer state was lost when a step failed "
                "mid-execution (the buffers are donated to the jitted "
                "update); restore from the last checkpoint via "
                "set_states before saving or updating again")
        per_param = {}
        for b, st in zip(self.plan, self._states):
            per_leaf = [unflatten_bucket(b, onp.asarray(s))
                        if getattr(s, "ndim", 0) else onp.asarray(s)
                        for s in st]
            for name in b.names:
                per_param[name] = tuple(
                    s[name] if isinstance(s, dict) else s
                    for s in per_leaf)
        return per_param

    def _flatten_to_plan(self, per_param):
        """Inverse of :meth:`_gather_per_param`: pack per-param leaf
        tuples into the current plan's buckets (each in its layout) and
        re-shard."""
        import jax.numpy as jnp

        new_states = []
        for b in self.plan:
            ref = per_param[b.names[0]]
            flat = []
            for li in range(len(ref)):
                if getattr(ref[li], "ndim", 0):
                    tree = {n: jnp.asarray(per_param[n][li])
                            for n in b.names}
                    flat.append(flatten_bucket(b, tree))
                else:
                    # replicated scalar state: identical across params
                    # by construction
                    flat.append(jnp.asarray(ref[li]))
            new_states.append(self._place_state(tuple(flat)))
        return new_states

    def get_states(self, dump_optimizer=False):
        """Gather the bucket shards back into the legacy per-param
        ``{name: state-tuple-of-NDArrays}`` pickle (the replicated
        Updater's exact on-disk layout, so sharded and replicated runs
        exchange ``.states`` files freely)."""
        import copy
        import pickle

        from .. import ndarray as nd

        states = {
            name: tuple(nd.array(leaf) for leaf in leaves)
            for name, leaves in self._gather_per_param().items()
        }
        # the fused rules take the step count t explicitly (bias
        # correction: adam/ftml/...), so it must ride the pickle — as a
        # reserved entry the eager Updater carries through untouched
        # (it only ever looks states up by param name)
        states["__step"] = (nd.array(onp.asarray([self._t],
                                                 onp.int64)),)
        if dump_optimizer:
            opt = copy.copy(self.optimizer)
            opt.param_dict = {}
            # the sharded path never ran opt._update_count, so the
            # copy's begin_num_update/_index_update_count are stale
            # (num_update is kept live by update_all): seed all three
            # coherently with our step count so an EAGER resume of this
            # file continues its adam/ftml bias correction instead of
            # restarting at t=1
            opt.num_update = opt.begin_num_update = self._t
            opt._index_update_count = {}
            return pickle.dumps((states, opt))
        return pickle.dumps(states)

    def set_states(self, states):
        """Re-shard a legacy per-param states pickle onto the mesh
        (the inverse of :meth:`get_states`; a replicated run's file
        loads the same way)."""
        import pickle

        import jax.numpy as jnp

        loaded = pickle.loads(states)
        have_opt = isinstance(loaded, tuple) and len(loaded) == 2
        if have_opt:
            loaded, new_opt = loaded
            # init_optimizer's eligibility gate ran against the
            # init-time optimizer only; a cross-mode resume can smuggle
            # in semantics the flat buckets cannot reproduce (an eager
            # dump's lr_scheduler would silently pin the lr at the
            # resume-point value).  Refuse loudly, keeping our own
            # optimizer untouched.
            bad = sharding_rule_reasons(new_opt)
            if bad:
                raise MXNetError(
                    "resumed optimizer states carry an optimizer the "
                    "sharded updater cannot run ({}); resume this "
                    "checkpoint with kvstore='local' (the eager "
                    "updater) instead".format("; ".join(bad)))
            new_opt.param_dict = getattr(self.optimizer, "param_dict", {})
            self.optimizer = new_opt
            self._rebuild_bucket_opts()
            self._hyper_sig = self._current_hyper_sig()
            self._fn = None  # hyper-params may have changed: re-trace
            self._pallas = None  # a new optimizer may change kernel
            #                      eligibility: re-decide the lowering
            # dumps carry the count on the optimizer itself — and it is
            # FRESHER than any "__step" states entry: an eager run that
            # resumed a sharded file carries the old "__step" inert
            # while its own counters kept advancing
            self._t = int(getattr(new_opt, "num_update", self._t))
        loaded = dict(loaded)
        stp = loaded.pop("__step", None)
        if stp is not None and not have_opt:
            v = stp[0]
            self._t = int(onp.asarray(
                v.asnumpy() if hasattr(v, "asnumpy") else v
            ).reshape(-1)[0])
        per_param = {}
        for b in self.plan:
            for name in b.names:
                st = loaded.get(name)
                if st is None:
                    raise MXNetError(
                        f"optimizer states missing parameter {name!r}")
                per_param[name] = tuple(
                    s._data if hasattr(s, "_data") else jnp.asarray(s)
                    for s in st)
        self._states = self._flatten_to_plan(per_param)
