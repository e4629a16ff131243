"""The compiled train step: ONE body over ONE exchange seam.

:func:`step_body` is the sequence every step follows::

    scale        the loss-scale this step computes with
    grad         one value_and_grad over loss_of(materialize(params))
    exchange     units: what an update is applied to, with its gradient
    update       the optimizer's rule on every unit, and with it the
    verdict      finite?  (nan_guard or dynamic scaling only)
    keep         a non-finite step leaves weight, state, residual
    publish      the params pytree the caller gets back
    bookkeeping  _loss_scale, _bad_steps, _counters, _fp8, _numerics

The two exchanges differ in what a unit is, how its gradient gets there
and how the result is published: :class:`ReplicatedExchange` (the named
leaves; XLA inserts the all-reduce from the shardings) and
:class:`ShardedExchange` (the bucket plan's owned shards, ZeRO stages
1/2/3, per device under ``shard_map``).  ``make_train_step`` picks one
at build time.  Each keeps its own unscale arithmetic (ROADMAP D13).
:class:`HostStep` is what the caller holds: the jitted step behind the
host's own work (RunLog compile events, the profiler's note, the
numerics read-back).
"""
from __future__ import annotations

import collections
import copy
import functools
import warnings

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import autotune as _at
from .. import ndarray as nd
from .. import profiler as _profiler
from .. import telemetry as _tm
from ..ops import pallas_opt as _po
from ..resilience import faultsim
from ..telemetry import numerics as _nm
from . import _is_norm_stat, amp_cast_params, compat_shard_map
from . import zero as _zero

#: one update's worth of work.  ``grad`` is what the verdict reads and
#: the rule applies (quantized first, under compression); ``weight`` is the leaf (replicated), the owned
#: shard (stage 3) or the named tree the shard is cut from inside the
#: update (stages 1/2); ``state`` is kept or replaced whole.
Unit = collections.namedtuple("Unit", "key grad weight state index")

#: what the body reads besides its exchange, fixed at build time
StepConfig = collections.namedtuple(
    "StepConfig", "loss_of ladder_arm dynamic static_scale nan_guard "
    "fp8_rung numerics_on counters")


# ------------------------------------------------------------------ loss
def make_loss_of(apply_fn, loss_fn, compute_dtype):
    """``(loss_of, ladder_arm)``: the mean loss of a batch under the
    AMP policy, and the dtype-ladder decision it reads at trace time."""

    def ladder_arm():
        """The dtype-ladder decision for THIS trace (None = ladder not
        consulted): an explicit compute_dtype always wins; otherwise a
        tuner force scope, the MXNET_DTYPE_LADDER hand override, or
        the cached per-program winner applied via program_scope."""
        if compute_dtype is not None or not _at.dtype_ladder_armed():
            return None
        return _at.variant_choice("dtype_ladder")

    def loss_of(param_dict, x, y, key, fp8=None):
        """``(mean loss, {counter: value})`` of a batch."""
        cdt = compute_dtype
        arm = ladder_arm()
        if arm == "bf16":
            # consulted at TRACE time only, and only when the knob arms
            # it (a dtype change is not numerics-neutral)
            cdt = "bfloat16"
        if arm == "fp8" and fp8 is not None:
            # the fp8 rung: matmul/conv weights and the batch input
            # snap to the e4m3 grid at the delayed per-tensor scales
            # carried in opt_state['_fp8']; the straight-through
            # backward snaps their gradients to e5m2
            # (ops/pallas_opt.fp8_qdq).  Norm params (amp policy) and
            # every other op stay in fp32.  A cached fp8 winner
            # reaching a step whose build did not provision the state
            # (fp8 is None) falls through to fp32.
            gscale = fp8["g"][0]
            param_dict = {
                n: (_po.fp8_qdq(v, fp8["w"][n][0], gscale)
                    if n in fp8["w"] else v)
                for n, v in param_dict.items()}
            if jnp.issubdtype(x.dtype, jnp.floating):
                x = _po.fp8_qdq(x, fp8["x"][0], gscale)
        # the scopes are metadata on the traced operations and nothing
        # else: backward reads transpose(jvp(mx_forward)) without
        # further code, and every gluon block names itself inside
        # (gluon.Block.__call__)
        with jax.named_scope("mx_forward"), _profiler.counting() as counted:
            if cdt is not None:
                # AMP policy (reference contrib/amp list semantics):
                # matmul/conv weights in bf16, norm affine+stats in fp32
                param_dict = amp_cast_params(param_dict, cdt)
                if jnp.issubdtype(x.dtype, jnp.floating):
                    # token ids stay whole: bf16 holds none above 256
                    x = x.astype(cdt)
            out = apply_fn(param_dict, x, key=key)
        with jax.named_scope("mx_loss"):
            loss_nd = loss_fn(nd.NDArray(out.astype(jnp.float32)),
                              nd.NDArray(y))
            # beside the loss, what the forward pass's blocks counted
            # (profiler.count): the step hands it out in its state
            return jnp.mean(loss_nd._data), {
                k: v for k, (_, v) in counted.items()}

    return loss_of, ladder_arm


# ------------------------------------------------------- fp8 rung's state
def fp8_state(params):
    """Delayed-scaling state of the fp8 dtype-ladder rung: one (scale,
    amax history) pair per matmul/conv weight, one for the batch input,
    one e5m2 pair for the gradients; history length is
    MXNET_FP8_AMAX_HISTORY."""
    from ..config import get_env

    hist_len = max(1, int(get_env("MXNET_FP8_AMAX_HISTORY")))

    def pair():
        # step-1 scale: identity until the history holds a real amax
        return (jnp.float32(1.0), jnp.zeros((hist_len,), jnp.float32))

    return {"x": pair(), "g": pair(),
            "w": {n: pair() for n, v in params.items()
                  if not _is_norm_stat(n) and getattr(v, "ndim", 0) >= 2}}


def _fp8_bookkeeping(state, params, x, grads):
    """The in-graph delayed-scaling update (ops/pallas_opt.
    fp8_delayed_scale beside the loss-scale bookkeeping): observe each
    quantized tensor class's |t|_inf THIS step, roll it into the
    history, and derive the NEXT step's scale — no host sync, and an
    overflowed observation backs the scale off without corrupting the
    state."""
    new = {}
    _, xh = state["x"]
    if jnp.issubdtype(x.dtype, jnp.floating):
        x_amax = jnp.max(jnp.abs(x.astype(jnp.float32)))
    else:
        x_amax = jnp.max(xh)  # integer inputs never quantize
    nh, ns = _po.fp8_delayed_scale(xh, x_amax)
    new["x"] = (ns, nh)
    _, gh = state["g"]
    g_amax = jnp.float32(0.0)
    for n in state["w"]:
        g_amax = jnp.maximum(
            g_amax, jnp.max(jnp.abs(grads[n].astype(jnp.float32))))
    ngh, ngs = _po.fp8_delayed_scale(gh, g_amax, fmax=_po.E5M2_MAX)
    new["g"] = (ngs, ngh)
    new_w = {}
    for n, (_, wh) in state["w"].items():
        w_amax = jnp.max(jnp.abs(params[n].astype(jnp.float32)))
        nwh, nws = _po.fp8_delayed_scale(wh, w_amax)
        new_w[n] = (nws, nwh)
    new["w"] = new_w
    return new


# ---------------------------------------------------------------- shared
@jax.named_scope("mx_guard")
def _keep_if_finite(finite, new, old):
    """Skip-the-update selection: a non-finite step leaves every leaf
    of ``old`` (weight, state, residual) as it came."""
    return jax.tree_util.tree_map(
        lambda u, o: jnp.where(finite, u, o), new, old)


# -------------------------------------------------------------- exchanges
class ReplicatedExchange:
    """No mesh, or a mesh the params replicate (or shard per
    ``param_spec``) over: a unit is a named leaf and the step has no
    collective of its own."""

    stage = plan = None

    def __init__(self, opt, dynamic):
        self.opt, self.dynamic = opt, dynamic

    def init_state(self, params):
        self.names = list(params)
        return params, {n: self.opt.fused_state(v)
                        for n, v in params.items()}

    def wrap(self, body, shardings):
        return functools.partial(body, self)

    def shardings(self, mesh, params, opt_state, param_spec):
        repl = NamedSharding(mesh, P())
        if param_spec is None:
            return (jax.tree_util.tree_map(lambda _: repl, params),
                    jax.tree_util.tree_map(lambda _: repl, opt_state))
        p_shard = {n: NamedSharding(mesh, param_spec.get(n, P()))
                   for n in params}
        # optimizer state (per-param moments) shards like its param;
        # scalar entries (loss-scale state) replicate
        opt_shard = {
            n: jax.tree_util.tree_map(
                lambda s, sh=p_shard.get(n, repl): sh
                if getattr(s, "ndim", 0) else repl, opt_state[n])
            for n in opt_state}
        return p_shard, opt_shard

    def forward_key(self, key):
        return key

    def materialize(self, params):
        return params

    def exchange(self, params, opt_state, lval, grads, scale):
        """``(loss, the scale it still carries, units)``.  A static
        scale divides loss and gradients here; a dynamic one multiplies
        the gradients by its inverse and leaves the loss to the
        bookkeeping."""
        if scale is not None:
            with jax.named_scope("mx_guard"):
                if self.dynamic:
                    inv = 1.0 / scale
                    grads = jax.tree_util.tree_map(lambda g: g * inv,
                                                   grads)
                else:
                    lval = lval / scale
                    grads = jax.tree_util.tree_map(lambda g: g / scale,
                                                   grads)
                    scale = None
        return lval, scale, (
            Unit(n, grads[n], params[n], opt_state[n], i)
            for i, n in enumerate(self.names))

    def update(self, u, t, key, want_finite):
        with jax.named_scope("mx_optimizer"):
            # stochastic rules (SGLD) get a distinct per-param key;
            # deterministic ones skip the fold-in (it compiles to ~2
            # dead scalar ops per parameter otherwise)
            sub = jax.random.fold_in(key, u.index) \
                if self.opt.needs_key else None
            uw, us = self.opt.fused_update(u.weight, u.grad, u.state, t,
                                           key=sub)
        return u.weight, uw, us, None

    def agree(self, finite):
        return finite

    def publish(self, u, w, s):
        return {u.key: w}, {u.key: s}

    def mean_loss(self, loss):
        return loss

    def total_counters(self, counted, hows):
        return counted


class ShardedExchange:
    """``optimizer_sharding="ps"`` (parallel.zero): a unit is a bucket's
    owned shard, its state the pair (optimizer state, compression
    residual or None).  Stage 1 sums the bucket and slices the shard
    off, stage 2 reduce-scatters it, stage 3 holds the params as shards
    and gathers them in ``materialize`` (whose transpose is the
    scatter).  The traced methods run PER DEVICE under ``shard_map``,
    on a copy that knows its place on the data axis (``idx``)."""

    idx = None

    def __init__(self, opt, stage, mesh, axis, bucket_bound, threshold):
        self.opt, self.stage, self.mesh, self.axis = opt, stage, mesh, axis
        self.n = int(mesh.shape[axis])
        self.bucket_bound, self.threshold = bucket_bound, threshold
        # the way round the axis that a leaf-shaped bucket's hops take
        self.ring = _zero.ring_order(mesh, axis)

    def init_state(self, params):
        _zero.check_bucket_rule(self.opt)
        self.plan = _zero.plan_buckets(params, self.n,
                                       capacity=self.bucket_bound)
        self.keys = _zero.stage3_param_keys(self.plan)
        needs_seg = not getattr(self.opt, "fused_elementwise", True)
        self.seg = [_zero.bucket_segments(b) for b in self.plan] \
            if needs_seg else None
        # the fused_bucket_opt lowering, resolved at BUILD time under
        # the shared flat-layout key (zero.resolve_bucket_variant) so
        # a winner measured by the Module updater's race — or a bench
        # bucket race over the same plan — reaches this step too; None
        # (undecided) leaves the trace-time variant_choice consult in
        # charge, so force scopes and program-scope winners still work
        self.pallas = _zero.resolve_bucket_variant(
            self.opt, self.plan, self.mesh, self.stage)
        # optimizer state is created over the buckets, each in its
        # layout (a flat pack, or the one leaf's own shape), and lives
        # sharded on dimension 0 for the step's whole life (the server
        # owning its key shard's state) — per-chip state bytes ~ total/N
        opt_state = {
            bk: self.opt.fused_state(_zero.flatten_bucket(b, params))
            for bk, b in zip(self.keys, self.plan)}
        if self.threshold is not None:
            for i, b in enumerate(self.plan):
                # error-feedback residual: per bucket-SHARD, fp32 (the
                # narrow-accumulate discipline — a bf16 residual would
                # lose the feedback below threshold/256)
                opt_state[f"_residual{i}"] = jnp.zeros(b.shape,
                                                       jnp.float32)
        if self.stage == 3:
            # the params move into their persistent layout — one array
            # per plan entry, sharded over the data axis at jit wiring
            # (per-chip param bytes ~ total/N); the named tree only
            # ever rematerializes transiently inside the step's gathers
            params = {bk: _zero.flatten_bucket(b, params)
                      for bk, b in zip(self.keys, self.plan)}
        return params, opt_state

    def shardings(self, mesh, params, opt_state, param_spec):
        """Params replicate (stages 1/2) or live sharded by bucket
        (stage 3); bucket states and residuals live sharded over the
        data axis; scalar entries (loss scale, bad steps) replicate."""
        shard = NamedSharding(mesh, P(self.axis))
        repl = NamedSharding(mesh, P())
        return (
            jax.tree_util.tree_map(
                lambda _: shard if self.stage == 3 else repl, params),
            jax.tree_util.tree_map(
                lambda l: shard if getattr(l, "ndim", 0) else repl,
                opt_state))

    def wrap(self, body, shardings):
        def local_step(params, opt_state, x, y, key, t):
            # x/y are the local batch shard, bucket states/residuals
            # the locally-owned shard
            here = copy.copy(self)
            here.idx = jax.lax.axis_index(self.axis)
            return body(here, params, opt_state, x, y, key, t)

        p_specs, s_specs = jax.tree_util.tree_map(lambda s: s.spec,
                                                  shardings)
        return compat_shard_map(
            local_step, self.mesh,
            in_specs=(p_specs, s_specs, P(self.axis), P(self.axis), P(),
                      P()),
            out_specs=(P(), p_specs, s_specs))

    def forward_key(self, key):
        return jax.random.fold_in(key, self.idx)

    def materialize(self, params):
        if self.stage != 3:
            return params
        # bucket-wise all-gather PREFETCH: every bucket's gather is
        # issued with no inter-bucket data dependency, so the scheduler
        # runs bucket k+1's gather while the compute consuming bucket k
        # executes instead of serializing all gathers at the step head
        named = {}
        for bk, b in zip(self.keys, self.plan):
            named.update(_zero.gather_bucket(b, params[bk], self.axis))
        return named

    def exchange(self, params, opt_state, lval, grads, scale):
        """``(loss, the scale it still carries, units)``.  Grad of the
        GLOBAL mean loss = psum(local-mean grads)/N; the unscale folds
        into the same multiply, on the float32 shard."""
        inv = 1.0 / self.n
        if scale is not None:
            inv = inv / scale
        return lval, scale, self._units(params, opt_state, grads, inv)

    def _units(self, params, opt_state, grads, inv):
        for i, (bk, b) in enumerate(zip(self.keys, self.plan)):
            if self.stage == 3:
                # the gathers' transposes emitted one reduce-scatter a
                # bucket, interleaved with the backward compute: the
                # gradient arrives summed and scattered
                g_sh = grads[bk]
            elif _zero.rides_ring(b, self.stage):
                # hop by hop round the ring, each hop asynchronous and
                # tied to this leaf's gradient alone: the backward pass
                # of the layers below runs under it (stage 1 takes the
                # same hops, so the two stages stay bit for bit equal)
                g_sh = _zero.ring_reduce_scatter(
                    grads[b.names[0]], self.axis, self.ring, self.idx)
            elif self.stage == 1:
                with jax.named_scope("mx_exchange"):
                    g_sh = _zero.shard_slice(
                        jax.lax.psum(_zero.flatten_bucket(b, grads),
                                     self.axis), self.n, self.idx)
            else:
                # one reduce-scatter for the whole bucket replaces
                # len(b.names) per-tensor all-reduces
                with jax.named_scope("mx_exchange"):
                    g_sh = jax.lax.psum_scatter(
                        _zero.flatten_bucket(b, grads), self.axis,
                        scatter_dimension=0, tiled=True)
            with jax.named_scope("mx_exchange"):
                g32 = g_sh.astype(jnp.float32) * inv
            resid = None if self.threshold is None \
                else opt_state[f"_residual{i}"]
            yield Unit(bk, g32, params[bk] if self.stage == 3 else params,
                       (opt_state[bk], resid), i)

    def update(self, u, t, key, want_finite):
        state, resid = u.state
        g32 = u.grad
        if self.threshold is not None:
            from ..kvstore import quantize_2bit

            # the verdict stays the body's check of the PRE-quantize
            # gradient (the kernel's fused one would see the quantized
            # values)
            want_finite = False
            with jax.named_scope("mx_exchange"):
                g32, resid = quantize_2bit(g32 + resid, self.threshold)
        sub = jax.random.fold_in(jax.random.fold_in(key, u.index),
                                 self.idx) if self.opt.needs_key else None
        # bucket_shard_update casts g to the bucket dtype and runs the
        # jnp rule OR the fused Pallas kernel per the "fused_bucket_opt"
        # variant decision; on the kernel arm the finiteness verdict of
        # the RAW f32 gradient rides the same VMEM pass (want_finite)
        res = _zero.bucket_shard_update(
            self.plan[u.index], self.opt, u.weight, g32, state, t,
            n_shards=self.n, idx=self.idx, axis=self.axis,
            seg=self.seg[u.index] if self.seg else None, key=sub,
            pallas=self.pallas, want_finite=want_finite,
            w_sh=u.weight if self.stage == 3 else None)
        w_sh, uw, us = res[:3]
        return w_sh, uw, (us, resid), res[3] if want_finite else None

    def agree(self, finite):
        # each device saw params/N elements: the psum makes the
        # verdict global
        with jax.named_scope("mx_guard"), jax.named_scope("mx_exchange"):
            bad = jax.lax.psum(1 - finite.astype(jnp.int32), self.axis)
            return bad == 0

    def publish(self, u, w, s):
        state, resid = s
        new_s = {u.key: state}
        if resid is not None:
            new_s[f"_residual{u.index}"] = resid
        if self.stage == 3:
            # params stay sharded: the updated shard IS the new param
            # bucket (the next forward's prefetch gathers it)
            return {u.key: w}, new_s
        b = self.plan[u.index]
        if _zero.rides_ring(b, self.stage):
            name = b.names[0]
            return {name: _zero.ring_gather(u.weight[name], w, self.axis,
                                            self.ring, self.idx)}, new_s
        return _zero.gather_bucket(b, w, self.axis), new_s

    def mean_loss(self, loss):
        with jax.named_scope("mx_exchange"):
            return jax.lax.pmean(loss, self.axis)

    def total_counters(self, counted, hows):
        with jax.named_scope("mx_exchange"):
            return {k: (jax.lax.pmax if hows[k] == "max" else jax.lax.psum)(
                v, self.axis) for k, v in counted.items()}


# ------------------------------------------------------------------ body
def step_body(cfg, ex, params, opt_state, x, y, key, t):
    """One train step (module docstring), traced under ``ex``."""
    scale, good = opt_state["_loss_scale"] if cfg.dynamic \
        else (cfg.static_scale, None)
    check = cfg.dynamic or cfg.nan_guard
    # fp8 rung (trace-time): thread the delayed scales into the loss.
    # On the other arms (a race's fp32/bf16 force, or a non-fp8 winner)
    # the provisioned state passes through untouched so every arm emits
    # the same opt_state pytree.
    fp8 = opt_state["_fp8"] if cfg.fp8_rung else None
    fp8_on = cfg.fp8_rung and cfg.ladder_arm() == "fp8"

    def scaled_loss(p, x_, y_, k_):
        lv, counted = cfg.loss_of(ex.materialize(p), x_, y_, k_,
                                  fp8=fp8 if fp8_on else None)
        if scale is not None:
            with jax.named_scope("mx_guard"):
                lv = lv * scale
        return lv, counted

    (lval, counted), grads = jax.value_and_grad(scaled_loss, has_aux=True)(
        params, x, y, ex.forward_key(key))
    loss, carried, units = ex.exchange(params, opt_state, lval, grads,
                                       scale)
    finite = None
    if check:
        # dynamic scaling's verdict is GRADIENT finiteness only (a
        # scaled loss can overflow while the unscaled grads are fine);
        # the nan guard additionally checks the loss
        with jax.named_scope("mx_guard"):
            finite = jnp.isfinite(loss) if cfg.nan_guard \
                else jnp.array(True)
    done = []
    for u in units:
        w, uw, us, fused = ex.update(u, t, key, check)
        if check:
            # on the shard or leaf the unit holds; ``fused`` is the
            # same verdict from the kernel's own pass over it
            with jax.named_scope("mx_guard"):
                finite = finite & (jnp.isfinite(u.grad).all()
                                   if fused is None else fused)
        done.append((u, w, uw, us))
    if check:
        finite = ex.agree(finite)
    new_p, new_s = {}, {}
    for u, w, uw, us in done:
        if check:
            uw, us = _keep_if_finite(finite, (uw, us), (w, u.state))
        p_out, s_out = ex.publish(u, uw, us)
        new_p.update(p_out)
        new_s.update(s_out)
    loss = ex.mean_loss(loss)
    with jax.named_scope("mx_guard"):
        if cfg.dynamic:
            # overflow: halve the scale; after 2000 consecutive finite
            # steps, double it (reference amp scaler)
            new_s["_loss_scale"] = _po.scale_bookkeeping(finite, scale,
                                                         good)
        if carried is not None:
            # the scale the loss was COMPUTED with, not the adjusted
            # one, or the reported loss jumps 2x on every scale change
            loss = loss / carried
        if cfg.nan_guard:
            # consecutive bad steps; any finite step resets it (the
            # host enforces MXNET_BAD_STEP_LIMIT reading it)
            new_s["_bad_steps"] = jnp.where(
                finite, jnp.int32(0), opt_state["_bad_steps"] + 1)
    if cfg.counters:
        # what the net's blocks counted in this step's forward pass,
        # over every chip's part of the batch; the host reads it from
        # the state (HostStep -> profiler.step_counters())
        new_s["_counters"] = ex.total_counters(
            {k: jnp.asarray(counted[k], jnp.float32) for k in cfg.counters},
            cfg.counters)
    if cfg.fp8_rung or cfg.numerics_on:
        named = dict(sorted((u.key, u.grad) for u, *_ in done))
    if cfg.fp8_rung:
        # the histories update even on a skipped step — the overflow
        # observation is exactly what backs the scale off
        new_s["_fp8"] = _fp8_bookkeeping(fp8, params, x, named) \
            if fp8_on else fp8
    if cfg.numerics_on:
        # stats of the step AS IT HAPPENED, guard or no guard: the bad
        # step's NaN counts are the explanation
        stats = _nm.summarize_tree(named)
        stats["__loss"] = _nm.summary(loss)
        new_s["_numerics"] = stats
    return loss, new_p, new_s


# ------------------------------------------------------------------ host
class HostStep:
    """What the caller holds: ``jitted`` behind the host's own work.
    ``tune = (variant_ops, level, platform, mesh description)`` is the
    autotune signature the RunLog's compile events name winners by."""

    def __init__(self, jitted, opt, ex, tune, numerics_on):
        self._jitted, self._ex, self._tune = jitted, ex, tune
        # callers introspecting the program (bench.py, the multichip
        # dryrun, the benchmark) need jit's lower(): same XLA program
        self.lower = jitted.lower
        self._hyper = {
            k: v for k, v in sorted(vars(opt).items())
            if not k.startswith("_")
            and isinstance(v, (int, float, bool, str, type(None)))}
        # stage 2 keeps the historic "ps" stamp (it IS that program);
        # stages 1/3 trace different exchanges and must name themselves
        # so the RunLog can blame a retrace on a stage flip
        self._sharding = "none" if ex.stage is None else (
            "ps" if ex.stage == 2 else f"zero{ex.stage}")
        if ex.stage is not None:
            # ... and how much of the exchange keeps its leaves' shapes
            n_leaf, n_buckets, share = _zero.leaf_share(ex.plan)
            n_ring, _, ring = _zero.ring_share(ex.plan, ex.stage)
            self._sharding += (
                f" ({n_leaf} of {n_buckets} buckets leaf-shaped, "
                f"{100 * share:.1f}% of the elements; {n_ring} by the "
                f"ring, {100 * ring:.1f}%)")
            # the layout contract for checkpointing/eval callers: under
            # stage 3 the params pytree is by bucket
            # (zero.gather_stage3_params reassembles the named tree)
            self.zero_stage, self.zero_plan = ex.stage, ex.plan
            self.zero_layout = _zero.bucket_layout(ex.plan, ex.stage)
        self._seen, self._last = set(), None
        self._nm_period = _nm.sample_period() if numerics_on else 0
        self._nm_step = self._calls = 0
        # MXNET_FAULT_SPEC names the point, or the step never asks
        self._faults = faultsim.armed("step.loss_nan")

    def _compile_event(self, rl, args):
        """One RunLog record per (re)trace of the fused step: the
        RunLog diffs the fingerprint against the previous one for this
        program to name the retrace cause.  A signature seen before
        that recurs after a change is a cache "hit" (XLA's jit cache
        still holds it)."""
        x = args[2]
        sig = (tuple(x.shape), str(x.dtype))
        if sig in self._seen and sig == self._last:
            return
        cache = "hit" if sig in self._seen else "miss"
        variant_ops, level, plat, mesh_d = self._tune
        winners = {}
        if _at.enabled(level):
            winners = {op: _at.lookup(op, x.shape, x.dtype, platform=plat,
                                      mesh=mesh_d) for op in variant_ops}
        try:
            rl.compile_event(
                "train_step",
                _tm.compile_fingerprint(
                    sig[0], sig[1], True, winners=winners,
                    hyper=self._hyper, sharding=self._sharding),
                cache=cache)
            if cache == "miss":
                # memory/flop/collective introspection of the program
                # about to run — a persistent-cache disk hit when the
                # XLA cache is enabled
                _tm.describe_program(self._jitted, *args,
                                     program="train_step")
        except Exception:
            pass  # telemetry must never kill the step
        self._seen.add(sig)
        self._last = sig

    def _note_program(self, args):
        """Under mx.profiler's device trace: dumps() reads the scopes
        of the traced operations from this program's compiled text."""
        noted = ("train_step", id(self._jitted), jnp.shape(args[2]))
        if noted in _profiler._programs:
            return
        # shapes now (the step donates its arrays), the text when asked
        shapes = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=a.sharding)
            if isinstance(a, jax.Array) else a, args)
        _profiler.note_program(
            noted,
            lambda: self._jitted.lower(*shapes).compile().as_text())

    def _read_numerics(self, rl, result):
        """Sampled readback of the in-graph summaries: the ONLY steps
        that pay a device sync for the monitor.  Inside an outer trace
        (bench's chained fori_loop) the values are tracers — nothing to
        read, skip."""
        try:
            loss_v, _, new_s = result
            vecs = new_s.get("_numerics")
            if vecs is not None and not isinstance(loss_v,
                                                   jax.core.Tracer):
                i = self._nm_step
                self._nm_step = i + 1
                if i % self._nm_period == 0:
                    _nm.emit(rl, i, vecs, where="grad")
        except Exception:
            pass  # the monitor must never kill the step

    def __call__(self, p, o, x, y, key, t):
        if self._faults and faultsim.inject("step.loss_nan") == "nan":
            x, y = _poisoned(x, y)
        # MXNET_RUNLOG unset => current() is None => zero per-step work
        # beyond one call
        rl = _tm.current()
        if rl is not None:
            self._compile_event(rl, (p, o, x, y, key, t))
        if _profiler._jax_trace_active:
            self._note_program((p, o, x, y, key, t))
        if self._ex.stage is not None:
            # a by-bucket tree saved flat, before leaf-shaped buckets,
            # is reshaped once (or refused); its own is handed through
            o = _zero.adopt_layout(self._ex.plan, o)
            if self._ex.stage == 3:
                p = _zero.adopt_layout(self._ex.plan, p)
        # the host span that causes this step's device work, on the
        # profiler's clock (inactive outside a profiler session)
        with _tm.tracing.region("mx_step", step_num=self._calls):
            result = self._jitted(p, o, x, y, key, t)
        self._calls += 1
        if "_counters" in result[2]:
            # the arrays as they are: nobody waits unless somebody asks
            _profiler.note_step_counters(result[2]["_counters"])
        if self._nm_period and rl is not None:
            self._read_numerics(rl, result)
        return result


def _poisoned(x, y):
    """``step.loss_nan`` (fault harness only): the batch poisoned with
    NaN BEFORE the compiled step, so the in-graph guard sees a genuinely
    non-finite step.  Integer dtypes have no NaN, so the first inexact
    input takes it (token id models poison through float labels)."""
    x, y = jnp.asarray(x), jnp.asarray(y)
    if jnp.issubdtype(x.dtype, jnp.inexact):
        x = x * jnp.asarray(jnp.nan, x.dtype)
    elif jnp.issubdtype(y.dtype, jnp.inexact):
        y = y * jnp.asarray(jnp.nan, y.dtype)
    else:
        warnings.warn(
            "step.loss_nan injection skipped: neither x nor y has an "
            "inexact dtype to poison", stacklevel=3)
    return x, y
