"""TPU-first parallelism (SURVEY.md §2.5 TPU-native equivalent).

The reference scales via DataParallelExecutorGroup (batch slicing across
GPUs, python/mxnet/module/executor_group.py:144) + KVStore reduce trees +
ps-lite servers.  The TPU-native design replaces all of that with ONE
compiled SPMD program over a ``jax.sharding.Mesh``:

  * dp  — batch axis sharded over 'data'; XLA inserts the gradient psum
          (the entire KVStore 'device'/'nccl'/'dist_sync' stack).
  * tp  — weight axes sharded over 'model' (absent in the reference —
          modern requirement).
  * sp  — sequence axis sharded over 'seq' (ring attention lives in
          mxnet_tpu.parallel.ring).
  * pp  — GPipe microbatch pipeline over a 'pipe' axis
          (mxnet_tpu.parallel.pipeline).
  * ep  — mixture-of-experts routing over an 'expert' axis
          (mxnet_tpu.parallel.moe).
  * Optimizer state shards with the params (ZeRO ≡ the reference's
    server-side optimizer, kvstore_dist_server.h:346).

`functionalize` turns a Gluon Block into (params pytree, pure apply_fn) —
the bridge from the imperative API to pjit.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as onp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import _rng, autograd
from .. import ndarray as nd
from ..base import MXNetError

__all__ = ["get_mesh", "functionalize", "make_train_step",
           "DataParallelTrainer", "Mesh", "NamedSharding", "P",
           "NORM_STAT_SUFFIXES", "amp_cast_params", "auto_tp_spec",
           "ring", "pipeline", "moe", "zero", "compat_shard_map",
           "make_predict_fn", "tune_microbatch"]


def compat_shard_map(f, mesh, in_specs, out_specs, check_vma=False):
    """``jax.shard_map`` with the package's default of no replication
    check (the per-device bodies here return values the checker cannot
    prove replicated, e.g. gathered buckets)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


#: parameter-name suffixes that stay fp32 under mixed precision (the AMP
#: policy the reference encodes in contrib/amp/lists: norm affine+stats)
NORM_STAT_SUFFIXES = ("gamma", "beta", "running_mean", "running_var",
                      "moving_mean", "moving_var")


def _is_norm_stat(name):
    return any(name.endswith(s) for s in NORM_STAT_SUFFIXES)


def amp_cast_params(params, compute_dtype):
    """Cast a {name: array} tree to the compute dtype, keeping norm
    affine/stat parameters in their original (fp32) dtype."""
    if compute_dtype is None:
        return params
    return {n: (v if _is_norm_stat(n) else v.astype(compute_dtype))
            for n, v in params.items()}


def get_mesh(shape=None, axis_names=("data",), devices=None):
    """Build a Mesh over the available devices.

    get_mesh() -> 1-D 'data' mesh over all devices;
    get_mesh((2, 4), ('data', 'model')) -> dp×tp grid.
    """
    if devices is None:
        devices = jax.devices()
    if shape is None:
        shape = (len(devices),)
    arr = onp.array(devices[: int(onp.prod(shape))]).reshape(shape)
    return Mesh(arr, axis_names)


def functionalize(block, train=False):
    """Extract (params, apply_fn) from a Gluon block.

    params: {flat_name: jax.Array} in deterministic order.
    apply_fn(params, *inputs, key=None): pure — swaps the traced values
    into the block (same mechanism as HybridBlock._call_cached) and runs
    the imperative forward, so ANY Block works, hybridized or not.
    """
    from ..gluon.block import _collect_all_params, _swap_param_values

    flat_params = _collect_all_params(block)
    names = []
    seen = {}
    for p in flat_params:
        name = p.name
        if name in seen:  # shared params appear once
            continue
        seen[name] = p
        names.append(name)
    params = {n: seen[n].data()._data for n in names}

    def apply_fn(param_dict, *inputs, key=None):
        if key is None:
            key = jax.random.key(0)
        vals = [param_dict[p.name] for p in flat_params]
        with _rng.trace_key_scope(key), autograd._Scope(False, train):
            saved = _swap_param_values(block, vals)
            try:
                args = [
                    nd.NDArray(x) if not isinstance(x, nd.NDArray) else x
                    for x in inputs
                ]
                out = block(*args)
            finally:
                _swap_param_values(block, saved)
        if isinstance(out, (list, tuple)):
            return [o._data for o in out]
        return out._data

    return params, apply_fn


def auto_tp_spec(block, tp_size, axis_name="model", min_dim=64):
    """Derive a tensor-parallel ``param_spec`` for a model-zoo network.

    Shards the leading (output-channel/units) axis of conv and dense
    weights over ``axis_name`` wherever it divides by ``tp_size`` and is
    at least ``min_dim`` (small layers replicate — the collective cost
    outweighs the split).  Norm statistics and biases replicate.  The
    reference has no TP (SURVEY.md §2.5: absent); this is the modern
    mandate's default policy, overridable per-param by the caller.
    """
    probe, _ = functionalize(block)
    spec = {}
    for name, v in probe.items():
        if _is_norm_stat(name) or name.endswith("_bias"):
            continue
        if name.endswith("_weight") and v.ndim >= 2 and \
                v.shape[0] % tp_size == 0 and v.shape[0] >= min_dim:
            spec[name] = P(*((axis_name,) + (None,) * (v.ndim - 1)))
    return spec


def _build_optimizer(optimizer, learning_rate, momentum, wd, beta1, beta2,
                     epsilon, opt_kwargs):
    """Resolve the ``optimizer`` argument to an Optimizer instance with a
    fused rule, filtering convenience kwargs to what its ctor accepts."""
    import inspect

    from .. import optimizer as opt_mod

    if isinstance(optimizer, opt_mod.Optimizer):
        if opt_kwargs:
            # same contract as gluon.Trainer: hyper-params belong to the
            # instance, silently dropping them would mislead
            raise MXNetError(
                "optimizer kwargs must not be given when optimizer is an "
                f"Optimizer instance (got {sorted(opt_kwargs)})")
        return optimizer
    klass = opt_mod.Optimizer.opt_registry.get(str(optimizer).lower())
    if klass is None:
        raise MXNetError(f"unknown optimizer {optimizer!r}")
    sig = inspect.signature(klass.__init__)
    accepted = set(sig.parameters)
    base_accepted = set(
        inspect.signature(opt_mod.Optimizer.__init__).parameters)
    if not any(p.kind is inspect.Parameter.VAR_KEYWORD
               for p in sig.parameters.values()):
        base_accepted = set()
    # the convenience defaults are filtered to what the ctor accepts;
    # explicit opt_kwargs must match exactly (typos should not pass)
    unknown = {k for k in opt_kwargs
               if k not in accepted and k not in base_accepted}
    if unknown:
        raise MXNetError(
            f"optimizer {optimizer!r} does not accept {sorted(unknown)}")
    kwargs = dict(learning_rate=learning_rate, wd=wd, momentum=momentum,
                  beta1=beta1, beta2=beta2, epsilon=epsilon)
    kwargs = {k: v for k, v in kwargs.items()
              if k in accepted or k in base_accepted}
    kwargs.update(opt_kwargs)
    return klass(**kwargs)


def make_train_step(block, loss_fn, optimizer="sgd", learning_rate=0.01,
                    momentum=0.9, wd=0.0, beta1=0.9, beta2=0.999,
                    epsilon=1e-8, mesh=None, data_axis="data",
                    param_spec=None, donate=True, compute_dtype=None,
                    loss_scale=None, sample_data=None, autotune=None,
                    variant_ops=None, nan_guard=None,
                    optimizer_sharding=None, bucket_bound=None,
                    zero_stage=None, gradient_compression=None,
                    **opt_kwargs):
    """Build ONE fully-fused jitted SPMD train step.

    Returns (step_fn, params, opt_state) where
      step_fn(params, opt_state, x, y, key, t) -> (loss, params, opt_state)

    The whole forward+backward+optimizer compiles into a single XLA
    program (the analog of GraphExecutor's full fwd+bwd graph plus the
    fused optimizer kernels, graph_executor.cc:416 +
    src/operator/optimizer_op.cc).  Under a mesh, x/y shard on the batch
    axis and params replicate (or shard per `param_spec` for tp/ZeRO);
    XLA inserts the gradient all-reduce over ICI.

    optimizer: any registry name ('sgd', 'adam', 'lars', 'ftml', ...) or
    an Optimizer instance — its pure ``fused_update`` rule is traced into
    the program (reference analog: server-side optimizer,
    kvstore_dist_server.h:346, and fused optimizer_op kernels).

    loss_scale: None, a static float, or 'dynamic' — dynamic loss scaling
    doubles the scale every 2000 consecutive finite steps and halves it
    on overflow, skipping the update (reference: contrib/amp loss scaler
    + all_finite, src/operator/contrib/all_finite.cc).

    donate=True (the default) donates the params/opt_state buffers to
    XLA: the step writes its updated state in place instead of
    allocating a second copy — the reference's ``static_alloc`` memory
    reuse (SURVEY §7 maps static_alloc ≈ donate_argnums).  The caller
    contract is the functional one this signature already imposes: the
    INPUT params/opt_state are dead after the call (you must thread the
    returned ones), donation just makes XLA exploit that.  Pass
    donate=False to keep calling with the same buffers (step-parity
    tests do).

    sample_data=(x, y): enables the in-step variant autotuner
    (mxnet_tpu.autotune, the cudnn_tune analog): each op in
    ``variant_ops`` races inside a jitted chained run of THIS step on
    the sample batch, the winner persists keyed on (op, batch shape,
    dtype, platform, mesh), and the returned step traces under it.
    On a warm cache the race is skipped (pure lookups).  autotune=None
    follows MXNET_AUTOTUNE; autotune=False disables for this step.
    Without sample_data no timing runs, but cached winners still apply
    to the returned step via the program scope.  In-step timing is
    single-device for now: under a mesh, sample_data warns and is
    ignored (mesh-keyed cached winners still apply).

    nan_guard: step-level NaN/Inf guard compiled INTO the program
    (skip-and-count, the same selection dynamic loss scaling uses): a
    step whose loss or any gradient is non-finite leaves params and
    optimizer state untouched, and ``opt_state['_bad_steps']`` counts
    CONSECUTIVE bad steps (reset to 0 by any finite step) so the host
    can enforce MXNET_BAD_STEP_LIMIT without a per-step sync.  None
    follows that env var (>0 arms it); dynamic loss scaling already
    skips non-finite updates, so the guard stays off there.

    optimizer_sharding="ps": the sharded-server gradient exchange
    (ZeRO-1 ≡ the reference's key-sharded servers running the
    server-side optimizer, kvstore_dist_server.h:346, see
    parallel.zero).  Gradients go into dtype-homogeneous buckets
    (split threshold: ``bucket_bound`` elements, default the authentic
    ``MXNET_KVSTORE_BIGARRAY_BOUND``), each bucket is summed and
    scattered over the data axis (``psum_scatter``), the optimizer's
    fused rule updates ONLY the locally-owned shard (optimizer state
    is created, donated and persisted SHARDED — per-chip state bytes
    ~ params/N), and the updated shards ``all_gather`` back.  A bucket
    of several leaves is packed flat; a bucket of ONE leaf whose rows
    divide over the shards into whole tiles keeps the leaf's shape,
    from the backward pass through the optimizer's state to the
    gathered weights (``step_fn.zero_layout`` says which:
    ``[(bucket key, "leaf" | "flat", elements)]``; the rule reads
    shapes only, ``zero._leaf_shaped``).  What a v5e runs for it
    (VGG-16 on four chips, from the compiled step): a leaf-shaped
    bucket's gradient is a native ``reduce-scatter`` (a chip receives
    a quarter) or the compiler's fused ``all-reduce-scatter``; a flat
    bucket's is an ``all-reduce`` of the whole bucket that the update
    then slices (the compiler keeps no ``reduce-scatter`` of a 1-D
    operand), several flat buckets to a launch; one ``all-gather`` a
    bucket, the small ones asynchronous.  So a step's collectives
    return 1.39x the parameters' bytes to a chip there (770.7 MB),
    2.00x with every bucket flat — which is what one all-reduce a
    tensor returns, at a launch a tensor.  ``None`` follows
    MXNET_OPTIMIZER_SHARDING ('ps' arms it, '0' force-disables, empty
    leaves it off); needs a mesh and does not compose with
    ``param_spec`` (tp) yet.  Dynamic loss scaling checks finiteness
    on the SCATTERED shard and psums the verdict; the nan-guard and
    donation contracts are unchanged; under the forward each device
    sees its local batch shard, so BatchNorm uses per-shard statistics
    — the reference DataParallel semantics (executor_group.py), vs the
    replicated path's SyncBatchNorm-style global stats.

    zero_stage: the ZeRO stage of the sharded exchange (1, 2 or 3;
    None follows MXNET_ZERO_STAGE, which overrides the argument, and
    defaults to stage 2).  Setting a stage opts the step into
    optimizer_sharding="ps" under a mesh.  Stage 1 is the classic
    ZeRO-1 exchange for ablation: one ``psum`` per bucket, the owned
    shard sliced off the replicated reduced gradient.  Stage 2 (the
    default) asks for each bucket's ``psum_scatter``, so that no
    device need hold a whole reduced gradient (it still does for a
    flat bucket on a v5e, see above).  Stage 3 additionally shards
    the PARAMETERS: the returned params pytree is
    ``{"_bucket<i>": the bucket's array}`` (flat padded, or the leaf
    itself) sharded over the data axis on dimension 0 (per-chip
    param+state bytes ~ total/N), the forward all-gathers each bucket
    with all launches issued up-front so bucket k+1's gather can
    overlap bucket k's compute (prefetch), the backward's
    reduce-scatters fall out of differentiating through those gathers
    (interleaved with backward compute), and nothing gathers back.
    The three stages share one bucket plan and one copy of either
    layout, and end bit for bit where each other does.  Use
    ``zero.gather_stage3_params(step_fn.zero_plan, params)`` to
    reassemble the named tree; ``step_fn.zero_stage`` /
    ``step_fn.zero_plan`` / ``step_fn.zero_layout`` expose the layout.
    An ``opt_state`` (or stage-3 params) saved by bucket before
    leaf-shaped buckets, every entry 1-D, is taken by the step: the
    content is the same row-major, so it is reshaped once
    (``zero.adopt_layout``); any other shape is refused.

    gradient_compression: ``{"type": "2bit", "threshold": t}`` —
    2-bit quantization (kvstore.GradientCompression math) applied
    per-bucket on the scattered gradient shard before the optimizer,
    with the error-feedback residual carried SHARD-LOCAL in fp32
    inside opt_state (``_residual<i>``) so narrow-dtype buckets keep
    full-precision accumulation.  Requires optimizer_sharding="ps".
    """
    from .. import autotune as _at
    from ..config import setup_compilation_cache

    setup_compilation_cache()
    params, apply_fn = functionalize(block, train=True)
    if mesh is None:
        # commit params to the accelerator once; otherwise every step
        # re-streams them host->HBM (Context default is cpu for reference
        # parity, but the fused step must live in device memory)
        dev = jax.local_devices()[0]
        params = jax.device_put(params, dev)

    opt = _build_optimizer(optimizer, learning_rate, momentum, wd, beta1,
                           beta2, epsilon, opt_kwargs)

    if variant_ops is None:
        # default race roster: the conv 1x1 lowering always; the
        # dtype ladder joins only when the knob arms it, no explicit
        # compute_dtype pins the answer, AND the env carries no hand
        # override (MXNET_DTYPE_LADDER=bf16/fp8/fp32 already decided —
        # racing a pinned step to discard the result would waste a
        # compile per signature).  Which rungs race — fp32/bf16, or
        # fp8 too — is the knob's roster (autotune.ladder_rungs).
        variant_ops = ("conv1x1_dot",)
        if (compute_dtype is None and _at.dtype_ladder_armed()
                and _at.variant_choice("dtype_ladder") is None):
            variant_ops += ("dtype_ladder",)

    def _ladder_arm():
        """The dtype-ladder decision for THIS trace (None = ladder not
        consulted): an explicit compute_dtype always wins; otherwise a
        tuner force scope, the MXNET_DTYPE_LADDER hand override, or
        the cached per-program winner applied via program_scope."""
        if compute_dtype is not None or not _at.dtype_ladder_armed():
            return None
        return _at.variant_choice("dtype_ladder")

    def loss_of(param_dict, x, y, key, fp8=None):
        cdt = compute_dtype
        arm = _ladder_arm()
        if arm == "bf16":
            # the bf16 dtype-ladder arm (round 14).  Consulted at
            # TRACE time only, and only when the knob arms it (a
            # dtype change is not numerics-neutral).
            cdt = "bfloat16"
        if arm == "fp8" and fp8 is not None:
            # the fp8 rung (round 19): matmul/conv weights and the
            # batch input snap to the e4m3 grid at the delayed
            # per-tensor scales carried in opt_state['_fp8']; the
            # straight-through backward snaps their gradients to e5m2
            # (ops/pallas_opt.fp8_qdq).  Norm params (amp policy) and
            # every other op stay in fp32 — the matmul/conv-only
            # eligibility the contrib/amp FP8 lists mirror.  A cached
            # fp8 winner reaching a step whose build did not provision
            # the state (fp8 is None) falls through to fp32: never
            # take a rung the build did not provision for.
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
        with jax.named_scope("mx_forward"):
            if cdt is not None:
                # AMP policy (reference contrib/amp list semantics):
                # matmul/conv weights in bf16, norm affine+stats in fp32
                param_dict = amp_cast_params(param_dict, cdt)
                x = x.astype(cdt)
            out = apply_fn(param_dict, x, key=key)
        with jax.named_scope("mx_loss"):
            loss_nd = loss_fn(nd.NDArray(out.astype(jnp.float32)),
                              nd.NDArray(y))
            return jnp.mean(loss_nd._data)

    dynamic_scaling = loss_scale == "dynamic"
    static_scale = float(loss_scale) if (
        loss_scale is not None and not dynamic_scaling) else 1.0

    # ---- sharded-server mode resolution (parallel.zero) --------------
    from . import zero as _zero

    ps_mode = optimizer_sharding
    env_ps = _zero.resolve_sharding_env()
    if env_ps is False:
        ps_mode = None  # '0' force-disables even explicit opt-ins
    elif ps_mode is None and env_ps == "ps":
        ps_mode = "ps"
    if ps_mode not in (None, False, "", "ps"):
        raise MXNetError(
            f"unknown optimizer_sharding {ps_mode!r} (only 'ps')")
    ps_mode = "ps" if ps_mode == "ps" else None
    # ---- ZeRO stage resolution (env overrides the argument, same
    # precedence as MXNET_OPTIMIZER_SHARDING; a stage implies the
    # sharded exchange unless the env force-off already vetoed it)
    env_stage = _zero.resolve_zero_stage()
    stage = env_stage if env_stage is not None else zero_stage
    if stage not in (None, 1, 2, 3):
        raise MXNetError(
            f"unknown zero_stage {stage!r} (use 1, 2 or 3)")
    if stage is not None and ps_mode is None and env_ps is not False:
        ps_mode = "ps"
    if ps_mode and mesh is None:
        import warnings

        warnings.warn(
            "optimizer_sharding='ps' needs a mesh (nothing to shard "
            "over on one device) — step stays replicated", stacklevel=2)
        ps_mode = None
    if ps_mode and param_spec:
        raise MXNetError(
            "optimizer_sharding='ps' does not compose with param_spec "
            "(tensor parallelism) yet")
    if gradient_compression is not None and not ps_mode:
        raise MXNetError(
            "gradient_compression in make_train_step requires "
            "optimizer_sharding='ps' (the replicated step has no "
            "bucketed wire to compress)")

    names = list(params)
    comp_threshold = None
    if not ps_mode:
        stage = None
    elif stage is None:
        stage = 2  # the default exchange: reduce-scattered gradients
    if ps_mode:
        n_sh = int(mesh.shape[data_axis])
        _zero.check_bucket_rule(opt)
        plan = _zero.plan_buckets(params, n_sh, capacity=bucket_bound)
        bucket_keys = _zero.stage3_param_keys(plan)
        # optimizer state is created over the buckets, each in its
        # layout (a flat pack, or the one leaf's own shape), and lives
        # sharded on dimension 0 for the step's whole life (the server
        # owning its key shard's state) — per-chip state bytes ~ total/N
        opt_state = {
            bk: opt.fused_state(_zero.flatten_bucket(b, params))
            for bk, b in zip(bucket_keys, plan)
        }
        if gradient_compression is not None:
            ctype = gradient_compression.get("type", "2bit")
            if ctype != "2bit":
                raise MXNetError(f"unsupported compression {ctype}")
            comp_threshold = float(
                gradient_compression.get("threshold", 0.5))
            for i, b in enumerate(plan):
                # error-feedback residual: per bucket-SHARD, fp32 (the
                # narrow-accumulate discipline — a bf16 residual would
                # lose the feedback below threshold/256)
                opt_state[f"_residual{i}"] = jnp.zeros(b.shape,
                                                       jnp.float32)
        if stage == 3:
            # stage 3: the params move into their persistent layout —
            # one array per plan entry (flat padded, or the leaf
            # itself), sharded over the data axis at jit wiring below
            # (per-chip param bytes ~ total/N); the named tree only
            # ever rematerializes transiently inside the step's
            # per-bucket gathers
            params = {bk: _zero.flatten_bucket(b, params)
                      for bk, b in zip(bucket_keys, plan)}
    else:
        opt_state = {n: opt.fused_state(v) for n, v in params.items()}
    if dynamic_scaling:
        opt_state["_loss_scale"] = (
            jnp.float32(2.0 ** 16),  # initial scale (reference amp)
            jnp.zeros((), jnp.int32),  # consecutive-finite counter
        )
    if nan_guard is None:
        from ..config import get_env

        nan_guard = get_env("MXNET_BAD_STEP_LIMIT") > 0
    nan_guard = bool(nan_guard) and not dynamic_scaling
    if nan_guard:
        opt_state["_bad_steps"] = jnp.zeros((), jnp.int32)

    # ---- fp8 dtype-ladder rung (round 19): delayed-scaling state.
    # Provisioned at BUILD time whenever the armed roster names fp8
    # (the race's fp8 arm and a cached fp8 winner both need it in the
    # SAME opt_state pytree the other arms thread through), absent
    # otherwise — an unarmed build's program stays HLO bit-identical
    # to round 18.  Per-tensor scales: one (scale, amax-history) pair
    # per matmul/conv weight, one for the batch input, one e5m2 pair
    # for the gradients; history length is MXNET_FP8_AMAX_HISTORY.
    # Not yet composed with the sharded-server exchange (gradients
    # live there as flat bucket shards, not named tensors).
    from ..ops import pallas_opt as _po

    fp8_rung = (compute_dtype is None and _at.dtype_ladder_armed()
                and "fp8" in _at.ladder_rungs() and not ps_mode)
    if fp8_rung:
        from ..config import get_env

        fp8_hist_len = max(1, int(get_env("MXNET_FP8_AMAX_HISTORY")))

        def _fp8_pair():
            return (jnp.float32(1.0),  # step-1 scale: identity until
                    #                     the history holds a real amax
                    jnp.zeros((fp8_hist_len,), jnp.float32))

        fp8_weight_names = [
            n for n in names
            if not _is_norm_stat(n) and getattr(params[n], "ndim", 0) >= 2
        ]
        opt_state["_fp8"] = {
            "x": _fp8_pair(),
            "g": _fp8_pair(),
            "w": {n: _fp8_pair() for n in fp8_weight_names},
        }

    def _fp8_bookkeeping(fp8_state, params_, x, grads):
        """The in-graph delayed-scaling update (ops/pallas_opt.
        fp8_delayed_scale beside the loss-scale bookkeeping): observe
        each quantized tensor class's |t|_inf THIS step, roll it into
        the history, and derive the NEXT step's scale — no host sync,
        and an overflowed observation backs the scale off without
        corrupting the state."""
        new = {}
        _, xh = fp8_state["x"]
        if jnp.issubdtype(x.dtype, jnp.floating):
            x_amax = jnp.max(jnp.abs(x.astype(jnp.float32)))
        else:
            x_amax = jnp.max(xh)  # integer inputs never quantize
        nh, ns = _po.fp8_delayed_scale(xh, x_amax)
        new["x"] = (ns, nh)
        _, gh = fp8_state["g"]
        g_amax = jnp.float32(0.0)
        for n in fp8_state["w"]:
            g_amax = jnp.maximum(
                g_amax, jnp.max(jnp.abs(grads[n].astype(jnp.float32))))
        ngh, ngs = _po.fp8_delayed_scale(gh, g_amax,
                                         fmax=_po.E5M2_MAX)
        new["g"] = (ngs, ngh)
        new_w = {}
        for n, (_, wh) in fp8_state["w"].items():
            w_amax = jnp.max(jnp.abs(params_[n].astype(jnp.float32)))
            nwh, nws = _po.fp8_delayed_scale(wh, w_amax)
            new_w[n] = (nws, nwh)
        new["w"] = new_w
        return new

    # ---- in-graph numerics monitor (telemetry.numerics, Monitor 2.0):
    # per-gradient summary reductions compile INTO the step and ride in
    # the returned state under the reserved _numerics key — zero host
    # callbacks, zero sync; the telemetry wrapper below reads them back
    # only on sampled steps.  Unarmed = the traced program is
    # bit-identical to a build without the monitor.
    from ..telemetry import numerics as _nm

    numerics_on = _nm.armed()
    if numerics_on and ps_mode:
        import warnings

        warnings.warn(
            "MXNET_NUMERICS under optimizer_sharding='ps' is not "
            "supported yet (gradients live as scattered bucket "
            "shards, not named tensors) — monitor disabled for this "
            "step", stacklevel=2)
        numerics_on = False
    if numerics_on:
        opt_state["_numerics"] = _nm.summary_template(
            dict.fromkeys([*names, "__loss"]))

    def _nm_pack(grads, loss):
        stats = _nm.summarize_tree(grads)
        stats["__loss"] = _nm.summary(loss)
        return stats

    # the dynamic-loss-scale verdict lives in ops/pallas_opt beside the
    # fp8 delayed-scaling verdict (round 19) — one module, so the two
    # backoff rules cannot drift; the replicated and sharded arms both
    # call this ONE copy (sharded-vs-replicated parity contract)
    _scale_bookkeeping = _po.scale_bookkeeping

    @jax.named_scope("mx_optimizer")
    def _apply_updates(params_, opt_state_, grads, t, key):
        new_p, new_s = {}, {}
        for i, n in enumerate(names):
            # stochastic rules (SGLD) get a distinct per-param key;
            # deterministic ones skip the fold-in (it compiles to ~2
            # dead scalar ops per parameter otherwise)
            sub = jax.random.fold_in(key, i) if opt.needs_key else None
            new_p[n], new_s[n] = opt.fused_update(
                params_[n], grads[n], opt_state_[n], t, key=sub)
        return new_p, new_s

    @jax.named_scope("mx_guard")
    def _all_finite(grads, loss=None):
        finite = jnp.array(True) if loss is None else jnp.isfinite(loss)
        for g in jax.tree_util.tree_leaves(grads):
            finite = finite & jnp.isfinite(g).all()
        return finite

    @jax.named_scope("mx_guard")
    def _keep_if_finite(finite, up_p, up_s, params_, opt_state_):
        """Skip-the-update selection: a non-finite step leaves every
        param and state leaf as it came."""
        new_p = {n: jnp.where(finite, up_p[n], params_[n])
                 for n in names}
        new_s = {
            n: jax.tree_util.tree_map(
                lambda u, o: jnp.where(finite, u, o),
                up_s[n], opt_state_[n])
            for n in names
        }
        return new_p, new_s

    def step(params_, opt_state_, x, y, key, t):
        # fp8 rung wiring (trace-time): thread the delayed scales into
        # the loss, and roll this step's amax observations into the
        # history.  On the other arms (a race's fp32/bf16 force, or a
        # non-fp8 winner) the provisioned state passes through
        # untouched so every arm emits the same opt_state pytree.
        fp8_on = fp8_rung and _ladder_arm() == "fp8"
        fp8_state = opt_state_["_fp8"] if fp8_rung else None

        def lo(p, x_, y_, k_):
            return loss_of(p, x_, y_, k_,
                           fp8=fp8_state if fp8_on else None)

        def _fp8_carry(new_s, grads):
            if fp8_rung:
                new_s["_fp8"] = _fp8_bookkeeping(
                    fp8_state, params_, x, grads) if fp8_on \
                    else fp8_state
            return new_s

        if dynamic_scaling:
            scale, good = opt_state_["_loss_scale"]

            def scaled_loss(p, x_, y_, k_):
                lv = lo(p, x_, y_, k_)
                with jax.named_scope("mx_guard"):
                    return lv * scale

            sloss, sgrads = jax.value_and_grad(scaled_loss)(
                params_, x, y, key)
            with jax.named_scope("mx_guard"):
                inv = 1.0 / scale
                grads = jax.tree_util.tree_map(lambda g: g * inv,
                                               sgrads)
            finite = _all_finite(grads)
            up_p, up_s = _apply_updates(
                {n: params_[n] for n in names},
                {n: opt_state_[n] for n in names}, grads, t, key)
            # overflow: skip the update, halve the scale; after 2000
            # consecutive finite steps, double it (reference amp scaler)
            new_p, new_s = _keep_if_finite(finite, up_p, up_s, params_,
                                           opt_state_)
            with jax.named_scope("mx_guard"):
                new_s["_loss_scale"] = _scale_bookkeeping(finite, scale,
                                                          good)
            # the fp8 histories update even on a skipped step — the
            # overflow observation is exactly what backs the scale off
            new_s = _fp8_carry(new_s, grads)
            if numerics_on:
                new_s["_numerics"] = _nm_pack(grads, sloss / scale)
            # unscale with the scale the loss was COMPUTED with, not the
            # adjusted one, or the reported loss jumps 2x on every
            # scale-change step
            with jax.named_scope("mx_guard"):
                return sloss / scale, new_p, new_s

        if static_scale != 1.0:
            def scaled_loss(p, x_, y_, k_):
                lv = lo(p, x_, y_, k_)
                with jax.named_scope("mx_guard"):
                    return lv * static_scale

            loss, grads = jax.value_and_grad(scaled_loss)(params_, x, y,
                                                          key)
            with jax.named_scope("mx_guard"):
                loss = loss / static_scale
                grads = jax.tree_util.tree_map(
                    lambda g: g / static_scale, grads)
        else:
            loss, grads = jax.value_and_grad(lo)(params_, x, y, key)
        if nan_guard:
            # skip-and-count: a non-finite step leaves params/opt state
            # untouched and bumps the consecutive-bad counter; any
            # finite step resets it (MXNET_BAD_STEP_LIMIT policy is
            # enforced by the host reading _bad_steps)
            finite = _all_finite(grads, loss)
            up_p, up_s = _apply_updates(
                params_, {n: opt_state_[n] for n in names}, grads, t,
                key)
            new_p, new_s = _keep_if_finite(finite, up_p, up_s, params_,
                                           opt_state_)
            with jax.named_scope("mx_guard"):
                new_s["_bad_steps"] = jnp.where(
                    finite, jnp.int32(0), opt_state_["_bad_steps"] + 1)
            new_s = _fp8_carry(new_s, grads)
            if numerics_on:
                # stats of the step AS IT HAPPENED, guard or no guard:
                # the bad step's NaN counts are the explanation
                new_s["_numerics"] = _nm_pack(grads, loss)
            return loss, new_p, new_s
        new_p, new_s = _apply_updates(
            params_, {n: opt_state_[n] for n in names}, grads, t, key)
        new_s = _fp8_carry(new_s, grads)
        if numerics_on:
            new_s["_numerics"] = _nm_pack(grads, loss)
        return loss, new_p, new_s

    # ---- sharded-server step (optimizer_sharding="ps") ---------------
    if ps_mode:
        needs_seg = not getattr(opt, "fused_elementwise", True)
        seg_info = [_zero.bucket_segments(b) for b in plan] \
            if needs_seg else None
        check_finite = dynamic_scaling or nan_guard
        # the fused_bucket_opt lowering, resolved at BUILD time under
        # the shared flat-layout key (zero.resolve_bucket_variant) so
        # a winner measured by the Module updater's race — or a bench
        # bucket race over the same plan — reaches this step too; None
        # (undecided) leaves the trace-time variant_choice consult in
        # charge, so force scopes and program-scope winners still work
        ps_pallas = _zero.resolve_bucket_variant(opt, plan, mesh, stage)

        def ps_local_step(params_, opt_state_, x, y, key, t):
            # runs PER DEVICE under shard_map: params replicated in
            # (stages 1/2) or the locally-owned bucket shards (stage
            # 3), x/y are the local batch shard, bucket
            # states/residuals are the locally-owned shard
            idx = jax.lax.axis_index(data_axis)
            fkey = jax.random.fold_in(key, idx)
            if dynamic_scaling:
                scale, good = opt_state_["_loss_scale"]
            else:
                scale = static_scale

            def local_loss(p, x_, y_, k_):
                if stage == 3:
                    # bucket-wise all-gather PREFETCH: every bucket's
                    # gather is issued with no inter-bucket data
                    # dependency, so the scheduler runs bucket k+1's
                    # gather while the compute consuming bucket k
                    # executes instead of serializing all gathers at
                    # the step head
                    named = {}
                    for bk_, b_ in zip(bucket_keys, plan):
                        named.update(_zero.gather_bucket(b_, p[bk_],
                                                         data_axis))
                    p = named
                lv = loss_of(p, x_, y_, k_)
                if dynamic_scaling or static_scale != 1.0:
                    with jax.named_scope("mx_guard"):
                        lv = lv * scale
                return lv

            lval, lgrads = jax.value_and_grad(local_loss)(
                params_, x, y, fkey)
            # grad of the GLOBAL mean loss = psum(local-mean grads)/N;
            # the unscale folds into the same multiply
            inv = 1.0 / n_sh
            if dynamic_scaling:
                inv = inv / scale
            elif static_scale != 1.0:
                inv = inv / static_scale
            # parity with the replicated arms: dynamic scaling's
            # verdict is GRADIENT finiteness only (a scaled loss can
            # overflow while the unscaled grads are fine); the nan
            # guard additionally checks the loss, as replicated does
            finite = None
            if nan_guard:
                with jax.named_scope("mx_guard"):
                    finite = jnp.isfinite(lval)
            elif dynamic_scaling:
                finite = jnp.array(True)
            staged = []
            for i, (bk, b) in enumerate(zip(bucket_keys, plan)):
                w_sh_in = None
                if stage == 3:
                    # differentiating through the tiled all-gather IS
                    # the exchange: its transpose emitted one reduce-
                    # scatter per bucket, interleaved with the rest of
                    # the backward compute — the gradient arrives
                    # already summed and scattered to the owned shard
                    g_sh = lgrads[bk]
                    w_sh_in = params_[bk]
                elif stage == 1:
                    # classic ZeRO-1 for the stage ladder: the whole
                    # reduced bucket lands on every device (one
                    # all-reduce) and the owned shard is sliced off it
                    with jax.named_scope("mx_exchange"):
                        g_sh = _zero.shard_slice(
                            jax.lax.psum(
                                _zero.flatten_bucket(b, lgrads),
                                data_axis), n_sh, idx)
                else:
                    # THE stage-2 exchange: one reduce-scatter for the
                    # whole bucket replaces len(b.names) per-tensor
                    # all-reduces; a leaf-shaped bucket goes in as the
                    # backward pass left it and comes out as its rows
                    with jax.named_scope("mx_exchange"):
                        g_sh = jax.lax.psum_scatter(
                            _zero.flatten_bucket(b, lgrads), data_axis,
                            scatter_dimension=0, tiled=True)
                with jax.named_scope("mx_exchange"):
                    g32 = g_sh.astype(jnp.float32) * inv
                new_resid = None
                if comp_threshold is not None:
                    from ..kvstore import quantize_2bit

                    # compression: the finiteness verdict stays a
                    # separate jnp check on the PRE-quantize gradient
                    # (the kernel's fused verdict would see the
                    # quantized values)
                    if check_finite:
                        with jax.named_scope("mx_guard"):
                            finite = finite & jnp.isfinite(g32).all()
                    with jax.named_scope("mx_exchange"):
                        acc = g32 + opt_state_[f"_residual{i}"]
                        g32, new_resid = quantize_2bit(acc,
                                                       comp_threshold)
                sub = jax.random.fold_in(
                    jax.random.fold_in(key, i), idx) \
                    if opt.needs_key else None
                # bucket_shard_update casts g to the bucket dtype and
                # runs the jnp rule OR the fused Pallas kernel per the
                # "fused_bucket_opt" variant decision; on the kernel
                # arm the loss-scale finiteness verdict of the RAW f32
                # gradient rides the same VMEM pass (want_finite)
                want_fin = check_finite and comp_threshold is None
                res = _zero.bucket_shard_update(
                    b, opt, params_, g32, opt_state_[bk], t,
                    n_shards=n_sh, idx=idx, axis=data_axis,
                    seg=seg_info[i] if needs_seg else None, key=sub,
                    pallas=ps_pallas, want_finite=want_fin,
                    w_sh=w_sh_in)
                if want_fin:
                    w_sh, uw, us, bfin = res
                    # finiteness verdict on the SCATTERED shard (each
                    # device sees params/N elements; psum below makes
                    # the verdict global) — fused when the kernel ran,
                    # bit-identical jnp check otherwise
                    with jax.named_scope("mx_guard"):
                        finite = finite & (
                            bfin if bfin is not None
                            else jnp.isfinite(g32).all())
                else:
                    w_sh, uw, us = res
                staged.append((i, bk, b, w_sh, uw, us, new_resid))
            new_p, new_s = {}, {}
            if check_finite:
                with jax.named_scope("mx_guard"), \
                        jax.named_scope("mx_exchange"):
                    bad = jax.lax.psum(1 - finite.astype(jnp.int32),
                                       data_axis)
                    finite = bad == 0
            for i, bk, b, w_sh, uw, us, new_resid in staged:
                if check_finite:
                    # skip-the-update selection (dynamic scaling / nan
                    # guard): shard, state AND residual all hold
                    with jax.named_scope("mx_guard"):
                        uw = jnp.where(finite, uw, w_sh)
                        us = jax.tree_util.tree_map(
                            lambda u, o: jnp.where(finite, u, o), us,
                            opt_state_[bk])
                        if new_resid is not None:
                            new_resid = jnp.where(
                                finite, new_resid,
                                opt_state_[f"_residual{i}"])
                new_s[bk] = us
                if new_resid is not None:
                    new_s[f"_residual{i}"] = new_resid
                if stage == 3:
                    # params stay sharded: the updated shard IS the
                    # new param bucket — no gather-back (the next
                    # forward's prefetch gathers it)
                    new_p[bk] = uw
                else:
                    new_p.update(_zero.gather_bucket(b, uw, data_axis))
            with jax.named_scope("mx_exchange"):
                loss = jax.lax.pmean(lval, data_axis)
            with jax.named_scope("mx_guard"):
                if dynamic_scaling:
                    new_s["_loss_scale"] = _scale_bookkeeping(
                        finite, scale, good)
                    loss = loss / scale
                elif static_scale != 1.0:
                    loss = loss / static_scale
                if nan_guard:
                    new_s["_bad_steps"] = jnp.where(
                        finite, jnp.int32(0),
                        opt_state_["_bad_steps"] + 1)
            return loss, new_p, new_s

        if stage == 3:
            ps_p_specs = {bk: P(data_axis) for bk in bucket_keys}
        else:
            ps_p_specs = {n: P() for n in params}
        ps_s_specs = jax.tree_util.tree_map(
            lambda l: P(data_axis) if getattr(l, "ndim", 0) else P(),
            opt_state)
        step = compat_shard_map(
            ps_local_step, mesh,
            in_specs=(ps_p_specs, ps_s_specs, P(data_axis),
                      P(data_axis), P(), P()),
            out_specs=(P(), ps_p_specs, ps_s_specs))

    # ---- in-step variant autotuning (mxnet_tpu.autotune) -------------
    mesh_d = _at.mesh_desc(mesh)
    try:
        plat = jax.local_devices()[0].platform
    except Exception:
        plat = None
    _tune_level = None if autotune is None else int(autotune)
    if sample_data is not None and _at.enabled(_tune_level):
        if mesh is None:
            xs, ys = sample_data
            _at.tune_train_step(
                step, params, opt_state, jnp.asarray(xs),
                jnp.asarray(ys), jax.random.key(0),
                variant_ops=variant_ops, platform=plat, mesh=mesh_d,
                level=_tune_level)
        else:
            # in-step timing under a mesh needs sharded sample state
            # (not built yet at this point) — be loud, not silent:
            # cached winners recorded for this mesh key still apply
            import warnings

            warnings.warn(
                "make_train_step: in-step autotuning under a mesh is "
                "not yet supported; sample_data ignored (cached "
                "winners for this mesh key still apply)", stacklevel=2)

    def _scoped_step(params_, opt_state_, x, y, key, t):
        # cached winners for this program signature apply at TRACE time
        # (the scope is entered on every call; only the first traces);
        # autotune=False opts this step out entirely
        if not _at.enabled(_tune_level):
            return step(params_, opt_state_, x, y, key, t)
        with _at.program_scope(x.shape, x.dtype, platform=plat,
                               mesh=mesh_d):
            return step(params_, opt_state_, x, y, key, t)

    donate_argnums = (0, 1) if donate else ()
    if donate:
        # device_put of an already-committed array aliases it, so the
        # first donated step would delete the gluon block's own weight
        # buffers out from under it.  A jitted identity materializes
        # fresh buffers the step is then free to consume.
        params = jax.jit(lambda p: p)(params)
    if mesh is not None:
        repl = NamedSharding(mesh, P())
        batch_sharding = NamedSharding(mesh, P(data_axis))
        if ps_mode:
            # params replicate (stages 1/2) or live sharded by bucket
            # (stage 3 — the parameter-memory win); bucket
            # states + residuals live SHARDED over the data axis (the
            # ZeRO-1 memory win); scalar entries (loss-scale, bad-step
            # counters) replicate
            shard1 = NamedSharding(mesh, P(data_axis))
            p_shard = jax.tree_util.tree_map(
                lambda _: shard1 if stage == 3 else repl, params)
            opt_shard = jax.tree_util.tree_map(
                lambda l: shard1 if getattr(l, "ndim", 0) else repl,
                opt_state)
        elif param_spec is None:
            p_shard = jax.tree_util.tree_map(lambda _: repl, params)
            opt_shard = jax.tree_util.tree_map(lambda _: repl, opt_state)
        else:
            p_shard = {
                n: NamedSharding(mesh, param_spec.get(n, P()))
                for n in params
            }
            # optimizer state (per-param moments) shards like its param;
            # scalar entries (loss-scale state) replicate
            opt_shard = {
                n: jax.tree_util.tree_map(
                    lambda s, sh=p_shard.get(n, repl): sh
                    if getattr(s, "ndim", 0) else repl, opt_state[n])
                for n in opt_state
            }
        step_fn = jax.jit(
            _scoped_step,
            in_shardings=(p_shard, opt_shard, batch_sharding,
                          batch_sharding, None, None),
            out_shardings=(None, p_shard, opt_shard),
            donate_argnums=donate_argnums,
        )
        params = jax.device_put(params, p_shard)
        opt_state = jax.device_put(opt_state, opt_shard)
    else:
        step_fn = jax.jit(_scoped_step, donate_argnums=donate_argnums,
                          static_argnums=())

    # ---- telemetry: compile events + program introspection -----------
    # One host-side record per (re)trace of the fused step: the RunLog
    # diffs the fingerprint against the previous one for this program
    # to name the retrace cause (shape / dtype / autotune_winner /
    # hyper_params / sharding).  A signature seen before that recurs
    # after a change is a cache "hit" (XLA's jit cache still holds it).
    # MXNET_RUNLOG unset => current() is None => zero per-step work
    # beyond one call + dict lookup.
    from .. import profiler as _profiler
    from .. import telemetry as _tm

    _jitted_step = step_fn
    _tm_hyper = {k: v for k, v in sorted(vars(opt).items())
                 if not k.startswith("_")
                 and isinstance(v, (int, float, bool, str, type(None)))}
    # stage 2 keeps the historic "ps" stamp (it IS that program);
    # stages 1/3 trace different exchanges and must name themselves so
    # the RunLog can blame a retrace on a stage flip
    _tm_sharding = "none" if not ps_mode else (
        "ps" if stage == 2 else f"zero{stage}")
    if ps_mode:
        # ... and how much of the exchange keeps its leaves' shapes
        n_leaf, n_buckets, share = _zero.leaf_share(plan)
        _tm_sharding += (f" ({n_leaf} of {n_buckets} buckets leaf-shaped, "
                         f"{100 * share:.1f}% of the elements)")
    _tm_seen = set()
    _tm_last = [None]
    _nm_period = _nm.sample_period() if numerics_on else 0
    _nm_step = [0]
    _calls = [0]

    def step_fn(p, o, x, y, key, t, _inner=_jitted_step):
        rl = _tm.current()
        if rl is not None:
            sig = (tuple(x.shape), str(x.dtype))
            if sig not in _tm_seen or sig != _tm_last[0]:
                cache = "hit" if sig in _tm_seen else "miss"
                winners = {}
                if _at.enabled(_tune_level):
                    winners = {
                        op: _at.lookup(op, x.shape, x.dtype,
                                       platform=plat, mesh=mesh_d)
                        for op in variant_ops}
                try:
                    rl.compile_event(
                        "train_step",
                        _tm.compile_fingerprint(
                            sig[0], sig[1], True, winners=winners,
                            hyper=_tm_hyper, sharding=_tm_sharding),
                        cache=cache)
                    if cache == "miss":
                        # memory/flop/collective introspection of the
                        # program about to run — a persistent-cache
                        # disk hit when the XLA cache is enabled
                        _tm.describe_program(_inner, p, o, x, y, key,
                                             t, program="train_step")
                except Exception:
                    pass  # telemetry must never kill the step
                _tm_seen.add(sig)
                _tm_last[0] = sig
        if _profiler._jax_trace_active:
            # under mx.profiler's device trace (one attribute read
            # outside it): dumps() reads the scopes of the traced
            # operations from this program's compiled text
            noted = ("train_step", id(_inner), jnp.shape(x))
            if noted not in _profiler._programs:
                # shapes now (the step donates its arrays), the text
                # when asked
                args = jax.tree_util.tree_map(
                    lambda a: jax.ShapeDtypeStruct(
                        a.shape, a.dtype, sharding=a.sharding)
                    if isinstance(a, jax.Array) else a,
                    (p, o, x, y, key, t))
                _profiler.note_program(
                    noted,
                    lambda: _inner.lower(*args).compile().as_text())
        if ps_mode:
            # a by-bucket tree saved flat, before leaf-shaped buckets,
            # is reshaped once (or refused); its own is handed through
            o = _zero.adopt_layout(plan, o)
            if stage == 3:
                p = _zero.adopt_layout(plan, p)
        # the host span that causes this step's device work, on the
        # profiler's clock (inactive outside a profiler session)
        with _tm.tracing.region("mx_step", step_num=_calls[0]):
            result = _inner(p, o, x, y, key, t)
        _calls[0] += 1
        if numerics_on and rl is not None:
            # sampled readback of the in-graph summaries: the ONLY
            # steps that pay a device sync for the monitor.  Inside an
            # outer trace (bench's chained fori_loop) the values are
            # tracers — nothing to read, skip.
            try:
                loss_v, _, new_s = result
                vecs = new_s.get("_numerics")
                if vecs is not None and not isinstance(
                        loss_v, jax.core.Tracer):
                    i = _nm_step[0]
                    _nm_step[0] = i + 1
                    if i % _nm_period == 0:
                        _nm.emit(rl, i, vecs, where="grad")
            except Exception:
                pass  # the monitor must never kill the step
        return result

    from ..resilience import faultsim

    if faultsim.armed("step.loss_nan"):
        # fault harness only (MXNET_FAULT_SPEC names the point): armed
        # hits poison the batch with NaN BEFORE the compiled step, so
        # the in-graph guard sees a genuinely non-finite step; the
        # disarmed fast path never grows this wrapper
        inner_step = step_fn

        def step_fn(p, o, x, y, key, t, _inner=inner_step):
            if faultsim.inject("step.loss_nan") == "nan":
                # integer dtypes have no NaN — poisoning them is a
                # silent no-op, so pick the first inexact input (token
                # id models poison through their float labels)
                x, y = jnp.asarray(x), jnp.asarray(y)
                if jnp.issubdtype(x.dtype, jnp.inexact):
                    x = x * jnp.asarray(jnp.nan, x.dtype)
                elif jnp.issubdtype(y.dtype, jnp.inexact):
                    y = y * jnp.asarray(jnp.nan, y.dtype)
                else:
                    import warnings

                    warnings.warn(
                        "step.loss_nan injection skipped: neither x "
                        "nor y has an inexact dtype to poison",
                        stacklevel=2)
            return _inner(p, o, x, y, key, t)

    if step_fn is not _jitted_step:
        # the telemetry/fault wrappers are plain functions; callers
        # introspecting the program (bench.py, the multichip dryrun)
        # still need jit's lower() — same XLA program either way
        step_fn.lower = _jitted_step.lower
    if ps_mode:
        # the layout contract for checkpointing/eval callers: under
        # stage 3 the params pytree is by bucket, and
        # zero.gather_stage3_params(step_fn.zero_plan, params)
        # reassembles the named tree; zero_layout says which buckets
        # keep their leaf's shape: [(key, "leaf" | "flat", elements)]
        step_fn.zero_stage = stage
        step_fn.zero_plan = plan
        step_fn.zero_layout = _zero.bucket_layout(plan)

    return step_fn, params, opt_state


class DataParallelTrainer:
    """High-level fused data-parallel training driver.

    The TPU-native replacement for Module+DataParallelExecutorGroup+
    KVStore: one object owning the sharded params/opt state and a
    compiled SPMD step.  Call ``fit_batch(x, y)`` per batch;
    ``sync_to_block()`` writes weights back into the Gluon block for
    checkpointing/eval via the normal APIs.
    """

    def __init__(self, block, loss_fn, optimizer="sgd", mesh=None,
                 **opt_kwargs):
        self._block = block
        self._mesh = mesh
        self._step_fn, self._params, self._opt_state = make_train_step(
            block, loss_fn, optimizer=optimizer, mesh=mesh, **opt_kwargs)
        self._t = 0
        self._key = jax.random.key(0)

    def fit_batch(self, x, y):
        x = x._data if isinstance(x, nd.NDArray) else jnp.asarray(x)
        y = y._data if isinstance(y, nd.NDArray) else jnp.asarray(y)
        self._t += 1
        self._key, sub = jax.random.split(self._key)
        loss, self._params, self._opt_state = self._step_fn(
            self._params, self._opt_state, x, y, sub, float(self._t))
        return loss

    @property
    def params(self):
        return self._params

    def sync_to_block(self):
        from ..gluon.block import _collect_all_params

        params = self._params
        if getattr(self._step_fn, "zero_stage", None) == 3:
            # stage-3 params live as flat bucket shards: reassemble
            # the named tree (host_gather handles the multi-process
            # world where no single host holds a whole bucket)
            from ..resilience.elastic import host_gather

            params = zero.gather_stage3_params(
                self._step_fn.zero_plan,
                {k: host_gather(v) for k, v in params.items()})
        for p in _collect_all_params(self._block):
            if p.name in params:
                # gather off the mesh so eager single-device ops work
                v = jnp.asarray(onp.asarray(params[p.name]))
                p.data()._adopt(v)


from . import moe, pipeline, ring, zero  # noqa: E402  (submodule
#                                           re-exports)
from .predict import make_predict_fn, tune_microbatch  # noqa: E402
