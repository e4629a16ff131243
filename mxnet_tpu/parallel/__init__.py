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

import functools

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
#: policy the reference encodes in contrib/amp/lists: norm affine+stats),
#: and with them what a state-space mixer and a router of experts keep
#: in float32: a decay's ``A_log``, ``dt_bias`` and skip ``D``, the
#: router's weights and its correction bias (gluon/nn/sequence_layers.py)
NORM_STAT_SUFFIXES = ("gamma", "beta", "running_mean", "running_var",
                      "moving_mean", "moving_var", "A_log", "dt_bias", "_D",
                      "router_weight", "correction_bias")


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


def _default_variant_ops(compute_dtype):
    """The race's roster: the conv 1x1 lowering always; the dtype ladder
    only when the knob arms it, no explicit compute_dtype pins the
    answer, and the env carries no hand override (racing a pinned step
    would waste a compile per signature)."""
    from .. import autotune as _at

    if (compute_dtype is None and _at.dtype_ladder_armed()
            and _at.variant_choice("dtype_ladder") is None):
        return ("conv1x1_dot", "dtype_ladder")
    return ("conv1x1_dot",)


def _compression_threshold(gradient_compression, stage):
    if gradient_compression is None:
        return None
    if stage is None:
        raise MXNetError(
            "gradient_compression in make_train_step requires "
            "optimizer_sharding='ps' (the replicated step has no "
            "bucketed wire to compress)")
    ctype = gradient_compression.get("type", "2bit")
    if ctype != "2bit":
        raise MXNetError(f"unsupported compression {ctype}")
    return float(gradient_compression.get("threshold", 0.5))


def _declared_counters(block):
    """``{name: "sum" | "max"}`` of what the blocks of ``block`` count in
    a forward pass (a block's ``step_counters``), by name."""
    found = dict(getattr(block, "step_counters", {}))
    for child in block._children.values():
        found.update(_declared_counters(child))
    return dict(sorted(found.items()))


def _release_block_state(block):
    """Move the arrays that ``block``'s parameters hold on an accelerator,
    data and gradient buffers, to the host's memory.  The compiled step
    trains its own copy (``params``, ``opt_state``); the block's stays a
    valid, stale copy that ``sync_to_block``/``set_data`` overwrite, and
    no longer takes a parameter's worth of bytes twice over on the chip
    (for a net initialised on the accelerator: 8 bytes a parameter)."""
    from ..gluon.block import _collect_all_params

    host = jax.local_devices(backend="cpu")[0]
    for p in _collect_all_params(block):
        held = p._data
        for arr in (held, getattr(held, "_grad", None)):
            value = getattr(arr, "_data", None)
            if isinstance(value, jax.Array) and not isinstance(
                    value, jax.core.Tracer) and any(
                        d.platform != "cpu" for d in value.devices()):
                arr._adopt(jax.device_put(value, host))


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

    Forward, backward and optimizer compile into a single XLA program
    (``parallel.train_step.step_body``).  Under a mesh, x/y shard on the
    batch axis and params replicate (or shard per ``param_spec``, tp);
    XLA inserts the gradient all-reduce.

    optimizer: a registry name ('sgd', 'adam', 'lars', ...) or an
    Optimizer instance; its pure ``fused_update`` rule is traced in.

    loss_scale: None, a static float, or 'dynamic' (doubles every 2000
    consecutive finite steps, halves on overflow and skips that update).

    donate=True donates params/opt_state to XLA: the inputs are dead
    after the call, thread the returned ones.  Either way the returned
    ``params`` are the training state from then on: the block's own
    arrays and gradient buffers are moved off the accelerator to the
    host's memory (``_release_block_state``), where they stay a valid,
    stale copy until ``sync_to_block`` or ``set_data`` writes them.

    What the net's blocks count in a forward pass (a block's
    ``step_counters``, ``profiler.count``) leaves the step in
    ``opt_state["_counters"]``; ``profiler.step_counters()`` reads the
    newest step's.

    sample_data=(x, y): races each op of ``variant_ops`` inside THIS step
    on the sample batch (mxnet_tpu.autotune); the winner persists and the
    returned step traces under it.  autotune=None follows
    MXNET_AUTOTUNE, False opts out; cached winners apply without
    sample_data.  Under a mesh it warns and is ignored.

    nan_guard: a step whose loss or any gradient is non-finite leaves
    params and optimizer state untouched, and ``opt_state['_bad_steps']``
    counts CONSECUTIVE bad steps, for the host to enforce
    MXNET_BAD_STEP_LIMIT with no per-step sync.  None follows that env
    var (>0 arms it); off under dynamic scaling, which skips already.

    optimizer_sharding="ps" / zero_stage=1|2|3: the sharded-server
    exchange (``parallel.zero``) over the mesh's data axis; "ps" is
    stage 2, and ``zero.resolve_stage`` is where the keywords and
    MXNET_ZERO_STAGE meet.  Does not compose with ``param_spec``.
    Gradients travel in dtype-homogeneous buckets (``bucket_bound``
    elements, default MXNET_KVSTORE_BIGARRAY_BOUND): several leaves
    packed flat, or ONE leaf in its own shape where its rows divide into
    whole tiles (``step_fn.zero_layout``).  The rule updates only the
    locally-owned shard; ``opt_state`` is by bucket and lives sharded.
    Stage 1 sums each bucket and slices its shard off, stage 2
    reduce-scatters it, both gather the updated shards back; both send
    a leaf-shaped bucket round the axis hop by hop instead
    (``zero.ring_reduce_scatter`` / ``ring_gather``: asynchronous hops
    that run under the backward pass).  Stage 3 shards the params too:
    the params pytree is ``{"_bucket<i>": array}``
    (``zero.gather_stage3_params(step_fn.zero_plan, params)`` names it
    again).  Stages 1 and 2 end bit for bit where each other does;
    stage 3 too where every bucket is flat, and within float32 rounding
    of them where a bucket rides the ring (its sum's order).  A
    by-bucket tree saved with every entry 1-D is reshaped once
    (``zero.adopt_layout``).  Each device's forward sees its local batch
    shard, so BatchNorm statistics are per shard, where the replicated
    step's are global.  What a chip runs for it: PERF.md section 5.

    gradient_compression: ``{"type": "2bit", "threshold": t}``, on each
    bucket's gradient shard, the error-feedback residual shard-local in
    fp32 in ``opt_state['_residual<i>']``.  Sharded exchange only.
    """
    import warnings

    from .. import autotune as _at
    from ..config import get_env, setup_compilation_cache
    from ..telemetry import numerics as _nm
    from . import train_step as _ts

    setup_compilation_cache()
    params, apply_fn = functionalize(block, train=True)
    if mesh is None:
        # commit params to the accelerator once; otherwise every step
        # re-streams them host->HBM (Context default is cpu for reference
        # parity, but the fused step must live in device memory)
        params = jax.device_put(params, jax.local_devices()[0])
    opt = _build_optimizer(optimizer, learning_rate, momentum, wd, beta1,
                           beta2, epsilon, opt_kwargs)
    if variant_ops is None:
        variant_ops = _default_variant_ops(compute_dtype)

    # ---- the mode: loss scale, guard, ZeRO stage ---------------------
    dynamic = loss_scale == "dynamic"
    static_scale = None if dynamic or loss_scale is None \
        or float(loss_scale) == 1.0 else float(loss_scale)
    if nan_guard is None:
        nan_guard = get_env("MXNET_BAD_STEP_LIMIT") > 0
    nan_guard = bool(nan_guard) and not dynamic
    stage = zero.resolve_stage(optimizer_sharding, zero_stage, mesh,
                               param_spec)
    # ---- the holes (ROADMAP D13): one gate each, in front of the body.
    # The sharded exchange's gradients are bucket shards, not named
    # tensors: the fp8 rung and the numerics monitor stay off there.
    threshold = _compression_threshold(gradient_compression, stage)
    fp8_rung = (compute_dtype is None and _at.dtype_ladder_armed()
                and "fp8" in _at.ladder_rungs() and stage is None)
    numerics_on = _nm.armed()
    if numerics_on and stage is not None:
        warnings.warn(
            "MXNET_NUMERICS under optimizer_sharding='ps' is not "
            "supported yet (gradients live as scattered bucket "
            "shards, not named tensors) — monitor disabled for this "
            "step", stacklevel=2)
        numerics_on = False

    # ---- the exchange and the state it lays out ----------------------
    if stage is None:
        ex = _ts.ReplicatedExchange(opt, dynamic)
    else:
        ex = _ts.ShardedExchange(opt, stage, mesh, data_axis, bucket_bound,
                                 threshold)
    params, opt_state = ex.init_state(params)
    if dynamic:
        opt_state["_loss_scale"] = (
            jnp.float32(2.0 ** 16),  # initial scale (reference amp)
            jnp.zeros((), jnp.int32),  # consecutive-finite counter
        )
    if nan_guard:
        opt_state["_bad_steps"] = jnp.zeros((), jnp.int32)
    if fp8_rung:
        # provisioned whenever the armed roster names fp8: the race's
        # fp8 arm and a cached fp8 winner both need it in the SAME
        # opt_state pytree the other arms thread through
        opt_state["_fp8"] = _ts.fp8_state(params)
    if numerics_on:
        # per-gradient summaries ride in the returned state; HostStep
        # reads them back on sampled steps only
        opt_state["_numerics"] = _nm.summary_template(
            dict.fromkeys([*params, "__loss"]))

    counters = _declared_counters(block)
    if counters:
        # like _numerics: the template keeps the state's tree the same
        # from the first call on
        opt_state["_counters"] = {k: jnp.zeros((), jnp.float32)
                                  for k in counters}

    # ---- the step ----------------------------------------------------
    cfg = _ts.StepConfig(
        *_ts.make_loss_of(apply_fn, loss_fn, compute_dtype), dynamic,
        static_scale, nan_guard, fp8_rung, numerics_on, counters)
    shardings = None if mesh is None else ex.shardings(
        mesh, params, opt_state, param_spec)
    step = ex.wrap(functools.partial(_ts.step_body, cfg), shardings)

    # ---- in-step variant autotuning (mxnet_tpu.autotune) -------------
    mesh_d = _at.mesh_desc(mesh)
    plat = jax.local_devices()[0].platform
    tune_level = None if autotune is None else int(autotune)
    if sample_data is not None and _at.enabled(tune_level):
        if mesh is None:
            xs, ys = sample_data
            _at.tune_train_step(
                step, params, opt_state, jnp.asarray(xs),
                jnp.asarray(ys), jax.random.key(0),
                variant_ops=variant_ops, platform=plat, mesh=mesh_d,
                level=tune_level)
        else:
            # in-step timing under a mesh needs sharded sample state
            # (not built yet at this point) — be loud, not silent
            warnings.warn(
                "make_train_step: in-step autotuning under a mesh is "
                "not yet supported; sample_data ignored (cached "
                "winners for this mesh key still apply)", stacklevel=2)

    def _scoped_step(params_, opt_state_, x, y, key, t):
        # cached winners for this program signature apply at TRACE time
        # (the scope is entered on every call; only the first traces);
        # autotune=False opts this step out entirely
        if not _at.enabled(tune_level):
            return step(params_, opt_state_, x, y, key, t)
        with _at.program_scope(x.shape, x.dtype, platform=plat,
                               mesh=mesh_d):
            return step(params_, opt_state_, x, y, key, t)

    # ---- jit and the shardings ---------------------------------------
    donate_argnums = (0, 1) if donate else ()
    if donate:
        # device_put of an already-committed array aliases it, so the
        # first donated step would delete the gluon block's own weight
        # buffers out from under it.  A jitted identity materializes
        # fresh buffers the step is then free to consume.
        params = jax.jit(lambda p: p)(params)
    if mesh is not None:
        p_shard, opt_shard = shardings
        batch_sharding = NamedSharding(mesh, P(data_axis))
        jitted = jax.jit(
            _scoped_step,
            in_shardings=(p_shard, opt_shard, batch_sharding,
                          batch_sharding, None, None),
            out_shardings=(None, p_shard, opt_shard),
            donate_argnums=donate_argnums,
        )
        params = jax.device_put(params, p_shard)
        opt_state = jax.device_put(opt_state, opt_shard)
    else:
        jitted = jax.jit(_scoped_step, donate_argnums=donate_argnums)

    # the step's state is the training state from here on: the block's
    # own copy of it, and its gradient buffers, leave the accelerator
    _release_block_state(block)

    # ---- the host's side ---------------------------------------------
    step_fn = _ts.HostStep(jitted, opt, ex,
                           (variant_ops, tune_level, plat, mesh_d),
                           numerics_on)
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
