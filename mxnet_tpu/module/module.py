"""Module: executor-backed trainable module.

Reference parity: python/mxnet/module/module.py (``Module`` :40 over
``DataParallelExecutorGroup``).  TPU-native: ONE executor, ONE compiled
SPMD program.  ``context=[gpu(0)..gpu(N-1)]`` builds a 1-D 'data' mesh
over those chips: batch args shard over it, params/aux replicate, and
XLA inserts the gradient all-reduce — the reference's
DataParallelExecutorGroup (executor_group.py:144 batch slicing, :304
grad reduce) collapses into sharding annotations.  BatchNorm under the
mesh computes GLOBAL batch stats (collectives inside the jitted graph),
i.e. SyncBatchNorm semantics — stricter than the reference's per-device
stats.
"""
from __future__ import annotations

import logging

import numpy as onp

from .. import initializer as init_mod
from .. import ndarray as nd
from .. import optimizer as opt
from ..base import MXNetError
from ..context import cpu
from .base_module import BaseModule

__all__ = ["Module"]


class Module(BaseModule):
    def __init__(self, symbol, data_names=("data",),
                 label_names=("softmax_label",), logger=logging,
                 context=None, work_load_list=None, fixed_param_names=None,
                 state_names=None, group2ctxs=None,
                 compression_params=None):
        super().__init__(logger=logger)
        self._symbol = symbol
        self._data_names = list(data_names) if data_names else []
        self._label_names = list(label_names) if label_names else []
        self._context = context or cpu()
        self._mesh = None
        if isinstance(self._context, (list, tuple)):
            ctxs = list(self._context)
            self._context = ctxs[0]
            if len(ctxs) > 1:
                import jax
                from jax.sharding import Mesh

                devs = [c.jax_device() for c in ctxs]
                if len(set(devs)) != len(devs):
                    raise MXNetError(
                        f"context list {ctxs} resolves to duplicate "
                        "devices — data parallelism needs distinct chips")
                self._mesh = Mesh(onp.array(devs), ("data",))
        self._fixed_param_names = set(fixed_param_names or [])
        arg_names = symbol.list_arguments()
        self._param_names = [
            n for n in arg_names
            if n not in self._data_names and n not in self._label_names
        ]
        self._aux_names = symbol.list_auxiliary_states()
        self._exec = None
        self._data_shapes = None
        self._label_shapes = None
        self._optimizer = None
        self._updater = None
        self._arg_params = None  # preloaded checkpoint weights (load())
        self._aux_params = None
        self._grad_req = None
        self._monitor = None
        # reference group2ctxs: one group->ctx dict per data-parallel
        # context; the TPU Module runs ONE executor, so a single dict
        # (or a 1-element list of dicts) maps groups to devices
        if isinstance(group2ctxs, (list, tuple)):
            if len(group2ctxs) > 1:
                raise MXNetError(
                    "group2ctxs: the TPU Module is one SPMD executor — "
                    "pass one group->Context dict (data parallelism "
                    "comes from context=[...], not per-ctx groups)")
            group2ctxs = group2ctxs[0] if group2ctxs else None
        self._group2ctx = group2ctxs

    # ------------------------------------------------------- descriptors
    @property
    def data_names(self):
        return self._data_names

    @property
    def label_names(self):
        return self._label_names

    @property
    def output_names(self):
        return self._symbol.list_outputs()

    @property
    def data_shapes(self):
        return self._data_shapes

    @property
    def label_shapes(self):
        return self._label_shapes

    @property
    def output_shapes(self):
        self._check_binded()
        shape_kwargs = {n: tuple(s) for n, s in self._data_shapes}
        if self._label_shapes:
            shape_kwargs.update(
                {n: tuple(s) for n, s in self._label_shapes})
        _, out_shapes, _ = self._symbol.infer_shape_partial(
            **shape_kwargs)
        return list(zip(self._symbol.list_outputs(), out_shapes))

    # ------------------------------------------------------------- bind
    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False,
             shared_module=None, grad_req="write"):
        if self.binded and not force_rebind:
            return
        # persistent compilation cache (no-op unless
        # JAX_COMPILATION_CACHE_DIR is set): a re-bind of a shape
        # already compiled — the common restart/recapture path — loads
        # the XLA executable from disk instead of recompiling
        from ..config import setup_compilation_cache

        setup_compilation_cache()
        self.for_training = for_training
        self._data_shapes = [(d[0], tuple(d[1])) for d in data_shapes]
        self._label_shapes = ([(d[0], tuple(d[1]))
                               for d in label_shapes]
                              if label_shapes else None)
        shape_kwargs = {}
        for desc in data_shapes:
            name, shape = desc[0], desc[1]
            shape_kwargs[name] = tuple(shape)
        if label_shapes:
            for desc in label_shapes:
                name, shape = desc[0], desc[1]
                shape_kwargs[name] = tuple(shape)
        req = {}
        for n in self._symbol.list_arguments():
            if n in self._data_names:
                req[n] = "write" if inputs_need_grad else "null"
            elif n in self._label_names or n in self._fixed_param_names:
                req[n] = "null"
            else:
                req[n] = grad_req if for_training else "null"
        self._grad_req = req
        self._exec = self._symbol.simple_bind(
            self._context, grad_req=req, group2ctx=self._group2ctx,
            **shape_kwargs)
        if self._mesh is not None:
            if self._group2ctx:
                raise MXNetError("group2ctxs cannot combine with a "
                                 "multi-context data mesh")
            self._place_on_mesh()
        self.binded = True
        if self._monitor is not None:
            self._monitor.install(self._exec)
        if shared_module is not None and shared_module._exec is not None:
            # share the actual parameter NDArray objects (reference:
            # shared_exec memory pool, bucketing_module.py) — an update
            # through any bucket is visible to all
            for n in self._param_names:
                if n in shared_module._exec.arg_dict:
                    self._exec.arg_dict[n] = \
                        shared_module._exec.arg_dict[n]
            for n in self._aux_names:
                if n in shared_module._exec.aux_dict:
                    self._exec.aux_dict[n] = \
                        shared_module._exec.aux_dict[n]
            self._exec.arg_arrays = [
                self._exec.arg_dict[n]
                for n in self._symbol.list_arguments()]
            self._exec.aux_arrays = [
                self._exec.aux_dict[n] for n in self._aux_names]
            if shared_module.params_initialized:
                self.params_initialized = True
        if self._arg_params is not None:
            # apply weights preloaded by Module.load (reference: load
            # stashes arg/aux params and bind installs them)
            self.init_params(arg_params=self._arg_params,
                             aux_params=self._aux_params,
                             force_init=True, allow_missing=True)

    # ------------------------------------------------------ mesh support
    def _data_sharding(self):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        return NamedSharding(self._mesh, P("data"))

    def _place_on_mesh(self):
        """Replicate params/aux/grads over the data mesh; batch args
        shard at feed time (reference: executor_group.py:144 slices the
        batch across contexts — here the sharding annotation does it)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        repl = NamedSharding(self._mesh, P())
        batch_names = set(self._data_names) | set(self._label_names)
        for store in (self._exec.arg_dict, self._exec.aux_dict,
                      self._exec.grad_dict):
            for n, v in store.items():
                if n in batch_names:
                    continue
                v._data = jax.device_put(v._data, repl)

    def _shard_batch(self, name, arr):
        import jax

        n_dev = self._mesh.devices.size
        if arr.shape[0] % n_dev:
            raise MXNetError(
                f"batch axis of '{name}' ({arr.shape[0]}) must divide "
                f"the {n_dev}-device data mesh")
        return jax.device_put(arr, self._data_sharding())

    # ----------------------------------------------------------- params
    def init_params(self, initializer=None, arg_params=None,
                    aux_params=None, allow_missing=False, force_init=False,
                    allow_extra=False):
        self._check_binded()
        if self.params_initialized and not force_init:
            return
        if initializer is None and (arg_params is None
                                    or aux_params is None):
            initializer = init_mod.Uniform(0.01)
        for name in self._param_names:
            arr = self._exec.arg_dict[name]
            if arg_params is not None and name in arg_params:
                arr._adopt(self._as_jax(arg_params[name], arr))
            elif initializer is not None:
                val = initializer(init_mod.InitDesc(name), arr.shape,
                                  str(arr.dtype))
                arr._adopt(nd.array(onp.asarray(val))._data)
            elif not allow_missing:
                raise MXNetError(f"missing parameter {name}")
        for name in self._aux_names:
            arr = self._exec.aux_dict[name]
            if aux_params is not None and name in aux_params:
                arr._adopt(self._as_jax(aux_params[name], arr))
            elif initializer is not None:
                val = initializer(init_mod.InitDesc(name), arr.shape,
                                  str(arr.dtype))
                arr._adopt(nd.array(onp.asarray(val))._data)
        if self._mesh is not None:
            # _adopt swapped in host-placed arrays; restore replication
            self._place_on_mesh()
        self.params_initialized = True

    @staticmethod
    def _as_jax(v, like):
        if isinstance(v, nd.NDArray):
            return v._data.astype(like._data.dtype)
        return nd.array(onp.asarray(v))._data.astype(like._data.dtype)

    def get_params(self):
        self._check_binded()
        arg = {n: self._exec.arg_dict[n].copy()
               for n in self._param_names}
        aux = {n: self._exec.aux_dict[n].copy() for n in self._aux_names}
        return arg, aux

    # -------------------------------------------------------- optimizer
    def _update_param_names(self):
        """Parameters the optimizer actually updates (grad_req not
        'null' and a gradient buffer exists) — the set the sharded
        bucket plan must cover exactly."""
        return [n for n in self._param_names
                if self._grad_req.get(n, "null") != "null"
                and self._exec.grad_dict.get(n) is not None]

    def _resolve_optimizer_sharding(self, kvstore, optimizer):
        """Map ``kvstore='dist_*'`` (whose reference semantics ARE the
        server-side optimizer on key shards, kvstore_dist_server.h:346)
        to the sharded-server updater over this module's data mesh.
        MXNET_ZERO_STAGE overrides in both directions (0 off; 1/2/3 on:
        the updater is ZeRO-1 whatever the stage).
        Per-param lr_mult/wd_mult ARE supported (the updater
        partitions buckets by effective (lr, wd)); semantics the flat
        buckets cannot reproduce — per-update lr schedules, stochastic
        rules, multi-precision masters, fused/eager state-layout
        mismatches — fall back to the eager per-param Updater with a
        logged reason."""
        from ..parallel.zero import resolve_stage, sharding_rule_reasons

        kv_name = kvstore if isinstance(kvstore, str) else \
            getattr(kvstore, "type", "")
        # one device: nothing to shard over, whatever the kvstore says
        asked = "ps" if str(kv_name).startswith("dist") \
            and self._mesh is not None else None
        if resolve_stage(asked, None, self._mesh) is None:
            return None
        reasons = sharding_rule_reasons(optimizer)
        if reasons:
            self.logger.warning(
                "optimizer sharding requested (kvstore=%r) but falling "
                "back to the replicated updater: %s", kv_name,
                "; ".join(reasons))
            return None
        return "ps"

    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        self._check_binded()
        if self.optimizer_initialized and not force_init:
            return
        if isinstance(optimizer, str):
            # key optimizer state by parameter NAME so the updater can be
            # shared across buckets whose graphs order params differently
            idx2name = {n: n for n in self._param_names}
            opt_params = dict(optimizer_params)
            if "rescale_grad" not in opt_params:
                # reference module.py: default grad rescale is 1/batch
                batch_size = self._exec.arg_dict[
                    self._data_names[0]].shape[0]
                opt_params["rescale_grad"] = 1.0 / batch_size
            optimizer = opt.create(
                optimizer, param_idx2name=idx2name, **opt_params)
        self._optimizer = optimizer
        if self._resolve_optimizer_sharding(kvstore, optimizer) == "ps":
            # ZeRO-1: optimizer state sharded over the data mesh in
            # flat buckets, updates run on the owned shard only, params
            # all-gather back (parallel.zero; the dist_sync
            # server-side-optimizer analog)
            from ..parallel.zero import ShardedBucketUpdater

            upd = {n: self._exec.arg_dict[n]._data
                   for n in self._update_param_names()}
            self._updater = ShardedBucketUpdater(optimizer, self._mesh,
                                                 upd)
        else:
            self._updater = opt.get_updater(optimizer)
        from .. import telemetry
        from ..parallel.zero import ShardedBucketUpdater as _SBU

        rl = telemetry.current()
        if rl is not None:
            # sticky context: every later step record carries the
            # optimizer-sharding mode actually in effect
            rl.set_context(sharding="ps" if isinstance(
                self._updater, _SBU) else "none")
        self.optimizer_initialized = True

    # ------------------------------------------------------------- exec
    def forward(self, data_batch, is_train=None):
        self._check_binded()
        if is_train is None:
            is_train = self.for_training
        feeds = {}
        for name, arr in zip(self._data_names, data_batch.data):
            feeds[name] = arr
        if data_batch.label is not None and self._label_names:
            for name, arr in zip(self._label_names, data_batch.label):
                feeds[name] = arr
        if self._mesh is not None:
            for name, arr in feeds.items():
                v = arr._data if isinstance(arr, nd.NDArray) else \
                    nd.array(onp.asarray(arr))._data
                feeds[name] = nd.NDArray(self._shard_batch(name, v))
        # rebind on shape change (reference module reshapes executors)
        for k, v in feeds.items():
            if tuple(self._exec.arg_dict[k].shape) != tuple(v.shape):
                self._exec = self._exec.reshape(
                    **{k2: tuple(v2.shape) for k2, v2 in feeds.items()})
                break
        self._exec.forward(is_train=is_train, **feeds)

    def backward(self, out_grads=None):
        self._check_binded()
        self._exec.backward(out_grads=out_grads)

    def update(self):
        self._check_binded()
        assert self.optimizer_initialized
        from ..parallel.zero import ShardedBucketUpdater

        if isinstance(self._updater, ShardedBucketUpdater):
            # one fused sharded program over ALL params (per-name calls
            # would defeat the flat bucketing)
            self._updater.update_all(
                [(n, self._exec.grad_dict[n], self._exec.arg_dict[n])
                 for n in self._update_param_names()])
            return
        for name in self._update_param_names():
            self._updater(name, self._exec.grad_dict[name],
                          self._exec.arg_dict[name])

    def update_metric(self, eval_metric, labels, pre_sliced=False):
        eval_metric.update(labels, self.get_outputs())

    def get_outputs(self, merge_multi_context=True):
        self._check_binded()
        return self._exec.outputs

    def get_input_grads(self, merge_multi_context=True):
        self._check_binded()
        return [self._exec.grad_dict.get(n) for n in self._data_names]

    # --------------------------------------------------------------- io
    def save_checkpoint(self, prefix, epoch, save_optimizer_states=False,
                        keep_n=None):
        """One atomic checkpoint version via resilience.checkpoint:
        params, optional optimizer state, symbol, CRC manifest and the
        `latest` pointer land together or not at all (legacy
        `prefix-NNNN.params`/`.states` layout preserved)."""
        from ..resilience.checkpoint import CheckpointManager

        arg_params, aux_params = self.get_params()
        states = None
        if save_optimizer_states:
            states = self._get_optimizer_states()
        CheckpointManager(prefix, keep_n=keep_n).save(
            epoch, symbol=self._symbol, arg_params=arg_params,
            aux_params=aux_params, optimizer_states=states)

    def _step_finite(self):
        """Outputs AND gradients: finite predictions can still carry a
        non-finite gradient (log(0) in the loss backward), and the
        guard's whole point is that such a step must not update."""
        if not self._outputs_finite():
            return False
        for name in self._update_param_names():
            g = self._exec.grad_dict[name]
            if not onp.isfinite(g.asnumpy()).all():
                return False
        return True

    def _named_grads(self):
        """The live gradient buffers by parameter name — the numerics
        monitor's (MXNET_NUMERICS) eager observation point: fit
        summarises these on sampled and bad steps so a NaN step names
        the tensor that went non-finite."""
        return {n: self._exec.grad_dict[n]
                for n in self._update_param_names()}

    def _topology_block(self):
        """The world this module trains in, for the checkpoint
        manifest's ``topology`` stamp: data-mesh width, process count,
        optimizer-sharding mode, the live bucket-plan fingerprint and
        the GLOBAL batch (local batch x process count — the unit the
        resume cursor is kept in, so it re-slices across any world)."""
        from ..parallel.zero import ShardedBucketUpdater
        from ..resilience import elastic

        upd = self._updater if isinstance(
            self._updater, ShardedBucketUpdater) else None
        global_batch = None
        try:
            local_b = int(
                self._exec.arg_dict[self._data_names[0]].shape[0])
            ctx = elastic.context()
            global_batch = local_b * (ctx.num_processes
                                      if ctx is not None else 1)
        except Exception:
            pass
        return elastic.topology_block(
            world_size=upd.n_shards if upd is not None else None,
            mesh=self._mesh,
            sharding="ps" if upd is not None else "none",
            plan=upd.plan if upd is not None else None,
            global_batch=global_batch)

    # optimizer-state hooks for fit's checkpoint/resume plumbing
    def _get_optimizer_states(self):
        if self._updater is None:
            raise MXNetError("optimizer not initialized")
        # dump_optimizer=True: the pickle carries the optimizer with
        # its update COUNTERS (num_update/_index_update_count — and the
        # sharded updater seeds them from its own step count), so a
        # resumed adam/ftml run continues its bias correction at the
        # right t in EITHER mode instead of silently restarting at 1.
        # Both Updater.set_states and ShardedBucketUpdater.set_states
        # accept the (states, optimizer) tuple form.
        return self._updater.get_states(dump_optimizer=True)

    def _set_optimizer_states(self, states):
        if self._updater is None:
            raise MXNetError("optimizer not initialized")
        self._updater.set_states(states)
        # a dump_optimizer pickle makes set_states install the
        # unpickled optimizer as the updater's live one; re-point the
        # module at it so post-resume mutations (the lr-decay callback
        # recipe: module._optimizer.lr = ...) reach the optimizer that
        # actually runs, not a dead pre-resume object
        live = getattr(self._updater, "optimizer", None)
        if live is not None:
            self._optimizer = live

    @staticmethod
    def load(prefix, epoch, load_optimizer_states=False, **kwargs):
        from .. import model

        sym, arg_params, aux_params = model.load_checkpoint(prefix, epoch)
        mod = Module(symbol=sym, **kwargs)
        mod._arg_params, mod._aux_params = arg_params, aux_params
        return mod

    def install_monitor(self, mon):
        """Attach a ``mx.monitor.Monitor`` to this module's executor
        (reference module.py install_monitor -> executor monitor
        callback): every forward records output stats under the
        monitor's tic/toc protocol.  Installs now if bound, else at
        bind."""
        self._monitor = mon
        if self._exec is not None:
            mon.install(self._exec)
