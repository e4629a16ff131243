"""Block / HybridBlock — the Gluon imperative layer API.

Reference parity: python/mxnet/gluon/block.py (``Block`` :228 with child
registry + param collection, ``HybridBlock`` :838 whose ``hybridize()``
:1039 builds a ``CachedOp`` :969 executing the traced graph).

TPU-native redesign: ``hybridize()`` wraps the block's forward in
``jax.jit``.  The jitted callable takes (params..., inputs..., prng key)
as explicit jax arrays and is differentiated as ONE tape node via
``jax.vjp`` — exactly the role of the reference's ``_CachedOp`` node in
autograd (src/imperative/cached_op.cc:1023/:1249).  ``static_alloc`` maps
to buffer donation; ``static_shape`` is implicit (XLA recompiles per
shape signature, cached — reference CachedOp re-infers shapes per call).
"""
from __future__ import annotations

import re
import threading
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as onp

from .. import _rng, autograd
from .. import ndarray as nd
from ..base import MXNetError
from ..context import current_context
from .parameter import DeferredInitializationError, Parameter, ParameterDict

__all__ = ["Block", "HybridBlock", "SymbolBlock"]


class _SuppressHooks(threading.local):
    """Set during internal forward passes (deferred-shape resolution) so
    user-registered hooks only observe real user-initiated forwards."""

    def __init__(self):
        self.flag = False


_suppress_hooks = _SuppressHooks()


class _BlockScope(threading.local):
    """Name-scope manager producing reference-compatible prefixes."""

    _current = threading.local()

    def __init__(self, block):
        self._block = block
        self._counter = {}
        self._old_scope = None

    @staticmethod
    def create(prefix, params, hint):
        current = getattr(_BlockScope._current, "value", None)
        if current is None:
            if prefix is None:
                prefix = _name_mgr().get(hint) + "_"
            if params is None:
                params = ParameterDict(prefix)
            else:
                params = ParameterDict(params.prefix, params)
            return prefix, params
        if prefix is None:
            count = current._counter.get(hint, 0)
            prefix = f"{hint}{count}_"
            current._counter[hint] = count + 1
        if params is None:
            parent = current._block.params
            params = ParameterDict(parent.prefix + prefix, parent._shared)
        else:
            params = ParameterDict(params.prefix, params)
        return current._block.prefix + prefix, params

    def __enter__(self):
        if self._block._empty_prefix:
            return self
        self._old_scope = getattr(_BlockScope._current, "value", None)
        _BlockScope._current.value = self
        return self

    def __exit__(self, ptype, value, trace):
        if self._block._empty_prefix:
            return
        _BlockScope._current.value = self._old_scope


class _NameManager(threading.local):
    def __init__(self):
        self._counter = {}

    def get(self, hint):
        count = self._counter.get(hint, 0)
        self._counter[hint] = count + 1
        return f"{hint}{count}"


_NM = _NameManager()


def _name_mgr():
    return _NM


def _flatten_to_nd(args):
    """Flatten nested (list/tuple) structure of NDArrays; returns flat list
    and a treedef-like spec for unflatten."""
    flat, fmt = [], []
    for a in args:
        if isinstance(a, nd.NDArray):
            flat.append(a)
            fmt.append(-1)
        elif isinstance(a, (list, tuple)):
            sub_flat, sub_fmt = _flatten_to_nd(a)
            flat.extend(sub_flat)
            fmt.append((len(sub_flat), sub_fmt, isinstance(a, tuple)))
        else:
            flat.append(a)
            fmt.append(-2)
    return flat, fmt


def _unflatten(flat, fmt):
    out = []
    i = 0
    for f in fmt:
        if f == -1 or f == -2:
            out.append(flat[i])
            i += 1
        else:
            n, sub_fmt, is_tuple = f
            sub, _ = _unflatten(flat[i : i + n], sub_fmt), None
            out.append(tuple(sub[0]) if is_tuple else sub[0])
            i += n
    return out, None


class Block:
    """Base class for all layers/models (reference gluon/block.py:228)."""

    def __init__(self, prefix=None, params=None):
        self._empty_prefix = prefix == ""
        self._prefix, self._params = _BlockScope.create(
            prefix, params, self._alias()
        )
        self._name = self._prefix[:-1] if self._prefix.endswith("_") else self._prefix
        self._scope = _BlockScope(self)
        self._children = OrderedDict()
        self._reg_params = {}
        self._forward_hooks = OrderedDict()
        self._forward_pre_hooks = OrderedDict()

    def _alias(self):
        return self.__class__.__name__.lower()

    # ------------------------------------------------------------ registry
    def __setattr__(self, name, value):
        if hasattr(self, name):
            existing = getattr(self, name)
            if isinstance(existing, (Parameter, Block)) and not isinstance(
                value, type(existing)
            ):
                raise MXNetError(
                    f"Changing attribute type for {getattr(self, 'name', '?')} "
                    f"is not allowed."
                )
        if isinstance(value, Block):
            self.register_child(value, name)
        elif isinstance(value, Parameter):
            self._reg_params[name] = value
        super().__setattr__(name, value)

    def register_child(self, block, name=None):
        if name is None:
            name = str(len(self._children))
        self._children[name] = block

    def register_forward_hook(self, hook):
        key = len(self._forward_hooks)
        self._forward_hooks[key] = hook
        return _HookHandle(self._forward_hooks, key)

    def register_forward_pre_hook(self, hook):
        key = len(self._forward_pre_hooks)
        self._forward_pre_hooks[key] = hook
        return _HookHandle(self._forward_pre_hooks, key)

    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._name

    def name_scope(self):
        return self._scope

    @property
    def params(self):
        return self._params

    def collect_params(self, select=None):
        """All Parameters of self + descendants, optionally regex-filtered
        (reference block.py collect_params)."""
        ret = ParameterDict(self._params.prefix)
        if select is None:
            ret.update(self.params)
        else:
            pattern = re.compile(select)
            ret.update(
                {k: v for k, v in self.params.items() if pattern.match(k)}
            )
        for child in self._children.values():
            ret.update(child.collect_params(select=select))
        return ret

    # ---------------------------------------------------------- lifecycle
    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False):
        self.collect_params().initialize(init, ctx, verbose, force_reinit)

    def hybridize(self, active=True, **kwargs):
        for child in self._children.values():
            child.hybridize(active, **kwargs)

    def cast(self, dtype):
        for child in self._children.values():
            child.cast(dtype)
        for _, param in self.params.items():
            param.cast(dtype)

    def apply(self, fn):
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    # -------------------------------------------------------------- io
    def save_parameters(self, filename, deduplicate=False):
        params = self._collect_params_with_prefix()
        nd.save(filename, {k: p.data() for k, p in params.items()})

    def _collect_params_with_prefix(self, prefix=""):
        if prefix:
            prefix += "."
        ret = {prefix + k: v for k, v in self._reg_params.items()}
        for name, child in self._children.items():
            ret.update(child._collect_params_with_prefix(prefix + name))
        return ret

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False,
                        dtype_source="current"):
        from .parameter import _strip_arg_aux

        loaded = _strip_arg_aux(nd.load(filename))
        params = self._collect_params_with_prefix()
        if not loaded and not params:
            return
        if not any("." in k for k in loaded.keys()):
            # legacy full-name format -> load via ParameterDict
            self.collect_params().load(
                filename, ctx, allow_missing, ignore_extra, self.prefix
            )
            return
        if not allow_missing:
            for name in params.keys():
                if name not in loaded:
                    raise MXNetError(
                        f"Parameter '{name}' is missing in file '{filename}'"
                    )
        for name in loaded:
            if name not in params:
                if not ignore_extra:
                    raise MXNetError(
                        f"Parameter '{name}' loaded from file '{filename}' "
                        "is not present in this Block"
                    )
                continue
            param = params[name]
            arr = loaded[name]
            if param._data is None:
                param.shape = tuple(arr.shape)
                if param._deferred_init is None:
                    param.initialize(ctx=ctx)
            param.set_data(arr)

    save_params = save_parameters
    load_params = load_parameters

    # ------------------------------------------------------------- forward
    def __call__(self, *args):
        # while a program is being traced (the first argument holds a
        # tracer) the block opens a scope with its own name, so that
        # the compiled step's operations, and their backward, carry it
        # (profiler.dumps() reads it back); an eager call pays this
        # one check
        if args and self.name and isinstance(
                getattr(args[0], "_data", None), jax.core.Tracer):
            with jax.named_scope(self.name):
                return self._call(*args)
        return self._call(*args)

    def _call(self, *args):
        if _suppress_hooks.flag:
            return self.forward(*args)
        for hook in self._forward_pre_hooks.values():
            hook(self, args)
        out = self.forward(*args)
        for hook in self._forward_hooks.values():
            hook(self, args, out)
        return out

    def forward(self, *args):
        raise NotImplementedError

    def summary(self, *inputs):
        summary = OrderedDict()
        seen = set()
        hooks = []

        def _register(block, prefix):
            def _hook(blk, ins, outs):
                name = prefix or blk.name
                out0 = outs[0] if isinstance(outs, (list, tuple)) else outs
                n_params = 0
                for p in blk._reg_params.values():
                    if p._shape_known():
                        n_params += int(onp.prod(p.shape))
                summary[name] = (
                    blk.__class__.__name__,
                    getattr(out0, "shape", None),
                    n_params,
                )

            hooks.append(block.register_forward_hook(_hook))
            for cname, child in block._children.items():
                _register(child, (prefix + "." if prefix else "") + cname)

        _register(self, "")
        try:
            self(*inputs)
        finally:
            for h in hooks:
                h.detach()
        lines = [f"{'Layer':<40}{'Output Shape':<24}{'Param #':<12}"]
        lines.append("=" * 76)
        total = 0
        for name, (cls, shape, n) in summary.items():
            lines.append(f"{cls + ' (' + name + ')':<40}{str(shape):<24}{n:<12}")
            total += n
        lines.append("=" * 76)
        lines.append(f"Total params: {total}")
        print("\n".join(lines))

    def __repr__(self):
        lines = [self.__class__.__name__ + "("]
        for name, child in self._children.items():
            child_repr = repr(child).replace("\n", "\n  ")
            lines.append(f"  ({name}): {child_repr}")
        lines.append(")")
        return "\n".join(lines)


class _HookHandle:
    def __init__(self, hooks, key):
        self._hooks = hooks
        self._key = key

    def detach(self):
        self._hooks.pop(self._key, None)


class HybridBlock(Block):
    """Block whose forward is expressible as a pure function of inputs +
    params — hybridizable to one compiled XLA program.

    Subclasses implement ``hybrid_forward(F, x, *, weight=..., ...)``
    where F is the ``nd`` (or ``symbol``) namespace, exactly like the
    reference.  Registered parameters are passed as kwargs.
    """

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._active = False
        self._jit_cache = {}
        self._flags = {}
        self._partial_shaping = False

    def __setattr__(self, name, value):
        super().__setattr__(name, value)
        if isinstance(value, HybridBlock):
            self._clear_cached_op()

    def hybridize(self, active=True, static_alloc=False, static_shape=False,
                  **kwargs):
        self._active = active
        self._flags = dict(
            static_alloc=static_alloc, static_shape=static_shape, **kwargs
        )
        self._clear_cached_op()
        # children keep running imperatively inside the parent's trace
        for child in self._children.values():
            if isinstance(child, HybridBlock):
                child._clear_cached_op()

    def _clear_cached_op(self):
        self._jit_cache = {}

    def cast(self, dtype):
        self._clear_cached_op()
        super().cast(dtype)

    def infer_shape(self, *args):
        """Resolve deferred parameter shapes from example inputs."""
        self._infer_and_init(*args)

    # ------------------------------------------------------------- forward
    def forward(self, x, *args):
        from .. import symbol as _sym_mod

        if isinstance(x, _sym_mod.Symbol):
            # symbolic trace (reference: hybrid_forward with F=mx.sym):
            # parameters appear as named variables so the exported graph
            # aligns with collect_params()/save_parameters names
            params = {k: v.var() for k, v in self._reg_params.items()}
            return self.hybrid_forward(_sym_mod, x, *args, **params)
        if isinstance(x, nd.NDArray) and not isinstance(
            x._data, jax.core.Tracer
        ) and self._active:
            return self._call_cached(x, *args)
        # imperative path (also the trace path when _data is a tracer)
        try:
            params = {k: v.data() for k, v in self._reg_params.items()}
        except DeferredInitializationError:
            self._infer_param_shapes(x, *args)
            for _, p in self._reg_params.items():
                p._finish_deferred_init()
            params = {k: v.data() for k, v in self._reg_params.items()}
        return self.hybrid_forward(nd, x, *args, **params)

    def _infer_and_init(self, *args):
        """Resolve deferred shapes across the whole subtree by running one
        eager (non-jit) forward; each leaf layer fills its own shapes via
        ``_infer_param_shapes`` when first called.  Reference analog:
        deferred shape inference in block.py _build_cache/infer_shape."""
        states = []

        def _disable(b):
            if isinstance(b, HybridBlock):
                states.append((b, b._active))
                b._active = False
            for c in b._children.values():
                _disable(c)

        _disable(self)
        prev = _suppress_hooks.flag
        _suppress_hooks.flag = True  # internal pass: no user hooks
        try:
            with autograd.pause():
                Block.__call__(self, *args)
        finally:
            _suppress_hooks.flag = prev
            for b, s in states:
                b._active = s

    def _infer_param_shapes(self, *args):
        """Subclasses with deferred shapes override (e.g. Dense infers
        in_units from input)."""
        raise DeferredInitializationError(
            f"{self.name}: parameter shapes unknown and block does not "
            "implement shape inference"
        )

    @staticmethod
    def _donating_fn(entry, pdata, idata):
        """Donating twin of the cached jit program (``static_alloc``
        memory reuse): parameters the traced program MUTATES (BatchNorm
        moving stats adopted a new value — ``upd_idx`` in the entry
        meta) are passed as a separate donated argument, so XLA writes
        each update into its input's buffer instead of allocating.
        Returns None (caller uses the plain program) until the first
        call has populated the meta, when mutation is absent, when
        donation is disabled, or when a mutated buffer is aliased into
        a non-donated slot (shared parameters)."""
        from .. import config as _config

        meta = entry["meta"]
        if meta is None or not meta[3] or \
                not _config.get_env("MXNET_EXEC_DONATE"):
            return None
        upd_idx = meta[3]
        upd_set = set(upd_idx)
        upd_ids = {id(pdata[i]) for i in upd_idx}
        aliased = any(id(v) in upd_ids for i, v in enumerate(pdata)
                      if i not in upd_set)
        aliased = aliased or any(v is not None and id(v) in upd_ids
                                 for v in idata)
        if aliased:
            return None
        if entry.get("fn_d") is None:
            run = entry["run"]

            def _run_d(key, upd_vals, rest_vals, in_vals):
                pv = list(rest_vals)
                for j, i in enumerate(upd_idx):
                    pv[i] = upd_vals[j]
                return run(key, pv, in_vals)

            entry["fn_d"] = jax.jit(_run_d, donate_argnums=(1,))
        return entry["fn_d"]

    def _telemetry_trace(self, sig, training, plat, probe, _at):
        """One compile record per new CachedOp jit entry.  No-op when
        MXNET_RUNLOG is unset (one call + dict lookup); the RunLog
        diffs this fingerprint against the program's previous one to
        name the retrace cause (shape/dtype/train_mode/
        autotune_winner)."""
        from .. import telemetry

        rl = telemetry.current()
        if rl is None:
            return
        shapes, train = sig
        try:
            winners = {}
            if probe is not None and _at.enabled():
                winners = {op: _at.lookup(op, probe.shape, probe.dtype,
                                          platform=plat)
                           for op in _at.VARIANT_OPS}
            rl.compile_event(
                f"cachedop:{self.name}",
                telemetry.compile_fingerprint(
                    [s[0] for s in shapes if s[0] != "#py"],
                    [s[1] for s in shapes if s[0] != "#py"],
                    train, winners=winners))
        except Exception:
            pass  # telemetry must never kill a forward

    def _call_cached(self, *args):
        """jit path: one compiled program, one autograd tape node.

        The traced callable swaps every subtree Parameter's value for a
        traced jax value, runs the ordinary imperative forward (children
        included), and returns the flat outputs — the analog of
        CachedOp::Forward executing the cached graph
        (src/imperative/cached_op.cc:1023)."""
        flat_in, fmt = _flatten_to_nd(args)
        try:
            all_params = _collect_all_params(self)
            pdata = [p.data()._data for p in all_params]
        except DeferredInitializationError:
            self._infer_and_init(*args)
            all_params = _collect_all_params(self)
            pdata = [p.data()._data for p in all_params]
        training = autograd.is_training()
        sig = (
            tuple(
                (a.shape, str(a.dtype)) if isinstance(a, nd.NDArray)
                else ("#py", repr(a))
                for a in flat_in
            ),
            training,
        )
        entry = self._jit_cache.get(sig)
        new_entry = entry is None
        if entry is None:
            entry = {"meta": None}
            # capture only non-array (python) inputs; array slots are fed
            # through in_vals so no device buffers pin in the closure
            py_slots = {
                i: a for i, a in enumerate(flat_in)
                if not isinstance(a, nd.NDArray)
            }

            def _run(key, param_vals, in_vals):
                with _rng.trace_key_scope(key), autograd._Scope(
                    False, training
                ):
                    saved = _swap_param_values(self, param_vals)
                    try:
                        arrs = [
                            nd.NDArray(v) if v is not None
                            else py_slots[i]
                            for i, v in enumerate(in_vals)
                        ]
                        rebuilt, _ = _unflatten(arrs, fmt)
                        out = Block.__call__(self, *rebuilt)
                        # state mutations (e.g. BatchNorm running stats
                        # adopted a new traced value) become extra outputs
                        flat_params = _collect_all_params(self)
                        upd_idx, upd_vals = [], []
                        for i, p in enumerate(flat_params):
                            cur = p._data._data
                            if cur is not param_vals[i]:
                                upd_idx.append(i)
                                upd_vals.append(cur)
                    finally:
                        _swap_param_values(self, saved)
                single = not isinstance(out, (list, tuple))
                flat_out, out_fmt = _flatten_to_nd([out] if single else out)
                entry["meta"] = (out_fmt, single, len(flat_out),
                                 tuple(upd_idx))
                return tuple(o._data for o in flat_out) + tuple(upd_vals)

            entry["fn"] = jax.jit(_run)
            entry["run"] = _run  # donating twin builds lazily from it
            self._jit_cache[sig] = entry

        jitted = entry["fn"]
        key = _rng.take_key()
        idata = [
            a._data if isinstance(a, nd.NDArray) else None for a in flat_in
        ]

        def _tracked(x):
            return x._is_var or x._node is not None

        # trace-platform hint for kernel-backed ops (ops/pallas_conv):
        # jax traces are platform-agnostic, so ops choosing between a
        # Pallas kernel and plain jnp need to know where THIS program's
        # concrete arguments live
        from .. import autotune as _at
        from ..ops import pallas_conv as _pc

        plat = _pc.platform_of(pdata) or _pc.platform_of(idata)
        _hint_prev = _pc.set_trace_platform(plat)
        # autotuned variant winners for this program's input signature
        # apply while the cached program traces (cudnn algo registry
        # consulted at CachedOp::Forward graph build)
        _probe = next((a for a in flat_in if isinstance(a, nd.NDArray)),
                      None)
        _scope = _at.program_scope(
            _probe.shape if _probe is not None else (),
            _probe.dtype if _probe is not None else "none",
            platform=plat)
        _scope.__enter__()
        if new_entry:
            # one compile record per new CachedOp program (the gluon
            # jit path's retrace observer, mirroring Executor's) —
            # the RunLog diffs the fingerprint to name the cause
            self._telemetry_trace(sig, training, plat, _probe, _at)
        try:
            nd_params = [p.data() for p in all_params]
            recording = autograd.is_recording() and (
                any(_tracked(p) for p in nd_params)
                or any(
                    isinstance(a, nd.NDArray) and _tracked(a)
                    for a in flat_in
                )
            )
            if recording:
                def _f(ps, xs):
                    return jitted(key, ps, xs)

                out_vals, vjp_fn = jax.vjp(_f, pdata, idata)

                def _pullback(cots):
                    if not isinstance(cots, tuple):
                        cots = (cots,)
                    # the custom-vjp bwd rules trace HERE (first
                    # backward), so the platform hint must be live
                    prev = _pc.set_trace_platform(plat)
                    try:
                        gp, gx = vjp_fn(cots)
                    finally:
                        _pc.set_trace_platform(prev)
                    return list(gp) + list(gx)

                node = autograd.TapeNode(
                    _pullback,
                    [p if _tracked(p) else None for p in nd_params]
                    + [
                        a if isinstance(a, nd.NDArray) and _tracked(a)
                        else None
                        for a in flat_in
                    ],
                    [(tuple(map(int, v.shape)), v.dtype)
                     for v in out_vals],
                    op_name=f"jit:{self.name}",
                )
                outs = []
                for i, v in enumerate(out_vals):
                    o = nd.NDArray(v)
                    o._node = node
                    o._oidx = i
                    outs.append(o)
            else:
                fn_d = self._donating_fn(entry, pdata, idata)
                if fn_d is not None:
                    upd_idx = entry["meta"][3]
                    upd_set = set(upd_idx)
                    upd_vals = [pdata[i] for i in upd_idx]
                    rest = [None if i in upd_set else v
                            for i, v in enumerate(pdata)]
                    out_vals = fn_d(key, upd_vals, rest, idata)
                else:
                    out_vals = jitted(key, pdata, idata)
                outs = [nd.NDArray(v) for v in out_vals]
        finally:
            _scope.__exit__(None, None, None)
            _pc.set_trace_platform(_hint_prev)

        out_fmt, single, n_primary, upd_idx = entry["meta"]
        if upd_idx:
            for i, v in zip(upd_idx, out_vals[n_primary:]):
                all_params[i]._data._adopt(v)
            outs = outs[:n_primary]
        rebuilt, _ = _unflatten(outs, out_fmt)
        return rebuilt[0] if single else rebuilt

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError

    def export(self, path, epoch=0):
        """Write ``path-symbol.json`` + ``path-{epoch:04d}.params``
        (reference block.py export): the graph comes from a symbolic
        trace of hybrid_forward, parameters are saved under the
        reference's ``arg:``/``aux:`` key convention so
        ``SymbolBlock.imports``/``mx.mod.Module`` can load them."""
        from .. import ndarray as _ndm
        from .. import symbol as _sym_mod

        data = _sym_mod.var("data")
        out = self(data)
        if isinstance(out, (list, tuple)):
            out = _sym_mod.Group(list(out))
        out.save(f"{path}-symbol.json")
        # arg/aux split follows the GRAPH's classification (__aux__
        # marking == nnvm mutable inputs), not grad_req: a frozen
        # trainable weight is still an arg
        aux_names = set(out.list_auxiliary_states())
        arg_aux = {}
        for name, p in self.collect_params().items():
            kind = "aux" if name in aux_names else "arg"
            arg_aux[f"{kind}:{name}"] = p.data()
        _ndm.save(f"{path}-{epoch:04d}.params", arg_aux)
        return out


def _collect_all_params(block):
    """Flat list of subtree Parameters in deterministic registry order —
    the order used both for jit inputs and for value swapping."""
    result = list(block._reg_params.values())
    for child in block._children.values():
        result.extend(_collect_all_params(child))
    return result


def _swap_param_values(block, values):
    """Temporarily rebind every subtree Parameter's jax value to the traced
    values (same flat order as _collect_all_params); returns the saved
    originals so the caller can restore after tracing."""
    flat = _collect_all_params(block)
    saved = []
    for p, v in zip(flat, values):
        arr = p._data
        saved.append(arr._data)
        arr._data = v
    return saved


class SymbolBlock(HybridBlock):
    """Construct a Block from a symbolic graph (lands fully with mx.sym;
    reference gluon/block.py:1190)."""

    def __init__(self, outputs, inputs, params=None):
        super().__init__(prefix="", params=params)
        self._outputs = outputs
        self._inputs = inputs
        # every non-input graph variable becomes a Parameter (aux vars
        # with grad_req='null'), so load_parameters/collect_params see
        # the full weight set (reference block.py:1236)
        input_names = {s.name for s in inputs}
        aux = set(outputs.list_auxiliary_states()) \
            if hasattr(outputs, "list_auxiliary_states") else set()
        for name in outputs.list_inputs():
            if name in input_names:
                continue
            self.params.get(
                name, grad_req="null" if name in aux else "write",
                allow_deferred_init=True, differentiable=name not in aux)

    @staticmethod
    def imports(symbol_file, input_names, param_file=None, ctx=None):
        from .. import symbol as sym_mod

        symbol = sym_mod.load(symbol_file)
        if isinstance(input_names, str):
            input_names = [input_names]
        inputs = [sym_mod.var(n) for n in input_names]
        ret = SymbolBlock(symbol, inputs)
        if param_file is not None:
            ret.load_parameters(param_file, ctx=ctx, cast_dtype=True)
        return ret

    def forward(self, *args):
        arg_dict = {s.name: a for s, a in zip(self._inputs, args)}
        aux_names = set(self._outputs.list_auxiliary_states()) \
            if hasattr(self._outputs, "list_auxiliary_states") else set()
        arg_params, aux_params = {}, {}
        for name, p in self.collect_params().items():
            (aux_params if name in aux_names else arg_params)[name] = \
                p.data()
        ex = self._outputs.bind(args={**arg_dict, **arg_params},
                                aux_states=aux_params)
        outs = ex.forward()
        return outs[0] if len(outs) == 1 else outs
