"""Testing utilities — assertion helpers, random data, and the
finite-difference gradient checker.

Reference parity: python/mxnet/test_utils.py — ``assert_almost_equal``,
``check_numeric_gradient`` (:981), ``check_symbolic_forward`` /
``check_symbolic_backward``, ``check_consistency`` (dtype ladder), and
the random tensor helpers.  The numeric gradient is the independent
oracle for autograd: central differences of the op's forward, compared
against the framework's analytic (vjp) gradients.
"""
from __future__ import annotations

import numpy as onp

from .base import MXNetError
from .context import Context, cpu, current_context

__all__ = [
    "default_context", "set_default_context", "assert_almost_equal",
    "almost_equal", "same", "rand_shape_2d", "rand_shape_3d",
    "rand_shape_nd", "rand_ndarray", "random_arrays", "numeric_grad",
    "check_numeric_gradient", "check_symbolic_forward",
    "check_symbolic_backward", "check_consistency", "simple_forward",
    "enable_x64", "write_rec_corpus", "corrupt_rec",
]


def enable_x64():
    """Context manager enabling 64-bit jax types.  Used by the f64
    reference rungs of the dtype ladder and the FD gradient sweeps."""
    import jax

    return jax.enable_x64(True)


_DEFAULT_RTOL = {
    onp.dtype(onp.float16): 1e-2,
    onp.dtype(onp.float32): 1e-4,
    onp.dtype(onp.float64): 1e-5,
}
_DEFAULT_ATOL = {
    onp.dtype(onp.float16): 1e-3,
    onp.dtype(onp.float32): 1e-5,
    onp.dtype(onp.float64): 1e-8,
}


def default_context() -> Context:
    """Reference: test_utils.py:58."""
    return current_context()


def set_default_context(ctx: Context):
    Context._default = ctx


def _to_numpy(a):
    from .ndarray import NDArray

    if isinstance(a, NDArray):
        return a.asnumpy()
    return onp.asarray(a)


def same(a, b):
    return onp.array_equal(_to_numpy(a), _to_numpy(b))


def almost_equal(a, b, rtol=None, atol=None, equal_nan=False):
    a, b = _to_numpy(a), _to_numpy(b)
    rtol = rtol if rtol is not None else _DEFAULT_RTOL.get(a.dtype, 1e-4)
    atol = atol if atol is not None else _DEFAULT_ATOL.get(a.dtype, 1e-5)
    return onp.allclose(a, b, rtol=rtol, atol=atol, equal_nan=equal_nan)


def assert_almost_equal(a, b, rtol=None, atol=None, names=("a", "b"),
                        equal_nan=False):
    """Reference: test_utils.py assert_almost_equal with tolerance ladder."""
    an, bn = _to_numpy(a), _to_numpy(b)
    rtol = rtol if rtol is not None else _DEFAULT_RTOL.get(an.dtype, 1e-4)
    atol = atol if atol is not None else _DEFAULT_ATOL.get(an.dtype, 1e-5)
    if an.shape != bn.shape:
        raise AssertionError(
            f"shape mismatch: {names[0]}{an.shape} vs {names[1]}{bn.shape}")
    if onp.allclose(an.astype(onp.float64), bn.astype(onp.float64),
                    rtol=rtol, atol=atol, equal_nan=equal_nan):
        return
    err = onp.abs(an.astype(onp.float64) - bn.astype(onp.float64))
    denom = onp.abs(bn.astype(onp.float64)) + atol
    rel = err / denom
    idx = onp.unravel_index(onp.argmax(rel), rel.shape)
    raise AssertionError(
        f"{names[0]} and {names[1]} differ: max rel err {rel.max():.3e} at "
        f"{idx} ({an[idx]!r} vs {bn[idx]!r}), rtol={rtol}, atol={atol}")


def rand_shape_2d(dim0=10, dim1=10):
    return (onp.random.randint(1, dim0 + 1), onp.random.randint(1, dim1 + 1))


def rand_shape_3d(dim0=10, dim1=10, dim2=10):
    return (onp.random.randint(1, dim0 + 1), onp.random.randint(1, dim1 + 1),
            onp.random.randint(1, dim2 + 1))


def rand_shape_nd(num_dim, dim=10):
    return tuple(onp.random.randint(1, dim + 1, size=num_dim))


def rand_ndarray(shape, dtype="float32", ctx=None, low=-1.0, high=1.0):
    from . import ndarray as nd

    data = onp.random.uniform(low, high, size=shape).astype(dtype)
    return nd.array(data, ctx=ctx or default_context())


def random_arrays(*shapes):
    arrays = [onp.random.randn(*s).astype(onp.float32) if s else
              onp.float32(onp.random.randn()) for s in shapes]
    if len(arrays) == 1:
        return arrays[0]
    return arrays


def simple_forward(sym, ctx=None, is_train=False, **inputs):
    """Reference: test_utils.py simple_forward — one-shot symbol eval."""
    from . import ndarray as nd

    ctx = ctx or default_context()
    args = {k: nd.array(v, ctx=ctx) for k, v in inputs.items()}
    exe = sym.bind(ctx, args=args)
    outs = exe.forward(is_train=is_train)
    outs = [o.asnumpy() for o in outs]
    return outs[0] if len(outs) == 1 else outs


def numeric_grad(f, args, eps=1e-3, out_grads=None, wrt=None):
    """Central-difference gradients of ``f(*args) -> array`` w.r.t. each
    numpy array in ``args``.

    out_grads: cotangent(s) to contract the output jacobian with; defaults
    to all-ones (matching executor.backward default).  wrt: arg indices
    to differentiate (others return zero gradients without paying the
    2-evaluations-per-element cost).  Reference: test_utils.py
    numeric_grad used by check_numeric_gradient (:981).
    """
    import jax

    # owned C-contiguous float64 copies: perturbation writes below go
    # through reshape(-1) views and must reach the evaluated buffer
    # (and must never mutate the caller's arrays)
    args = [onp.array(a, dtype=onp.float64, order="C", copy=True)
            if onp.issubdtype(onp.asarray(a).dtype, onp.floating)
            else onp.asarray(a) for a in args]

    def eval_f(xs):
        # full fp32 matmul precision: on TPU the MXU default is bf16,
        # which would swallow the +-eps/2 perturbations entirely
        with jax.default_matmul_precision("highest"):
            out = f(*[x.astype(onp.float32) if onp.issubdtype(x.dtype,
                      onp.floating) else x for x in xs])
        outs = out if isinstance(out, (tuple, list)) else (out,)
        outs = [_to_numpy(o).astype(onp.float64) for o in outs]
        if out_grads is None:
            return sum(o.sum() for o in outs)
        ogs = out_grads if isinstance(out_grads, (tuple, list)) \
            else (out_grads,)
        return sum((o * onp.asarray(g, dtype=onp.float64)).sum()
                   for o, g in zip(outs, ogs))

    grads = []
    for i, a in enumerate(args):
        if not onp.issubdtype(a.dtype, onp.floating) or \
                (wrt is not None and i not in wrt):
            grads.append(onp.zeros_like(a, dtype=onp.float64))
            continue
        g = onp.zeros_like(a)
        flat = a.reshape(-1)
        gflat = g.reshape(-1)
        for j in range(flat.size):
            orig = flat[j]
            flat[j] = orig + eps / 2
            fp = eval_f(args)
            flat[j] = orig - eps / 2
            fm = eval_f(args)
            flat[j] = orig
            gflat[j] = (fp - fm) / eps
        grads.append(g)
    return grads


def check_numeric_gradient(sym_or_fn, location, aux_states=None,
                           numeric_eps=1e-3, rtol=1e-2, atol=None,
                           grad_nodes=None, use_forward_train=True,
                           ctx=None, wrt=None, **op_params):
    """Verify analytic gradients against finite differences.

    Reference: test_utils.py:981.  Accepts either a Symbol (bound and
    backward-ed through the executor) or a callable/op-name (run through
    eager autograd) — both exercise the REAL user paths, with numpy
    central differences as the independent oracle.
    """
    from . import autograd
    from . import ndarray as nd
    from .symbol import Symbol

    ctx = ctx or default_context()
    atol = atol if atol is not None else rtol * 1e-1

    if isinstance(sym_or_fn, Symbol):
        sym = sym_or_fn
        if isinstance(location, (list, tuple)):
            location = {k: v for k, v in
                        zip(sym.list_arguments(), location)}
        args = {k: nd.array(v, ctx=ctx) for k, v in location.items()}
        grad_nodes = grad_nodes or list(location.keys())
        args_grad = {k: nd.zeros(args[k].shape, ctx=ctx)
                     for k in grad_nodes}
        aux = {k: nd.array(v, ctx=ctx)
               for k, v in (aux_states or {}).items()}
        exe = sym.bind(ctx, args=args, args_grad=args_grad,
                       aux_states=aux)
        outs = exe.forward(is_train=use_forward_train)
        out_grads = [nd.ones(o.shape, ctx=ctx) for o in outs]
        exe.backward(out_grads if len(out_grads) > 1 else out_grads[0])
        analytic = {k: exe.grad_dict[k].asnumpy() for k in grad_nodes}

        names = sym.list_arguments()

        # ONE reusable no-grad executor for the whole numeric sweep:
        # the old simple_forward-per-probe re-bound a fresh executor —
        # a fresh jit cache, so XLA recompiled the graph for EVERY
        # +-eps evaluation (2 per element; an LSTM-projection FD check
        # paid ~200 compiles ~= 83 s).  Adopting the perturbed values
        # into one executor compiles once and replays.
        eval_exe = sym.bind(
            ctx, args={k: nd.array(v, ctx=ctx)
                       for k, v in location.items()},
            grad_req="null",
            aux_states={k: nd.array(v, ctx=ctx)
                        for k, v in (aux_states or {}).items()})

        def f(*xs):
            f_outs = eval_exe.forward(is_train=use_forward_train,
                                      **dict(zip(names, xs)))
            f_outs = [o.asnumpy() for o in f_outs]
            return f_outs[0] if len(f_outs) == 1 else f_outs

        loc_list = [location[k] for k in names]
        keep_idx = {i for i, k in enumerate(names) if k in grad_nodes}
        numeric = numeric_grad(f, loc_list, eps=numeric_eps, wrt=keep_idx)
        numeric = {k: g for k, g in zip(names, numeric)
                   if k in grad_nodes}
    else:
        fn = sym_or_fn
        if isinstance(fn, str):
            opname = fn
            fn = lambda *xs: nd.invoke(opname, list(xs), **op_params)  # noqa: E731
        if isinstance(location, dict):
            location = list(location.values())
        arrs = [nd.array(v, ctx=ctx) for v in location]
        for a in arrs:
            a.attach_grad()
        with autograd.record():
            out = fn(*arrs)
            outs = out if isinstance(out, (tuple, list)) else [out]
            head = outs[0]
        if len(outs) > 1:
            autograd.backward(
                outs, head_grads=[nd.ones(o.shape, ctx=ctx) for o in outs])
        else:
            head.backward(nd.ones(head.shape, ctx=ctx))
        keep = set(range(len(arrs))) if wrt is None else set(wrt)
        analytic = {i: a.grad.asnumpy() for i, a in enumerate(arrs)
                    if i in keep}
        numeric = {i: g for i, g in
                   enumerate(numeric_grad(fn, location, eps=numeric_eps,
                                          wrt=keep))
                   if i in keep}

    for k in analytic:
        assert_almost_equal(
            analytic[k], numeric[k], rtol=rtol, atol=atol,
            names=(f"analytic_grad[{k}]", f"numeric_grad[{k}]"))
    return analytic


def check_symbolic_forward(sym, location, expected, rtol=1e-4, atol=None,
                           aux_states=None, ctx=None):
    """Reference: test_utils.py check_symbolic_forward."""
    from . import ndarray as nd

    ctx = ctx or default_context()
    if isinstance(location, (list, tuple)):
        location = {k: v for k, v in zip(sym.list_arguments(), location)}
    args = {k: nd.array(v, ctx=ctx) for k, v in location.items()}
    aux = {k: nd.array(v, ctx=ctx) for k, v in (aux_states or {}).items()}
    exe = sym.bind(ctx, args=args, aux_states=aux)
    outs = exe.forward(is_train=False)
    expected = expected if isinstance(expected, (list, tuple)) else [expected]
    for o, e in zip(outs, expected):
        assert_almost_equal(o, e, rtol=rtol, atol=atol,
                            names=("forward", "expected"))
    return [o.asnumpy() for o in outs]


def check_symbolic_backward(sym, location, out_grads, expected, rtol=1e-4,
                            atol=None, aux_states=None, grad_req="write",
                            ctx=None):
    """Reference: test_utils.py check_symbolic_backward."""
    from . import ndarray as nd

    ctx = ctx or default_context()
    if isinstance(location, (list, tuple)):
        location = {k: v for k, v in zip(sym.list_arguments(), location)}
    if isinstance(expected, (list, tuple)):
        expected = {k: v for k, v in zip(sym.list_arguments(), expected)}
    args = {k: nd.array(v, ctx=ctx) for k, v in location.items()}
    args_grad = {k: nd.zeros(args[k].shape, ctx=ctx) for k in expected}
    aux = {k: nd.array(v, ctx=ctx) for k, v in (aux_states or {}).items()}
    exe = sym.bind(ctx, args=args, args_grad=args_grad, aux_states=aux,
                   grad_req=grad_req)
    exe.forward(is_train=True)
    ogs = [nd.array(g, ctx=ctx) for g in (
        out_grads if isinstance(out_grads, (list, tuple)) else [out_grads])]
    exe.backward(ogs if len(ogs) > 1 else ogs[0])
    for k, e in expected.items():
        assert_almost_equal(exe.grad_dict[k], e, rtol=rtol, atol=atol,
                            names=(f"grad[{k}]", f"expected[{k}]"))
    return {k: exe.grad_dict[k].asnumpy() for k in expected}


def check_consistency(sym, ctx_list=None, dtypes=("float64", "float32"),
                      location=None, rtol=None, atol=None, scale=1.0):
    """Reference: test_utils.py check_consistency (~:1259): run the same
    symbol across a dtype ladder (the reference's cpu-vs-gpu axis has no
    TPU analog — one XLA program serves every backend — so the dtype axis
    carries the check) and compare outputs against the widest dtype.
    """
    from . import ndarray as nd

    ctxs = ctx_list or [default_context()] * len(dtypes)
    if location is None:
        location = {
            k: onp.random.normal(scale=scale, size=s).astype(onp.float64)
            for k, s in zip(sym.list_arguments(),
                            _infer_arg_shapes(sym))
        }
    results = []
    for ctx, dtype in zip(ctxs, dtypes):
        args = {k: nd.array(onp.asarray(v).astype(dtype), ctx=ctx)
                for k, v in location.items()}
        exe = sym.bind(ctx, args=args)
        outs = exe.forward(is_train=False)
        results.append([o.asnumpy().astype(onp.float64) for o in outs])
    ref = results[0]
    for res, dtype in list(zip(results, dtypes))[1:]:
        dt = onp.dtype(dtype)
        for r, e in zip(res, ref):
            assert_almost_equal(
                r, e, rtol=rtol or _DEFAULT_RTOL.get(dt, 1e-3) * 10,
                atol=atol or _DEFAULT_ATOL.get(dt, 1e-4) * 10,
                names=(f"out[{dtype}]", f"out[{dtypes[0]}]"))
    return results


def _infer_arg_shapes(sym):
    shapes, _, _ = sym.infer_shape_partial()
    return shapes


# ------------------------------------------ data-plane fault corpora
def write_rec_corpus(path, n=32, size=16, seed=23, labels=None,
                     quality=90):
    """Write a deterministic .rec shard of random JPEGs for data-plane
    drills (bench ``data_plane`` phase, ``tools/chaos.py`` rec
    scenarios, corruption tests).  ``labels`` maps a record ordinal to
    its float label (default: the ordinal itself).  Returns the
    per-record byte offsets — what :func:`corrupt_rec` seeks by.

    JPEGs are encoded via PIL, not ``pack_img`` — cv2 is absent from
    the CI environment, and these corpora feed tier-1 tests, the bench
    ``data_plane`` phase and the chaos rec scenarios."""
    import io as _io

    from PIL import Image

    from . import recordio

    w = recordio.MXRecordIO(path, "w")
    offsets = []
    rng = onp.random.RandomState(seed)
    try:
        for i in range(n):
            img = (rng.rand(size, size, 3) * 255).astype("uint8")
            bio = _io.BytesIO()
            Image.fromarray(img).save(bio, format="JPEG",
                                      quality=quality)
            offsets.append(w.tell())
            lab = float(labels(i)) if labels is not None else float(i)
            w.write(recordio.pack(
                recordio.IRHeader(0, lab, i, 0), bio.getvalue()))
    finally:
        w.close()
    return offsets


def corrupt_rec(path, offsets, torn=(), unpack=(), decode=()):
    """Seed the three data-plane corruption shapes into a .rec written
    by :func:`write_rec_corpus` (record indices per style):

    * ``torn``   — garbled frame magic (framing-level; the resync
      reader must skip to the next boundary);
    * ``unpack`` — a 0xFFFFFFFF IRHeader flag (frame parses,
      ``recordio.unpack`` raises);
    * ``decode`` — the JPEG payload smeared with a non-magic pattern
      (unpack succeeds, image decode fails).

    ONE corruption recipe shared by every harness, so what chaos
    injects and what bench measures cannot drift apart."""
    with open(path, "r+b") as f:
        for i in torn:
            f.seek(offsets[i])
            f.write(b"\xde\xad\xbe\xef")
        for i in unpack:
            f.seek(offsets[i] + 8)  # past magic+lrec, into the header
            f.write(b"\xff\xff\xff\xff")
        for i in decode:
            f.seek(offsets[i] + 36)  # into the JPEG payload
            f.write(b"\x55" * 48)
