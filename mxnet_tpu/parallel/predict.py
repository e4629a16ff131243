"""Inference predictor with bind-time micro-batch autotuning.

Reference analog: ``cudnn_tune='fastest'`` (src/operator/nn/cudnn/
cudnn_algoreg-inl.h) benchmarks candidate convolution algorithms at
bind time and caches the winner per shape.  On TPU the algorithm space
is XLA's conv-emitter selection, which is keyed to the operand shapes —
and its cost model picks badly for some large-batch fp32 shapes
(measured r05, v5e: ResNet-152 fp32 bs128 runs 1.5x slower PER IMAGE
than bs32; the same net as ``lax.map`` over 4 chunks of 32 runs 58%
faster than the monolithic batch and matches bs32's per-image cost).
The tunable knob is therefore the micro-batch split: run a batch-B
forward as ``lax.map`` over k chunks of B/k inside ONE jitted program,
picking k by measuring, exactly like cudnn_tune picks an algo.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

__all__ = ["make_predict_fn", "tune_microbatch"]


#: chunk counts up to this unroll by default; beyond it the k-times
#: program-size growth starts to cost more compile time than the loop
#: machinery costs run time
_UNROLL_LIMIT = 8


def make_predict_fn(apply_fn, *, microbatch=1, unroll="auto"):
    """Jitted ``predict(params, x)`` that runs ``apply_fn(params, xc)``
    over ``microbatch`` sequential chunks of the leading batch axis,
    reassembling each output pytree leaf.  microbatch=1 is the plain
    full-batch program.

    unroll=True inlines the k chunk programs: each chunk compiles
    exactly like a standalone batch-B/k call, so XLA keeps its
    double-buffered schedule per chunk.  unroll=False uses ``lax.map``
    (one compiled chunk body, small program) — measured r05/r06 on
    v5e, the map body LOSES cross-iteration double-buffering and ran
    bs128-as-4x32 ~22% slower per image than four standalone bs32
    calls (12.96 ms vs 4x2.65 ms), which re-opened the fp32
    batch-scaling regression the microbatch split exists to fix.
    The "auto" default therefore unrolls for k <= 8 and falls back to
    ``lax.map`` only for chunk counts where the unrolled program size
    would dominate compile time."""
    from ..config import setup_compilation_cache

    setup_compilation_cache()
    k = int(microbatch)
    if unroll == "auto":
        unroll = k <= _UNROLL_LIMIT

    @jax.jit
    def predict(params, x):
        if k == 1:
            return apply_fn(params, x)
        b = x.shape[0]
        if b % k:
            raise ValueError(f"batch {b} not divisible by microbatch {k}")
        xc = x.reshape((k, b // k) + x.shape[1:])
        if unroll:
            chunks = [apply_fn(params, xc[i]) for i in range(k)]
            return jax.tree_util.tree_map(
                lambda *os: jnp.concatenate(os, axis=0), *chunks)
        out = jax.lax.map(lambda c: apply_fn(params, c), xc)
        return jax.tree_util.tree_map(
            lambda o: o.reshape((b,) + o.shape[2:]), out)

    return predict


def _chain_time(fn, args, iters=30):
    """Marginal seconds/call via a fori_loop-chained device program —
    the same two-K-slope method as benchmark/devtime.py, trimmed for
    in-package use (dispatch jitter on the host clock can exceed
    small-batch inference latency)."""

    def zero_of(out):
        leaves = jax.tree_util.tree_leaves(out)
        z = jnp.float32(0.0)
        for o in leaves:
            if jnp.issubdtype(o.dtype, jnp.floating):
                z = z + jnp.sum(o.astype(jnp.float32))
        z = jnp.where(jnp.isfinite(z), z, 0.0)
        return jnp.minimum(jnp.abs(z), 0.0)

    @jax.jit
    def loop(n, a):
        def body(_, carry):
            cargs, s = carry
            cargs = list(cargs)
            cargs[0] = cargs[0] + s.astype(cargs[0].dtype)
            cargs = jax.lax.optimization_barrier(tuple(cargs))
            return cargs, zero_of(fn(*cargs))

        _, s = jax.lax.fori_loop(0, n, body,
                                 (tuple(a), jnp.float32(0.0)))
        return s

    def run(n):
        t0 = time.perf_counter()
        _ = float(loop(jnp.int32(n), args))
        return time.perf_counter() - t0

    run(2)  # compile
    t1 = run(2)
    t2 = run(2 + iters)
    return max(t2 - t1, 1e-9) / iters


def _enc_form(k, unroll):
    return f"{k}:{'unroll' if unroll else 'map'}"


def tune_microbatch(apply_fn, params, sample_x, candidates=(1, 2, 4),
                    iters=20, try_unroll=True, use_cache=None):
    """Measure ``apply_fn`` under each micro-batch split (and, for
    k>1, both the lax.map and unrolled chunk forms) on the sample batch
    and return (best, results) where best = (k, unroll) and results
    maps (k, unroll) -> seconds.  Candidates that do not divide the
    batch are skipped.  Bind-time cost is a few timed loops per
    candidate — the cudnn_tune='fastest' contract.

    Winners persist through the framework autotune cache
    (mxnet_tpu.autotune, keyed on a params-signature digest + the
    sample batch shape/dtype/platform): a later call — or another
    process — with the same model/input signature reloads the recorded
    winner and timings instead of re-timing.  use_cache=None follows
    MXNET_AUTOTUNE (level 2 re-times even on a hit); use_cache=False
    bypasses."""
    import hashlib

    from .. import autotune as at

    b = sample_x.shape[0]
    candidates = tuple(candidates)
    if not any(k >= 1 and b % k == 0 for k in candidates):
        candidates = candidates + (1,)  # always have a valid baseline
    # the model rides in the key via its parameter signature (leaf
    # shapes+dtypes), so two different nets sharing an input shape
    # cannot inherit each other's winner — the same discrimination the
    # cudnn algo registry gets from keying on the filter descriptor
    import jax

    sig = ",".join(
        f"{tuple(getattr(l, 'shape', ()))}{getattr(l, 'dtype', '')}"
        for l in jax.tree_util.tree_leaves(params))
    op_key = ("predict_microbatch:"
              + hashlib.sha1(sig.encode()).hexdigest()[:12])
    lvl = at.autotune_level() if use_cache is None else \
        int(bool(use_cache))
    if lvl == 1:
        entry = at.lookup_entry(op_key, sample_x.shape,
                                sample_x.dtype)
        # a corrupt/partially-written autotune.json must mean
        # "re-tune", never a crash: the loader already drops non-dict
        # entries, and any malformed winner/timings payload inside a
        # surviving entry falls through to the measuring path below
        # (whose record() rewrites the file atomically)
        try:
            w = entry.get("winner") if entry else None
        except AttributeError:
            w = None
        if w is not None:
            if isinstance(w, (list, tuple)) and len(w) == 2 \
                    and w[0] in candidates and b % int(w[0]) == 0:
                results = {}
                try:
                    for ks, t in (entry.get("timings") or {}).items():
                        kk, form = str(ks).split(":")
                        results[(int(kk), form == "unroll")] = float(t)
                except (AttributeError, TypeError, ValueError):
                    results = {}
                best = (int(w[0]), bool(w[1]))
                # the stored race must be EXACTLY what this call would
                # probe: a narrower earlier race must not answer a
                # wider one (k values never timed), and the caller
                # must not see candidates or unroll forms it excluded
                want = set()
                for k in candidates:
                    if k < 1 or b % k:
                        continue
                    want.add((k, False))
                    if k > 1 and try_unroll:
                        want.add((k, True))
                if best in results \
                        and results[best] == min(results.values()) \
                        and set(results) == want:
                    return best, results
    results = {}
    for k in candidates:
        if k < 1 or b % k:
            continue
        forms = ((False,) if k == 1 else
                 ((False, True) if try_unroll else (False,)))
        for unroll in forms:
            pred = make_predict_fn(apply_fn, microbatch=k,
                                   unroll=unroll)
            results[(k, unroll)] = _chain_time(
                lambda xv, p: pred(p, xv), [sample_x, params],
                iters=iters)
    best = min(results, key=results.get)
    if lvl >= 1:
        at.record(op_key, sample_x.shape, sample_x.dtype,
                  [int(best[0]), bool(best[1])],
                  timings={_enc_form(k, u): float(t)
                           for (k, u), t in results.items()})
    return best, results
