"""Device-side chained timing for per-op benchmarks.

Host-loop timing (launch op K times, read back, divide) charges every
sub-millisecond op its dispatch and readback, and their jitter can
exceed the op.  This module times a K-iteration
``lax.fori_loop`` whose iterations are serialized by a genuine data
dependence (each iteration perturbs an input with a zero derived from
the previous output), executed as ONE device program with ONE scalar
readback.  The marginal per-iteration time comes from two K values, so
the constant dispatch+readback cost cancels exactly once rather than
once per iteration.

Reference analog: benchmark/opperf/utils/benchmark_utils.py times ops
under the engine profiler, which also records device time, not host
enqueue time.
"""
from __future__ import annotations

import time

import numpy as onp


def _zero_like_scalar(out, jnp):
    """A traced scalar that is always 0 but data-depends on ``out``.

    NOT ``z * 0`` — XLA's algebraic simplifier folds that to a constant,
    which severs the chain, lets the loop body dead-code-eliminate, and
    "times" an empty loop (observed: 4096^3 matmul at 4,143 TF/s, 20x
    over the chip's peak).  min(|finite(z)|, 0) is runtime-zero but not
    provably zero to the compiler."""
    outs = out if isinstance(out, (list, tuple)) else (out,)
    # the scalar must consume EVERY element of EVERY output: with a
    # partial dependence XLA slices or DCEs the producer itself
    # (observed: slice(dot) rewritten to a [1,512]x[512,1] dot, emptying
    # the loop; a tuple op's unused outputs would be eliminated the same
    # way).  The full reduces cost one extra read of the outputs per
    # iteration — documented overhead of the method.
    z = jnp.float32(0.0)
    for o in outs:
        if jnp.iscomplexobj(o):
            o = jnp.real(o)
        z = z + jnp.sum(o.astype(jnp.float32))
    z = jnp.where(jnp.isfinite(z), z, 0.0)  # NaN would poison the args
    return jnp.minimum(jnp.abs(z), 0.0)


def _perturb(args, s, jnp):
    """Inject the zero scalar into the first mutable numeric arg so the
    next iteration cannot be reordered before the previous output."""
    new = list(args)
    for i, a in enumerate(new):
        if not hasattr(a, "dtype") or a.dtype == jnp.bool_:
            continue
        if jnp.issubdtype(a.dtype, jnp.integer):
            delta = s.astype(a.dtype)
        elif a.dtype in (jnp.float32, jnp.float64, jnp.float16,
                         jnp.bfloat16) or jnp.issubdtype(
                             a.dtype, jnp.floating):
            delta = s.astype(a.dtype)
        elif jnp.issubdtype(a.dtype, jnp.complexfloating):
            delta = s.astype(a.dtype)
        else:
            continue
        if a.ndim:
            idx = (0,) * a.ndim
            new[i] = a.at[idx].add(delta)
        else:
            new[i] = a + delta
        return new
    return new  # no numeric arg: rely on jit not hoisting effectful fn


_OVERHEAD_CACHE = []


def chain_overhead():
    """Per-iteration cost of the timing skeleton itself (perturb +
    barrier + scalar reduce on a tiny array, plus the while-loop
    bookkeeping) — measured once and cached.  Sub-us ops are dominated
    by this floor, so opperf subtracts it."""
    if not _OVERHEAD_CACHE:
        import jax.numpy as jnp

        dt, _ = device_chain_time(lambda a: a, [jnp.zeros((8,))],
                                  subtract_overhead=False)
        _OVERHEAD_CACHE.append(max(dt, 0.0))
    return _OVERHEAD_CACHE[0]


def device_chain_time(fn, args, k_small=2, trials=3, target_spread=0.8,
                      max_seconds=20.0, max_runs=2_000_000,
                      subtract_overhead=False, return_samples=False):
    """Median marginal seconds per call of ``fn(*args)`` on device.

    fn must be jax-traceable with fixed shapes.  Returns (dt_seconds,
    runs_used) — or (dt_seconds, runs_used, samples) with
    ``return_samples=True``, where ``samples`` is the per-trial
    marginal-seconds list (ascending) so callers can report
    tail-latency percentiles, not just the median.  The K spread is
    sized adaptively so the marginal time (runs x dt) is
    ~``target_spread`` seconds — the dispatch+readback constant
    jitters, so the spread must dwarf it — clamped so one timing stays under ``max_seconds``.
    """
    import jax
    import jax.numpy as jnp

    # leave pytree args (dicts/lists of arrays) alone — jit flattens
    # them; only promote bare scalars/numpy arrays
    args = [a if hasattr(a, "dtype") or isinstance(a, (dict, list, tuple))
            else jnp.asarray(a) for a in args]

    @jax.jit
    def loop(k, loop_args):
        # k is a TRACED bound (lowers to a while loop) so every K shares
        # ONE compiled program — a compile costs far more than
        # the op, and three static-K programs per op tripled it
        def body(_, carry):
            cargs, s = carry
            cargs = tuple(_perturb(cargs, s, jnp))
            # barrier: keeps the perturbed args (and thus fn) from being
            # hoisted or simplified out of the loop
            cargs = jax.lax.optimization_barrier(cargs)
            out = fn(*cargs)
            return cargs, _zero_like_scalar(out, jnp)

        _, s = jax.lax.fori_loop(
            0, k, body, (tuple(loop_args), jnp.float32(0.0)))
        return s

    def run(k):
        t0 = time.perf_counter()
        s = loop(jnp.int32(k), args)
        _ = float(s)  # scalar readback drains the chain
        return time.perf_counter() - t0

    # Geometric probe ladder: grow K until the marginal time is clearly
    # above the dispatch jitter, then stop.  A single mid-size probe is
    # NOT safe: jitter can make per-iter read as ~0, and extrapolating
    # from that launches multi-minute device loops.
    run(k_small)  # compiles the program
    t_small = run(k_small)
    k = 4
    while True:
        t_k = run(k_small + k)
        delta = t_k - t_small
        if delta > target_spread / 2 or k >= max_runs \
                or t_k > max_seconds / 2:
            break
        k = min(k * 8, max_runs)
    # the ladder stops as soon as the spread is MEASURABLE (> spread/2);
    # scale up to the full target so the trials' spread dwarfs the
    # ~40 ms jitter rather than merely exceeding it, bounded by
    # max_seconds per timing
    if 0 < delta < target_spread and t_k < max_seconds / 2:
        per_iter = delta / k
        k = min(max(k, int(target_spread / per_iter)), max_runs,
                max(int((max_seconds / 2) / per_iter), k))
    runs = k
    ts = []
    for _ in range(trials):
        t1 = run(k_small)
        t2 = run(k_small + runs)
        ts.append((t2 - t1) / runs)
    ts.sort()
    dt = ts[len(ts) // 2]
    if subtract_overhead:
        oh = chain_overhead()
        dt = max(dt - oh, 0.0)
        ts = [max(t - oh, 0.0) for t in ts]
    if return_samples:
        return dt, runs, ts
    return dt, runs
