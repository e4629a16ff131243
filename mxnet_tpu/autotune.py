"""In-step variant autotuner — the cuDNN algo-registry analog, on TPU.

Reference parity: ``cudnn_tune='fastest'`` (src/operator/nn/cudnn/
cudnn_convolution-inl.h) benchmarks candidate convolution algorithms at
Bind time and ``cudnn_algoreg-inl.h`` caches the winner per
(shape, dtype) so later binds skip the timing.  On TPU the "algorithm"
space is which lowering a registered op uses: channel-last 1x1 convs as
``dot_general`` vs the conv emitter (ops/conv.py), the Pallas fused
BN+ReLU+conv backward vs stock XLA (ops/pallas_conv.py), the
predictor's micro-batch chunking (parallel/predict.py).

The r05 lesson drives the design: the Pallas kernel WON in isolation
(0.48 vs 1.18 ms) and LOST in-step (54.8 vs 46.3 ms) because XLA's
layout assignment and fusion decisions around the variant change with
it.  So variants are timed **inside a jitted representative step** —
the caller's real train/predict program, chained through a
``lax.fori_loop`` carry so iterations serialize and ONE readback
closes the pipeline (device time with no host in the loop, bench.py
MEASUREMENT NOTE) — never as isolated kernels.

Winners persist on disk (``autotune.json`` next to the XLA compilation
cache) keyed on (op, shape, dtype, platform, mesh); a process that
sees the same key again — or a different process on the same host —
loads the winner instead of re-timing, exactly like the cuDNN algo
registry persisting across Bind calls.

Decision precedence at trace time (``variant_choice``):

  1. ``force(...)``   — the tuner's own scope while timing a variant;
  2. an explicitly-set env var (``MXNET_CONV_1X1_DOT=1`` etc.) — the
     user's hand override, also what bench.py --conv-ab uses per arm;
  3. ``program_scope(...)`` — cached winners applied by the jit entry
     points (make_train_step, CachedOp, Executor) for their program's
     input signature;
  4. the op's registered default.

``MXNET_AUTOTUNE`` (config.py): 0 = off (no consult, no tune);
1 = consult cache + tune where the caller provides sample data
(default); 2 = re-tune even on a cache hit (cudnn_tune='fastest'
semantics on every bind).
"""
from __future__ import annotations

import json
import os
import threading
import time

from .base import MXNetError

__all__ = ["variant_choice", "force", "program_scope", "lookup",
           "record", "tune", "tune_train_step", "mesh_desc",
           "cache_path", "cache_clear", "last_report",
           "dtype_ladder_armed", "ladder_rungs", "chain_time",
           "VARIANT_OPS", "op_variants"]

#: op -> {variant name: forced value}.  The forced value is what the
#: op's trace-time ``variant_choice`` consumer receives.
VARIANT_OPS = {
    "conv1x1_dot": {"conv": False, "dot": True},
    # round 14: three-way — "stock" (the unfused layer path, the r05
    # in-step winner), "jnp" (the fused op's jnp backward), "pallas"
    # (the fused op's one-pass kernel backward).  All three race
    # in-step so the per-shape winner is measured, not documented.
    "pallas_bnreluconv": {"stock": "stock", "jnp": "jnp",
                          "pallas": "pallas"},
    # round 14: the Pallas fused-bucket optimizer kernels
    # (ops/pallas_opt.py) vs the jnp fused_bucket_update baseline,
    # consulted by parallel.zero.bucket_shard_update
    "fused_bucket_opt": {"jnp": False, "pallas": True},
    # round 14: flash-attention lowering incl. block-size sub-variants
    # and the aligned-padding shim (ops/flash_attention.py)
    "flash_attention": {"naive": "naive", "pallas": "pallas",
                        "pallas_b256": "pallas_b256",
                        "pallas_pad": "pallas_pad"},
    # round 14: the bf16 dtype-ladder arm — make_train_step's compute
    # dtype raced fp32 vs bf16 (amp_cast_params) per program signature;
    # consulted only when the MXNET_DTYPE_LADDER knob arms it (a dtype
    # change is not numerics-neutral, so adoption is opt-in).
    # round 19 adds the fp8 rung (e4m3 fwd / e5m2 grad with delayed
    # per-tensor scaling, ops/pallas_opt.fp8_qdq) — raced only when
    # the knob's roster names it (ladder_rungs), never implied by a
    # bare MXNET_DTYPE_LADDER=1
    "dtype_ladder": {"fp32": "fp32", "bf16": "bf16", "fp8": "fp8"},
    # round 18: the int8 quantized-inference arms — a rewritten net's
    # QuantizedConv/QuantizedDense wrappers consult these at trace
    # (mxnet_tpu.quantization.rewrite): True runs the calibrated int8
    # program, False the wrapped fp32 layer.  quantization.
    # tune_quantized races them inside a chained run of the real
    # inference forward, so int8 is adopted per (op, shape, platform)
    # only where it measures a win.  round 19 adds the fp8 arm
    # (e4m3 operands, f32 accumulation, calibrated amax scales) to
    # the same per-op race.
    "quantized_conv": {"fp32": False, "int8": True, "fp8": "fp8"},
    "quantized_fc": {"fp32": False, "int8": True, "fp8": "fp8"},
    # round 17: decode-time attention over the PAGED kv cache
    # (ops/flash_attention.paged_decode_attention) — "gather"
    # materializes each slot's pages then runs one fused masked
    # softmax (XLA's fusion, wins at small pools), "paged" walks the
    # page list with an online-softmax accumulator (the vLLM-style
    # schedule, wins when the page table is long).  Raced by the
    # generative server's warmup on the real pool shapes.
    "paged_decode_attention": {"gather": "gather", "paged": "paged"},
}


def _parse_bool(raw):
    return raw.lower() in ("1", "true", "yes", "on")


def _parse_flash(raw):
    lowered = raw.lower()
    if lowered in ("0", "false", "no", "off", "naive"):
        return "naive"
    if lowered in ("1", "true", "yes", "on", "pallas"):
        return "pallas"
    if lowered in ("pallas_b256", "pallas_pad"):
        return lowered
    return None  # unknown value: no override


#: MXNET_DTYPE_LADDER rung spellings -> canonical rung name
_LADDER_TOKENS = {
    "fp32": "fp32", "float32": "fp32",
    "bf16": "bf16", "bfloat16": "bf16",
    "fp8": "fp8", "float8": "fp8", "e4m3": "fp8",
}


def _parse_ladder(raw):
    lowered = raw.lower()
    if "," in lowered:
        return None  # a roster ("fp32,bf16,fp8"): armed, race decides
    if lowered in ("bf16", "bfloat16"):
        return "bf16"
    if lowered in ("fp8", "float8", "e4m3"):
        return "fp8"
    if lowered in ("0", "off", "fp32", "float32"):
        return "fp32"
    return None  # "1"/"auto": armed, but no hand override


def ladder_rungs():
    """The dtype-ladder rungs this process may race/apply, parsed from
    MXNET_DTYPE_LADDER: a comma roster ("fp32,bf16,fp8") names them
    explicitly, a single rung pins it (and is the only rung), and the
    legacy arming values ("1"/"auto"/...) keep the round-14 pair —
    fp8 NEVER joins implicitly, because its delayed-scaling state must
    be provisioned in opt_state at build time and its numerics are a
    bigger departure than bf16's.  () when the ladder is unarmed."""
    raw = os.environ.get("MXNET_DTYPE_LADDER")
    if raw is None or not dtype_ladder_armed():
        return ()
    lowered = raw.lower()
    if "," in lowered:
        out = []
        for tok in lowered.split(","):
            rung = _LADDER_TOKENS.get(tok.strip())
            if rung is not None and rung not in out:
                out.append(rung)
        return tuple(out)
    single = _LADDER_TOKENS.get(lowered)
    if single is not None:
        return (single,)
    return ("fp32", "bf16")  # "1"/"auto": the round-14 race pair


def _parse_bnreluconv(raw):
    lowered = raw.lower()
    return lowered if lowered in ("stock", "jnp", "pallas") else None


def _parse_paged(raw):
    """MXNET_PAGED_ATTENTION: gather/0 pins the dense-gather decode
    attention, paged/1 the online-softmax page walk; anything else
    (e.g. 'auto') carries no override — the measured winner decides."""
    lowered = raw.lower()
    if lowered in ("0", "false", "no", "off", "gather", "dense"):
        return "gather"
    if lowered in ("1", "true", "yes", "on", "paged"):
        return "paged"
    return None


def _parse_quantize(raw):
    """MXNET_QUANTIZE: 0/off/fp32 pins the fp32 fallback arm,
    1/on/int8 pins the int8 program, fp8 pins the fp8 program
    (round 19); anything else (e.g. 'auto') carries no override —
    the measured winner decides."""
    lowered = raw.lower()
    if lowered in ("0", "false", "no", "off", "fp32", "float32"):
        return False
    if lowered in ("1", "true", "yes", "on", "int8"):
        return True
    if lowered in ("fp8", "float8", "e4m3"):
        return "fp8"
    return None


#: env var that explicitly overrides each variant op (precedence 2),
#: with a per-op parser from the raw env string to the forced value
#: (None = this raw value carries no override)
_ENV_OVERRIDE = {
    "conv1x1_dot": ("MXNET_CONV_1X1_DOT", _parse_bool),
    "fused_bucket_opt": ("MXNET_PALLAS_OPT", _parse_bool),
    "flash_attention": ("MXNET_FLASH_ATTENTION", _parse_flash),
    "dtype_ladder": ("MXNET_DTYPE_LADDER", _parse_ladder),
    "pallas_bnreluconv": ("MXNET_BNRELUCONV_VARIANT",
                          _parse_bnreluconv),
    # round 18: ONE knob hand-overrides both int8 arms (the operator
    # story is "quantization on/off", not per-op)
    "quantized_conv": ("MXNET_QUANTIZE", _parse_quantize),
    "quantized_fc": ("MXNET_QUANTIZE", _parse_quantize),
    "paged_decode_attention": ("MXNET_PAGED_ATTENTION", _parse_paged),
}


def dtype_ladder_armed():
    """The bf16 ladder arm races/applies only when the knob arms it:
    MXNET_DTYPE_LADDER set to anything but '0'/'off'/'fp32'-like.  A
    cached bf16 winner changes step numerics, so it never applies to a
    caller that did not opt in."""
    raw = os.environ.get("MXNET_DTYPE_LADDER")
    if raw is None:
        return False
    return raw.lower() not in ("", "0", "off", "false", "no")

_tls = threading.local()
_lock = threading.Lock()
_mem = {"path": None, "mtime": None, "entries": {}}
_last_report = {}


# ------------------------------------------------------------ decisions
def _get_scope(name):
    return getattr(_tls, name, None) or {}


class _Scope:
    def __init__(self, name, choices):
        self._name = name
        self._choices = dict(choices)

    def __enter__(self):
        self._prev = getattr(_tls, self._name, None)
        merged = dict(self._prev or {})
        merged.update(self._choices)
        setattr(_tls, self._name, merged)
        return self

    def __exit__(self, *exc):
        setattr(_tls, self._name, self._prev)


def force(**choices):
    """Tuning scope: pin variant ops to concrete values while the
    representative step traces (wins over everything)."""
    return _Scope("forced", choices)


def variant_choice(op, default=None):
    """The trace-time decision an op consults (see module docstring for
    the precedence ladder).  Returns the chosen value or ``default``."""
    forced = _get_scope("forced")
    if op in forced:
        return forced[op]
    env = _ENV_OVERRIDE.get(op)
    if env is not None:
        raw = os.environ.get(env[0])
        if raw is not None:
            parsed = env[1](raw)
            if parsed is not None:
                return parsed
    applied = _get_scope("applied")
    if op in applied:
        return applied[op]
    return default


def program_scope(shape, dtype, platform=None, mesh=None):
    """Apply every cached winner matching this program's input
    signature (entered by the jit entry points around trace/call:
    make_train_step's step, CachedOp._call_cached, Executor.forward).
    No-op when autotune is off or nothing is cached for the key."""
    if not enabled():
        return _Scope("applied", {})
    entries = _load(cache_path())  # one stat/load for all variant ops
    choices = {}
    if entries:
        for op in VARIANT_OPS:
            # op_variants narrows the ladder to the armed rungs: a
            # cached fp8 winner never applies to a program whose
            # roster (and opt_state provisioning) did not opt into it
            variants = op_variants(op)
            entry = entries.get(_key(op, shape, dtype, platform, mesh))
            winner = entry.get("winner") if entry else None
            if winner is not None and winner in variants:
                choices[op] = variants[winner]
    return _Scope("applied", choices)


# ------------------------------------------------------------ the cache
def enabled(override=None):
    lvl = autotune_level() if override is None else int(bool(override))
    return lvl >= 1


def autotune_level():
    from .config import get_env

    try:
        return int(get_env("MXNET_AUTOTUNE"))
    except Exception:
        return 1


def cache_path():
    """``autotune.json`` next to the persistent XLA compilation cache
    (the cudnn algo registry persisted beside the cubin cache): it
    decides which program is compiled, so it lives where the compiled
    programs do."""
    from .config import compilation_cache_dir, get_env

    d = get_env("MXNET_AUTOTUNE_CACHE_DIR") or compilation_cache_dir()
    return os.path.join(d, "autotune.json")


def _current_platform():
    import jax

    from .ops import pallas_conv as _pc

    hint = getattr(_pc._hint, "platform", None)
    if hint is not None:
        return hint
    return jax.local_devices()[0].platform


def mesh_desc(mesh):
    """Stable string key for a jax Mesh (or None)."""
    if mesh is None:
        return "none"
    try:
        return ",".join(f"{n}={s}" for n, s in
                        zip(mesh.axis_names, mesh.devices.shape))
    except Exception:
        return "mesh"


def _key(op, shape, dtype, platform, mesh):
    platform = platform or _current_platform()
    mesh = mesh if isinstance(mesh, str) else mesh_desc(mesh)
    return "|".join((op, str(tuple(shape)), str(dtype), platform, mesh))


def _sane_entries(data):
    """The entries dict of a parsed autotune.json, with anything a
    corrupt/partially-written file could smuggle dropped: a non-dict
    root or entries value becomes empty, non-dict entry values are
    filtered — so every consumer's entry.get() stays safe and a
    corrupt cache can only ever cost a re-measurement.  One helper
    shared by the read (_load) and read-merge-write (_save) paths so
    the sanitization rules cannot drift."""
    entries = data.get("entries", {}) if isinstance(data, dict) else {}
    if not isinstance(entries, dict):
        entries = {}
    return {k: v for k, v in entries.items() if isinstance(v, dict)}


def _load(path):
    """mtime-checked load so winners recorded by ANOTHER process on the
    same host are visible without restarting (algo-registry sharing)."""
    try:
        mtime = os.stat(path).st_mtime_ns
    except OSError:
        return {}
    with _lock:
        if _mem["path"] == path and _mem["mtime"] == mtime:
            return _mem["entries"]
    try:
        with open(path) as f:
            entries = _sane_entries(json.load(f))
    except (OSError, ValueError):
        entries = {}
    with _lock:
        _mem.update(path=path, mtime=mtime, entries=entries)
    return entries


def _save(path, new_entries):
    """Read-merge-write under an exclusive flock + atomic rename:
    concurrent tuners — other threads via _lock, other PROCESSES via
    the .lock file — lose no winners (last writer wins per key only)."""
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with _lock:
        lock_f = open(f"{path}.lock", "a+")
        try:
            try:
                import fcntl

                fcntl.flock(lock_f, fcntl.LOCK_EX)
            except ImportError:  # non-POSIX: thread lock only
                pass
            try:
                with open(path) as f:
                    on_disk = _sane_entries(json.load(f))
            except (OSError, ValueError):
                on_disk = {}
            on_disk.update(new_entries)
            tmp = f"{path}.{os.getpid()}.tmp"
            with open(tmp, "w") as f:
                json.dump({"version": 1, "entries": on_disk}, f,
                          indent=1)
            os.replace(tmp, path)
            _mem.update(path=path, entries=on_disk,
                        mtime=os.stat(path).st_mtime_ns)
        finally:
            lock_f.close()  # releases the flock


def lookup(op, shape, dtype, platform=None, mesh=None):
    """Cached winner (variant name / JSON value) or None."""
    entry = _load(cache_path()).get(_key(op, shape, dtype, platform,
                                         mesh))
    if entry is None:
        return None
    return entry.get("winner")


def lookup_entry(op, shape, dtype, platform=None, mesh=None):
    return _load(cache_path()).get(_key(op, shape, dtype, platform,
                                        mesh))


def record(op, shape, dtype, winner, timings=None, platform=None,
           mesh=None):
    """Persist a winner (timings in seconds ride along for the report)."""
    entry = {"winner": winner, "timings": timings or {},
             "recorded": time.time()}
    _save(cache_path(), {_key(op, shape, dtype, platform, mesh): entry})
    return entry


def cache_clear():
    """Drop the in-memory mirror (tests poke the cache dir env var)."""
    with _lock:
        _mem.update(path=None, mtime=None, entries={})


def last_report():
    """The most recent tuning session's report (bench.py JSON)."""
    return dict(_last_report)


def op_variants(op):
    """The variant roster ``op`` actually races: VARIANT_OPS[op], with
    the dtype ladder narrowed to the rungs MXNET_DTYPE_LADDER names
    (a "fp32,bf16" roster must not spend a compile measuring an fp8
    arm the caller did not opt into; a cached winner outside the
    roster is ignored by the same rule and simply re-races)."""
    variants = VARIANT_OPS[op]
    if op == "dtype_ladder":
        rungs = ladder_rungs()
        narrowed = {k: v for k, v in variants.items() if k in rungs}
        if narrowed:
            return narrowed
    return variants


# ------------------------------------------------------------- the tuner
def chain_time(fn, init, iters=8):
    """Marginal sec/iteration of ``fn(carry, i) -> carry`` measured
    INSIDE one jitted program: a dynamic-bound fori_loop threads the
    carry (iterations serialize by construction), ONE readback of the
    first carry leaf drains the pipeline, and the two-K slope cancels
    the dispatch+readback constant (bench.py methodology).  The ONE
    shared
    timer behind every variant race — _step_chain_time, the
    ShardedBucketUpdater's exchange race, bench's fused-kernels phase
    — so a methodology fix lands everywhere at once."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def multi(k, c):
        def body(i, c_):
            return fn(c_, i)

        c2 = jax.lax.fori_loop(0, k, body, c)
        return jax.tree_util.tree_leaves(c2)[0].ravel()[0]

    def run(k):
        t0 = time.perf_counter()
        _ = float(multi(jnp.int32(k), init))
        return time.perf_counter() - t0

    run(2)  # compile (the dynamic bound keeps it to ONE program)
    t1 = run(2)
    t2 = run(2 + iters)
    return max(t2 - t1, 1e-9) / iters


def _step_chain_time(step, params, opt_state, x, y, key, iters=8):
    """:func:`chain_time` over a make_train_step-shaped
    ``step(params, opt_state, x, y, key, t) -> (loss, params,
    opt_state)`` (loss rides the carry so the readback sees it)."""
    import jax.numpy as jnp

    def body(carry, i):
        _, p_, o_ = carry
        loss, p2, o2 = step(p_, o_, x, y, key,
                            (i + 1).astype(jnp.float32))
        return (loss, p2, o2)

    return chain_time(body, (jnp.float32(0.0), params, opt_state),
                      iters=iters)


def tune(op, shape, dtype, variants, measure, platform=None, mesh=None,
         level=None):
    """Generic variant race: ``measure(variant_value)`` is called under
    ``force(op=value)`` for each candidate; the fastest wins and is
    recorded.  A cache hit (level 1) returns the stored winner WITHOUT
    measuring — the reload-skips-retiming contract.

    Returns (winner_name, report) where report carries timings (sec)
    and whether the cache answered."""
    lvl = autotune_level() if level is None else level
    if lvl < 1:
        return None, {"enabled": False}
    if lvl == 1:
        entry = lookup_entry(op, shape, dtype, platform=platform,
                             mesh=mesh)
        if entry is not None and entry.get("winner") in variants:
            _telemetry_winner(op, shape, dtype, entry["winner"],
                              cached=True)
            return entry["winner"], {"cached": True,
                                     "timings": entry.get("timings", {})}
    timings = {}
    for name, value in variants.items():
        with force(**{op: value}):
            try:
                timings[name] = measure(value)
            except Exception as e:
                # an arm that cannot compile or run is a fault in that
                # arm, reported under its name — never a lost race
                raise MXNetError(
                    f"autotune: arm {name!r} of {op!r} failed for "
                    f"shape {tuple(shape)} {dtype}: "
                    f"{type(e).__name__}: {e}") from e
    winner = min(timings, key=timings.get)
    record(op, shape, dtype, winner, timings=timings, platform=platform,
           mesh=mesh)
    _telemetry_winner(op, shape, dtype, winner, cached=False,
                      timings=timings)
    return winner, {"cached": False, "timings": timings}


def _telemetry_winner(op, shape, dtype, winner, cached, timings=None):
    """One run-log event per tuning decision: which variant won, for
    which signature, and whether the registry answered from cache —
    the record the compile events' ``autotune_winner`` retrace cause
    cross-references."""
    try:
        from . import telemetry

        telemetry.event(
            "autotune", op=op, shape=str(tuple(shape)),
            dtype=str(dtype), winner=winner, cached=bool(cached),
            timings={k: round(float(v), 6)
                     for k, v in (timings or {}).items()})
    except Exception:
        pass  # telemetry must never kill a tuning session


def tune_train_step(step, params, opt_state, x, y, key,
                    variant_ops=("conv1x1_dot",), platform=None,
                    mesh=None, iters=8, level=None):
    """Race each listed variant op inside the REAL train step (the
    others held at their current decision), greedily one op at a time.
    Keyed on the step's batch-input signature — the program signature
    the winners later apply to via ``program_scope``.

    Called by make_train_step when the caller supplies sample data;
    cheap on a warm cache (pure lookups, zero compiles)."""
    global _last_report
    report = {}
    decided = {}  # earlier winners pinned while later ops race
    for op in variant_ops:
        variants = op_variants(op)

        def measure(_value, _decided=dict(decided)):
            with force(**_decided):
                return _step_chain_time(step, params, opt_state, x, y,
                                        key, iters=iters)

        winner, info = tune(op, x.shape, x.dtype, variants, measure,
                            platform=platform, mesh=mesh, level=level)
        if winner is not None:
            decided[op] = variants[winner]
            report[op] = {"winner": winner, **info}
    _last_report = report
    return report
