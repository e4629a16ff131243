"""Traffic kind ``train_closed``: a closed training loop.

One process drives ``DeviceFeedIter`` -> the function that
``mxnet_tpu.parallel.make_train_step`` returns, as a training script that
logs its loss does: batches come in turn from a pool of host arrays made
from the seed, and the loop reads the loss of step i - ``inflight`` before
it dispatches step i.  Everything that differs between cells is in the
traffic file and the configuration file; this module names neither.  It
drives any gluon net that ``make_train_step`` takes.

What a configuration declares (``chipbench/configs/<config>.json``):
``input.kind``, ``image`` (the default: ``height``, ``width``,
``channels``, ``dtype``; labels are class ids below ``classes``, one a
row) or ``tokens`` (``seq``, ``vocab``, ``dtype``, ``ids``: ``zipf1``,
which is ``floor(vocab ** u)`` for uniform ``u``; a row is ``seq + 1``
ids drawn so, the input its first ``seq`` and the labels its last: the
next token; the loss is the mean over all positions).  A row of
the batch is an image or a sequence: ``window["images"]`` counts rows, so
``images_per_s`` reads sequences a second under tokens, and the result
carries ``tokens`` (a step's) beside ``batch``.  ``optimizer.name`` is
``sgd`` (``momentum``) or ``adamw`` (``beta1``, ``beta2``, ``epsilon``),
each with ``learning_rate`` and ``wd``.  Under ``adamw`` both sides read
the first gradient from the first moment after the first step
(``opt_state[name][0] / (1 - beta1)``; ``chipbench/compare.py`` says
why), which needs a state kept by the leaf's name: not yet with
``optimizer_sharding``, whose state is kept by bucket.

What the tests call, and what a configuration of another shape has to
find here: :func:`build_net`, :func:`sample_input`, :func:`make_pool`,
:func:`specs_of`, :func:`reference_side`, :func:`compared_names`.

Traffic parameters (``chipbench/traffic/<traffic>.json``):
``batch_per_chip``, ``pool`` (host batches), ``inflight`` (steps not yet
awaited), ``optimizer_sharding`` (``null`` or ``"ps"``, four chips),
``check_steps`` (first steps the reference follows), ``warm_steps``,
``span_steps`` (steps to one timed span of the tail), ``trace_steps``,
``limits`` (the limit of every number compared).
"""
import contextlib
import importlib
import json
import os
import shutil
import time

import numpy as np

from chipbench import compare, trace_reduce, weights

SPANS = ("feed_wait", "dispatch", "loss_read")


# ------------------------------------------------------------------ build
def build_net(config, batch):
    """The configuration's net from the program's model zoo, its deferred
    shapes resolved by an abstract forward at the cell's own batch (no
    program is compiled for it) and every parameter then made anew."""
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import nn

    c = config["constructor"]
    fn = getattr(importlib.import_module(c["module"]), c["function"])
    if c.get("default_layout"):
        with nn.default_layout(c["default_layout"]):
            net = fn(*c.get("args", []), **c.get("kwargs", {}))
    else:
        net = fn(*c.get("args", []), **c.get("kwargs", {}))
    ctx = mx.tpu(0)
    tokens = input_kind(config) == "tokens"
    net.initialize(init=mx.init.Zero(), ctx=ctx)
    jax.eval_shape(lambda x: net(mx.nd.NDArray(x))._data,
                   jax.ShapeDtypeStruct(
                       input_shape(config, batch),
                       jnp.int32 if tokens else jnp.float32))
    # the abstract forward left tracers in the deferred parameters
    net.initialize(init=mx.init.Zero(), ctx=ctx, force_reinit=True)
    return net


def place_weights(params, names, arrays):
    """``params`` with ``arrays`` in the place of ``names``, each laid
    out as the program laid out the array it replaces."""
    import jax

    out = dict(params)
    for n, a in zip(names, arrays):
        if tuple(a.shape) != tuple(params[n].shape):
            raise ValueError(
                f"the reference's parameter {tuple(a.shape)} does not fit "
                f"the program's {n} {tuple(params[n].shape)}")
        out[n] = jax.device_put(a.astype(params[n].dtype),
                                params[n].sharding)
    return out


def input_kind(config):
    return config["input"].get("kind", "image")


def input_shape(config, batch):
    i = config["input"]
    if input_kind(config) == "tokens":
        return (batch, i["seq"])
    return (batch, i["height"], i["width"], i["channels"])


def sample_input(config, batch, key):
    """One batch ``(x, y)`` as the program is fed it, drawn from ``key``:
    images in the input type with float class ids, or token ids with the
    next token at every position as float labels."""
    import jax
    import jax.numpy as jnp

    i = config["input"]
    kx, ky = jax.random.split(key)
    if input_kind(config) == "image":
        x = jax.random.normal(kx, input_shape(config, batch),
                              jnp.float32).astype(i["dtype"])
        y = jax.random.randint(ky, (batch,), 0, config["classes"])
        return x, y.astype(jnp.float32)
    if i["ids"] != "zipf1":
        raise ValueError(f"unknown distribution of ids {i['ids']!r}")
    u = jax.random.uniform(kx, (batch, i["seq"] + 1), jnp.float32)
    ids = jnp.minimum(jnp.floor(i["vocab"] ** u).astype(jnp.int32),
                      i["vocab"] - 1)
    return ids[:, :-1].astype(i["dtype"]), ids[:, 1:].astype(jnp.float32)


def make_pool(config, batch, n, seed):
    """``n`` host batches as :func:`sample_input` draws them, on the
    device from the seed, copied back."""
    import jax

    draw = jax.jit(lambda key: sample_input(config, batch, key))
    key = jax.random.fold_in(weights.seed_key(seed), 1)
    pool = []
    for k in range(n):
        x, y = draw(jax.random.fold_in(key, k))
        pool.append((np.asarray(x), np.asarray(y)))
    return pool


def cycle(pool):
    i = 0
    while True:
        yield pool[i % len(pool)]
        i += 1


# ------------------------------------------------------------------- loop
class Loop:
    """The loop that both the first steps and the window run."""

    def __init__(self, step, params, opt_state, feed, inflight):
        import jax

        self.step, self.params, self.opt_state = step, params, opt_state
        self.feed, self.inflight = feed, inflight
        self.key = jax.random.key(0)  # no layer of these nets draws
        self.t = 0
        self.pending = []
        self.losses = []
        self.done_at = []
        self.spans = {k: [] for k in SPANS}
        self.annotate = None

    def _span(self, name):
        return self.annotate(name) if self.annotate \
            else contextlib.nullcontext()

    def _read_one(self):
        t0 = time.perf_counter()
        with self._span("cb_loss_read"):
            self.losses.append(float(self.pending.pop(0)))
        t1 = time.perf_counter()
        self.spans["loss_read"].append((t0, t1))
        self.done_at.append(t1)

    def one(self):
        t0 = time.perf_counter()
        with self._span("cb_feed_wait"):
            x, y = self.feed.next()
        t1 = time.perf_counter()
        with self._span("cb_dispatch"):
            self.t += 1
            loss, self.params, self.opt_state = self.step(
                self.params, self.opt_state, x._data, y._data, self.key,
                float(self.t))
        t2 = time.perf_counter()
        self.spans["feed_wait"].append((t0, t1))
        self.spans["dispatch"].append((t1, t2))
        self.pending.append(loss)
        if len(self.pending) > self.inflight:
            self._read_one()

    def drain(self):
        while self.pending:
            self._read_one()

    def mark(self):
        """Forget what was recorded so far (the steps stay taken)."""
        self.losses, self.done_at = [], []
        self.spans = {k: [] for k in SPANS}


def host_leaves(params, names):
    return [np.asarray(params[n], dtype=np.float32) for n in names]


# -------------------------------------------------------------- reference
def reference_steps(ref, config, specs, w0, batches, groups,
                    precision="float32", rows=None, only_group=None,
                    frozen=()):
    """The plain reference through the same first steps: losses, the
    state after the first step and after the last, and the first gradient
    where the optimizer's state holds it (else None).  The optimizer moves
    every leaf but those of the kinds that the forward pass moves itself
    (``weights.MOVED_BY_FORWARD``).  ``groups`` is how many equal parts of
    a batch are normalised each by its own statistics (one per chip where
    the program shards the batch); their losses, gradients and moved
    statistics are averaged.  Planted faults: ``rows`` keeps only that
    many rows of each part (part of the batch left out, the mean taken
    over the rest); ``only_group`` takes that one part for the whole (the
    exchange between chips left out); ``frozen`` names kinds of leaf
    that are left as they were (a step that returns part of its state
    unchanged)."""
    import jax.numpy as jnp

    from chipbench import refmath

    fn = refmath.loss_and_grad(ref.forward, config["arch"], precision)
    slots, update, first_gradient = refmath.optimizer_rule(
        config["optimizer"])
    kinds = [spec[0] for spec in specs]
    w = [jnp.asarray(a) for a in w0]
    state = [(jnp.zeros_like(a),) * slots for a in w]
    losses, w1, grad1 = [], None, None
    for t, (x, y) in enumerate(batches, 1):
        n = x.shape[0] // groups
        parts = range(groups) if only_group is None else [only_group]
        loss, moved = 0.0, {}
        grads = [jnp.zeros_like(a) for a in w]
        for g in parts:
            sl = slice(g * n, g * n + (rows or n))
            (lg, mv), gg = fn(w, jnp.asarray(x[sl]), jnp.asarray(y[sl]))
            loss = loss + float(lg) / len(parts)
            grads = [a + b / len(parts) for a, b in zip(grads, gg)]
            for i, v in mv.items():
                moved[i] = moved.get(i, 0.0) + v / len(parts)
        for i, kind in enumerate(kinds):
            if kind in frozen:
                continue
            if kind in weights.MOVED_BY_FORWARD:
                w[i] = moved[i]
            else:
                w[i], state[i] = update(w[i], state[i], grads[i], float(t))
        losses.append(loss)
        if w1 is None:
            w1 = [np.asarray(a) for a in w]
            if first_gradient:
                grad1 = [np.asarray(first_gradient(s)) for s in state]
    return losses, w1, [np.asarray(a) for a in w], grad1


# -------------------------------------------------------------------- run
def build(cell):
    """The program's compiled step with its state, as a training script
    builds it; ``names`` are the leaves of its parameters in the order
    the net applies them."""
    from mxnet_tpu import autotune, gluon, parallel
    from mxnet_tpu.config import setup_compilation_cache

    config, traffic, say = cell["config"], cell["traffic"], cell["say"]
    chips = cell["chips"]
    devices = cell["devices"][:chips]
    batch = traffic["batch_per_chip"] * chips
    say(f"compile cache: {setup_compilation_cache()}")
    net = build_net(config, batch)
    mesh = parallel.get_mesh((chips,), ("data",), devices=devices) \
        if chips > 1 else None
    opt = config["optimizer"]
    step, params, opt_state = parallel.make_train_step(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), optimizer=opt["name"],
        learning_rate=opt["learning_rate"], wd=opt["wd"],
        compute_dtype=config["compute_dtype"], mesh=mesh,
        optimizer_sharding=traffic.get("optimizer_sharding"), donate=True,
        **{k: opt[k] for k in ("momentum", "beta1", "beta2", "epsilon")
           if k in opt})
    say("autotune winners loaded: "
        + str({k: v.get("winner") for k, v in
               autotune.last_report().items()} or "none"))
    # the order in which the net's layers are applied (the step's own
    # dict comes back sorted by name)
    names = list(parallel.functionalize(net, train=True)[0])
    return {"step": step, "params": params, "opt_state": opt_state,
            "names": names, "mesh": mesh, "devices": devices,
            "batch": batch,
            "groups": chips if traffic.get("optimizer_sharding") else 1}


def specs_of(cell):
    """The reference's ``param_specs(arch, width of the input, classes)``:
    the channels and classes of an image configuration, the vocabulary
    twice of a token one."""
    config = cell["config"]
    if input_kind(config) == "tokens":
        vocab = config["input"]["vocab"]
        return cell["reference"].param_specs(config["arch"], vocab, vocab)
    return cell["reference"].param_specs(
        config["arch"], config["input"]["channels"], config["classes"])


def compared_names(traffic):
    """The names a traffic file's ``limits`` has to hold: every number
    this kind's comparison can yield."""
    return set(compare.NAMES) | {
        f"loss_gap_{k}" for k in range(1, traffic["check_steps"] + 1)}


def start(cell, built, seed, params, opt_state, pool_size):
    """Weights and batches from ``seed`` and a loop ready for its first
    step: ``(loop, feed, w0, pool)``."""
    from mxnet_tpu.io.device_feed import DeviceFeedIter

    config = cell["config"]
    w0 = weights.make(specs_of(cell), seed)
    params = place_weights(params, built["names"], w0)
    w0 = [np.asarray(a) for a in w0]
    pool = make_pool(config, built["batch"], pool_size, seed)
    feed = DeviceFeedIter(
        cycle(pool), mesh=built["mesh"],
        device=None if built["mesh"] else built["devices"][0])
    step = (cell.get("wrap_step") or (lambda s: s))(built["step"])
    loop = Loop(step, params, opt_state, feed, cell["traffic"]["inflight"])
    return loop, feed, w0, pool


def first_steps(cell, built, loop, w0):
    """The first steps through the loop's own call and feed, each awaited:
    the program's side of the comparison."""
    from chipbench import refmath

    opt = cell["config"]["optimizer"]
    first_gradient = refmath.optimizer_rule(opt)[2]
    w1 = grad1 = None
    for k in range(cell["traffic"]["check_steps"]):
        loop.one()
        loop.drain()
        if k == 0:
            w1 = host_leaves(loop.params, built["names"])
            if first_gradient:
                grad1 = [np.asarray(first_gradient(
                    [np.asarray(s, np.float32)
                     for s in loop.opt_state[n]])) for n in built["names"]]
    w_last = host_leaves(loop.params, built["names"])
    return compare.side(loop.losses, w0, w1, w_last, opt["learning_rate"],
                        grad=grad1)


def reference_side(cell, built, w0, pool, **fault):
    import jax

    n = cell["traffic"]["check_steps"]
    with jax.default_device(built["devices"][0]):
        losses, w1, w_last, grad1 = reference_steps(
            cell["reference"], cell["config"], specs_of(cell), w0,
            pool[:n], built["groups"], **fault)
    return compare.side(losses, w0, w1, w_last,
                        cell["config"]["optimizer"]["learning_rate"],
                        grad=grad1)


def run(cell):
    """One run of one cell.  ``cell`` carries the configuration, the
    traffic, the arguments, the reference module, the devices and the
    clock's origin (see ``chipbench/run.py``); returns what ``run.py``
    prints."""
    traffic, say = cell["traffic"], cell["say"]
    compiles = _count_compiles()
    built = build(cell)
    names, batch = built["names"], built["batch"]
    loop, feed, w0, pool = start(
        cell, built, cell["seed"], built.pop("params"),
        built.pop("opt_state"), traffic["pool"])
    say(f"net, step and weights built: {len(names)} leaves, "
        f"{sum(a.size for a in w0):,} elements; batch {batch} on "
        f"{cell['chips']} chip(s)")
    try:
        # ---- the first steps, which the reference follows afterwards
        prog = first_steps(cell, built, loop, w0)
        say("first losses: " + " ".join(f"{v:.4f}" for v in loop.losses))
        for _ in range(traffic["warm_steps"]):
            loop.one()
        loop.drain()
        loop.mark()
        feed0 = feed.stats()
        compiles_before = compiles["n"]

        # ---- the window
        setup_s = time.perf_counter() - cell["t0"]
        t_start = time.perf_counter()
        while time.perf_counter() - t_start < cell["seconds"]:
            loop.one()
        loop.drain()
        t_end = loop.done_at[-1]
        window = _window_record(loop, feed, feed0, t_start, t_end, batch)
        window["compiles_in_window"] = compiles["n"] - compiles_before
        finite = bool(np.all(np.isfinite(loop.losses)))

        # ---- a traced tail of the same loop, and the step's text
        trace = hlo_text = None
        if cell["trace"]:
            events = _traced_tail(cell, loop, traffic["trace_steps"])
            x, y = feed.next()
            hlo_text = built["step"].lower(
                loop.params, loop.opt_state, x._data, y._data, loop.key,
                1.0).compile().as_text()
            trace = trace_reduce.reduce(events, hlo_text)
            trace["steps"] = traffic["trace_steps"]
        memory_peak = max(_peak_bytes(d) for d in built["devices"])
    finally:
        feed.close()
    # ---- free the program's state, then the reference
    loop.params = loop.opt_state = loop.step = None
    built.pop("step")
    t_ref = time.perf_counter()
    ref = reference_side(cell, built, w0, pool)
    values, where = compare.numbers(prog, ref)
    say(f"reference: {traffic['check_steps']} steps in "
        f"{time.perf_counter() - t_ref:.1f} s; losses "
        + " ".join(f"{v:.4f}" for v in ref["losses"])
        + "; widest gaps at " + ", ".join(
            f"{k}: {names[i]}" for k, i in where.items()))
    ok, table, reported = compare.verdict(values, traffic["limits"])
    return {
        "correct": bool(ok and finite
                        and window["compiles_in_window"] == 0),
        "attempted": window["steps"],
        "failed": 0 if finite else window["steps"],
        "setup_s": setup_s, "window": window, "trace": trace,
        "hlo_text": hlo_text, "memory_peak_bytes": int(memory_peak),
        "reported": reported, "compared": table, "batch": batch,
        "tokens": batch * cell["config"]["input"]["seq"]
        if input_kind(cell["config"]) == "tokens" else None,
    }


def readings(cell, seeds, n_controls, flush=None, program_until=None,
             until=None):
    """The readings a limit is set from, in one process: for every seed
    the program's first steps against the reference, and then for the
    first ``n_controls`` seeds the planted faults (with several parts, all
    parts but the first left out, which is the exchange between chips
    left out; half of each batch part left out) and the control (the
    reference in the nearest precision below the configuration's) against
    the reference.  Returns ``{seed: {who: {number: value}, "correct":
    {who: the verdict under the cell's limits}, "leaves": every side's
    losses and leaf norms}, "names": the leaves' names}`` and hands the
    same to ``flush`` after every reading, so that a call cut short keeps
    what it read.  No seed's program starts later than ``program_until``
    seconds after the clock's origin, no reading later than ``until``: a
    call on the chip is paid by the second, and a cold one compiles for
    minutes.

    The chip holds the program's state once, whatever the cell's size:
    each seed takes the state the seed before it left, its weights placed
    anew from the seed and its optimizer state set to nought in place,
    which is the state that :func:`build` makes (a step whose state starts
    otherwise is refused).  The host keeps a seed's losses, leaf norms and
    batches, and draws its weights again for the reference."""
    import jax
    import jax.numpy as jnp

    config, traffic, say = cell["config"], cell["traffic"], cell["say"]
    built = build(cell)
    # bfloat16's; the tests' float32 twin of a cell takes the same
    below = {"bfloat16": "float8", "float32": "float8"}[
        config["compute_dtype"]]
    faults = {"control_" + below: {"precision": below},
              "fault_half_batch": {"rows": traffic["batch_per_chip"] // 2}}
    if built["groups"] > 1:
        faults = dict(fault_no_exchange={"only_group": 0}, **faults)

    def late(limit):
        return limit is not None \
            and time.perf_counter() - cell["t0"] > limit

    params, opt_state = built.pop("params"), built.pop("opt_state")
    zeros = jax.jit(lambda tree: jax.tree_util.tree_map(
        lambda a: jnp.all(a == 0), tree))(opt_state)
    if not all(jax.tree_util.tree_leaves(zeros)):
        raise ValueError("readings restart each seed's optimizer state at "
                         "nought, and this step's does not start there")
    wipe = jax.jit(
        lambda tree: jax.tree_util.tree_map(jnp.zeros_like, tree),
        donate_argnums=0,
        out_shardings=jax.tree_util.tree_map(lambda a: a.sharding,
                                             opt_state))
    kept = {}
    for seed in seeds:
        if kept and late(program_until):
            say(f"no time for the program on seed {seed} and after")
            break
        loop, feed, w0, pool = start(cell, built, seed, params,
                                     wipe(opt_state), traffic["check_steps"])
        params = opt_state = None  # the loop's now: one copy on the chip
        try:
            kept[seed] = (first_steps(cell, built, loop, w0), pool)
        finally:
            feed.close()
        say(f"seed {seed}: program losses "
            + " ".join(f"{v:.4f}" for v in loop.losses))
        params, opt_state = loop.params, loop.opt_state
        loop.params = loop.opt_state = None
    built.pop("step")
    del params, opt_state
    out = {"names": built["names"]}

    def weights_of(seed):
        return [np.asarray(a) for a in weights.make(specs_of(cell), seed)]

    def read(seed, who, side, ref):
        values = compare.numbers(side, ref)[0]
        entry = out.setdefault(seed, {"correct": {}, "leaves": {
            "reference": _plain(ref)}})
        entry[who] = values
        entry["correct"][who] = compare.verdict(values, traffic["limits"])[0]
        entry["leaves"][who] = _plain(side)
        say(f"seed {seed} {who}: correct {entry['correct'][who]} "
            + json.dumps(values))
        if flush:
            flush(out)

    refs = {}
    for seed, (prog, pool) in kept.items():
        if refs and late(until):
            say(f"no time for the reference on seed {seed} and after")
            break
        refs[seed] = reference_side(cell, built, weights_of(seed), pool)
        read(seed, "program", prog, refs[seed])
    for who, fault in faults.items():
        for seed in list(refs)[:n_controls]:
            if late(until):
                say(f"no time for {who} on seed {seed} and after")
                break
            read(seed, who, reference_side(cell, built, weights_of(seed),
                                           kept[seed][1], **fault),
                 refs[seed])
    return out


def _plain(side):
    """A side's losses and leaf norms as lists, for the look behind a
    limit."""
    return {k: np.asarray(v).tolist() for k, v in side.items()}


def _peak_bytes(device):
    """The peak of a chip's memory: what arrays held at their peak and,
    where the runtime reports it apart (a TPU's ``peak_bytes_reserved``),
    what compiled programs reserved for their temporaries at theirs."""
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use", 0) \
        + stats.get("peak_bytes_reserved", 0)


def _count_compiles():
    """Counts programs that XLA compiled or read from the persistent
    cache in this process: none may come inside the window."""
    import jax

    box = {"n": 0}

    def on_event(name, *_, **__):
        if name in ("/jax/compilation_cache/cache_hits",
                    "/jax/compilation_cache/cache_misses"):
            box["n"] += 1

    jax.monitoring.register_event_listener(on_event)
    return box


def _window_record(loop, feed, feed0, t_start, t_end, batch):
    feed1 = feed.stats()
    done = np.array(loop.done_at)
    return {
        "steps": len(done), "images": len(done) * batch,
        "seconds": t_end - t_start, "t_start": t_start,
        "done_at": done,
        "spans": {k: np.array(v) for k, v in loop.spans.items()},
        "feed": {k: feed1[k] - feed0[k] for k in feed1},
    }


def _traced_tail(cell, loop, n_steps):
    """``n_steps`` more steps of the same loop under the profiler; the
    events of the trace (``chipbench/trace_reduce.py``).  The host side
    is traced at its coarsest level: at the default one the feed's
    host-side layout change alone writes millions of events a second."""
    import jax

    trace_dir = cell["trace_dir"]
    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    loop.annotate = jax.profiler.TraceAnnotation
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("cb_traced_window"):
            for _ in range(n_steps):
                loop.one()
            loop.drain()
    finally:
        jax.profiler.stop_trace()
        loop.annotate = None
    try:
        path = trace_reduce.find_xplane(trace_dir)
        cell["say"](f"trace: {os.path.getsize(path):,} bytes")
        return trace_reduce.load_xplane(path)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
