"""Round-6 satellite fixes.

* _ps.py sync merge accumulates half-precision keys in fp32 (native
  shard widens through double) and casts to the stored dtype once, at
  apply time.
* VariationalDropoutCell allows input/output-only dropout over a
  BidirectionalCell (the bidirectional guard applies to STATE dropout
  only, matching the reference).
* config registry carries the round's perf knobs.
"""
import os

import numpy as onp

import jax.numpy as jnp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon


# ------------------------------------------------------ PS fp32 sync merge
def _shard(size):
    from mxnet_tpu._ps import _ServerShard

    s = _ServerShard(0, size)
    s._sock.close()  # handle messages directly, no network
    return s


@pytest.mark.parametrize("half_dt", ["float16", "bfloat16"])
def test_ps_sync_merge_fp32_accumulation(half_dt):
    """4 workers push [1, eps/2, eps/2, eps/2]: merging in the stored
    half dtype collapses every small addend into 1.0; the fp32 merge
    with ONE apply-time cast keeps their sum."""
    dt = onp.dtype(half_dt) if half_dt == "float16" else \
        onp.asarray(jnp.zeros((), jnp.bfloat16)).dtype
    # eps = ulp at 1.0; eps/2 additions round away sequentially
    eps = 2.0 ** -10 if half_dt == "float16" else 2.0 ** -7
    s = _shard(4)
    s._handle(("init", "k", onp.zeros(2, dt), 0))
    grads = [1.0, eps / 2, eps / 2, eps / 2]
    for w, g in enumerate(grads):
        s._handle(("push", "k",
                   onp.full(2, g, onp.float32), "sync", {"sender": w}))
    got = s.values["k"]
    assert got.dtype == dt  # stored dtype never changes
    expect = onp.float32(sum(grads)).astype(dt)  # one final rounding
    stale = dt.type(1.0)  # what sequential half merging produces
    assert got[0] == expect != stale


def test_ps_sync_merge_f32_keys_unchanged():
    s = _shard(2)
    s._handle(("init", "k", onp.zeros(3, onp.float32), 0))
    s._handle(("push", "k", onp.ones(3, onp.float32), "sync",
               {"sender": 0}))
    s._handle(("push", "k", onp.full(3, 2.0, onp.float32), "sync",
               {"sender": 1}))
    onp.testing.assert_array_equal(s.values["k"],
                                   onp.full(3, 3.0, onp.float32))


def test_ps_sync_spush_fp32_accumulation():
    """Row-sparse sync rounds get the same fp32 merge treatment."""
    dt = onp.dtype("float16")
    s = _shard(4)
    s._handle(("init", "k", onp.zeros((2, 2), dt), 0))
    eps = 2.0 ** -10
    grads = [1.0, eps / 2, eps / 2, eps / 2]
    for w, g in enumerate(grads):
        s._handle(("spush", "k", onp.array([1], onp.int64),
                   onp.full((1, 2), g, onp.float32), "sync",
                   {"sender": w}))
    got = s.values["k"]
    assert got.dtype == dt
    expect = onp.float32(sum(grads)).astype(dt)
    assert got[1, 0] == expect != dt.type(1.0)
    assert (got[0] == 0).all()  # untouched row


# ----------------------------------------- sparse pull refreshes _store
class _FakePS:
    """Stands in for the PS backend: returns 'trained' values."""

    def __init__(self, trained):
        self.trained = trained

    def pull(self, key):
        return self.trained.reshape(-1)

    def spull(self, key, rows):
        return self.trained[onp.asarray(rows, onp.int64)]


def _fake_dist_store(shape=(4, 3)):
    from mxnet_tpu import kvstore as kv
    from mxnet_tpu import ndarray as nd

    trained = onp.arange(onp.prod(shape), dtype=onp.float32) \
        .reshape(shape) + 100.0
    s = kv.DistKVStore.__new__(kv.DistKVStore)
    s._sparse_keys = {"emb"}
    s._store = {"emb": nd.zeros(shape)}  # init-time values
    s._ps_active = lambda: False
    s._ps_backend = lambda: _FakePS(trained)
    s._ps_op = lambda k, fn: fn()
    s._ps_key = lambda k: f"t/{k}"
    return s, trained


def test_sparse_pull_refreshes_local_store():
    """A sparse pull() must update the worker's local mirror too
    (dense-path parity) — otherwise a post-restart refill re-seeds the
    shard with init-time values, silently discarding training."""
    from mxnet_tpu import ndarray as nd

    s, trained = _fake_dist_store()
    out = nd.zeros((4, 3))
    s.pull("emb", out=out)
    onp.testing.assert_allclose(out.asnumpy(), trained)
    onp.testing.assert_allclose(s._store["emb"].asnumpy(), trained)


def test_row_sparse_pull_merges_rows_into_store():
    from mxnet_tpu import ndarray as nd

    s, trained = _fake_dist_store()
    out = nd.zeros((4, 3))
    rows = nd.array(onp.array([1, 3], onp.float32))
    s.row_sparse_pull("emb", out=out, row_ids=rows)
    got = s._store["emb"].asnumpy()
    onp.testing.assert_allclose(got[[1, 3]], trained[[1, 3]])
    assert (got[[0, 2]] == 0).all()  # un-pulled rows keep local values
    o = out.asnumpy()
    onp.testing.assert_allclose(o[[1, 3]], trained[[1, 3]])
    assert (o[[0, 2]] == 0).all()


# -------------------------------------- VariationalDropoutCell bi-guard
def test_vardrop_io_only_over_bidirectional():
    """Input/output-only variational dropout over a BidirectionalCell:
    allowed (the reference gates the guard on drop_states) and the
    unroll runs through the base cell's own unroll."""
    from mxnet_tpu.gluon.contrib import rnn as crnn

    mx.random.seed(0)
    bi = gluon.rnn.BidirectionalCell(
        gluon.rnn.LSTMCell(4, input_size=6),
        gluon.rnn.LSTMCell(4, input_size=6))
    cell = crnn.VariationalDropoutCell(bi, drop_inputs=0.5,
                                       drop_outputs=0.5)
    cell.initialize()
    x = mx.nd.ones((2, 3, 6))
    with autograd.record(train_mode=True):
        outs, states = cell.unroll(3, x, layout="NTC",
                                   merge_outputs=True)
    assert outs.shape == (2, 3, 8)  # fwd+bwd concat
    o = outs.asnumpy()
    assert (o == 0).any()  # dropout actually applied
    # inference unroll: dropout is identity, still runs
    outs2, _ = cell.unroll(3, x, layout="NTC", merge_outputs=True)
    assert outs2.shape == (2, 3, 8)


def test_vardrop_state_dropout_over_bidirectional_still_asserts():
    from mxnet_tpu.gluon.contrib import rnn as crnn

    bi = gluon.rnn.BidirectionalCell(
        gluon.rnn.LSTMCell(4, input_size=6),
        gluon.rnn.LSTMCell(4, input_size=6))
    with pytest.raises(AssertionError, match="state dropout"):
        crnn.VariationalDropoutCell(bi, drop_states=0.5)


# -------------------------------------------------- config registry knobs
def test_round6_env_knobs_registered():
    from mxnet_tpu import config

    for name in ("JAX_COMPILATION_CACHE_DIR", "MXNET_CONV_1X1_DOT",
                 "MXNET_EXEC_DONATE"):
        assert name in config.list_env()
    assert config.get_env("MXNET_EXEC_DONATE") is True
    assert config.get_env("MXNET_CONV_1X1_DOT") is False


@pytest.mark.parametrize("placed_from_outside", [True, False])
def test_setup_compilation_cache(tmp_path, monkeypatch,
                                 placed_from_outside):
    """One rule: JAX_COMPILATION_CACHE_DIR if set, and no other;
    else the fixed git-ignored <checkout>/.cache/xla — never a
    temporary, per-pid or per-run name."""
    import jax

    from mxnet_tpu import autotune, config

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.delenv("MXNET_AUTOTUNE_CACHE_DIR", raising=False)
    if placed_from_outside:
        want = str(tmp_path / "cc")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    else:
        want = os.path.join(repo, ".cache", "xla")
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    # force re-activation even if an earlier test set the same dir
    config._CC_STATE["dir"] = None
    try:
        assert config.compilation_cache_dir() == want
        assert config.setup_compilation_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert os.path.isdir(want)
        # autotune.json decides which program is compiled: same place
        assert autotune.cache_path() == os.path.join(want,
                                                     "autotune.json")
    finally:
        # back to the suite's own cache for the tests that follow
        monkeypatch.undo()
        config._CC_STATE["dir"] = None
        config.setup_compilation_cache()
