"""The witness: a configuration that is no convnet, added by files alone.

``tests/chipbench/witness/`` holds what a ``model_config`` PR would add
for a token model: a configuration whose input is token ids and whose
optimizer is AdamW, whose table's rows run over a sequence, its plain
reference, its traffic file, a kernels file with one
made-up name, the function that builds its net from the zoo's layers,
a row whose work is given outright (the look-up of the embeddings, no
product), a metric that reads the time of one of its blocks and one that
reads the time of its kernel, a recorded trace and the checks of its
own that run the two over it (``tokwit_checks.py``), and its entries
(``entries.json``: one configuration, one cell, the cell's two per-layer
metrics).  Its bank-shaped leaf, the hidden layer's weights, is a 2-D
``dense`` leaf.  The test copies ``chipbench/``, the suite and the
tree's own ``BENCHMARK.json`` to a temporary directory, lays the
witness's files beside them, appends its entries at the end of
``configs``, ``workloads`` and ``per_layer``, edits no other file (every
copied file's hash is compared afterwards), and runs the suite there:
the suite's generic checks for the witness's cell, and every test of the
suite that opens ``BENCHMARK.json`` (names it, or reaches it through
``load_cell``, itself or by a helper or fixture of its file), so that a
test that holds an entry by its place fails here.  What it does by hand
is what such a PR does in the tree.
"""
import ast
import hashlib
import json
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
WITNESS = os.path.join(HERE, "witness")
ENTRIES = "entries.json"

#: the suite's checks that have to pass for the witness, by test id
REQUIRED = (
    "test_benchmark_json_has_exactly_the_contract_keys",
    "test_names_are_unique_and_plain",
    "test_cell_resolves_to_its_files[tokwit_train]",
    "test_layer_table_holds_the_reference_weights[tokwit]",
    "test_reference_agrees_with_the_zoo_forward[tokwit]",
    "test_sound_run_is_correct_and_each_fault_is_not[tokwit_train]",
    "test_lower_precision_control_reads_wider_than_the_reference"
    "[tokwit_train]",
    "test_every_reader_reads_a_made_up_run_of_the_cell[tokwit_train]",
    "test_every_listed_kernel_is_a_conv_dot_event",
    "test_run_refuses_to_measure_on_a_cpu",
    "test_embedding_row_is_the_hand_count",
    "test_readers_of_a_block_and_of_a_kernel_read_the_recorded_trace",
)


def _files(top):
    out = {}
    for folder, dirs, names in os.walk(top):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in names:
            if not name.endswith(".pyc"):
                path = os.path.join(folder, name)
                with open(path, "rb") as f:
                    out[os.path.relpath(path, top)] = hashlib.sha256(
                        f.read()).hexdigest()
    return out


#: the calls through which a test opens ``BENCHMARK.json`` without naming
#: it: ``chipbench/run.py``'s ``load_cell``, and the ``main`` of
#: ``run.py`` and ``readings.py``, which go through it
OPENERS = frozenset({"load_cell", "main"})


def _opens_the_benchmark(node, helpers):
    """Whether ``node`` names ``BENCHMARK.json``, calls one of
    :data:`OPENERS` or of ``helpers`` (``f(...)`` or ``x.f(...)``), or
    takes one of ``helpers`` as an argument (a fixture)."""
    names = set(OPENERS) | helpers
    for n in ast.walk(node):
        if isinstance(n, ast.Constant) and n.value == "BENCHMARK.json":
            return True
        if isinstance(n, ast.Call):
            f = n.func
            if (f.attr if isinstance(f, ast.Attribute)
                    else getattr(f, "id", None)) in names:
                return True
    return isinstance(node, ast.FunctionDef) \
        and any(a.arg in helpers for a in node.args.args)


def _readers_of_the_benchmark():
    """Beyond ``test_chipbench.py``, which runs whole: the ids of the
    suite's tests that open ``BENCHMARK.json``, themselves or through a
    function or fixture of their file that does, or the whole file where
    its top level does."""
    found = []
    for name in sorted(os.listdir(HERE)):
        if not name.startswith("test_") or not name.endswith(".py") \
                or name in ("test_chipbench.py", "test_witness.py"):
            continue
        with open(os.path.join(HERE, name)) as f:
            tree = ast.parse(f.read())
        defs = [n for n in tree.body if isinstance(n, ast.FunctionDef)]
        tests = [n for n in defs if n.name.startswith("test_")]
        helpers = set()
        while True:
            more = {n.name for n in defs if n not in tests
                    and n.name not in helpers
                    and _opens_the_benchmark(n, helpers)}
            if not more:
                break
            helpers |= more
        path = "tests/chipbench/" + name
        if _opens_the_benchmark(ast.Module(
                body=[n for n in tree.body if n not in defs],
                type_ignores=[]), helpers):
            found.append(path)
        else:
            found += [f"{path}::{t.name}" for t in tests
                      if _opens_the_benchmark(t, helpers)]
    return found


def test_a_token_configuration_is_added_by_files_alone(tmp_path):
    copy = tmp_path / "tree"
    shutil.copytree(os.path.join(ROOT, "chipbench"), copy / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(HERE, copy / "tests" / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "witness"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), copy)
    before = _files(copy)
    added = _files(WITNESS)
    del added[ENTRIES]
    assert not set(added) & set(before), "the witness edits no file"
    shutil.copytree(WITNESS, copy, dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns(ENTRIES))

    # the tree's own benchmark with the witness's entries appended: one
    # configuration, one cell, and that cell's own two metrics
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(WITNESS, ENTRIES)) as f:
        entries = json.load(f)
    assert [len(entries[k]) for k in ("configs", "workloads",
                                      "per_layer")] == [1, 1, 2]
    assert [m["workloads"] for m in entries["per_layer"]] \
        == [["tokwit_train"]] * 2
    # the tree's own configurations and cells, whose cases are the
    # tree's suite's to run (their files are in the copy too)
    own = sorted(f[:-len(".json")] for f in os.listdir(
        os.path.join(ROOT, "chipbench", "configs")))
    own += [w["name"] for w in bench["workloads"]]
    for key, more in entries.items():
        bench[key] = bench[key] + more
    with open(copy / "BENCHMARK.json", "w") as f:
        json.dump(bench, f, indent=1)

    # the suite's generic cases for the witness, and by id every test
    # that opens BENCHMARK.json
    readers = _readers_of_the_benchmark()
    assert {"tests/chipbench/test_nemotron3.py::"
            "test_benchmark_entries_name_the_cell",
            "tests/chipbench/test_feed_metrics.py::"
            "test_readers_go_through_run_py"} <= set(readers)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("XLA_FLAGS", None)  # one device: the witness has one chip
    done = subprocess.run(
        [sys.executable, "-m", "pytest", "tests/chipbench/test_chipbench.py",
         "tests/chipbench/tokwit_checks.py", *readers, "-v",
         "-p", "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly",
         "-k", " and ".join("not " + name for name in own)],
        cwd=copy, env=env, capture_output=True, text=True, timeout=600)
    tail = done.stdout[-6000:] + done.stderr[-2000:]
    assert done.returncode == 0, tail
    for test in REQUIRED:
        assert f"::{test} PASSED" in done.stdout, (test, tail)
    lines = done.stdout.splitlines()
    for test in readers:
        assert any(line.startswith(test) and " PASSED" in line
                   for line in lines), (test, tail)

    after = _files(copy)
    assert {k: after[k] for k in before if k != "BENCHMARK.json"} \
        == {k: v for k, v in before.items() if k != "BENCHMARK.json"}, \
        "a copied file changed"
    assert set(after) - set(before) >= set(added)
