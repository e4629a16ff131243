"""The witness: a configuration that is no convnet, added by files alone.

``tests/chipbench/witness/`` holds what a ``model_config`` PR would add
for a token model: a configuration whose input is token ids and whose
optimizer is AdamW, whose table's rows run over a sequence, its plain
reference, its traffic file, a kernels file with one
made-up name, the function that builds its net from the zoo's layers,
a row whose work is given outright (the look-up of the embeddings, no
product), a metric that reads the time of one of its blocks and one that
reads the time of its kernel, a recorded trace and the checks of its
own that run the two over it (``tokwit_checks.py``), and a
``BENCHMARK.json`` that names them.  Its bank-shaped leaf, the hidden
layer's weights, is a 2-D ``dense`` leaf.  The test copies ``chipbench/``
and the suite to a temporary directory, lays the witness's files beside
them, edits none (every copied file's hash is compared afterwards), and
runs the suite there: what it does by hand is what such a PR does in the
tree.
"""
import hashlib
import json
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
WITNESS = os.path.join(HERE, "witness")

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


def test_a_token_configuration_is_added_by_files_alone(tmp_path):
    copy = tmp_path / "tree"
    shutil.copytree(os.path.join(ROOT, "chipbench"), copy / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.makedirs(copy / "tests" / "chipbench")
    shutil.copy(os.path.join(HERE, "test_chipbench.py"),
                copy / "tests" / "chipbench")
    before = _files(copy)
    added = _files(WITNESS)
    assert not set(added) & set(before), "the witness edits no file"
    shutil.copytree(WITNESS, copy, dirs_exist_ok=True)

    # every metric that is read in every cell is read in the witness's
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(copy / "BENCHMARK.json") as f:
        witness = json.load(f)
    assert witness["end_to_end"] == bench["end_to_end"]
    everywhere = [m for m in bench["per_layer"] if "workloads" not in m]
    assert witness["per_layer"][:len(everywhere)] == everywhere
    # and its own: the time of a block and of a kernel, in its cell alone
    assert [m["workloads"] for m in witness["per_layer"][len(everywhere):]] \
        == [["tokwit_train"]] * 2

    # the suite's cases for the witness, not those of the tree's own
    # configurations (their files are in the copy too)
    others = " and ".join(
        "not " + name[:-len(".json")] for name in sorted(
            os.listdir(os.path.join(ROOT, "chipbench", "configs"))))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("XLA_FLAGS", None)  # one device: the witness has one chip
    done = subprocess.run(
        [sys.executable, "-m", "pytest", "tests/chipbench/test_chipbench.py",
         "tests/chipbench/tokwit_checks.py", "-v", "-p", "no:cacheprovider",
         "-p", "no:xdist", "-p", "no:randomly", "-k", others],
        cwd=copy, env=env, capture_output=True, text=True, timeout=600)
    tail = done.stdout[-6000:] + done.stderr[-2000:]
    assert done.returncode == 0, tail
    for test in REQUIRED:
        assert f"::{test} PASSED" in done.stdout, (test, tail)

    after = _files(copy)
    assert {k: after[k] for k in before} == before, "a copied file changed"
    assert set(after) - set(before) >= set(added)
