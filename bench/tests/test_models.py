"""Model modules: what only one network knows lives in
bench/models/<model>.py, found by the name in its configuration file, so a
network is added as files alone.  The shared code draws the same pools and
reads the same reference as it did before the mesh net moved into its
module, and no shared file names a mesh-net key."""
import glob
import hashlib
import json
import os
import shutil

import pytest

from benchtest import BENCH, dump, load, tiny_root

import cells
import reference
import traffic

SEED = 2147483659
# Computed with the shared code as it stood before the model modules, on
# the CPU: the smoke-size mesh1k cell's pool for SEED, and the reference's
# three steps on its first three batches.
POOL_SHA256 = \
    "39a4dcc17e010f468e2d58a6856a4be9a3670951e482e629839cdacc154d5787"
LOSSES = [0.7901992797851562, 0.8537707328796387, 0.7995336651802063]
READINGS_SHA256 = \
    "43a12ab620171fbd4b5b39a5d4bf427d591b2202b1c2144b871006f86d818cc4"
MESH_ONLY = ("convs_per_block", "pred_kernel", "label_positive_rate")
SHARED = ["cells", "check", "devtrace", "flops", "harness", "limits",
          "reference", "run", "traffic"]


def pool_sha256(pool):
    h = hashlib.sha256()
    for b in pool:
        for k in sorted(b):
            h.update(k.encode())
            h.update(str(b[k].dtype).encode())
            h.update(b[k].tobytes())
    return h.hexdigest()


def test_pool_and_reference_readings_are_bit_identical(tmp_path):
    import jax
    cell = cells.resolve("tiny", tiny_root(tmp_path))
    pool = traffic.batch_pool(cell.config, cell.traffic, SEED)
    assert pool_sha256(pool) == POOL_SHA256
    ref = reference.Runner(cell.config, cell.traffic["schedule_steps"],
                           jax.devices()[:1],
                           cell.config["matmul_precision"]).run(SEED,
                                                                pool[:3])
    assert ref["losses"] == LOSSES
    assert hashlib.sha256(json.dumps(ref, sort_keys=True).encode()) \
        .hexdigest() == READINGS_SHA256


def test_a_model_added_as_files_alone_runs_correct(tmp_path):
    import time

    import jax

    import harness
    from benchtest import FAKE_PEAKS
    root = tiny_root(tmp_path, name="copy")
    shutil.copy(os.path.join(BENCH, "models", "meshnet.py"),
                os.path.join(root, "bench", "models", "copied.py"))
    path = os.path.join(root, "bench", "configs", "copy.json")
    dump(dict(load(path), model="copied"), path)
    cell = cells.resolve("copy", root)
    assert cells.model_of(cell.config).__file__ == cell.model_path
    assert cell.model_path.endswith(os.path.join("models", "copied.py"))
    res = harness.run_cell(cell, 7, 0.5, False, time.time(),
                           jax.devices()[:1], FAKE_PEAKS)
    assert res["correct"], res["check"]


def test_a_missing_model_module_is_named(tmp_path):
    root = tiny_root(tmp_path, name="lost")
    path = os.path.join(root, "bench", "configs", "lost.json")
    dump(dict(load(path), model="absent"), path)
    with pytest.raises(cells.CellError, match="absent.py"):
        cells.resolve("lost", root)


@pytest.mark.parametrize("path", [f"{m}.py" for m in SHARED] + sorted(
    os.path.relpath(p, BENCH)
    for p in glob.glob(os.path.join(BENCH, "metrics", "*.py"))))
def test_no_shared_file_names_a_mesh_net_key(path):
    with open(os.path.join(BENCH, path)) as f:
        text = f.read()
    assert [k for k in MESH_ONLY if k in text] == []
