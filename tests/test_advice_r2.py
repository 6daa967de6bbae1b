"""Regression tests for the round-2 advisor findings.

Each test pins the FIXED behavior:
  1. snapshot meta rides inside the .npz → single-rename atomic save
  2. RecordFile.close() works after the module-level native IO plane is
     disabled/reset (CDLL cached on the instance)
  3. a parallel.h-only edit makes the native build stale
  4. the flock()-based build lock ignores leftover lock files
     (covered by test_streaming.py::test_build_lock_stale_takeover)
"""

import json
import os
import shutil
import time

import numpy as np
import pytest

from znicz_tpu import prng
from znicz_tpu.backends import Device
from znicz_tpu.config import root
from znicz_tpu.loader import records as rec
from znicz_tpu.loader.records import RecordFile, write_records
from znicz_tpu.models import mnist
from znicz_tpu.snapshotter import SnapshotterToFile


def test_snapshot_load_needs_no_sidecar(tmp_path):
    """The .json sidecar is informational only: deleting it must not
    break load(), because meta commits atomically inside the npz."""
    root.mnist.synthetic.update({"n_train": 200, "n_valid": 100,
                                 "n_test": 0})
    root.mnist.minibatch_size = 100
    prng.seed_all(7)
    wf = mnist.MnistWorkflow()
    wf.initialize(device=Device.create("numpy"))
    snap = SnapshotterToFile(wf, directory=str(tmp_path), interval=1)
    wf.snapshotter = snap
    wf.loader.epoch_number = 3
    path = snap.save("current")
    os.unlink(path + ".json")          # sidecar gone — load must not care

    prng.seed_all(8)                   # perturb; restore must bring back
    wf2 = mnist.MnistWorkflow()
    wf2.initialize(device=Device.create("numpy"))
    meta = SnapshotterToFile.load(wf2, path)
    assert meta["epoch_number"] == 3
    assert wf2.loader.epoch_number == 3
    # arrays restored too (weights equal to the saved net's)
    w1 = [u for u in wf.units if getattr(u, "weights", None)][0]
    w2 = [u for u in wf2.units if getattr(u, "weights", None)][0]
    np.testing.assert_array_equal(np.asarray(w1.weights.mem),
                                  np.asarray(w2.weights.mem))


def test_snapshot_meta_not_restored_as_array(tmp_path):
    """__meta_json__ must never leak into restore_state's array dict
    (no unit is ever named __meta_json__, but keep the contract
    explicit: load() pops it before restoring)."""
    root.mnist.synthetic.update({"n_train": 200, "n_valid": 100,
                                 "n_test": 0})
    prng.seed_all(7)
    wf = mnist.MnistWorkflow()
    wf.initialize(device=Device.create("numpy"))
    snap = SnapshotterToFile(wf, directory=str(tmp_path))
    wf.snapshotter = snap
    path = snap.save("x")
    arrays = dict(np.load(path, allow_pickle=False))
    assert "__meta_json__" in arrays
    meta = json.loads(arrays["__meta_json__"].tobytes())
    assert "prng_state" in meta


def test_recordfile_close_survives_native_disable(tmp_path, monkeypatch):
    """ADVICE r2: close() used to re-resolve the library via _native();
    disabling native IO between open and close leaked the handle and
    raised.  The CDLL is now cached on the instance."""
    data = np.arange(4 * 2 * 2, dtype=np.float32).reshape(4, 2, 2, 1)
    p = write_records(str(tmp_path / "a.znr"), data,
                      np.arange(4, dtype=np.int32))[0]
    rf = RecordFile(p)
    if rf._h is None:
        pytest.skip("native reader unavailable")
    # simulate the kill switch flipping mid-life (tests/ops do this)
    monkeypatch.setenv("ZNICZ_TPU_NO_NATIVE_IO", "1")
    monkeypatch.setattr(rec, "_native_lib", None)
    monkeypatch.setattr(rec, "_native_tried", False)
    rf.close()                          # must not raise
    assert rf._h is None


def test_parallel_h_edit_triggers_rebuild(tmp_path, monkeypatch):
    """ADVICE r2: fresh() compared the .so only against znr_reader.cpp;
    a parallel.h edit must rebuild too."""
    if not (shutil.which("g++") and shutil.which("make")):
        pytest.skip("no native toolchain")
    repo_native = os.path.abspath(os.path.join(os.path.dirname(
        os.path.abspath(rec.__file__)), os.pardir, os.pardir, "native"))
    sandbox = str(tmp_path / "native")
    os.makedirs(sandbox)
    for f in ("znr_reader.cpp", "parallel.h", "Makefile"):
        shutil.copy(os.path.join(repo_native, f),
                    os.path.join(sandbox, f))
    monkeypatch.setenv("ZNICZ_TPU_NATIVE_DIR", sandbox)
    monkeypatch.delenv("ZNICZ_TPU_NO_NATIVE_IO", raising=False)
    monkeypatch.setattr(rec, "_native_lib", None)
    monkeypatch.setattr(rec, "_native_tried", False)
    assert rec._native() is not None
    so = os.path.join(sandbox, "libznr_reader.so")
    header = os.path.join(sandbox, "parallel.h")
    with open(so + ".digest") as f:
        built_from = f.read()
    # edit ONLY parallel.h, and push the .so's mtime past every source:
    # a copied or unpacked tree keeps no mtime order, so the content
    # alone must decide (native_build keys freshness on a digest)
    with open(header, "a") as f:
        f.write("\n// edited\n")
    future = time.time() + 100
    os.utime(so, (future, future))
    monkeypatch.setattr(rec, "_native_lib", None)
    monkeypatch.setattr(rec, "_native_tried", False)
    assert rec._native() is not None
    with open(so + ".digest") as f:
        assert f.read() != built_from, \
            "parallel.h-only edit did not trigger a rebuild"
    # ... and unchanged sources stay fresh whatever their mtimes say
    from znicz_tpu import native_build
    os.utime(header, (future + 10, future + 10))
    assert native_build.is_fresh(
        so, [os.path.join(sandbox, "znr_reader.cpp"), header])


def test_failed_native_build_raises(tmp_path):
    """A failed ``make`` is not swallowed: the caller without a
    fallback (the C++ inference engine) must never dlopen a stale
    .so after an edit whose rebuild failed."""
    if not shutil.which("make"):
        pytest.skip("no make")
    from znicz_tpu import native_build
    src = tmp_path / "x.cpp"
    src.write_text("int main() { return 0; }\n")
    (tmp_path / "Makefile").write_text("libx.so:\n\tfalse\n")
    with pytest.raises(native_build.NativeBuildError, match="rc="):
        native_build.ensure_built(str(tmp_path / "libx.so"), [str(src)],
                                  str(tmp_path), "libx.so")
