"""Regression tests for ADVICE round-3 findings.

1 medium — RecordLoader.load_meta must reject shard sets with divergent
label geometry (the native scatter would otherwise memcpy out of
bounds); plus the read_batch_into row-width guard.
3 low — LMDB overflow EOF bound (tested in test_importers.py),
host-only augment policies keep the host prefetch path under run_fused,
and the bf16 MXU operand cast of the unit-graph matmul follows the
platform alone (the environment lever that disabled it is gone).
"""

import numpy as np
import pytest

from znicz_tpu import prng
from znicz_tpu.backends import Device
from znicz_tpu.loader import RecordLoader, write_records
from znicz_tpu.workflow import Workflow


def _dataset(n=40, shape=(5, 5, 1), classes=4, seed=0):
    rng = np.random.default_rng(seed)
    data = rng.normal(size=(n, *shape)).astype(np.float32)
    labels = (rng.integers(0, classes, n)).astype(np.int32)
    return data, labels


class TestShardLabelGeometry:
    def test_divergent_label_shape_rejected(self, tmp_path):
        """ADVICE r3 medium: shards disagreeing on label shape must be
        refused in load_meta — the C++ scatter sizes the labels buffer
        from files[0] and would corrupt the heap."""
        data, labels = _dataset(n=20)
        a = write_records(str(tmp_path / "a.znr"), data[:10],
                          labels[:10])
        vec = np.stack([labels[10:].astype(np.float32)] * 3, axis=1)
        b = write_records(str(tmp_path / "b.znr"), data[10:], vec)
        ld = RecordLoader(Workflow(name="w"), train_paths=a + b,
                          minibatch_size=4)
        with pytest.raises(ValueError, match="label shape"):
            ld.load_meta()

    def test_divergent_label_dtype_rejected(self, tmp_path):
        data, labels = _dataset(n=20)
        a = write_records(str(tmp_path / "a.znr"), data[:10],
                          labels[:10])
        b = write_records(str(tmp_path / "b.znr"), data[10:],
                          labels[10:].astype(np.int64))
        ld = RecordLoader(Workflow(name="w"), train_paths=a + b,
                          minibatch_size=4)
        with pytest.raises(ValueError, match="label dtype"):
            ld.load_meta()

    def test_read_batch_into_width_guard(self, tmp_path):
        """Defense in depth: read_batch_into refuses (returns False →
        caller falls back) when destination row widths disagree with
        the shard's geometry instead of invoking the native scatter."""
        from znicz_tpu.loader.records import RecordFile
        data, labels = _dataset(n=8)
        p = write_records(str(tmp_path / "w.znr"), data, labels)
        rf = RecordFile(p[0])
        good_d = np.empty((4, 5, 5, 1), np.float32)
        good_l = np.empty((4,), np.int32)
        bad_d = np.empty((4, 5, 6, 1), np.float32)   # wrong row width
        bad_l = np.empty((4, 2), np.int32)
        pos = np.arange(4)
        idx = np.arange(4)
        assert rf.read_batch_into(idx, bad_d, good_l, pos) is False
        assert rf.read_batch_into(idx, good_d, bad_l, pos) is False
        if rf.read_batch_into(idx, good_d, good_l, pos):
            np.testing.assert_array_equal(good_d, data[:4])
            np.testing.assert_array_equal(good_l, labels[:4])
        rf.close()


class _HostOnlyAugment:
    """A custom policy implementing ONLY the documented host contract
    (apply + out_shape) — no device twin."""

    def __init__(self, out_hw):
        self.out_hw = tuple(out_hw)

    def out_shape(self, sample_shape):
        return (*self.out_hw, *sample_shape[2:])

    def apply(self, data, indices, epoch, is_train):
        h, w = self.out_hw
        return data[:, :h, :w]                 # deterministic corner crop


class TestHostOnlyAugmentFallback:
    def test_run_fused_keeps_host_path(self, tmp_path):
        """ADVICE r3: run_fused force-enabled device_augment for ANY
        augment policy; one without device_apply must fall back to the
        host prefetch path (and still train)."""
        from znicz_tpu.standard_workflow import StandardWorkflow

        data, labels = _dataset(n=60, shape=(6, 6, 1))
        tr = write_records(str(tmp_path / "tr.znr"), data[12:],
                           labels[12:])
        va = write_records(str(tmp_path / "va.znr"), data[:12],
                           labels[:12])
        prng.seed_all(5)
        wf = StandardWorkflow(
            None, "swf",
            layers=[{"type": "softmax", "->": {"output_sample_shape": 4},
                     "<-": {"learning_rate": 0.05}}],
            loader=RecordLoader(None, train_paths=tr,
                                validation_paths=va, minibatch_size=12,
                                augment=_HostOnlyAugment((5, 5))),
            decision_config={"max_epochs": 2, "fail_iterations": 10})
        wf.initialize(device=Device.create("xla"))
        tr_obj = wf.run_fused()
        assert tr_obj.device_augment is False
        ms = wf.decision.epoch_metrics
        assert len(ms) == 2
        assert np.isfinite(ms[-1]["train_loss"])

    def test_device_twin_still_takes_device_path(self, tmp_path):
        """The stock policy (has device_apply) keeps device_augment."""
        from znicz_tpu.loader.augment import RandomCropFlip
        from znicz_tpu.standard_workflow import StandardWorkflow

        data, labels = _dataset(n=60, shape=(6, 6, 1))
        tr = write_records(str(tmp_path / "tr.znr"), data[12:],
                           labels[12:])
        va = write_records(str(tmp_path / "va.znr"), data[:12],
                           labels[:12])
        prng.seed_all(5)
        wf = StandardWorkflow(
            None, "swf",
            layers=[{"type": "softmax", "->": {"output_sample_shape": 4},
                     "<-": {"learning_rate": 0.05}}],
            loader=RecordLoader(None, train_paths=tr,
                                validation_paths=va, minibatch_size=12,
                                augment=RandomCropFlip((5, 5),
                                                       mirror=False)),
            decision_config={"max_epochs": 1, "fail_iterations": 10})
        wf.initialize(device=Device.create("xla"))
        tr_obj = wf.run_fused()
        assert tr_obj.device_augment is True


class TestMXULever:
    def test_env_lever_disables_cast(self, monkeypatch):
        """ADVICE r3 asked for an environment lever that disables the
        bf16 MXU operand cast on TPU; the lever is gone, and the cast
        follows the platform alone: on TPU (monkeypatched on_tpu) float32
        operands are cast, bf16 ones are already the MXU's."""
        import jax.numpy as jnp

        from znicz_tpu.ops import matmul as mm
        from znicz_tpu.ops import tuning
        monkeypatch.setattr(tuning, "on_tpu", lambda: True)
        assert mm._mxu_cast(jnp.float32) == jnp.bfloat16
        assert mm._mxu_cast(jnp.bfloat16) is None

    def test_cpu_never_casts(self):
        import jax.numpy as jnp

        from znicz_tpu.ops import matmul as mm
        from znicz_tpu.ops import tuning
        if tuning.on_tpu():
            pytest.skip("real TPU attached")
        assert mm._mxu_cast(jnp.float32) is None
        assert mm._mxu_cast(jnp.bfloat16) is None
