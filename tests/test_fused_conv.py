"""Fused-step conv-stack tests: the compiled whole-chain step must
reproduce the unit-graph path through Conv/Pool/LRN/Dropout layers
(SURVEY.md §7 — the fused step is the TPU hot path, the unit graph the
contract), and run sharded on the virtual mesh."""

import numpy as np
import pytest

from znicz_tpu import prng
from znicz_tpu.backends import Device
from znicz_tpu.config import root
from znicz_tpu.models import cifar
from znicz_tpu.parallel import (FusedTrainer, extract_model, fused,
                                make_mesh)

import helpers


@pytest.fixture(autouse=True)
def small_synthetic():
    saved = root.cifar.synthetic.to_dict()
    root.cifar.synthetic.update({"n_train": 200, "n_valid": 80,
                                 "n_test": 80, "noise": 0.3, "size": 16})
    root.cifar.minibatch_size = 40
    yield
    root.cifar.synthetic.update(saved)
    root.cifar.minibatch_size = 100


def _workflow(layers=None):
    prng.seed_all(1234)
    wf = cifar.CifarWorkflow(layers=layers)
    wf.initialize(device=Device.create("xla"))
    return wf


DROPOUT_LAYERS = [
    {"type": "conv_tanh", "->": {"n_kernels": 8, "kx": 3, "padding": 1},
     "<-": {"learning_rate": 0.1, "gradient_moment": 0.9}},
    {"type": "max_pooling", "->": {"kx": 2}},
    {"type": "dropout", "->": {"dropout_ratio": 0.3}},
    {"type": "all2all_tanh", "->": {"output_sample_shape": 32},
     "<-": {"learning_rate": 0.1, "gradient_moment": 0.9}},
    {"type": "softmax", "->": {"output_sample_shape": 10},
     "<-": {"learning_rate": 0.1, "gradient_moment": 0.9}},
]


def _drive_graph(wf, idx):
    """Drive the unit graph manually over the identical minibatches the
    fused path consumed (same pattern as test_fused_parallel)."""
    ld = wf.loader
    n = len(idx)
    for off in range(0, n, ld.max_minibatch_size):
        mb = idx[off:off + ld.max_minibatch_size]
        ld.minibatch_class = 2
        ld.minibatch_size = len(mb)
        # counters the stochastic units key their RNG on
        ld.minibatch_offset = min(off + ld.max_minibatch_size, n)
        ld.fill_minibatch(mb, 2)
        for f in wf.forwards:
            f.run()
        wf.evaluator.run()
        for g in reversed(wf.gds):
            g.run()


def _assert_params_match(wf, tr):
    # spec rows address units through unit_index (the lrn_pool merge
    # makes them fewer than the forward units)
    umap = tr.spec.unit_index or tuple(range(len(tr.params)))
    for i, (ui, (w, b)) in enumerate(zip(umap, tr.params)):
        if w is None:
            continue
        np.testing.assert_allclose(
            np.asarray(w), wf.forwards[ui].weights.mem, rtol=5e-4,
            atol=1e-5, err_msg=f"layer {i} weights diverged")


class TestFusedConvEquivalence:
    def test_fused_matches_unit_graph(self):
        """Deterministic conv chain: fused weights == unit-graph weights
        after one epoch over the same minibatch order."""
        wf = _workflow()
        spec, params, vels = extract_model(wf)
        kinds = [layer.kind for layer in spec.layers]
        assert kinds == ["conv", "max_pool", "lrn", "conv", "avg_pool",
                         "fc", "fc"]
        tr = FusedTrainer(spec=spec, params=params, vels=vels)
        ld = wf.loader
        n0, n1, n2 = ld.class_lengths
        idx = np.arange(n0 + n1, n0 + n1 + n2)   # unshuffled train set
        tr.train_epoch(ld.original_data.devmem,
                       ld.original_labels.devmem, idx,
                       ld.max_minibatch_size)
        _drive_graph(wf, idx)
        _assert_params_match(wf, tr)

    def test_fused_matches_unit_graph_with_merged_lrn_pool(self):
        """AlexNet layer order (conv → LRN → max-pool): extract_model
        MERGES the pair, so this is the decisive unit-graph-vs-merged
        equivalence — the reference execution model against the fused
        pair op (forward, offsets, backward, activation fold)."""
        wf = _workflow(layers=[
            {"type": "conv_str",
             "->": {"n_kernels": 8, "kx": 5, "sliding": 2},
             "<-": {"learning_rate": 0.1, "gradient_moment": 0.9}},
            {"type": "norm", "->": {"n": 5}},
            {"type": "max_pooling", "->": {"kx": 3, "sliding": 2}},
            {"type": "all2all_tanh", "->": {"output_sample_shape": 24},
             "<-": {"learning_rate": 0.1, "gradient_moment": 0.9}},
            {"type": "softmax", "->": {"output_sample_shape": 10},
             "<-": {"learning_rate": 0.1, "gradient_moment": 0.9}},
        ])
        spec, params, vels = extract_model(wf)
        kinds = [layer.kind for layer in spec.layers]
        assert kinds == ["conv", "lrn_pool", "fc", "fc"]
        assert spec.layers[1].cfg["fold_act"] == "strict_relu"
        tr = FusedTrainer(spec=spec, params=params, vels=vels)
        ld = wf.loader
        n0, n1, n2 = ld.class_lengths
        idx = np.arange(n0 + n1, n0 + n1 + n2)
        tr.train_epoch(ld.original_data.devmem,
                       ld.original_labels.devmem, idx,
                       ld.max_minibatch_size)
        _drive_graph(wf, idx)
        _assert_params_match(wf, tr)

    @pytest.mark.parametrize("conv_type", ["conv_str", "conv_tanh"])
    def test_merged_equals_split_with_bf16_storage(self, conv_type):
        """storage_dtype=bfloat16: the pair kernel must SELECT in the
        storage dtype (the split path pools the bf16-stored y), so
        winner offsets and training stay identical to the split spec.
        conv_tanh exercises the VALUE-dependent activation fold, whose
        derivative must also evaluate on the storage-dtype y."""
        import dataclasses
        wf = _workflow(layers=[
            {"type": conv_type,
             "->": {"n_kernels": 8, "kx": 5, "sliding": 2},
             "<-": {"learning_rate": 0.1, "gradient_moment": 0.9}},
            {"type": "norm", "->": {"n": 5}},
            {"type": "max_pooling", "->": {"kx": 3, "sliding": 2}},
            {"type": "softmax", "->": {"output_sample_shape": 10},
             "<-": {"learning_rate": 0.1, "gradient_moment": 0.9}},
        ])
        # merge + fold (the bit-equality contract; the split convs of
        # rewrite (iii) are allclose-only) against the unrewritten rows
        spec_m, params, vels = helpers.routed(wf, fused.merge_lrn_pool,
                                              fused.fold_pair_act)
        assert spec_m.layers[1].cfg["fold_act"] == {
            "conv_str": "strict_relu", "conv_tanh": "tanh"}[conv_type]
        spec_s, params_s, vels_s = helpers.routed(wf)
        ld = wf.loader
        n0, n1, n2 = ld.class_lengths
        idx = np.arange(n0 + n1, n0 + n1 + n2)

        def run(spec, p, v):
            spec = dataclasses.replace(spec, storage_dtype="bfloat16")
            tr = FusedTrainer(
                spec=spec,
                params=[tuple(np.array(a) if a is not None else None
                              for a in r) for r in p],
                vels=[tuple(np.array(a) if a is not None else None
                            for a in r) for r in v])
            m = tr.train_epoch(ld.original_data.devmem,
                               ld.original_labels.devmem, idx,
                               ld.max_minibatch_size)
            return m, tr.params

        m_m, p_m = run(spec_m, params, vels)
        m_s, p_s = run(spec_s, params_s, vels_s)
        np.testing.assert_array_equal(np.asarray(m_m["loss"]),
                                      np.asarray(m_s["loss"]))
        for a, b in zip([np.asarray(x) for r in p_m for x in r
                         if x is not None],
                        [np.asarray(x) for r in p_s for x in r
                         if x is not None]):
            np.testing.assert_array_equal(a, b)

    def test_fused_matches_unit_graph_with_dropout(self):
        """Counter-RNG alignment: the fused step reproduces the unit
        path's dropout masks (same epoch/offset counters)."""
        wf = _workflow(layers=DROPOUT_LAYERS)
        spec, params, vels = extract_model(wf)
        assert [la.kind for la in spec.layers] == \
            ["conv", "max_pool", "dropout", "fc", "fc"]
        tr = FusedTrainer(spec=spec, params=params, vels=vels)
        ld = wf.loader
        n0, n1, n2 = ld.class_lengths
        idx = np.arange(n0 + n1, n0 + n1 + n2)
        tr.train_epoch(ld.original_data.devmem,
                       ld.original_labels.devmem, idx,
                       ld.max_minibatch_size, epoch=0)
        _drive_graph(wf, idx)
        _assert_params_match(wf, tr)

    def test_run_fused_bfloat16_converges(self):
        """compute_dtype='bfloat16': MXU operands in bf16, params and
        accumulation f32 — training must still converge (mixed-precision
        contract of the fused path)."""
        wf = _workflow()
        wf.run_fused(max_epochs=4, compute_dtype="bfloat16")
        last = wf.decision.epoch_metrics[-1]
        assert last["validation_err_pct"] < 25.0, wf.decision.epoch_metrics
        assert np.isfinite(wf.forwards[0].weights.mem).all()
        assert wf.forwards[0].weights.mem.dtype == np.float32  # master f32

    def test_run_fused_bf16_storage_converges(self):
        """storage_dtype='bfloat16': inter-layer activations (and the
        backward caches) live in bf16, halving activation HBM traffic;
        params/grads/loss stay f32 and training still converges."""
        wf = _workflow()
        wf.run_fused(max_epochs=4, storage_dtype="bfloat16")
        last = wf.decision.epoch_metrics[-1]
        assert last["validation_err_pct"] < 25.0, wf.decision.epoch_metrics
        assert wf.forwards[0].weights.mem.dtype == np.float32

    def test_bf16_storage_cache_dtypes(self):
        """The storage cast lands where claimed: inner-layer caches are
        bf16, the input and the loss-head output stay f32."""
        import dataclasses

        import jax.numpy as jnp

        from znicz_tpu.parallel import fused
        wf = _workflow()
        spec, params, vels = extract_model(wf)
        spec = dataclasses.replace(spec, storage_dtype="bfloat16")
        ld = wf.loader
        x = jnp.asarray(np.asarray(ld.original_data.mem[:8]))
        dev_params = [(jnp.asarray(w) if w is not None else None,
                       jnp.asarray(b) if b is not None else None)
                      for w, b in params]
        out, caches = fused.forward(spec, dev_params, x,
                                    want_caches=True, train=True)
        assert out.dtype == jnp.float32          # logits full precision
        assert caches[0][0].dtype == jnp.float32  # layer-0 input = x
        inner = [c[0].dtype for c in caches[1:]]
        assert all(dt == jnp.bfloat16 for dt in inner), inner

    def test_run_fused_converges_conv(self):
        wf = _workflow()
        trainer = wf.run_fused(max_epochs=4)
        last = wf.decision.epoch_metrics[-1]
        assert last["validation_err_pct"] < 15.0, wf.decision.epoch_metrics
        # weights written back into the unit graph
        assert np.isfinite(wf.forwards[0].weights.mem).all()
        del trainer


FULL_STACK_LAYERS = [
    # conv + max-pool + LRN + dropout + fc: every kind whose fused
    # parity logic (deferred tail, pending-update carryover, counter
    # RNG) VERDICT round 1 item 8 asked to protect over multiple epochs
    {"type": "conv_tanh", "->": {"n_kernels": 8, "kx": 3, "padding": 1},
     "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}},
    {"type": "max_pooling", "->": {"kx": 2}},
    {"type": "norm", "->": {"n": 5}},
    {"type": "dropout", "->": {"dropout_ratio": 0.25}},
    {"type": "all2all_tanh", "->": {"output_sample_shape": 32},
     "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}},
    {"type": "softmax", "->": {"output_sample_shape": 10},
     "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}},
]


class TestFusedWithPallasKernels:
    def test_fused_epoch_with_interpret_pallas(self, monkeypatch):
        """The TPU fused path runs Pallas kernels (dropout, LRN,
        pool-select/scatter) INSIDE the jitted epoch scan — a
        composition CPU tests otherwise never execute.  Interpret mode
        makes the dispatchers take the Pallas tier here and the result
        must match the XLA-tier run bit-for-all-practical-bits."""
        from znicz_tpu.ops import tuning

        wf = _workflow(layers=[
            {"type": "conv_tanh", "->": {"n_kernels": 8, "kx": 3,
                                         "padding": 1},
             "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}},
            {"type": "max_pooling", "->": {"kx": 2}},
            {"type": "norm", "->": {"n": 5}},
            {"type": "dropout", "->": {"dropout_ratio": 0.3}},
            {"type": "softmax", "->": {"output_sample_shape": 10},
             "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}},
        ])
        spec, params, vels = extract_model(wf)
        ld = wf.loader
        n0, n1, n2 = ld.class_lengths
        idx = np.arange(n0 + n1, n0 + n1 + n2)
        # deep-copy params/vels for the reference trainer: the epoch fn
        # donates its buffers (donate_argnums), so the two trainers must
        # not share arrays
        import jax
        cp = lambda t: jax.tree_util.tree_map(np.array, t)  # noqa: E731
        # XLA-tier reference epoch — force the XLA formulations even if
        # this ever runs on a TPU backend (where use_pallas() is already
        # true and both runs would otherwise compare Pallas to itself)
        monkeypatch.setenv("ZNICZ_TPU_NO_PALLAS", "1")
        tr_ref = FusedTrainer(spec=spec, params=cp(params),
                              vels=cp(vels))
        tr_ref.train_epoch(ld.original_data.devmem,
                           ld.original_labels.devmem, idx,
                           ld.max_minibatch_size, epoch=0)
        # Pallas-tier (interpret) epoch over the same inputs
        monkeypatch.delenv("ZNICZ_TPU_NO_PALLAS")
        monkeypatch.setattr(tuning, "_INTERPRET", True)
        assert tuning.use_pallas()
        tr = FusedTrainer(spec=spec, params=params, vels=vels)
        tr.train_epoch(ld.original_data.devmem,
                       ld.original_labels.devmem, idx,
                       ld.max_minibatch_size, epoch=0)
        for i, ((w1, _), (w2, _)) in enumerate(zip(tr_ref.params,
                                                   tr.params)):
            if w1 is None:
                continue
            np.testing.assert_allclose(
                np.asarray(w1), np.asarray(w2), rtol=5e-4, atol=1e-5,
                err_msg=f"layer {i}: Pallas-tier fused epoch diverged")


class TestRunVsRunFusedConvStack:
    def test_three_epoch_equivalence(self):
        """wf.run() (unit-graph loop: decision, shuffle stream, per-unit
        dispatch) vs wf2.run_fused() (compiled epochs with the deferred
        tail-minibatch logic of standard_workflow) over 3 epochs on a
        conv+pool+LRN+dropout net: identical weights — the RNG contract
        makes even the dropout masks line up."""
        import copy
        prng.seed_all(777)
        wf = cifar.CifarWorkflow(layers=copy.deepcopy(FULL_STACK_LAYERS))
        wf.decision.max_epochs = 3
        wf.initialize(device=Device.create("xla"))
        wf.run()
        prng.seed_all(777)
        wf2 = cifar.CifarWorkflow(layers=copy.deepcopy(FULL_STACK_LAYERS))
        wf2.decision.max_epochs = 3
        wf2.initialize(device=Device.create("xla"))
        wf2.run_fused(max_epochs=3)
        for f1, f2 in zip(wf.forwards, wf2.forwards):
            if not f1.weights:
                continue
            np.testing.assert_allclose(f1.weights.mem, f2.weights.mem,
                                       rtol=5e-4, atol=1e-5,
                                       err_msg=f1.name)
        # train loss tracks too (the fused tail minibatch's metrics come
        # from an eval-mode forward, so dropout widens the tolerance —
        # weights above are the strict check).  Validation metrics are
        # NOT compared: the unit-graph loader serves valid minibatches
        # BEFORE each epoch's training, the fused loop evaluates after —
        # a documented phase offset, not a divergence.
        m1 = wf.decision.epoch_metrics
        m2 = wf2.decision.epoch_metrics
        assert len(m1) == len(m2) == 3
        for a, b in zip(m1, m2):
            np.testing.assert_allclose(a["train_loss"], b["train_loss"],
                                       rtol=0.05)


TIED_AE_LAYERS = [
    {"type": "conv", "->": {"n_kernels": 8, "kx": 5, "ky": 5,
                            "padding": 2},
     "<-": {"learning_rate": 2e-4, "gradient_moment": 0.9}},
    {"type": "max_pooling", "->": {"kx": 2, "ky": 2}},
    {"type": "depooling", "->": {"tie": 1}},
    {"type": "deconv", "->": {"tie": 0},
     "<-": {"learning_rate": 2e-4, "gradient_moment": 0.9}},
]


class TestTiedDeconvFused:
    """Weight-tied Deconv in the fused step (VERDICT round 1, item 6):
    the shared Vector receives BOTH GD updates in the unit graph's
    sequential order, so fused weights must track it exactly."""

    def _ae_workflow(self):
        from znicz_tpu.models import autoencoder          # noqa: F401
        from znicz_tpu.standard_workflow import StandardWorkflow
        from znicz_tpu.loader.fullbatch import FullBatchLoaderMSE
        from znicz_tpu.models.mnist import MnistLoader

        class _Loader(FullBatchLoaderMSE, MnistLoader):
            def load_data(self):
                MnistLoader.load_data(self)
                self.original_data.mem = self.original_data.mem.reshape(
                    -1, 28, 28, 1).astype(np.float32)

        prng.seed_all(1234)
        wf = StandardWorkflow(
            None, "TiedAE", layers=TIED_AE_LAYERS,
            loader=_Loader(minibatch_size=40,
                           synthetic_sizes={"n_train": 120, "n_valid": 0,
                                            "n_test": 0, "noise": 0.3}),
            loss_function="mse",
            decision_config={"max_epochs": 2, "fail_iterations": 10})
        wf.initialize(device=Device.create("xla"))
        return wf

    def test_tied_ae_fused_matches_unit_graph(self):
        wf = self._ae_workflow()
        # tying is a true Vector share in the unit graph
        assert wf.forwards[3].weights is wf.forwards[0].weights
        spec, params, vels = extract_model(wf)
        assert spec.layers[3].cfg["tie"] == 0
        assert params[3][0] is None          # stored once, at the conv
        assert vels[3][0] is not None        # own velocity
        tr = FusedTrainer(spec=spec, params=params, vels=vels)
        ld = wf.loader
        n0, n1, n2 = ld.class_lengths
        idx = np.arange(n0 + n1, n0 + n1 + n2)
        for ep in range(2):
            tr.train_epoch(ld.original_data.devmem,
                           ld.original_targets.devmem, idx,
                           ld.max_minibatch_size, epoch=ep)
            _drive_graph(wf, idx)
        np.testing.assert_allclose(
            np.asarray(tr.params[0][0]), wf.forwards[0].weights.mem,
            rtol=5e-4, atol=1e-5, err_msg="tied weights diverged")
        np.testing.assert_allclose(
            np.asarray(tr.vels[3][0]),
            wf.gds[3].velocity_weights.mem, rtol=5e-4, atol=1e-5,
            err_msg="deconv velocity diverged")
        np.testing.assert_allclose(
            np.asarray(tr.vels[0][0]),
            wf.gds[0].velocity_weights.mem, rtol=5e-4, atol=1e-5,
            err_msg="conv velocity diverged")

    def test_tied_ae_run_fused(self):
        wf = self._ae_workflow()
        wf.run_fused(max_epochs=2)
        ms = wf.decision.epoch_metrics
        assert len(ms) == 2 and np.isfinite(ms[-1]["train_mse"])


class TestFusedConvMesh:
    def test_dp_mesh_conv(self):
        import jax
        wf = _workflow()
        spec, params, vels = extract_model(wf)
        mesh = make_mesh(n_data=4, n_model=1,
                         devices=jax.devices()[:4])
        tr = FusedTrainer(spec=spec, params=params, vels=vels, mesh=mesh)
        ld = wf.loader
        n0, n1, n2 = ld.class_lengths
        order = np.arange(n0 + n1, n0 + n1 + n2)
        m = tr.train_epoch(np.asarray(ld.original_data.mem),
                           np.asarray(ld.original_labels.mem), order,
                           ld.max_minibatch_size)
        assert np.isfinite(m["loss"]).all()

    def test_dp_tp_mesh_conv(self):
        import jax
        wf = _workflow()
        spec, params, vels = extract_model(wf)
        mesh = make_mesh(n_data=4, n_model=2, devices=jax.devices())
        tr = FusedTrainer(spec=spec, params=params, vels=vels, mesh=mesh)
        ld = wf.loader
        n0, n1, n2 = ld.class_lengths
        order = np.arange(n0 + n1, n0 + n1 + n2)
        m = tr.train_epoch(np.asarray(ld.original_data.mem),
                           np.asarray(ld.original_labels.mem), order,
                           ld.max_minibatch_size)
        assert np.isfinite(m["loss"]).all()
        # conv weights actually sharded over the model axis
        assert len(tr.params[0][0].sharding.device_set) == 8

    def test_dtype_knobs_from_config_tree(self):
        """root.common.{compute,storage}_dtype reach the fused spec via
        train() — the two-file-CLI/--set route to mixed precision."""
        wf = _workflow()
        saved = {k: root.common.get(k)
                 for k in ("storage_dtype", "compute_dtype")}
        root.common.update({"storage_dtype": "bfloat16",
                            "compute_dtype": "bfloat16"})
        try:
            tr = wf.train(fused=True, max_epochs=1)
        finally:
            root.common.update(saved)
        assert tr.spec.storage_dtype == "bfloat16"
        assert tr.spec.compute_dtype == "bfloat16"
