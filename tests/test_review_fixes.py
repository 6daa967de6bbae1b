"""Regression tests for the second review round's findings."""

import numpy as np
import pytest

from znicz_tpu import prng
from znicz_tpu.backends import Device, NumpyDevice
from znicz_tpu.config import root
from znicz_tpu.models import mnist
from znicz_tpu.parallel import FusedTrainer, extract_model, fused


@pytest.fixture(autouse=True)
def small_synthetic():
    root.mnist.synthetic.update({"n_train": 250, "n_valid": 150,
                                 "n_test": 0, "noise": 0.35})
    root.mnist.minibatch_size = 100
    yield
    root.mnist.minibatch_size = 100


def test_short_final_batch_not_double_counted():
    """150 valid samples at batch=100 → eval must count exactly 150 rows
    (wrap-padded tail masked), so err_pct can never exceed 100%."""
    prng.seed_all(1234)
    wf = mnist.MnistWorkflow()
    wf.initialize(device=Device.create("xla"))
    spec, params, vels = extract_model(wf)
    tr = FusedTrainer(spec=spec, params=params, vels=vels)
    ld = wf.loader
    valid_idx = np.arange(0, 150)
    em = tr.eval_epoch(ld.original_data.devmem,
                       ld.original_labels.devmem, valid_idx, 100)
    # untrained net ≈ 90% error; inflated counting would exceed 150
    assert em["n_err"].sum() <= 150
    # cross-check against exact per-row computation
    probs_err = 0
    import jax.numpy as jnp
    out = fused.predict(spec, tr.params,
                        jnp.asarray(ld.original_data.mem[valid_idx]))
    pred = np.asarray(out).argmax(1)
    probs_err = int((pred != ld.original_labels.mem[valid_idx]).sum())
    assert int(em["n_err"].sum()) == probs_err


def test_needs_input_activation_rejected():
    with pytest.raises(NotImplementedError, match="needs its pre-activation input"):
        fused.ModelSpec(layers=(
            fused.LayerSpec("fc", "log", True, (0.01, 0, 0, 0),
                            (0.01, 0, 0, 0)),), loss="softmax")


def test_loader_is_workflow_member():
    prng.seed_all(1234)
    wf = mnist.MnistWorkflow()
    assert wf.loader in wf.units
    assert "mnist_loader" in wf.generate_graph()


def test_confusion_matrix_resets_each_epoch():
    prng.seed_all(1234)
    wf = mnist.MnistWorkflow()
    wf.decision.max_epochs = 2
    wf.initialize(device=Device.create("numpy"))
    wf.run()
    total = wf.evaluator.confusion_matrix.mem.sum()
    # one epoch's worth of samples at most (train+valid of final epoch
    # ends the run mid-reset-cycle; must be ≤ one epoch, not 2×)
    assert total <= wf.loader.total_samples


def test_confusion_matrix_stays_on_the_host(xla_device):
    """Counted and read on the host: an XLA workflow's evaluator puts no
    copy of it on the device (a language model's is vocabulary squared),
    and the tick path still counts every sample into it."""
    prng.seed_all(1234)
    wf = mnist.MnistWorkflow()
    wf.decision.max_epochs = 1
    wf.initialize(device=xla_device)
    cm = wf.evaluator.confusion_matrix
    assert cm and cm._devmem is None
    wf.run()
    assert cm._devmem is None
    assert 0 < cm.mem.sum() <= wf.loader.total_samples


def test_sgd_update_dispatcher_used_by_gd(xla_device):
    """gd xla path goes through ops.update.sgd_update_h."""
    from znicz_tpu import Vector
    from znicz_tpu.nn import All2AllTanh, GDTanh
    from znicz_tpu.ops import update
    f = All2AllTanh(name="f", output_sample_shape=4)
    f.__dict__["input"] = Vector(np.zeros((2, 3), np.float32))
    f.initialize(device=xla_device)
    g = GDTanh(name="g")
    g.setup_from_forward(f)
    g.initialize(device=xla_device)
    assert g._apply_fn is update.sgd_update_h
