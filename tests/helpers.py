"""Shared single-unit test harness (reference DummyWorkflow pattern,
SURVEY.md §4): wire one unit into a dummy workflow with fixed inputs."""

import numpy as np

from znicz_tpu import Vector, Workflow, prng
from znicz_tpu.backends import NumpyDevice
from znicz_tpu.loader.fullbatch import FullBatchLoader, FullBatchLoaderMSE


class Dummy(Workflow):
    """Minimal parent (reference DummyWorkflow fixture)."""


def _x(shape, stream="x"):
    return prng.get(stream).normal(size=shape)


def wire(cls, x, device=None, **kw):
    """Instantiate a Forward unit over a fixed input tensor."""
    wf = Dummy(name="dummy")
    unit = cls(wf, **kw)
    unit.__dict__["input"] = Vector(np.asarray(x, np.float32))
    unit.initialize(device or NumpyDevice())
    return unit


def wire_gd(cls, fwd, err, device=None, **kw):
    """Pair a gradient unit with its forward, feeding a fixed error."""
    unit = cls(fwd.workflow, **kw)
    unit.setup_from_forward(fwd)
    unit.__dict__["err_output"] = Vector(np.asarray(err, np.float32))
    unit.initialize(device or NumpyDevice())
    return unit


def routed(wf, *rewrites):
    """(spec, params, vels) of an initialized StandardWorkflow with only
    ``rewrites`` of ``parallel.fused`` applied to its rows, in that
    order: none is the unit graph's own rows (the reference every
    rewrite is compared against), both are what ``extract_model``
    ships."""
    from znicz_tpu.parallel import fused
    rows, params, vels = fused.workflow_rows(wf)
    for rewrite in rewrites:
        rows = rewrite(rows)
    return fused.model_of_rows(rows, params, vels, wf.loss_function)


class TinyRows(FullBatchLoader):
    """Three minibatches of seeded normal rows of one shape, ten classes:
    one to validate, two to train."""

    def __init__(self, workflow=None, name="rows", *, shape, batch,
                 **kwargs):
        super().__init__(workflow, name, minibatch_size=batch,
                         normalization_type="none", **kwargs)
        self.shape, self.batch = tuple(shape), batch

    def load_data(self) -> None:
        gen = np.random.default_rng(3)
        n = 3 * self.batch
        self.original_data.mem = gen.normal(
            size=(n, *self.shape)).astype(np.float32)
        self.original_labels.mem = gen.integers(0, 10, n).astype(np.int32)
        self.class_lengths = [0, self.batch, 2 * self.batch]


class TinyRowsMSE(FullBatchLoaderMSE, TinyRows):
    """The same rows with themselves as targets."""


def tiny_workflow(layers, shape, batch, loss="softmax", seed=19):
    """An initialized StandardWorkflow of ``layers`` over ``TinyRows`` on
    the XLA device."""
    from znicz_tpu.backends import Device
    from znicz_tpu.standard_workflow import StandardWorkflow
    prng.seed_all(seed)
    loader = (TinyRowsMSE if loss == "mse" else TinyRows)(
        shape=shape, batch=batch)
    wf = StandardWorkflow(None, "tiny", layers=layers, loader=loader,
                          loss_function=loss, snapshotter_config=None)
    wf.initialize(device=Device.create("xla"))
    return wf
