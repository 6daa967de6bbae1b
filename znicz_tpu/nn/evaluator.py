"""Evaluators: loss + error statistics at the end of the forward chain.

Parity target: the reference ``veles/znicz/evaluator.py`` (mount empty —
surveyed contract, SURVEY.md §2.2): ``EvaluatorSoftmax`` (cross-entropy,
``n_err`` count, confusion matrix, ``max_err_output_sum``) and
``EvaluatorMSE``.  Produces ``err_output`` consumed by the last GD unit.

Division of labor (matches reference): the evaluator scales the error by
1/batch_size; GD units apply it raw.  TPU-first addition: padded rows of a
short final minibatch are zeroed here so downstream gradient math needs no
masking."""

from __future__ import annotations

import numpy as np


from ..accelerated_units import AcceleratedUnit
from ..memory import Vector
from ..ops import softmax as softmax_ops


class EvaluatorBase(AcceleratedUnit):
    def __init__(self, workflow=None, name=None, **kwargs):
        super().__init__(workflow, name, **kwargs)
        self.err_output = Vector()
        self.mean_loss = 0.0

    @property
    def batch_size(self) -> int:
        return self.loader.minibatch_size

    def link_loader(self, loader) -> None:
        self.loader = loader


class EvaluatorSoftmax(EvaluatorBase):
    """Cross-entropy evaluator over All2AllSoftmax output.

    Inputs (linked): ``output`` (softmax probs), ``max_idx``, ``labels``.
    Outputs: ``err_output`` = (y − onehot)/batch (padded rows zeroed),
    ``n_err`` (this minibatch's miss count), ``confusion_matrix``,
    ``max_err_output_sum``."""

    def __init__(self, workflow=None, name=None, compute_confusion=True,
                 **kwargs):
        super().__init__(workflow, name, **kwargs)
        self.n_err = 0
        self.compute_confusion = compute_confusion
        self.confusion_matrix = Vector()
        self.max_err_output_sum = 0.0

    def initialize(self, device=None, **kwargs) -> None:
        super().initialize(device, **kwargs)
        n_classes = self.output.shape[-1]
        if self.compute_confusion and not self.confusion_matrix:
            self.confusion_matrix.mem = np.zeros((n_classes, n_classes),
                                                 np.int64)
        self.init_vectors(self.err_output, self.confusion_matrix)
        self._confusion_epoch = -1

    def numpy_run(self) -> None:
        # a target a position (``(B, T, V)`` probabilities, ``(B, T)``
        # labels) is B*T rows of the same head
        y = self.output.mem.reshape(-1, self.output.shape[-1])
        bs = self.batch_size * (len(y) // len(self.output.mem))
        self.n_targets = bs
        labels = self.labels.mem.astype(np.int64).reshape(-1)
        loss, err = softmax_ops.np_softmax_ce(y[:bs], labels[:bs])
        full = np.zeros(y.shape, np.float32)
        full[:bs] = err / bs
        self.err_output.mem = full.reshape(self.output.shape)
        pred = self.max_idx.mem.reshape(-1)[:bs]
        self.n_err = int(np.sum(pred != labels[:bs]))
        self.mean_loss = float(loss.mean())
        self.max_err_output_sum = float(np.abs(full).sum(axis=1).max())
        if self.compute_confusion:
            epoch = getattr(self.loader, "epoch_number", 0)
            self.confusion_matrix.map_write()
            if epoch != self._confusion_epoch:   # fresh matrix per epoch
                self.confusion_matrix.mem[...] = 0
                self._confusion_epoch = epoch
            np.add.at(self.confusion_matrix.mem, (labels[:bs], pred), 1)

    def xla_run(self) -> None:
        # Metrics are host-side scalars consumed by Decision each tick, so
        # compute on host from mapped outputs (tiny: batch × classes), but
        # build err_output with the same math as numpy_run.
        self.numpy_run()


class EvaluatorMSE(EvaluatorBase):
    """Mean-squared-error evaluator (reference EvaluatorMSE contract):
    err_output = (y − target)/batch; metrics: per-minibatch mse and rmse."""

    def __init__(self, workflow=None, name=None, **kwargs):
        super().__init__(workflow, name, **kwargs)
        self.mse = 0.0
        self.n_err = 0   # uniform Decision interface: mse-thresholded count

    def initialize(self, device=None, **kwargs) -> None:
        super().initialize(device, **kwargs)
        self.init_vectors(self.err_output)

    def numpy_run(self) -> None:
        bs = self.batch_size
        y = self.output.mem.reshape(len(self.output.mem), -1)
        t = self.target.mem.reshape(y.shape)
        err = np.zeros(y.shape, np.float32)
        err[:bs] = (y[:bs] - t[:bs]) / bs
        self.err_output.mem = err.reshape(self.output.shape)
        sq = ((y[:bs] - t[:bs]) ** 2).mean(axis=1)
        self.mse = float(sq.mean())
        self.mean_loss = self.mse
        self.n_err = int(bs)   # decision tracks loss for MSE flows

    def xla_run(self) -> None:
        self.numpy_run()
