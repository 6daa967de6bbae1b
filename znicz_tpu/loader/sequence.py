"""SequenceLoader: a resident data set whose rows are token sequences.

A row is ``T`` int32 ids and its label is ``T`` int32 ids too: a target a
position (a language model's next token).  Nothing is normalised and no
class count is reckoned: the vocabulary is the model's business.
Subclasses (or callers) set ``original_data`` ``(n, T)``,
``original_labels`` ``(n, T)`` and ``class_lengths`` in ``load_data``."""

from __future__ import annotations

import numpy as np

from .fullbatch import FullBatchLoader


class SequenceLoader(FullBatchLoader):
    def __init__(self, workflow=None, name=None, **kwargs):
        kwargs.setdefault("normalization_type", "none")
        super().__init__(workflow, name, **kwargs)

    def _normalize(self) -> None:
        """Ids are not measurements."""

    def initialize(self, device=None, **kwargs) -> None:
        super().initialize(device, **kwargs)
        for vec in (self.original_data, self.original_labels):
            if vec.shape != self.original_data.shape or vec.mem.dtype.kind \
                    not in "iu":
                raise ValueError(
                    f"{self.name}: rows and labels are (n, T) integer ids; "
                    f"got {vec.shape} {vec.mem.dtype}")
        # the base class sized the label buffer for a label a row
        self.minibatch_labels.mem = np.zeros(
            (self.max_minibatch_size, *self.original_labels.shape[1:]),
            self.original_labels.dtype)
        self.minibatch_labels.initialize(device)
