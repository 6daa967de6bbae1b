"""Seconds of ``workflow.initialize()``: the resident data set made and
placed, the unit graph and its buffers allocated."""


def read(run: dict):
    return run["dataset_s"]
