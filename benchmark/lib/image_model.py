"""The ``model`` file of the image classifiers (``alexnet``, ``vgg11``):
everything the harness needs to know about what a row and a parameter
tree are, behind the functions ``benchmark/README.md`` lists.

A row is one image with its class label.  The program is
``StandardWorkflow`` over the configuration's ``layers`` under
``root.alexnet`` (``lib/workflow.py``); every parameterised layer holds a
weight and a bias.  Rows and weights come from ``lib/data.py`` and the
seed, counts from ``lib/flops.py``."""

from __future__ import annotations

import numpy as np

from . import data, flops as _flops
from .errors import BenchError

#: what one row of the data set is, for a person (the result's ``window``)
row = {"kind": "image"}

#: a parameterised layer's leaves, in the trainer's order
LEAVES = ("weights", "bias")


def _in_shape(cfg: dict) -> tuple:
    return (cfg["input_size"], cfg["input_size"], cfg["input_channels"])


def overrides(cfg: dict, traffic: dict, seed: int) -> list[str]:
    """The ``path=value`` lines for the program's config tree."""
    return [
        f"bench.seed={int(seed)}",
        f"alexnet.minibatch_size={int(traffic['minibatch'])}",
        *(f"alexnet.synthetic.{k}={int(traffic[k])}"
          for k in ("n_train", "n_valid", "n_test")),
        f"alexnet.synthetic.noise={cfg['assumed']['noise']}",
        "alexnet.decision.max_epochs=1000000000",
        "alexnet.decision.fail_iterations=1000000000"]


def make_rows(seed: int, rows, cfg: dict, traffic: dict | None = None):
    """(images ``(n, h, w, c)``, labels ``(n,)``) of the global row
    numbers ``rows``: what the loader of ``lib/workflow.py`` holds in
    those rows."""
    size, _, channels = _in_shape(cfg)
    return data.make_rows(seed, np.asarray(rows, np.uint32), size, channels,
                          cfg["n_classes"], float(cfg["assumed"]["noise"]))


def param_shapes(cfg: dict) -> list:
    """One entry a layer of the configuration's list: None, or the
    shapes of its (weights, bias): conv weights (ky, kx, c_in, c_out),
    fc weights (n_in, n_out)."""
    layers, shape = cfg["layers"], _in_shape(cfg)
    out = []
    for layer, nxt in zip(layers, _flops.shapes_after(layers, shape)):
        kind, c = layer["type"], layer.get("->", {})
        if kind.startswith("conv"):
            out.append(((c["ky"], c["kx"], shape[2], c["n_kernels"]),
                        (c["n_kernels"],)))
        elif kind.startswith("all2all") or kind == "softmax":
            out.append(((int(np.prod(shape)), nxt[0]), (nxt[0],)))
        else:
            out.append(None)
        shape = nxt
    return out


def hypers(cfg: dict) -> list:
    """One entry a layer: None, or for each leaf its
    ``{"learning_rate", "weights_decay"}``, from the layer's own ``"<-"``
    entry."""
    out = []
    for layer, sh in zip(cfg["layers"], param_shapes(cfg)):
        if sh is None:
            out.append(None)
            continue
        h = layer["<-"]
        out.append(tuple(
            {key: float(h[key + suffix])
             for key in ("learning_rate", "weights_decay")}
            for suffix in ("", "_bias")))
    return out


def make_weights(seed: int, shapes: list) -> list:
    """He-normal weights and zero biases, on the default device, in one
    jitted call."""
    return data.make_weights(seed, shapes)


def install(wf, weights: list) -> None:
    """Put ``weights`` in place of the program's own, one entry a
    forward unit of the workflow."""
    if len(wf.forwards) != len(weights):
        raise BenchError(f"the program built {len(wf.forwards)} forward "
                         f"units, the configuration lists {len(weights)} "
                         "layers")
    for unit, leaves in zip(wf.forwards, weights):
        if leaves is None:
            continue
        for attr, leaf in zip(LEAVES, leaves):
            theirs = tuple(getattr(unit, attr).shape)
            if theirs != tuple(leaf.shape):
                raise BenchError(
                    f"{unit.name}: the program's {attr} are {theirs}, the "
                    f"configuration's {tuple(leaf.shape)}")
            getattr(unit, attr).mem = np.asarray(leaf)


def flops(cfg: dict, traffic: dict | None = None) -> dict:
    """Per row: ``forward`` and ``train_step`` operations, ``params``,
    and ``matmul_train``, the parameter layers' share of
    ``train_step``."""
    return _flops.model_flops(cfg["layers"], _in_shape(cfg))


def step_bytes(cfg: dict, traffic: dict | None, batch: int) -> float:
    """Least HBM traffic of one training step."""
    return _flops.step_bytes(cfg["layers"], _in_shape(cfg), batch)


def output_leaf(cfg: dict) -> int:
    """The flat leaf ``out_grad_diff`` reads: the output layer's
    weights, the last leaf but one (..., weights, bias)."""
    n_leaves = sum(len(sh) for sh in param_shapes(cfg) if sh is not None)
    return n_leaves - 2
