"""Operations and bytes a configuration's step needs, counted from the
configuration's layer list and input shape alone (never from the
program's ``ModelSpec``, which a PR may rewrite).

Same conventions as the program's ``ops/flops.py`` at the time of
writing, so that the two agree (pinned by a test): one multiply-add is 2
operations; a parameter layer's training step costs 3 times its forward
(forward, error back-propagation, weight gradient); the other layers 2
times; the optimizer 6 operations a parameter."""

from __future__ import annotations

import numpy as np


def _out(size: int, k: int, stride: int, pad: int) -> int:
    return (size + 2 * pad - k) // stride + 1


def shapes_after(layers: list, input_shape: tuple) -> list:
    """Output shape (no batch axis) after each layer."""
    shape, out = tuple(input_shape), []
    for layer in layers:
        kind, cfg = layer["type"], layer.get("->", {})
        if kind.startswith("conv"):
            s, p = cfg.get("sliding", 1), cfg.get("padding", 0)
            shape = (_out(shape[0], cfg["ky"], s, p),
                     _out(shape[1], cfg["kx"], s, p), cfg["n_kernels"])
        elif kind == "max_pooling":
            s, p = cfg.get("sliding", 1), cfg.get("padding", 0)
            shape = (_out(shape[0], cfg["ky"], s, p),
                     _out(shape[1], cfg["kx"], s, p), shape[2])
        elif kind.startswith("all2all") or kind == "softmax":
            shape = (int(cfg["output_sample_shape"]),)
        elif kind in ("norm", "dropout"):
            pass
        else:
            raise ValueError(f"layer type {kind!r} is not counted by "
                             "benchmark/lib/flops.py")
        out.append(shape)
    return out


def model_flops(layers: list, input_shape: tuple) -> dict:
    """Per image: ``forward`` and ``train_step`` operations, ``params``,
    and ``matmul_train``, the parameter layers' share of ``train_step``."""
    shape = tuple(input_shape)
    fwd = train = matmul = 0.0
    n_params = 0
    for layer, nxt in zip(layers, shapes_after(layers, shape)):
        kind, cfg = layer["type"], layer.get("->", {})
        if kind.startswith("conv"):
            macs = cfg["ky"] * cfg["kx"] * shape[2] * int(np.prod(nxt))
            f = 2.0 * macs + int(np.prod(nxt))
            n_params += cfg["ky"] * cfg["kx"] * shape[2] * nxt[2] + nxt[2]
            fwd, train, matmul = fwd + f, train + 3 * f, matmul + 3 * f
        elif kind.startswith("all2all") or kind == "softmax":
            n_in = int(np.prod(shape))
            f = 2.0 * n_in * nxt[0] + nxt[0]
            n_params += n_in * nxt[0] + nxt[0]
            fwd, train, matmul = fwd + f, train + 3 * f, matmul + 3 * f
        elif kind == "max_pooling":
            f = float(cfg["ky"] * cfg["kx"] * int(np.prod(nxt)))
            fwd, train = fwd + f, train + 2 * f
        elif kind == "norm":
            n_el = int(np.prod(shape))
            f = 2.0 * cfg["n"] * n_el + 6.0 * n_el
            fwd, train = fwd + f, train + 2 * f
        elif kind == "dropout":
            f = 4.0 * int(np.prod(shape))
            fwd, train = fwd + f, train + 2 * f
        shape = nxt
    if layers and layers[-1]["type"] == "softmax":
        fwd += 5.0 * shape[0]
        train += 10.0 * shape[0]
    train += 6.0 * n_params
    return {"forward": fwd, "train_step": train, "params": n_params,
            "matmul_train": matmul}


def step_bytes(layers: list, input_shape: tuple, batch: int) -> float:
    """Least HBM traffic of one training step: parameters and velocities
    read and written once (float32), the minibatch read once."""
    n_params = model_flops(layers, input_shape)["params"]
    return 4.0 * (4 * n_params + batch * int(np.prod(input_shape)))
