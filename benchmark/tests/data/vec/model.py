"""The ``model`` file of the test configuration ``tiny-vec``: a row is a
feature vector with its class label; the program is ``StandardWorkflow``
over fully connected layers (``workflow.py`` beside this file)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import data
from benchmark.lib.errors import BenchError

row = {"kind": "vector"}

KINDS = ("all2all_str", "softmax")


def _widths(cfg: dict) -> list[int]:
    for layer in cfg["layers"]:
        if layer["type"] not in KINDS:
            raise ValueError(f"layer type {layer['type']!r} is not one of "
                             f"{KINDS}")
    return [int(cfg["features"])] + [
        int(la["->"]["output_sample_shape"]) for la in cfg["layers"]]


def overrides(cfg: dict, traffic: dict, seed: int) -> list[str]:
    return [
        f"bench.seed={int(seed)}",
        f"bench_vec.minibatch_size={int(traffic['minibatch'])}",
        *(f"bench_vec.sizes.{k}={int(traffic[k])}"
          for k in ("n_train", "n_valid", "n_test")),
        f"bench_vec.noise={cfg['assumed']['noise']}",
        "bench_vec.decision.max_epochs=1000000000",
        "bench_vec.decision.fail_iterations=1000000000"]


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _make_rows(words, rows, features: int, n_classes: int, noise: float):
    labels = data.labels_of(words, rows, n_classes)
    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.key(0x7EC), words[0]), words[1])
    protos = jax.random.normal(jax.random.fold_in(key, 1),
                               (n_classes, features), jnp.float32)
    eps = jax.vmap(lambda r: jax.random.uniform(
        jax.random.fold_in(jax.random.fold_in(key, 2), r), (features,),
        jnp.float32, -1.0, 1.0))(rows)
    return protos[labels] + np.float32(noise) * eps, labels


def make_rows(seed: int, rows, cfg: dict, traffic: dict | None = None):
    """(vectors ``(n, features)``, labels ``(n,)``) of the global row
    numbers ``rows``: a class prototype plus uniform noise."""
    return _make_rows(data.seed_array(seed), np.asarray(rows, np.uint32),
                      int(cfg["features"]), int(cfg["n_classes"]),
                      float(cfg["assumed"]["noise"]))


def param_shapes(cfg: dict) -> list:
    w = _widths(cfg)
    return [((a, b), (b,)) for a, b in zip(w, w[1:])]


def hypers(cfg: dict) -> list:
    return [tuple({key: float(la["<-"][key + suffix])
                   for key in ("learning_rate", "weights_decay")}
                  for suffix in ("", "_bias")) for la in cfg["layers"]]


def make_weights(seed: int, shapes: list) -> list:
    return data.make_weights(seed, shapes)


def install(wf, weights: list) -> None:
    for unit, (w, b) in zip(wf.forwards, weights):
        if tuple(unit.weights.shape) != tuple(w.shape):
            raise BenchError(f"{unit.name}: the program's weights are "
                             f"{tuple(unit.weights.shape)}, the "
                             f"configuration's {tuple(w.shape)}")
        unit.weights.mem = np.asarray(w)
        unit.bias.mem = np.asarray(b)


def flops(cfg: dict, traffic: dict | None = None) -> dict:
    w = _widths(cfg)
    fwd = sum(2.0 * a * b + b for a, b in zip(w, w[1:])) + 5.0 * w[-1]
    params = sum(a * b + b for a, b in zip(w, w[1:]))
    matmul = 3.0 * sum(2.0 * a * b + b for a, b in zip(w, w[1:]))
    return {"forward": fwd, "train_step": matmul + 10.0 * w[-1]
            + 6.0 * params, "params": params, "matmul_train": matmul}


def step_bytes(cfg: dict, traffic: dict | None, batch: int) -> float:
    return 4.0 * (4 * flops(cfg)["params"] + batch * int(cfg["features"]))


def output_leaf(cfg: dict) -> int:
    return 2 * len(cfg["layers"]) - 2
