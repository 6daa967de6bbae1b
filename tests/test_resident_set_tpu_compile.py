"""The resident set as the epoch programs take it, compiled for a
described v5e through the trainer's own builder, at the sizes of the
image cells: 9,216 rows of 227x227x3 at minibatch 128 before AlexNet's
first layers, 4,608 rows of 224x224x3 at 64 before VGG-A's.

The default device layout of ``[rows, H, W, 3]`` puts the rows
minor-most; a gather of rows wants them major-most; so a program that
is handed the set in that layout re-lays all of it out before its first
step, 3.3 GB of temporaries and 15 ms a launch (PERF.md section 6, PR
35).  ``FusedTrainer._ask_layout`` leaves the layout to the compiler
once; ``hold`` keeps the set in bfloat16 with the dims that layout tiles
rounded up to its tile (``fused.tiled_shape``), a shape whose DEFAULT
layout is the asked one; and the head (k minibatches), the tail (one)
and the evaluation program over such a ``HeldSet`` may hold no operation
over the whole set.

Nothing runs, so this says nothing of results or times.  The topology
is described inside a fixture, never at import: only the worker that is
given this file loads the TPU's library."""

import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from znicz_tpu.ops import tuning
from znicz_tpu.parallel import fused

HYP = (0.01, 0.0005, 0.0, 0.9)
N_CLASSES = 16


def _layer(kind, activation="linear", bias=False, **cfg):
    return fused.LayerSpec(kind, activation, bias, HYP, HYP,
                           tuple(sorted(cfg.items())))


#: first layers as the configurations have them, then the least that
#: ends in a softmax: (rows, H, W, minibatch, layers, parameter shapes)
MODELS = {
    "alexnet": (9216, 227, 227, 128, (
        _layer("conv", "strict_relu", True, stride=(4, 4), padding=(0, 0),
               act_folded=True),
        _layer("lrn_pool", n=5, alpha=1e-4, beta=0.75, k=2.0, ksize=(3, 3),
               stride=(2, 2), padding=(0, 0), use_abs=False,
               fold_act="strict_relu"),
        _layer("conv", "strict_relu", True, stride=(2, 2), padding=(0, 0)),
        _layer("fc", bias=True)),
        [((11, 11, 3, 96), (96,)), None, ((3, 3, 96, 32), (32,)),
         ((13 * 13 * 32, N_CLASSES), (N_CLASSES,))]),
    "vgg11": (4608, 224, 224, 64, (
        _layer("conv", "strict_relu", True, stride=(1, 1), padding=(1, 1)),
        _layer("max_pool", ksize=(2, 2), stride=(2, 2), padding=(0, 0)),
        _layer("conv", "strict_relu", True, stride=(4, 4), padding=(0, 0)),
        _layer("fc", bias=True)),
        [((3, 3, 3, 64), (64,)), None, ((4, 4, 64, 32), (32,)),
         ((28 * 28 * 32, N_CLASSES), (N_CLASSES,))]),
}
PROGRAMS = ("head", "tail", "eval")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but never read back: keep it out
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cached)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def compiled(one_chip):
    """For each model: the layout the trainer asked for, and its three
    epoch programs compiled over the set as ``hold`` keeps it and over
    the float32 set as given (what every launch ran before)."""
    patch = pytest.MonkeyPatch()
    patch.setattr(tuning, "on_tpu", lambda: True)
    out = {}
    try:
        for name, (rows, h, w, batch, layers, shapes) in MODELS.items():
            out[name] = _compile_model(one_chip, rows, h, w, batch, layers,
                                       shapes)
    finally:
        patch.undo()
    return out


def _compile_model(chip, rows, h, w, batch, layers, shapes) -> dict:
    def on_chip(shape, dtype=jnp.float32, sharding=chip):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    zeros = [(None, None) if s is None else tuple(
        np.zeros(leaf, np.float32) for leaf in s) for s in shapes]
    tr = fused.FusedTrainer(spec=fused.ModelSpec(layers, "softmax"),
                            params=zeros, vels=zeros)
    tr._build()
    # the state as the described chip would hold it
    tr.params = tr.vels = [
        (None, None) if s is None else tuple(on_chip(leaf) for leaf in s)
        for s in shapes]
    source = on_chip((rows, h, w, 3))
    labels = on_chip((rows,), jnp.int32)
    asked = tr._ask_layout(source, batch)
    held = fused.HeldSet(
        on_chip(fused.tiled_shape(source.shape, asked), jnp.bfloat16),
        source.shape[1:])

    def programs(data) -> dict:
        def train(steps):
            idx, mask, ctrs = tr._idx_matrix(np.arange(steps * batch),
                                             batch)
            scales = np.ones(steps, np.float32)
            return tr._train_epoch_fn.fn.lower(
                tr.params, tr.vels, data, labels, idx, mask, ctrs,
                np.uint32(0), scales, scales).compile()
        idx, mask, _ = tr._idx_matrix(np.arange(8 * batch), batch)
        return {"head": train(rows // batch - 9), "tail": train(1),
                "eval": tr._eval_epoch_fn.fn.lower(
                    tr.params, data, labels, idx, mask).compile()}
    return {"asked": asked, "shape": source.shape,
            "held": held.rows.shape, **programs(held),
            "as-given": programs(source)}


def _set_layouts(program, shape) -> list:
    """The layouts of the program's arguments of shape ``shape``."""
    args, _ = program.input_formats
    return [leaf.layout for leaf, struct in zip(
        jax.tree.leaves(args), jax.tree.leaves(program.in_avals[0]))
        if struct.shape == shape]


#: an instruction that makes an array led by the set's rows
def _whole_set_ops(text: str, rows: int) -> list:
    made = re.findall(
        r"^\s*(?:ROOT )?%?[\w.\-]+ = \w+\[" + str(rows)
        + r",[\d,]*\]\S* ([\w\-]+)\(", text, re.M)
    return [op for op in made
            if op not in ("parameter", "get-tuple-element", "tuple")]


@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize("model", sorted(MODELS))
def test_no_operation_over_the_whole_set(compiled, model, program):
    got = compiled[model]
    text = got[program].as_text()
    rows, height = got["held"][:2]
    assert f"bf16[{rows},{height}," in text, \
        "the set is not among the program's arguments"
    assert _whole_set_ops(text, rows) == []


def _temporaries(program) -> int:
    return program.memory_analysis().temp_size_in_bytes


@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize("model", sorted(MODELS))
def test_temporaries_without_a_copy_of_the_set(compiled, model, program):
    """The step's own temporaries are left: under 1 GB before AlexNet's
    first layers, where the same program over the set as given plans
    3.3 GB more.  VGG-A's first conv alone writes 1.6 GB a step, and in
    a program of ONE step the copy's room serves the step afterwards,
    so there the plan only may not grow."""
    got = compiled[model]
    set_bytes = 2 * int(np.prod(got["shape"]))
    temp = _temporaries(got[program])
    as_given = _temporaries(got["as-given"][program])
    if model == "alexnet":
        assert temp < 1e9 and as_given > 3.2e9, (temp, as_given)
    elif program == "tail":
        assert temp <= as_given, (temp, as_given)
    else:
        assert temp + 0.9 * set_bytes < as_given, (temp, as_given)


@pytest.mark.parametrize("model", sorted(MODELS))
def test_the_held_shapes_default_layout_is_the_asked_one(compiled, model):
    """No program is handed a layout: the set's padded shape is one
    whose default layout the compiler's own choice for the rows is, in
    all three programs, in bfloat16."""
    got = compiled[model]
    assert got["asked"].major_to_minor[0] == 0           # rows major-most
    assert got["held"] == fused.tiled_shape(got["shape"], got["asked"])
    assert got["held"][0] == got["shape"][0] and got["held"][3] == 3
    for p in PROGRAMS:
        assert _set_layouts(got[p], got["held"]) == [got["asked"]], p
        assert [struct.dtype for struct in
                jax.tree.leaves(got[p].in_avals[0])
                if struct.shape == got["held"]] == [jnp.bfloat16]


@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize("model", sorted(MODELS))
def test_the_set_as_given_is_laid_out_again_every_launch(compiled, model,
                                                         program):
    """Why ``hold`` exists: handed the float32 set in its default layout
    (the rows minor-most), a program makes a bfloat16 copy of all of it
    in the layout its gather wants before the first step."""
    got = compiled[model]
    program = got["as-given"][program]
    (given,) = _set_layouts(program, got["shape"])
    assert given.major_to_minor[-1] == 0                 # rows minor-most
    assert "copy" in _whole_set_ops(program.as_text(), got["shape"][0])
    assert _temporaries(program) > 2 * int(np.prod(got["shape"]))
