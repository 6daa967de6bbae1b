"""The form the fused trainer holds a resident set in (ISSUE 35): as
given off the TPU, in every program the parent built; on a TPU once as
bfloat16, padded to the tiles of the layout the epoch programs ask for,
by a rule over what the trainer can observe, and only where the array
then lies as asked; one prepare pass a source whoever calls; the form in
the start line and the rows, the passes in a gauge; under ``run_fused``
the held form replaces the loader's rows on the device.

The TPU's side runs here on the CPU backend with ``tuning.on_tpu``
patched: ``Layout.AUTO`` then answers with the default layout (no
tiles, so no padding), and the ask, the pass and the memo are the
chip's code; one test hands the trainer a v5e's answer.  What the chip's
compiler answers is ``test_resident_set_tpu_compile.py``'s."""

import hashlib
import json
import logging

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout

import test_train_tracing as tiny
from znicz_tpu.memory import Vector
from znicz_tpu.ops import tuning
from znicz_tpu.parallel import FusedTrainer, extract_model, fused
from znicz_tpu.telemetry import tracing
from znicz_tpu.telemetry.registry import REGISTRY

#: sha256 of the jaxprs of the epoch programs of ``test_train_tracing``'s
#: model (conv, LRN+pool, dropout, two fc) as the commit before the held
#: set traced them.  Off the TPU the set is passed as given, so the
#: programs are those: a change that means to alter them replaces the
#: digests and says so.  Replaced once since: the conv before the pair is
#: one plain convolution on the XLA tier too, where it was two
#: column-parity convolutions (3 convolutions in the train program, 1 in
#: the eval program, where there were 6 and 2).
EPOCH_PROGRAMS = {
    "train": "b70c56d09925291bf3bb1123bbef9da5102609fc469e78e4eac18ddf60b16c1c",
    "eval": "3734ef65c19b11d3d1a2de15ec86984be0ebc9e9523f5523047d77abec7980d4",
}
HYP = (0.1, 0.0, 0.0, 0.9)
BATCH = 8


@pytest.fixture
def as_on_a_tpu(monkeypatch):
    """The trainer's rule sees a TPU; the kernels stay XLA's."""
    monkeypatch.setattr(tuning, "on_tpu", lambda: True)
    monkeypatch.setenv("ZNICZ_TPU_NO_PALLAS", "1")


def _prepares() -> float:
    """The gauge's reading, 0 before any trainer of the process held a
    set."""
    return float(REGISTRY.as_dict().get("train_set_prepares", 0.0))


def _trainer_and_set():
    wf = tiny._workflow()
    spec, params, vels = extract_model(wf)
    ld = wf.loader
    return (FusedTrainer(spec=spec, params=params, vels=vels),
            ld.original_data.devmem, ld.original_labels.devmem)


# -- off the TPU -----------------------------------------------------------------
@pytest.mark.parametrize("program", sorted(EPOCH_PROGRAMS))
def test_off_the_tpu_the_programs_are_the_parents(program):
    tr, data, target = _trainer_and_set()
    before = _prepares()
    assert tr.hold(data, tiny.BATCH) is data
    assert (tr.set_form, _prepares()) == ("as-given", before)
    idx, mask, ctrs = tr._idx_matrix(np.arange(2 * tiny.BATCH), tiny.BATCH)
    scales = np.ones(2, np.float32)
    jaxpr = (jax.make_jaxpr(tr._train_epoch_fn.fn)(
        tr.params, tr.vels, data, target, idx, mask, ctrs, np.uint32(0),
        scales, scales) if program == "train"
        else jax.make_jaxpr(tr._eval_epoch_fn.fn)(
            tr.params, data, target, idx, mask))
    assert hashlib.sha256(str(jaxpr).encode()).hexdigest() \
        == EPOCH_PROGRAMS[program]


def test_off_the_tpu_a_run_is_bit_equal_to_one_over_the_set_as_given():
    """Through ``train_epoch`` (which holds the set) and through the
    program itself, handed the loader's array."""
    tr, data, target = _trainer_and_set()
    other = FusedTrainer(spec=tr.spec, params=tr.params, vels=tr.vels)
    # the programs donate their state: each trainer its own copy
    other.params, other.vels = jax.tree.map(jnp.copy,
                                            (tr.params, tr.vels))
    indices = np.arange(3 * tiny.BATCH)
    got = tr.train_epoch(data, target, indices, tiny.BATCH, epoch=0)
    other._build()
    idx, mask, ctrs = other._idx_matrix(indices, tiny.BATCH)
    scales = jnp.ones(3, jnp.float32)
    params, _, want = other._train_epoch_fn(
        other.params, other.vels, data, target, idx, mask, ctrs,
        jnp.uint32(0), scales, scales)
    np.testing.assert_array_equal(got["loss"], np.asarray(want["loss"]))
    for mine, theirs in zip(jax.tree.leaves(tr.params),
                            jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(mine), np.asarray(theirs))


# -- the rule ------------------------------------------------------------------------
def _layer(kind, activation="linear"):
    cfg = {"conv": {"stride": (1, 1), "padding": (0, 0)},
           "max_pool": {"ksize": (2, 2), "stride": (2, 2),
                        "padding": (0, 0)}}.get(kind, {})
    return fused.LayerSpec(kind, activation, kind in fused.PAIR_KINDS, HYP,
                           HYP, tuple(sorted(cfg.items())))


def _small_trainer(first: str, mesh=None, **spec_kw) -> FusedTrainer:
    """8x8x3 rows into ``first``, then an fc to 4 classes."""
    gen = np.random.default_rng(35)

    def pair(*shape):
        return (gen.normal(0, 0.05, shape).astype(np.float32),
                np.zeros(shape[-1], np.float32))
    fc_in = {"conv": 6 * 6 * 4, "max_pool": 4 * 4 * 3}.get(first, 8 * 8 * 3)
    params = [{"conv": pair(3, 3, 3, 4), "fc": pair(192, 192)}.get(
        first, (None, None)), pair(fc_in, 4)]
    vels = [tuple(None if a is None else np.zeros_like(a) for a in p)
            for p in params]
    spec = fused.ModelSpec((_layer(first, "strict_relu"), _layer("fc")),
                           "softmax", **spec_kw)
    return FusedTrainer(spec=spec, params=params, vels=vels, mesh=mesh)


def _rows(dtype=np.float32, device=True):
    rows = np.arange(32 * 8 * 8 * 3).reshape(32, 8, 8, 3).astype(dtype)
    return jnp.asarray(rows) if device else rows


RULE = {
    # case: (first layer, rows, matmul precision, held as bfloat16)
    "float32 rows into a conv": ("conv", _rows, None, True),
    # no compile for a described chip covers these sets: as given
    "float32 rows into an fc": ("fc", _rows, None, False),
    "float32 rows into a deconv": ("deconv", _rows, None, False),
    "rows of two dims into a conv": (
        "conv", lambda: _rows().reshape(32, -1), None, False),
    "a first layer that is no MXU product": ("max_pool", _rows, None, False),
    "an activation before the first product": ("activation", _rows, None,
                                               False),
    "a matmul precision that is not the default": ("conv", _rows, "highest",
                                                   False),
    "integer rows": ("conv", lambda: _rows(np.int32), None, False),
    "rows on the host": ("conv", lambda: _rows(device=False), None, False),
}


@pytest.mark.parametrize("case", sorted(RULE))
def test_the_rule_on_a_tpu(as_on_a_tpu, case):
    first, rows, precision, held = RULE[case]
    tr = _small_trainer(first)
    with jax.default_matmul_precision(precision):
        assert tr._holds_bfloat16(rows()) is held


@pytest.mark.parametrize("case", sorted(RULE))
def test_off_the_tpu_the_rule_holds_nothing(case):
    first, rows, precision, _ = RULE[case]
    with jax.default_matmul_precision(precision):
        assert not _small_trainer(first)._holds_bfloat16(rows())


def test_a_compute_dtype_without_bfloat16_operands_is_passed_as_given(
        as_on_a_tpu):
    assert _small_trainer("conv", compute_dtype="bfloat16"
                          )._holds_bfloat16(_rows())
    assert not _small_trainer("conv", compute_dtype="float16"
                              )._holds_bfloat16(_rows())


def test_an_augmentation_that_only_selects_keeps_the_set_held(as_on_a_tpu):
    from znicz_tpu.loader.augment import RandomCropFlip
    tr = _small_trainer("conv")
    tr.augment = RandomCropFlip((8, 8))
    assert tr._holds_bfloat16(_rows())
    tr.augment = object()           # whatever it does to a row
    assert not tr._holds_bfloat16(_rows())


# -- one pass a source ---------------------------------------------------------------
def test_the_set_is_prepared_once_a_source(as_on_a_tpu):
    """Head, tail, the tail's evaluation, a validation pass and a second
    epoch read ONE held form; a second source is a second pass."""
    tr = _small_trainer("conv")
    data, labels = _rows(), jnp.zeros(32, jnp.int32)
    before = _prepares()

    def epoch(rows):
        tr.train_epoch(rows, labels, np.arange(8), BATCH, sync=False)
        tr.train_epoch(rows, labels, np.arange(8, 24), BATCH)
        tr.eval_epoch(rows, labels, np.arange(24, 32), BATCH)
        tr.eval_epoch(rows, labels, np.arange(16), BATCH)

    epoch(data)
    epoch(data)
    assert _prepares() - before == 1
    held = tr.hold(data, BATCH)
    assert held is tr.hold(data, BATCH)
    assert isinstance(held, fused.HeldSet) and held.row_shape == (8, 8, 3)
    assert held.rows.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(held.rows, np.float32),
        np.asarray(data.astype(jnp.bfloat16), np.float32))
    assert tr.set_form == "bfloat16 major_to_minor=(0,1,2,3) rows=8x8x3"
    epoch(_rows() + 1.0)
    assert _prepares() - before == 2
    epoch(data)                     # the first source is still held
    assert _prepares() - before == 2


def test_the_pass_is_a_span_of_its_own_and_the_builds_are_counted(
        as_on_a_tpu):
    """Two counted builds (the program that is asked for its layout, the
    pass's two executables together), neither around the pass over the
    set."""
    tr = _small_trainer("conv")
    tr._build()
    tracing.clear()
    tr.hold(_rows(), BATCH)
    builds = tracing.recent_spans(name="compile")
    (made,) = tracing.recent_spans(name="trainer.prepare_set")
    assert [(b.attrs["site"], b.attrs["cause"]) for b in builds] \
        == [("train.fused", "cold")] * 2
    assert all(b._t0 + b.duration_ms / 1e3 <= made._t0 for b in builds)
    tr.hold(_rows(), BATCH)         # a second source: the ask is kept
    assert len(tracing.recent_spans(name="compile")) == 3


V5E = Layout(major_to_minor=(0, 3, 1, 2), tiling=((8, 128), (2, 1)))


@pytest.mark.parametrize("shape, layout, want", [
    ((9216, 227, 227, 3), V5E, (9216, 232, 256, 3)),
    ((4608, 224, 224, 3), V5E, (4608, 224, 256, 3)),
    ((9216, 227, 227, 3), Layout(major_to_minor=(0, 1, 2, 3)),
     (9216, 227, 227, 3)),
    ((1000, 700), Layout(major_to_minor=(0, 1), tiling=((8, 128),)),
     (1000, 768)),
], ids=["alexnet", "vgg11", "no-tiles", "two-dims"])
def test_tiled_shape_rounds_the_tiled_dims_up(shape, layout, want):
    assert fused.tiled_shape(shape, layout) == want


@pytest.mark.parametrize("n", [32, 256, 300, 1100],
                         ids=["one-piece", "two-pieces", "a-lapping-piece",
                              "eight-pieces-and-a-lap"])
def test_the_pieces_make_the_padded_set(n):
    """By pieces of rows (128 at least, the last lapping the one before
    where the rows do not divide) what one pass would make."""
    data = jnp.asarray(np.random.default_rng(n).normal(
        size=(n, 5, 6, 3)).astype(np.float32))
    got = fused.padded_bfloat16(data, (n, 8, 128, 3))
    assert got.dtype == jnp.bfloat16 and got.shape == (n, 8, 128, 3)
    want = jnp.pad(data.astype(jnp.bfloat16),
                   ((0, 0), (0, 3), (0, 122), (0, 0)))
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_a_padded_set_trains_what_the_rows_train(as_on_a_tpu, monkeypatch):
    """With a v5e's answer the set is held padded to its tiles, and the
    step cuts the padding off the rows it takes: the same losses as over
    the set held without padding."""
    data, labels = _rows() / 1e4, jnp.arange(32, dtype=jnp.int32) % 4
    plain = _small_trainer("conv").train_epoch(data, labels, np.arange(24),
                                               BATCH)
    monkeypatch.setattr(FusedTrainer, "_ask_layout", lambda *a: V5E)
    monkeypatch.setattr(FusedTrainer, "_laid_as",
                        staticmethod(lambda rows, asked: True))
    tr = _small_trainer("conv")
    padded = tr.train_epoch(data, labels, np.arange(24), BATCH)
    held = tr.hold(data, BATCH)
    assert (held.rows.shape, held.row_shape) == ((32, 8, 128, 3), (8, 8, 3))
    assert tr.set_form.endswith("rows=8x128x3")
    assert float(jnp.abs(held.rows[:, :, 8:]).max()) == 0.0
    np.testing.assert_array_equal(padded["loss"], plain["loss"])
    ev = tr.eval_epoch(data, labels, np.arange(24, 32), BATCH)
    assert np.isfinite(ev["loss"]).all()


def test_a_set_that_does_not_lie_as_asked_is_passed_as_given(as_on_a_tpu,
                                                             monkeypatch):
    """The compiler's answer and the padded shape's default layout have
    to agree (here they cannot: a v5e's answer, the CPU's array); where
    they do not, the programs get the set as given, once decided."""
    data, labels = _rows() / 1e4, jnp.arange(32, dtype=jnp.int32) % 4
    monkeypatch.setattr(FusedTrainer, "_ask_layout", lambda *a: V5E)
    tr = _small_trainer("conv")
    before = _prepares()
    assert tr.hold(data, BATCH) is data and tr.set_form == "as-given"
    got = tr.train_epoch(data, labels, np.arange(24), BATCH)
    tr.eval_epoch(data, labels, np.arange(24, 32), BATCH)
    assert _prepares() - before == 1        # the pass that found out
    assert not data.is_deleted()
    monkeypatch.setattr(tuning, "on_tpu", lambda: False)
    want = _small_trainer("conv").train_epoch(data, labels, np.arange(24),
                                              BATCH)
    np.testing.assert_array_equal(got["loss"], want["loss"])


@pytest.mark.parametrize("has, lies", [
    (V5E, True),
    (Layout(major_to_minor=(0, 3, 1, 2), tiling=((8, 128),)), False),
    (Layout(major_to_minor=(0, 2, 3, 1), tiling=((8, 128), (2, 1))), False),
], ids=["as-asked", "other-tiles", "rows-minor-most"])
def test_laid_as_compares_order_and_tiles(has, lies):
    class Rows:
        format = type("Format", (), {"layout": has})
    assert FusedTrainer._laid_as(Rows, V5E) is lies
    rows_last = Layout(major_to_minor=(1, 0))
    Rows.format.layout = rows_last
    assert not FusedTrainer._laid_as(Rows, rows_last)


def test_the_held_form_is_what_is_laid_over_a_mesh(as_on_a_tpu):
    from znicz_tpu.parallel import mesh as mesh_lib
    on_mesh = _small_trainer("conv", mesh=mesh_lib.make_mesh(4, 1))
    data, labels = _rows() / 1e4, jnp.arange(32, dtype=jnp.int32) % 4
    ms = on_mesh.train_epoch(data, labels, np.arange(16), BATCH)
    on_mesh.eval_epoch(data, labels, np.arange(16, 32), BATCH)
    held = on_mesh.hold(data, BATCH).rows
    assert held.dtype == jnp.bfloat16 and held.sharding == on_mesh._repl
    # the labels are placed as given, under their own key of the memo
    assert on_mesh._mesh_place(labels).dtype == jnp.int32
    alone = _small_trainer("conv").train_epoch(data, labels,
                                               np.arange(16), BATCH)
    assert alone["loss"][0] != alone["loss"][1]
    np.testing.assert_allclose(ms["loss"], alone["loss"], rtol=1e-5)


def test_a_held_set_trains_what_its_rounded_rows_train(as_on_a_tpu,
                                                       monkeypatch):
    """The held form changes WHEN the rows are rounded, not what the
    step computes from them: a trainer handed rows that bfloat16 holds
    exactly reads the same losses held and as given."""
    tr, data, target = _trainer_and_set()
    exact = data.astype(jnp.bfloat16).astype(jnp.float32)
    indices = np.arange(3 * tiny.BATCH)
    before = _prepares()
    held = tr.train_epoch(exact, target, indices, tiny.BATCH, epoch=0)
    assert _prepares() - before == 1
    monkeypatch.setattr(tuning, "on_tpu", lambda: False)
    tr2, _, _ = _trainer_and_set()
    given = tr2.train_epoch(exact, target, indices, tiny.BATCH, epoch=0)
    assert _prepares() - before == 1
    np.testing.assert_array_equal(held["loss"], given["loss"])


# -- the run says which --------------------------------------------------------------
def _run(wf, tmp_path, epochs=2):
    lines = []
    handler = logging.Handler()
    handler.emit = lambda record: lines.append(record.getMessage())
    wf.logger.addHandler(handler)
    try:
        path = tmp_path / "rows.jsonl"
        trainer = wf.train(fused=True, max_epochs=epochs,
                           timeline_jsonl=str(path))
    finally:
        wf.logger.removeHandler(handler)
    (start,) = [ln for ln in lines if ln.startswith("fused trainer on")]
    return trainer, start, [json.loads(ln)
                            for ln in path.read_text().splitlines()]


def test_the_start_line_and_the_rows_say_as_given(tmp_path):
    before = _prepares()
    trainer, start, rows = _run(tiny._workflow(), tmp_path)
    assert "set_form='as-given'" in start
    assert len(rows) == 2 and all(r["set_form"] == "as-given" for r in rows)
    assert _prepares() == before


def test_the_start_line_the_rows_and_the_gauge_say_held(as_on_a_tpu,
                                                        tmp_path):
    before = _prepares()
    trainer, start, rows = _run(tiny._workflow(), tmp_path)
    form = "bfloat16 major_to_minor=(0,1,2,3) rows=16x16x3"
    assert f"set_form='{form}'" in start
    assert len(rows) == 2 and all(r["set_form"] == form for r in rows)
    # head, tail, the tail's evaluation and the validation pass of two
    # epochs: one pass over the loader's set
    assert _prepares() - before == 1
    assert all(np.isfinite(r["wall_ms"]) for r in rows)


# -- the held form replaces the loader's rows ----------------------------------------
def test_a_released_vector_with_a_host_copy_uploads_anew():
    from znicz_tpu.backends import Device
    vec = Vector(np.arange(12, dtype=np.float32).reshape(3, 4))
    vec.initialize(Device.create("xla"))
    first = vec.devmem
    assert vec.release_device() is vec and first.is_deleted()
    again = vec.devmem
    assert again is not first and not again.is_deleted()
    np.testing.assert_array_equal(np.asarray(again), vec.mem)


def test_a_released_vector_made_on_the_device_is_gone():
    vec = Vector()
    vec.devmem = jnp.arange(12.0)
    made = vec.devmem
    vec.release_device()
    assert made.is_deleted() and vec.devmem is made
    with pytest.raises(RuntimeError, match="deleted"):
        vec.mem
    assert Vector().release_device().devmem is None


def test_a_device_side_store_drops_the_host_copy():
    """What ``release_device`` stands on: a host copy that is there is
    the vector's value."""
    vec = Vector(np.zeros(3, np.float32))
    vec.devmem = jnp.ones(3)
    assert vec._mem is None
    np.testing.assert_array_equal(vec.mem, np.ones(3, np.float32))


def test_run_fused_releases_the_rows_it_holds_in_its_own_form(as_on_a_tpu,
                                                            tmp_path):
    wf = tiny._workflow()
    source = wf.loader.original_data.devmem
    labels = wf.loader.original_labels.devmem
    want = np.array(wf.loader.original_data.mem)
    trainer, _, rows = _run(wf, tmp_path)
    assert source.is_deleted() and not labels.is_deleted()
    assert len(rows) == 2 and all(np.isfinite(r["wall_ms"]) for r in rows)
    # the trainer, kept for further use, answers for the source it held
    before = _prepares()
    ev = trainer.eval_epoch(source, labels, np.arange(tiny.BATCH),
                            tiny.BATCH)
    assert np.isfinite(ev["loss"]).all() and _prepares() == before
    # and the unit graph's loader has its rows back on demand
    np.testing.assert_array_equal(
        np.asarray(wf.loader.original_data.devmem), want)


def test_run_fused_keeps_rows_it_passes_as_given(tmp_path):
    wf = tiny._workflow()
    source = wf.loader.original_data.devmem
    _run(wf, tmp_path, epochs=1)
    assert not source.is_deleted()
    assert wf.loader.original_data.devmem is source

