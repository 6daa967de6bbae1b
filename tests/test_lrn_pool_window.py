"""The merged LRN+pool pair on the convolutions' own layout
(``ops/lrn_pool.py`` header): the window kernels on the (H, W, B, C)
view of an unsplit x, run here in the Pallas interpreter.

What is compared with what.  The winner OFFSETS equal the numpy golden
path's everywhere.  With an LRN that is the identity (alpha 0, k 1: the
denominator is exactly 1) the pooled VALUES and the whole backward —
the flat-tap-order float32 sums of the scatter — are BIT-EQUAL to
``np_lrn_maxpool`` / ``np_gd_lrn_maxpool``.  With AlexNet's LRN the
values are held to float32 rounding: XLA's CPU backend contracts
``k + alpha * s`` and ``err * p - ...`` into fused multiply-adds where
numpy rounds twice, so an element in some ten thousand differs in its
last bit — in today's kernels and in the composed XLA ops alike (the
same tolerance class as ``tests/test_lrn_pool.py``).  Everything the
rule refuses must keep the column-parity kernels."""

import json
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import helpers
from znicz_tpu import prng
from znicz_tpu.backends import Device
from znicz_tpu.config import root
from znicz_tpu.models import cifar
from znicz_tpu.ops import lrn_pool, tuning
from znicz_tpu.parallel import fused, make_mesh

LRN = (5, 1e-4, 0.75, 2.0)            # n, alpha, beta, k: AlexNet's
IDENTITY = (5, 0.0, 0.75, 1.0)        # d = 1 exactly: y = x, dx = err_y
POOL = ((3, 3), (2, 2), 0)            # ksize, stride, padding: AlexNet's
#: the view's tiles lie over batch x channel: whole sublanes
B = 8


@pytest.fixture(autouse=True)
def interpret_mode(monkeypatch):
    monkeypatch.setattr(tuning, "_INTERPRET", True)
    yield


@pytest.fixture
def kernels(monkeypatch):
    """Counts the calls that reach each family of pair kernels."""
    calls = {"window": 0, "split": 0}

    def counted(name, family):
        fn = getattr(lrn_pool, name)

        def call(*a, **kw):
            calls[family] += 1
            return fn(*a, **kw)
        monkeypatch.setattr(lrn_pool, name, call)
    counted("pallas_lrn_maxpool_window", "window")
    counted("pallas_gd_lrn_maxpool_window", "window")
    counted("pallas_lrn_maxpool_split", "split")
    counted("pallas_gd_lrn_maxpool_split", "split")
    return calls


def _x(shape, seed=0, relu=False):
    x = np.random.default_rng(seed).normal(size=shape).astype(np.float32)
    return np.maximum(x, 0.0) if relu else x


def _both_ways(x, lrn, pool, use_abs=False, fold_act=None):
    """((y, idx, dx) of the dispatchers, the same of the golden path)."""
    y_ref, idx_ref = lrn_pool.np_lrn_maxpool(x, *lrn, *pool, use_abs)
    err = _x(y_ref.shape, seed=1) * 0.1
    dx_ref = lrn_pool.np_gd_lrn_maxpool(err, idx_ref, x, *lrn, *pool,
                                        fold_act)
    y, idx = lrn_pool.lrn_maxpool(jnp.asarray(x), *lrn, *pool, use_abs)
    dx = lrn_pool.gd_lrn_maxpool(jnp.asarray(err), jnp.asarray(idx_ref),
                                 jnp.asarray(x), *lrn, *pool, fold_act)
    assert idx.dtype == jnp.int32 and dx.dtype == jnp.float32
    return ((np.asarray(y), np.asarray(idx), np.asarray(dx)),
            (y_ref, idx_ref, dx_ref))


#: (H, W, C): AlexNet's two pairs and pool5's extent, odd like theirs;
#: 3 and 96 channels leave a lane register part empty, 256 fill two
SHAPES = [(55, 55, 3), (27, 27, 96), (13, 13, 256), (55, 27, 96),
          (27, 13, 256), (13, 55, 3)]


@pytest.mark.parametrize("h,w,c", SHAPES)
def test_identity_lrn_is_bit_equal_to_golden(kernels, h, w, c):
    """The pooling half alone: values, offsets and every float32 sum of
    the backward's scatter, to the bit."""
    got, want = _both_ways(_x((B, h, w, c), relu=True), IDENTITY, POOL)
    for g, r in zip(got, want):
        np.testing.assert_array_equal(g, r)
    assert kernels == {"window": 2, "split": 0}


@pytest.mark.parametrize("fold_act", [None, "strict_relu", "tanh"])
@pytest.mark.parametrize("h,w,c", SHAPES[:3])
def test_alexnet_lrn_matches_golden(kernels, h, w, c, fold_act):
    x = _x((B, h, w, c), relu=fold_act == "strict_relu")
    if fold_act == "tanh":
        x = np.tanh(x)
    (y, idx, dx), (y_ref, idx_ref, dx_ref) = _both_ways(
        x, LRN, POOL, fold_act=fold_act)
    np.testing.assert_array_equal(idx, idx_ref)
    np.testing.assert_allclose(y, y_ref, rtol=1e-6, atol=0)
    np.testing.assert_allclose(dx, dx_ref, rtol=1e-5, atol=1e-7)
    assert kernels == {"window": 2, "split": 0}


@pytest.mark.parametrize("h,w,c", SHAPES[:3])
def test_max_abs_keeps_the_sign(kernels, h, w, c):
    got, want = _both_ways(_x((B, h, w, c)), IDENTITY, POOL, use_abs=True)
    for g, r in zip(got, want):
        np.testing.assert_array_equal(g, r)
    assert (got[0] < 0).any()
    (y, idx, _), (y_ref, idx_ref, _) = _both_ways(
        _x((B, h, w, c)), LRN, POOL, use_abs=True)
    np.testing.assert_array_equal(idx, idx_ref)
    np.testing.assert_allclose(y, y_ref, rtol=1e-6, atol=0)


#: ksize, stride of geometries ``fusable`` admits beside 3x3/2: no halo
#: row (2x2/2), two (3 rows at stride 1), a tall window, rows skipped
GEOMETRIES = {"2x2/2": ((2, 2), (2, 2)), "3x3/1,2": ((3, 3), (1, 2)),
              "4x2/3,2": ((4, 2), (3, 2)), "2x3/3,2": ((2, 3), (3, 2)),
              "3x4/2": ((3, 4), (2, 2))}


@pytest.mark.parametrize("even", [False, True])
@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_every_fusable_geometry(kernels, geometry, even):
    ksize, stride = GEOMETRIES[geometry]
    h, w = (14, 12) if even else (13, 11)
    got, want = _both_ways(_x((B, h, w, 8), relu=True), IDENTITY,
                           (ksize, stride, 0), fold_act="strict_relu")
    for g, r in zip(got, want):
        np.testing.assert_array_equal(g, r)
    assert kernels == {"window": 2, "split": 0}


#: channels, VMEM budget, H: budgets a test-size array overflows, at which
#: the 14 to 16 pooled rows are no multiple of a block's
RAGGED = [(16, 1 << 20, 29), (16, 3 << 19, 31), (256, 1 << 21, 33),
          (256, 3 << 20, 35)]


@pytest.mark.parametrize("c,budget,h", RAGGED)
def test_a_last_block_that_reaches_beyond_the_array(monkeypatch, c, budget,
                                                    h):
    """More pooled rows than one block holds, and not a multiple of it:
    forward and backward work on the rows the last block holds.  (The
    jitted kernels are keyed by shape, not by the budget: a shape a
    case.)"""
    monkeypatch.setattr(lrn_pool, "_WINDOW_VMEM", budget)
    oh = (h - 3) // 2 + 1
    r = lrn_pool._window_rows(oh, 13, 6, c, 2, 1, False)
    assert 1 < r < oh and oh % r, (oh, r)
    got, want = _both_ways(_x((16, h, 13, c), relu=True), IDENTITY, POOL,
                           fold_act="strict_relu")
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    (y, idx, dx), (y_ref, idx_ref, dx_ref) = _both_ways(
        _x((16, h, 13, c), seed=5), LRN, POOL)
    np.testing.assert_array_equal(idx, idx_ref)
    np.testing.assert_allclose(y, y_ref, rtol=1e-6, atol=0)
    np.testing.assert_allclose(dx, dx_ref, rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("use_abs", [False, True])
def test_all_zeros_keep_the_first_tap(kernels, use_abs):
    """After a ReLU most windows are all zeros: every tap ties."""
    x = np.zeros((B, 13, 13, 96), np.float32)
    y, idx = lrn_pool.lrn_maxpool(jnp.asarray(x), *LRN, *POOL, use_abs)
    assert not np.asarray(y).any() and not np.asarray(idx).any()
    assert kernels["window"] == 1


@pytest.mark.parametrize("use_abs", [False, True])
def test_planted_ties_keep_the_first_of_the_tied(kernels, use_abs):
    """A window's largest value planted in two of its slots (for
    max-abs: with opposite signs): the earlier slot in row-major order
    wins, max-abs hands on that slot's sign, and the backward puts the
    whole error there."""
    gen = np.random.default_rng(7)
    x = gen.uniform(-1.0, 1.0, (B, 13, 13, 5)).astype(np.float32)
    # an odd column belongs to one 3x3/2 window, as its slot column 1;
    # an even row to two, as slot row 2 of one and slot row 0 of the
    # next: planted there, every window holds the value in slot 1 and
    # in slot 7
    x[:, 0::4, 1::2, :] = -2.0 if use_abs else 2.0
    x[:, 2::4, 1::2, :] = 2.0
    got, want = _both_ways(x, IDENTITY, POOL, use_abs)
    for g, r in zip(got, want):
        np.testing.assert_array_equal(g, r)
    y, idx, _ = got
    assert (idx == 1).all()
    assert (y[:, 0::2] == (-2.0 if use_abs else 2.0)).all()
    assert (y[:, 1::2] == 2.0).all()


#: shape, ksize, stride, padding, dtype of pairs the rule refuses
SPLIT_CASES = {
    "batch of 4": ((4, 9, 9, 8), (3, 3), (2, 2), 0, jnp.float32),
    "batch of 12": ((12, 9, 9, 8), (3, 3), (2, 2), 0, jnp.float32),
    "bfloat16 activations": ((8, 9, 9, 8), (3, 3), (2, 2), 0,
                             jnp.bfloat16),
}


@pytest.mark.parametrize("case", sorted(SPLIT_CASES))
def test_everything_else_keeps_the_parity_kernels(kernels, case):
    shape, ksize, stride, padding, dtype = SPLIT_CASES[case]
    assert lrn_pool.fusable(ksize, stride, padding)
    assert not lrn_pool.windowed(shape, ksize, stride, padding, dtype)
    x = jnp.asarray(_x(shape)).astype(dtype)
    y, idx = lrn_pool.lrn_maxpool(x, *LRN, ksize, stride, padding)
    y_ref, idx_ref = lrn_pool.xla_lrn_maxpool(x, *LRN, ksize, stride,
                                              padding)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(idx_ref))
    assert y.dtype == dtype
    err = jnp.asarray(_x(y.shape, seed=1))
    dx = lrn_pool.gd_lrn_maxpool(err, idx, x, *LRN, ksize, stride,
                                 padding, "strict_relu")
    dx_ref = lrn_pool.xla_gd_lrn_maxpool(err, idx, x, *LRN, ksize, stride,
                                         padding, "strict_relu")
    packed = dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(dx), np.asarray(dx_ref),
                               rtol=2e-2 if packed else 1e-5,
                               atol=1e-2 if packed else 1e-6)
    assert kernels == {"window": 0, "split": 2}


@pytest.mark.parametrize("case", ["padded", "stride-W 3", "stride-W 1"])
def test_what_no_pair_kernel_takes_stays_composed(kernels, case):
    """``fusable`` refuses these before either family is asked: the rule
    holds them off the window kernels as well."""
    ksize, stride, padding = {"padded": ((3, 3), (2, 2), 1),
                              "stride-W 3": ((3, 3), (3, 3), 0),
                              "stride-W 1": ((2, 2), (2, 1), 0)}[case]
    assert not lrn_pool.windowed((8, 9, 9, 8), ksize, stride, padding)
    x = _x((8, 9, 9, 8))
    y, idx = lrn_pool.lrn_maxpool(jnp.asarray(x), *LRN, ksize, stride,
                                  padding)
    y_ref, idx_ref = lrn_pool.np_lrn_maxpool(x, *LRN, ksize, stride,
                                             padding)
    np.testing.assert_array_equal(np.asarray(idx), idx_ref)
    np.testing.assert_allclose(np.asarray(y), y_ref, rtol=1e-6)
    assert kernels == {"window": 0, "split": 0}


def test_batch_sharded_under_a_mesh(kernels):
    """Under the trainer's mesh the kernels run in a shard_map over the
    batch, and the rule reads the batch one device holds: 32 rows over
    4 data shards are 8 a device."""
    x = _x((32, 13, 13, 16), relu=True)
    y_ref, idx_ref = lrn_pool.np_lrn_maxpool(x, *IDENTITY, *POOL)
    err = _x(y_ref.shape, seed=1)
    dx_ref = lrn_pool.np_gd_lrn_maxpool(err, idx_ref, x, *IDENTITY, *POOL,
                                        "strict_relu")
    mesh = make_mesh(n_data=4, n_model=2)

    @jax.jit
    def both(x, err):
        with tuning.kernel_mesh(mesh):
            assert tuning.device_rows(x.shape[0]) == 8
            y, idx = lrn_pool.lrn_maxpool(x, *IDENTITY, *POOL)
            return y, idx, lrn_pool.gd_lrn_maxpool(
                err, idx, x, *IDENTITY, *POOL, "strict_relu")
    y, idx, dx = both(jnp.asarray(x), jnp.asarray(err))
    np.testing.assert_array_equal(np.asarray(y), y_ref)
    np.testing.assert_array_equal(np.asarray(idx), idx_ref)
    np.testing.assert_array_equal(np.asarray(dx), dx_ref)
    assert kernels == {"window": 2, "split": 0}


@pytest.mark.parametrize("c", [3, 96, 128, 192, 256])
@pytest.mark.parametrize("n", [3, 5])
def test_lane_window_sum_is_the_generic_sum(c, n):
    """The kernels' lane-rotation window sum against ``_window_sum``:
    the same terms in the same order, so the same bits — at channel
    counts that leave the last register part empty, fill it exactly, and
    span two."""
    from jax.experimental import pallas as pl

    from znicz_tpu.ops import normalization as lrn_math
    a = _x((5, B, c), seed=c + n)

    def kernel(a_ref, o_ref):
        o_ref[:] = lrn_pool._lane_window_sum(a_ref[:], n)
    got = pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct(a.shape, jnp.float32),
        interpret=True)(jnp.asarray(a))
    np.testing.assert_array_equal(np.asarray(got),
                                  lrn_math._window_sum(a, n, np))


# -- the rule in the step, and the counter that says it engaged --------------
def _pair_row(**cfg):
    hyp = (0.0, 0.0, 0.0, 0.0)
    full = {"n": 5, "alpha": 1e-4, "beta": 0.75, "k": 2.0,
            "ksize": (3, 3), "stride": (2, 2), "padding": (0, 0),
            "use_abs": False, **cfg}
    return fused.LayerSpec("lrn_pool", "linear", False, hyp, hyp,
                           tuple(sorted(full.items())))


def _unit(shape):
    return types.SimpleNamespace(input=types.SimpleNamespace(shape=shape))


def test_pair_routes_counts_each_family(monkeypatch):
    rows = (_pair_row(), _pair_row(fold_act="strict_relu"))
    spec = fused.ModelSpec(rows, "mse")
    units = [_unit((8, 13, 13, 4)), None, _unit((8, 7, 7, 4)), None]
    assert fused.windowed_pairs(rows, units) == (0, 1)
    assert fused.pair_routes(spec, units) == "window:2 split:0"
    # a batch that fills no sublane tile, in one pair of the two
    units[2] = _unit((4, 7, 7, 4))
    assert fused.windowed_pairs(rows, units) == (0,)
    assert fused.pair_routes(spec, units) == "window:1 split:1"
    # packed activations: neither
    packed = fused.ModelSpec(rows, "mse", storage_dtype="bfloat16")
    assert fused.pair_routes(packed, units) == "window:0 split:2"
    # under a mesh a device holds its share of the batch
    mesh = make_mesh(n_data=4, n_model=2)
    assert fused.pair_routes(spec, units, mesh) == "window:0 split:2"
    units[0] = units[2] = _unit((32, 7, 7, 4))
    assert fused.pair_routes(spec, units, mesh) == "window:2 split:0"
    # a model without a pair, and any model off the Pallas tier
    assert fused.pair_routes(fused.ModelSpec((), "mse"), []) == \
        "window:0 split:0"
    monkeypatch.setattr(tuning, "_INTERPRET", False)
    assert fused.pair_routes(spec, units) == "window:0 split:0"


#: conv -> pair -> conv -> pair -> fc: AlexNet's head at test size
TWO_PAIRS = [
    {"type": "conv_str", "->": {"n_kernels": 8, "kx": 3, "padding": 1},
     "<-": {"learning_rate": 0.02, "gradient_moment": 0.9}},
    {"type": "norm", "->": {"n": 5, "alpha": 1e-4, "beta": 0.75, "k": 2.0}},
    {"type": "max_pooling", "->": {"kx": 3, "sliding": 2}},
    {"type": "conv_str", "->": {"n_kernels": 16, "kx": 3, "padding": 1},
     "<-": {"learning_rate": 0.02, "gradient_moment": 0.9}},
    {"type": "norm", "->": {"n": 5, "alpha": 1e-4, "beta": 0.75, "k": 2.0}},
    {"type": "max_pooling", "->": {"kx": 3, "sliding": 2}},
    {"type": "softmax", "->": {"output_sample_shape": 10},
     "<-": {"learning_rate": 0.02, "gradient_moment": 0.9}},
]


def _train(tmp_path, name, batch=16):
    saved = root.cifar.synthetic.to_dict()
    saved_batch = root.cifar.minibatch_size
    root.cifar.synthetic.update({"n_train": 2 * batch, "n_valid": batch,
                                 "n_test": 0, "noise": 0.3, "size": 15})
    root.cifar.minibatch_size = batch
    try:
        prng.seed_all(1234)
        wf = cifar.CifarWorkflow(layers=TWO_PAIRS)
        wf.initialize(device=Device.create("xla"))
    finally:
        root.cifar.synthetic.update(saved)
        root.cifar.minibatch_size = saved_batch
    path = tmp_path / f"{name}.jsonl"
    wf.train(fused=True, max_epochs=3, timeline_jsonl=str(path))
    rows = [json.loads(line) for line in path.read_text().splitlines()]
    return rows, wf.decision.epoch_metrics


def test_a_two_pair_job_trains_as_the_composed_ops_do(tmp_path, kernels,
                                                      monkeypatch):
    rows, metrics = _train(tmp_path, "window")
    assert rows and all(r["kernel_tier"] == "pallas-interpret"
                        and r["pair_routes"] == "window:2 split:0"
                        and r["pool_routes"] == "windowed:0 taps:0"
                        for r in rows)
    assert kernels["window"] >= 4 and kernels["split"] == 0
    # a batch the rule refuses: today's rewrites and kernels
    split_rows, _ = _train(tmp_path, "split", batch=12)
    assert all(r["pair_routes"] == "window:0 split:2" for r in split_rows)
    assert kernels["split"] >= 4
    # the same job on the XLA tier: the composed ops, no family claimed
    monkeypatch.setattr(tuning, "_INTERPRET", False)
    xla_rows, xla_metrics = _train(tmp_path, "xla")
    assert all(r["kernel_tier"] == "xla"
               and r["pair_routes"] == "window:0 split:0"
               for r in xla_rows)
    for got, want in zip(metrics, xla_metrics):
        np.testing.assert_allclose(got["train_loss"], want["train_loss"],
                                   rtol=1e-5)
        np.testing.assert_allclose(got["validation_loss"],
                                   want["validation_loss"], rtol=1e-5)


def test_the_rows_of_a_windowed_pair(monkeypatch):
    """Where the rule holds the conv before the pair stays whole (its
    activation's derivative still folded into the pair's backward);
    where it does not, rewrite (iii) splits it as before."""
    def marks(batch):
        wf = helpers.tiny_workflow(TWO_PAIRS, (15, 15, 3), batch)
        spec = fused.extract_model(wf)[0]
        return [sorted(k for k in ("act_folded", "split_out", "fold_act",
                                   "emit_split") if k in la.cfg)
                for la in spec.layers[:4]]
    whole = [["act_folded"], ["fold_act"]] * 2
    split = [["act_folded", "split_out"], ["emit_split", "fold_act"]] * 2
    assert marks(8) == whole
    assert marks(12) == split
    monkeypatch.setattr(tuning, "_INTERPRET", False)     # the XLA tier
    assert marks(8) == split
