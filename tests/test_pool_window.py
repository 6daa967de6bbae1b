"""The non-overlapping max / max-abs pool on its windowed view
(``ops/pooling.py`` header): one Pallas pass a direction, run here in
interpret mode.  Winners, slot indices and gradients must EQUAL the numpy
golden path's; everything whose windows overlap, pad or leave a ragged
edge, and a batch that fills no whole sublane tile, must keep the
tap-stack path; the trainer's start record says which rows took which."""

import json
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from znicz_tpu import prng
from znicz_tpu.backends import Device
from znicz_tpu.config import root
from znicz_tpu.models import cifar
from znicz_tpu.ops import elementwise, pooling as pool_ops, tuning
from znicz_tpu.parallel import fused, make_mesh


@pytest.fixture(autouse=True)
def interpret_mode(monkeypatch):
    monkeypatch.setattr(tuning, "_INTERPRET", True)
    yield


@pytest.fixture
def kernels(monkeypatch):
    """Counts the calls that reach each windowed kernel."""
    calls = {"fwd": 0, "bwd": 0}
    fwd, bwd = (elementwise.pallas_pool_window,
                elementwise.pallas_gd_pool_window)

    def pool_window(*a, **kw):
        calls["fwd"] += 1
        return fwd(*a, **kw)

    def gd_pool_window(*a, **kw):
        calls["bwd"] += 1
        return bwd(*a, **kw)
    monkeypatch.setattr(elementwise, "pallas_pool_window", pool_window)
    monkeypatch.setattr(elementwise, "pallas_gd_pool_window",
                        gd_pool_window)
    return calls


def _x(shape, stream="x"):
    return np.asarray(prng.get(stream).normal(size=shape), np.float32)


FORWARD = {False: (pool_ops.max_pooling, pool_ops.np_max_pooling),
           True: (pool_ops.maxabs_pooling, pool_ops.np_maxabs_pooling)}
#: 3 and 64 are lane blocks narrower than a vreg, 256 two whole lane
#: blocks, 192 a ragged last one
CHANNELS = [3, 64, 128, 192, 256]
#: the windowed view's tiles lie over batch x channel: whole sublanes
B = 8


@pytest.mark.parametrize("use_abs", [False, True])
@pytest.mark.parametrize("c", CHANNELS)
@pytest.mark.parametrize("k", [2, 3])
def test_forward_equals_golden(kernels, k, c, use_abs):
    x = _x((B, 2 * k, 4 * k, c))
    run, golden = FORWARD[use_abs]
    y_ref, idx_ref = golden(x, (k, k))
    y, idx = run(jnp.asarray(x), (k, k))
    np.testing.assert_array_equal(np.asarray(y), y_ref)
    np.testing.assert_array_equal(np.asarray(idx), idx_ref)
    assert idx.dtype == jnp.int32
    assert kernels == {"fwd": 1, "bwd": 0}


@pytest.mark.parametrize("use_abs", [False, True])
@pytest.mark.parametrize("k", [2, 3])
def test_all_zeros_keep_the_first_tap(kernels, k, use_abs):
    """After a ReLU most windows are all zeros: every tap ties."""
    x = np.zeros((B, 2 * k, 3 * k, 64), np.float32)
    y, idx = FORWARD[use_abs][0](jnp.asarray(x), (k, k))
    assert not np.asarray(y).any() and not np.asarray(idx).any()
    assert kernels["fwd"] == 1


@pytest.mark.parametrize("use_abs", [False, True])
@pytest.mark.parametrize("k", [2, 3])
def test_planted_ties_keep_the_first_of_the_tied(kernels, k, use_abs):
    """A window's largest value planted in two slots (for max-abs: with
    opposite signs): the earlier slot in row-major order wins, and
    max-abs hands on that slot's sign."""
    gen = np.random.default_rng(7)
    x = gen.uniform(-1.0, 1.0, (B, 3 * k, 3 * k, 5)).astype(np.float32)
    win = x.reshape(B, 3, k, 3, k, 5)
    first, second = 1, k * k - 1
    win[:, :, first // k, :, first % k, :] = -2.0 if use_abs else 2.0
    win[:, :, second // k, :, second % k, :] = 2.0
    run, golden = FORWARD[use_abs]
    y_ref, idx_ref = golden(x, (k, k))
    y, idx = run(jnp.asarray(x), (k, k))
    assert (np.asarray(idx) == first).all()
    np.testing.assert_array_equal(np.asarray(idx), idx_ref)
    np.testing.assert_array_equal(np.asarray(y), y_ref)
    assert (np.asarray(y) == (-2.0 if use_abs else 2.0)).all()


@pytest.mark.parametrize("c", CHANNELS)
@pytest.mark.parametrize("k", [2, 3])
def test_backward_equals_golden(kernels, k, c):
    x = np.maximum(_x((B, 2 * k, 4 * k, c)), 0.0)      # ties among zeros
    _, idx = pool_ops.np_max_pooling(x, (k, k))
    err = _x(idx.shape, "err")
    ref = pool_ops.np_gd_max_pooling(err, idx, x.shape, (k, k))
    dx = pool_ops.gd_max_pooling(jnp.asarray(err), jnp.asarray(idx),
                                 x.shape, (k, k))
    assert dx.dtype == jnp.float32 and dx.shape == x.shape
    np.testing.assert_array_equal(np.asarray(dx), ref)
    assert kernels == {"fwd": 0, "bwd": 1}


@pytest.mark.parametrize("k,c", [(2, 64), (2, 192), (3, 5)])
def test_depooling_equals_golden(kernels, k, c):
    x = _x((B, 3 * k, 2 * k, c))
    y, idx = pool_ops.np_maxabs_pooling(x, (k, k))
    ref = pool_ops.np_depooling(y, idx, x.shape, (k, k))
    up = pool_ops.depooling(jnp.asarray(y), jnp.asarray(idx), x.shape,
                            (k, k))
    np.testing.assert_array_equal(np.asarray(up), ref)
    assert kernels == {"fwd": 0, "bwd": 1}


def test_columns_that_fill_no_whole_block(monkeypatch):
    """More pooled columns than one block holds, and not a multiple of
    it (at a budget a test-size array overflows)."""
    monkeypatch.setattr(elementwise, "_WINDOW_VMEM", 1 << 18)
    x = _x((16, 6, 14, 128))
    assert elementwise._window_blocks(7, 4, 16, 128) == (2, 16, 128)
    y_ref, idx_ref = pool_ops.np_max_pooling(x, (2, 2))
    y, idx = pool_ops.max_pooling(jnp.asarray(x), (2, 2))
    np.testing.assert_array_equal(np.asarray(y), y_ref)
    np.testing.assert_array_equal(np.asarray(idx), idx_ref)
    err = _x(y_ref.shape, "err")
    dx = pool_ops.gd_max_pooling(jnp.asarray(err), idx, x.shape, (2, 2))
    np.testing.assert_array_equal(
        np.asarray(dx),
        pool_ops.np_gd_max_pooling(err, idx_ref, x.shape, (2, 2)))


#: shape, ksize, stride, padding of pools the windowed view cannot hold
TAP_STACK_CASES = {
    "overlapping 3x3/2": ((8, 7, 7, 4), (3, 3), (2, 2), (0, 0)),
    "padded 2x2/2": ((8, 6, 6, 4), (2, 2), (2, 2), (1, 1)),
    "H not a multiple": ((8, 7, 6, 4), (2, 2), (2, 2), (0, 0)),
    "W not a multiple": ((8, 6, 7, 4), (2, 2), (2, 2), (0, 0)),
    "stride beyond the window": ((8, 6, 6, 4), (2, 2), (3, 3), (0, 0)),
    "batch not a multiple of 8": ((12, 6, 6, 4), (2, 2), (2, 2), (0, 0)),
}


@pytest.mark.parametrize("case", sorted(TAP_STACK_CASES))
def test_everything_else_keeps_the_tap_stack(kernels, case):
    shape, ksize, stride, padding = TAP_STACK_CASES[case]
    assert not pool_ops.windowed(shape, ksize, stride, padding)
    x = _x(shape)
    y_ref, idx_ref = pool_ops.np_max_pooling(x, ksize, stride, padding)
    y, idx = pool_ops.max_pooling(jnp.asarray(x), ksize, stride, padding)
    np.testing.assert_array_equal(np.asarray(y), y_ref)
    np.testing.assert_array_equal(np.asarray(idx), idx_ref)
    err = _x(y_ref.shape, "err")
    dx = pool_ops.gd_max_pooling(jnp.asarray(err), idx, shape, ksize,
                                 stride, padding)
    np.testing.assert_allclose(
        np.asarray(dx),
        pool_ops.np_gd_max_pooling(err, idx_ref, shape, ksize, stride,
                                   padding), rtol=1e-6, atol=1e-6)
    assert kernels == {"fwd": 0, "bwd": 0}


def test_packed_activations_keep_the_tap_stack(kernels):
    """The kernel does not lower for a packed dtype, so bfloat16
    activations stay on the tap stack whatever the geometry."""
    assert pool_ops.windowed((8, 4, 4, 8), (2, 2))
    assert not pool_ops.windowed((8, 4, 4, 8), (2, 2), dtype=jnp.bfloat16)
    x = jnp.asarray(_x((8, 4, 4, 8))).astype(jnp.bfloat16)
    y, idx = pool_ops.max_pooling(x, (2, 2))
    y_ref, idx_ref = pool_ops.xla_max_pooling(x, (2, 2))
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(idx_ref))
    assert y.dtype == jnp.bfloat16 and kernels["fwd"] == 0


def test_batch_sharded_under_a_mesh(kernels):
    """Under the trainer's mesh the kernels run in a shard_map over the
    batch, shapes taken from the per-device operands."""
    x = np.maximum(_x((32, 4, 6, 16)), 0.0)
    y_ref, idx_ref = pool_ops.np_max_pooling(x, (2, 2))
    err = _x(y_ref.shape, "err")
    dx_ref = pool_ops.np_gd_max_pooling(err, idx_ref, x.shape, (2, 2))
    mesh = make_mesh(n_data=4, n_model=2)

    @jax.jit
    def both(x, err):
        with tuning.kernel_mesh(mesh):
            y, idx = pool_ops.max_pooling(x, (2, 2))
            return y, idx, pool_ops.gd_max_pooling(err, idx, x.shape,
                                                   (2, 2))
    y, idx, dx = both(jnp.asarray(x), jnp.asarray(err))
    np.testing.assert_array_equal(np.asarray(y), y_ref)
    np.testing.assert_array_equal(np.asarray(idx), idx_ref)
    np.testing.assert_array_equal(np.asarray(dx), dx_ref)
    assert kernels == {"fwd": 1, "bwd": 1}


# -- the counter that says it engaged ----------------------------------------
def _pool(kind, ksize, stride, padding=(0, 0)):
    hyp = (0.0, 0.0, 0.0, 0.0)
    return fused.LayerSpec(kind, "linear", False, hyp, hyp, (
        ("ksize", ksize), ("padding", padding), ("stride", stride)))


def _unit(in_shape, out_shape):
    return types.SimpleNamespace(
        input=types.SimpleNamespace(shape=in_shape),
        output=types.SimpleNamespace(shape=out_shape))


ROUTE_ROWS = [
    (_pool("max_pool", (2, 2), (2, 2)), _unit((8, 6, 6, 4), (8, 3, 3, 4))),
    (_pool("maxabs_pool", (3, 3), (3, 3)),
     _unit((8, 6, 9, 4), (8, 2, 3, 4))),
    (_pool("stochastic_pool", (2, 2), (2, 2)),
     _unit((8, 4, 4, 4), (8, 2, 2, 4))),
    (_pool("depooling", (2, 2), (2, 2)), _unit((8, 3, 3, 4), (8, 6, 6, 4))),
    (_pool("max_pool", (3, 3), (2, 2)), _unit((8, 7, 7, 4), (8, 3, 3, 4))),
    (_pool("max_pool", (2, 2), (2, 2), (1, 1)),
     _unit((8, 6, 6, 4), (8, 4, 4, 4))),
    (_pool("max_pool", (2, 2), (2, 2)), _unit((8, 7, 6, 4), (8, 4, 3, 4))),
    (_pool("depooling", (2, 2), (2, 2)), _unit((8, 4, 3, 4), (8, 7, 6, 4))),
    # rows that never reach the ops.pooling dispatchers
    (_pool("avg_pool", (2, 2), (2, 2)), _unit((8, 6, 6, 4), (8, 3, 3, 4))),
    (_pool("lrn_pool", (3, 3), (2, 2)), _unit((8, 7, 7, 4), (8, 3, 3, 4))),
]


def _routes(rows, **spec_kw):
    spec = fused.ModelSpec(tuple(r[0] for r in rows), "mse", **spec_kw)
    return fused.pool_routes(spec, [r[1] for r in rows])


def test_pool_routes_counts_each_path(monkeypatch):
    assert _routes(ROUTE_ROWS) == "windowed:4 taps:4"
    assert _routes(ROUTE_ROWS[:1]) == "windowed:1 taps:0"
    assert _routes(ROUTE_ROWS[4:5]) == "windowed:0 taps:1"
    assert _routes(ROUTE_ROWS[8:]) == "windowed:0 taps:0"
    assert _routes(ROUTE_ROWS,
                   storage_dtype="bfloat16") == "windowed:0 taps:8"
    # a merged spec names its units through unit_index
    spec = fused.ModelSpec((ROUTE_ROWS[0][0],), "mse", unit_index=(2,))
    units = [None, None, ROUTE_ROWS[0][1]]
    assert fused.pool_routes(spec, units) == "windowed:1 taps:0"
    # under a mesh a device pools its share of the batch: 8 rows over
    # 4 data shards fill no sublane tile, 32 rows do
    mesh = make_mesh(n_data=4, n_model=2)
    assert fused.pool_routes(spec, units, mesh) == "windowed:0 taps:1"
    units[2] = _unit((32, 6, 6, 4), (32, 3, 3, 4))
    assert fused.pool_routes(spec, units, mesh) == "windowed:1 taps:0"
    # off the Pallas tier neither path runs
    monkeypatch.setattr(tuning, "_INTERPRET", False)
    assert _routes(ROUTE_ROWS) == "windowed:0 taps:0"


#: one non-overlapping pool, one overlapping: both paths in one step
LAYERS = [
    {"type": "conv_str", "->": {"n_kernels": 8, "kx": 3, "padding": 1},
     "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}},
    {"type": "max_pooling", "->": {"kx": 2, "sliding": 2}},
    {"type": "max_pooling", "->": {"kx": 3, "sliding": 2}},
    {"type": "softmax", "->": {"output_sample_shape": 10},
     "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}},
]


def _train(tmp_path, name):
    saved = root.cifar.synthetic.to_dict()
    saved_batch = root.cifar.minibatch_size
    root.cifar.synthetic.update({"n_train": 32, "n_valid": 16,
                                 "n_test": 0, "noise": 0.3, "size": 16})
    root.cifar.minibatch_size = 16
    try:
        prng.seed_all(1234)
        wf = cifar.CifarWorkflow(layers=LAYERS)
        wf.initialize(device=Device.create("xla"))
    finally:
        root.cifar.synthetic.update(saved)
        root.cifar.minibatch_size = saved_batch
    path = tmp_path / f"{name}.jsonl"
    wf.train(fused=True, max_epochs=2, timeline_jsonl=str(path))
    rows = [json.loads(line) for line in path.read_text().splitlines()]
    return rows, wf.decision.epoch_metrics


def test_trainer_states_its_pool_routes(tmp_path, kernels, monkeypatch):
    rows, metrics = _train(tmp_path, "pallas")
    assert rows and all(r["kernel_tier"] == "pallas-interpret"
                        and r["pool_routes"] == "windowed:1 taps:1"
                        for r in rows)
    assert kernels["fwd"] >= 1 and kernels["bwd"] >= 1
    # the same job on the XLA tier: same losses, and no path claimed
    monkeypatch.setattr(tuning, "_INTERPRET", False)
    xla_rows, xla_metrics = _train(tmp_path, "xla")
    assert all(r["kernel_tier"] == "xla"
               and r["pool_routes"] == "windowed:0 taps:0"
               for r in xla_rows)
    for got, want in zip(metrics, xla_metrics):
        np.testing.assert_allclose(got["train_loss"], want["train_loss"],
                                   rtol=1e-5)
