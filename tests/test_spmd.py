"""SPMD by default (ISSUE 8): mesh-sharded fused training and
tensor-parallel serving on the REAL hot paths.

Promotes the MULTICHIP dryrun assertions into tier-1: on the virtual
8-device CPU mesh (conftest forces it), dp×tp / pure-dp / tp-heavy
fused training through the PUBLIC entry point
(``StandardWorkflow.train(mesh_shape=...)``) must match the
single-device path within BASELINE tolerances; the tensor-parallel
serving forward must match the single-device engine; an
``EngineReplicaSet`` must serve a concurrent burst with zero non-200s
and survive one replica's breaker opening; the persistent compile
cache must make a second cold start's ``compile_time_ms`` visibly
cheaper; and census-driven warmup must leave steady-state traffic with
zero request-path compiles across a hot reload."""

import json
import os
import subprocess
import sys
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from znicz_tpu import prng
from znicz_tpu.backends import Device
from znicz_tpu.config import root
from znicz_tpu.export import ACT, KIND, _pack_layer, _write_header
from znicz_tpu.parallel.mesh import (mesh_shape_of, parse_mesh_arg,
                                     resolve_mesh)
from znicz_tpu.serving import (EngineReplicaSet, ServingEngine,
                               ServingServer)
from znicz_tpu.telemetry import compilestats
from znicz_tpu.telemetry.flightrecorder import FlightRecorder

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the repo-wide fused-vs-reference tolerance (BASELINE contract)
TOL = dict(rtol=1e-4, atol=1e-5)


@pytest.fixture(autouse=True)
def small_synthetic():
    root.mnist.synthetic.update({"n_train": 600, "n_valid": 200,
                                 "n_test": 200, "noise": 0.35})
    yield


def _train(mesh_shape=None, epochs=2):
    """Fresh identically-seeded mnist workflow trained through the
    PUBLIC entry point — the surface this PR promotes the mesh to."""
    from znicz_tpu.models import mnist
    prng.seed_all(1234)
    wf = mnist.MnistWorkflow()
    wf.initialize(device=Device.create("xla"))
    wf.train(fused=True, mesh_shape=mesh_shape, max_epochs=epochs)
    return wf


def _site_compiles(site):
    return dict(compilestats.snapshot()["compiles"].get(site, {}))


def _write_mlp_znn(path, fin=6, hidden=8, classes=4, seed=0):
    gen = np.random.default_rng(seed)
    w1 = gen.standard_normal((fin, hidden)).astype(np.float32)
    b1 = gen.standard_normal(hidden).astype(np.float32)
    w2 = gen.standard_normal((hidden, classes)).astype(np.float32)
    with open(path, "wb") as fh:
        _write_header(fh, 3)
        _pack_layer(fh, KIND["fc"], ACT["tanh"], [fin, hidden], w1, b1)
        _pack_layer(fh, KIND["fc"], ACT["linear"], [hidden, classes],
                    w2)
        _pack_layer(fh, KIND["softmax"], 0, [])


def _post(url, obj):
    req = urllib.request.Request(
        url, json.dumps(obj).encode(), {"Content-Type":
                                        "application/json"})
    try:
        r = urllib.request.urlopen(req, timeout=30)
        return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


# -- mesh resolution policy -------------------------------------------------
class TestMeshResolution:
    def test_1x1_and_none_degenerate_to_single_device(self):
        assert resolve_mesh(None) is None
        assert resolve_mesh((1, 1)) is None
        assert resolve_mesh("1,1") is None
        assert mesh_shape_of(None) == (1, 1)

    def test_string_and_tuple_forms_agree(self):
        m1 = resolve_mesh("4,2")
        m2 = resolve_mesh((4, 2))
        assert mesh_shape_of(m1) == mesh_shape_of(m2) == (4, 2)

    def test_single_number_means_pure_dp(self):
        assert parse_mesh_arg("8") == (8, 1)

    def test_oversubscribed_mesh_refuses(self):
        with pytest.raises(ValueError, match="devices"):
            resolve_mesh((16, 2))

    def test_junk_rejected(self):
        for bad in ("", "a,b", "0,1", "1,2,3"):
            with pytest.raises(ValueError):
                parse_mesh_arg(bad)
        # tuple form must refuse too, never silently truncate the
        # extra axis to a different layout
        with pytest.raises(ValueError, match="mesh_shape"):
            resolve_mesh((4, 2, 2))

    def test_launcher_mesh_lands_in_config(self):
        from znicz_tpu.launcher import Launcher
        try:
            Launcher(workflow="znicz_tpu.models.wine",
                     mesh="2,2").build()
            assert tuple(root.common.mesh_shape) == (2, 2)
        finally:
            root.common.mesh_shape = None    # global tree: never leak


# -- mesh-sharded training on the public entry point ------------------------
class TestMeshTrainEntrypoint:
    """dp×tp / pure-dp / tp-heavy through ``wf.train(mesh_shape=...)``
    must reproduce the single-device run: same per-epoch metrics, same
    final weights (the MULTICHIP dryrun contract, now on the real hot
    path and tier-1)."""

    _baseline = None

    @classmethod
    def baseline(cls):
        if cls._baseline is None:
            wf = _train(mesh_shape=None)
            cls._baseline = (
                [dict(m) for m in wf.decision.epoch_metrics],
                np.array(wf.forwards[0].weights.mem))
        return cls._baseline

    @pytest.mark.parametrize("mesh_shape", [(8, 1), (4, 2), (2, 4)],
                             ids=["pure-dp", "dp-tp", "tp-heavy"])
    def test_mesh_matches_single_device(self, mesh_shape):
        ref_metrics, ref_w = self.baseline()
        wf = _train(mesh_shape=mesh_shape)
        for got, want in zip(wf.decision.epoch_metrics, ref_metrics):
            assert got["train_n_err"] == want["train_n_err"]
            np.testing.assert_allclose(got["train_loss"],
                                       want["train_loss"], rtol=1e-5)
            np.testing.assert_allclose(got["validation_loss"],
                                       want["validation_loss"],
                                       rtol=1e-5)
        np.testing.assert_allclose(
            np.array(wf.forwards[0].weights.mem), ref_w, **TOL)

    def test_train_with_string_mesh_shape(self):
        """The CLI hands the config tree a string; train must accept
        it and actually shard (weights land on all 8 devices)."""
        from znicz_tpu.models import mnist
        prng.seed_all(1234)
        wf = mnist.MnistWorkflow()
        wf.initialize(device=Device.create("xla"))
        tr = wf.train(fused=True, mesh_shape="4,2", max_epochs=1)
        w0 = tr.params[0][0]
        assert len(w0.sharding.device_set) == 8


class TestPallasTierUnderMesh:
    def test_kernels_shard_map_over_the_mesh(self, monkeypatch):
        """Mosaic cannot partition a kernel automatically, so under a
        mesh the Pallas tier runs each batch-parallel kernel in a
        shard_map (tuning.batch_sharded).  Interpret mode takes that
        exact path on the virtual CPU mesh: conv -> fused LRN+pool ->
        max-pool -> LRN -> softmax-CE must reproduce the single-device
        loss under pure-dp and dp x tp."""
        import jax
        import jax.numpy as jnp

        from znicz_tpu.ops import tuning
        from znicz_tpu.parallel import FusedTrainer, fused, make_mesh
        monkeypatch.setattr(tuning, "_INTERPRET", True)
        assert tuning.kernel_tier() == "pallas-interpret"
        hyp = (0.01, 0.0, 0.0, 0.9)
        lrn = (("alpha", 1e-4), ("beta", 0.75), ("k", 2.0), ("n", 5))
        pool = (("ksize", (2, 2)), ("padding", (0, 0)),
                ("stride", (2, 2)))
        spec = fused.ModelSpec(layers=(
            fused.LayerSpec("conv", "tanh", True, hyp, hyp,
                            (("padding", (1, 1)), ("stride", (1, 1)))),
            fused.LayerSpec("lrn_pool", "linear", False, hyp, hyp,
                            tuple(sorted(lrn + pool
                                         + (("use_abs", False),)))),
            fused.LayerSpec("max_pool", "linear", False, hyp, hyp, pool),
            fused.LayerSpec("lrn", "linear", False, hyp, hyp, lrn),
            fused.LayerSpec("fc", "linear", True, hyp, hyp)),
            loss="softmax")
        size, c1, classes, batch = 12, 8, 12, 8
        gen = prng.get("pallas_mesh")
        params = [(gen.normal(0, 0.1, (3, 3, 3, c1)),
                   np.zeros(c1, np.float32)),
                  (None, None), (None, None), (None, None),
                  (gen.normal(0, 0.05, (3 * 3 * c1, classes)),
                   np.zeros(classes, np.float32))]
        data = jnp.asarray(gen.normal(0, 1.0, (2 * batch, size, size, 3)))
        labels = jnp.asarray(
            gen.randint(0, classes, 2 * batch).astype(np.int32))
        idx = np.arange(2 * batch)

        def loss(mesh):
            # fresh copies: the epoch fn donates its buffers
            ps = [(None if w is None else np.array(w),
                   None if b is None else np.array(b))
                  for w, b in params]
            vs = [(None if w is None else np.zeros_like(w),
                   None if b is None else np.zeros_like(b))
                  for w, b in params]
            tr = FusedTrainer(spec=spec, params=ps, vels=vs, mesh=mesh)
            return tr.train_epoch(data, labels, idx, batch)["loss"]

        want = loss(None)
        assert np.isfinite(want).all()
        for dp, tp in ((2, 1), (2, 2)):
            np.testing.assert_allclose(
                loss(make_mesh(dp, tp, jax.devices()[:dp * tp])), want,
                rtol=1e-5, atol=1e-6, err_msg=f"mesh {dp}x{tp}")


class TestMeshTrainEdgeCases:
    def _spec_params(self, widths=(8, 10, 5)):
        from znicz_tpu.parallel import fused

        def layer(act):
            return fused.LayerSpec(
                kind="fc", activation=act, include_bias=True,
                hypers=(0.1, 0.0, 0.0, 0.0),
                hypers_bias=(0.1, 0.0, 0.0, 0.0))
        spec = fused.ModelSpec(
            (layer("tanh"),) * (len(widths) - 2) + (layer("linear"),),
            "softmax")
        gen = np.random.default_rng(3)
        params = [(gen.standard_normal((a, b)).astype(np.float32),
                   np.zeros(b, np.float32))
                  for a, b in zip(widths, widths[1:])]
        vels = [tuple(np.zeros_like(x) for x in p) for p in params]
        return spec, params, vels

    def test_indivisible_tp_dim_replicates_and_matches(self):
        """Widths the model axis doesn't divide must replicate (same
        rule as serving), not crash device_put — and still train
        identically to the meshless step."""
        from znicz_tpu.parallel import fused

        spec, params, vels = self._spec_params(widths=(8, 10, 5))
        gen = np.random.default_rng(4)
        data = gen.standard_normal((32, 8)).astype(np.float32)
        labels = gen.integers(0, 5, 32).astype(np.int32)

        def copy(pv):
            return [tuple(np.array(a) if a is not None else None
                          for a in p) for p in pv]

        tr1 = fused.FusedTrainer(spec=spec, params=copy(params),
                                 vels=copy(vels))
        m1 = tr1.train_epoch(data, labels, np.arange(32), 8)
        # tp=4: 10 % 4 != 0 (even parity, split -1) and 5 % 4 != 0
        # after the parity restart — both layers replicate
        trm = fused.FusedTrainer(spec=spec, params=copy(params),
                                 vels=copy(vels),
                                 mesh=resolve_mesh((2, 4)))
        mm = trm.train_epoch(data, labels, np.arange(32), 8)
        np.testing.assert_allclose(np.asarray(mm["loss"]),
                                   np.asarray(m1["loss"]),
                                   rtol=1e-5, atol=1e-6)
        for (w1, _), (wm, _) in zip(tr1.params, trm.params):
            np.testing.assert_allclose(np.asarray(wm),
                                       np.asarray(w1), **TOL)

    @pytest.mark.parametrize("accum", [1, 2])
    def test_stream_mesh_accum_matches_meshless(self, tmp_path, accum):
        """StreamTrainer under a dp×tp mesh WITH gradient accumulation
        (the gsh out_shardings pytree path) reproduces the meshless
        stream run."""
        from znicz_tpu.backends import NumpyDevice
        from znicz_tpu.loader.records import write_records
        from znicz_tpu.loader.streaming import RecordLoader
        from znicz_tpu.parallel import extract_model
        from znicz_tpu.parallel.stream import StreamTrainer
        from znicz_tpu.workflow import Workflow
        from znicz_tpu.models import mnist

        prng.seed_all(1234)
        wf = mnist.MnistWorkflow()
        wf.initialize(device=Device.create("xla"))
        spec, params, vels = extract_model(wf)
        ld = wf.loader
        idx = np.arange(sum(ld.class_lengths[:2]), ld.total_samples)
        paths = write_records(
            str(tmp_path / "mesh.znr"),
            np.asarray(ld.original_data.mem),
            np.asarray(ld.original_labels.mem), shard_size=256)

        def stream(mesh_shape):
            sld = RecordLoader(Workflow(name="w"), train_paths=paths,
                               minibatch_size=120)
            sld.initialize(NumpyDevice())
            st = StreamTrainer(spec=spec, params=params, vels=vels,
                               loader=sld, accum_steps=accum,
                               mesh=resolve_mesh(mesh_shape))
            m = st.train_epoch(None, None, idx, 120, epoch=0)
            return m, st.params

        m0, p0 = stream(None)
        m8, p8 = stream((4, 2))
        np.testing.assert_allclose(np.asarray(m8["loss"]),
                                   np.asarray(m0["loss"]),
                                   rtol=1e-5, atol=1e-6)
        for (w0, _), (w8, _) in zip(p0, p8):
            np.testing.assert_allclose(np.asarray(w8),
                                       np.asarray(w0), **TOL)


# -- tensor-parallel serving ------------------------------------------------
class TestTensorParallelServing:
    def test_tp_forward_matches_single_device(self, tmp_path):
        path = str(tmp_path / "m.znn")
        _write_mlp_znn(path)
        e1 = ServingEngine(path, buckets=(1, 4, 8))
        etp = ServingEngine(path, buckets=(1, 4, 8), tp=4)
        try:
            x = np.random.default_rng(0).standard_normal(
                (5, 6)).astype(np.float32)
            np.testing.assert_allclose(etp.predict(x), e1.predict(x),
                                       rtol=1e-5, atol=1e-6)
            # the weights are genuinely sharded over the model axis
            w = etp._current().params()[0][0]
            assert len(w.sharding.device_set) == 4
            assert etp.mesh_shape == (1, 4)
            assert etp.metrics()["mesh"] == "1x4"
        finally:
            e1.close()
            etp.close()

    def test_indivisible_layer_replicates_and_stays_correct(
            self, tmp_path):
        """A width the mesh doesn't divide must replicate that layer,
        not crash or shard wrong."""
        path = str(tmp_path / "odd.znn")
        _write_mlp_znn(path, hidden=5, classes=3)
        e1 = ServingEngine(path, buckets=(1, 4))
        etp = ServingEngine(path, buckets=(1, 4), tp=4)
        try:
            x = np.random.default_rng(1).standard_normal(
                (3, 6)).astype(np.float32)
            np.testing.assert_allclose(etp.predict(x), e1.predict(x),
                                       rtol=1e-5, atol=1e-6)
        finally:
            e1.close()
            etp.close()

    def test_tp_survives_reload(self, tmp_path):
        path = str(tmp_path / "m.znn")
        _write_mlp_znn(path, seed=0)
        etp = ServingEngine(path, buckets=(1, 4), tp=2)
        try:
            x = np.ones((2, 6), np.float32)
            y1 = etp.predict(x)
            path2 = str(tmp_path / "m2.znn")
            _write_mlp_znn(path2, seed=7)     # new weights, new path
            rec = etp.reload(path2)
            assert rec["outcome"] == "ok" and etp.generation == 2
            y2 = etp.predict(x)
            assert not np.allclose(y1, y2)
            w = etp._current().params()[0][0]
            assert len(w.sharding.device_set) == 2
        finally:
            etp.close()

    def test_tp_needs_jax_backend(self, tmp_path):
        path = str(tmp_path / "m.znn")
        _write_mlp_znn(path)
        with pytest.raises(ValueError, match="jax"):
            ServingEngine(path, backend="native", tp=2)


# -- data-parallel replica set ----------------------------------------------
class TestEngineReplicaSet:
    def _set(self, tmp_path, n=3):
        path = str(tmp_path / "m.znn")
        _write_mlp_znn(path)
        return EngineReplicaSet.of(path, n, buckets=(1, 4, 8))

    def test_round_robin_spreads_dispatches(self, tmp_path):
        rs = self._set(tmp_path)
        try:
            x = np.ones((2, 6), np.float32)
            for _ in range(6):
                rs.predict(x)
            calls = [e.metrics()["forward_calls"]
                     for e in rs.replicas]
            assert calls == [2, 2, 2]
        finally:
            rs.close()

    def test_sick_replica_is_routed_around_and_readmitted(
            self, tmp_path):
        rs = self._set(tmp_path)
        try:
            x = np.ones((2, 6), np.float32)
            rs.predict(x)            # warm rotation
            sick = rs.replicas[0]
            for _ in range(sick.breaker.failure_threshold):
                sick.breaker.record_failure()
            assert sick.breaker.state == "open"
            before = sick.metrics()["forward_calls"]
            for _ in range(6):
                rs.predict(x)
            assert sick.metrics()["forward_calls"] == before, \
                "an open-breaker replica still received dispatches"
            assert rs.resilience_state() == "ok"
            # heal: breaker closes, replica rejoins with no operator
            # action
            sick.breaker.record_success()
            rs.predict(x)
            rs.predict(x)
            rs.predict(x)
            assert sick.metrics()["forward_calls"] > before
        finally:
            rs.close()

    def test_rolling_reload_swaps_every_replica(self, tmp_path):
        rs = self._set(tmp_path)
        try:
            x = np.ones((1, 6), np.float32)
            y1 = rs.predict(x)
            path2 = str(tmp_path / "m2.znn")
            _write_mlp_znn(path2, seed=9)
            rec = rs.reload(path2)
            assert rec["outcome"] == "ok"
            assert rs.generation == 2
            assert [r["generation"] for r in rs.replica_status()] \
                == [2, 2, 2]
            assert not np.allclose(rs.predict(x), y1)
        finally:
            rs.close()

    def test_shared_breaker_rejected(self, tmp_path):
        path = str(tmp_path / "m.znn")
        _write_mlp_znn(path)
        from znicz_tpu.resilience.breaker import CircuitBreaker
        with pytest.raises(ValueError, match="replica"):
            EngineReplicaSet.of(path, 2, breaker=CircuitBreaker())

    def test_http_burst_zero_non_200_with_sick_replica(self, tmp_path):
        """The acceptance drill: a concurrent burst through the REAL
        HTTP front stays all-200 while one replica's breaker is
        open, and /healthz + /statusz make the sick replica
        visible."""
        rs = self._set(tmp_path)
        server = ServingServer(rs, port=0, max_wait_ms=1.0).start()
        url = server.url
        try:
            sick = rs.replicas[1]
            for _ in range(sick.breaker.failure_threshold):
                sick.breaker.record_failure()
            codes = []
            lock = threading.Lock()

            def hit(i):
                code, _ = _post(url + "predict",
                                {"inputs": [[0.1] * 6] * (1 + i % 4)})
                with lock:
                    codes.append(code)
            threads = [threading.Thread(target=hit, args=(i,))
                       for i in range(16)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(30)
            assert codes and set(codes) == {200}
            health = json.loads(urllib.request.urlopen(
                url + "healthz", timeout=10).read())
            assert health["status"] == "ok"
            assert health["mesh"] == "1x1"
            states = {r["replica"]: r["breaker"]
                      for r in health["replicas"]}
            assert states[1] == "open"
            assert states[0] == states[2] == "closed"
            page = urllib.request.urlopen(
                url + "statusz", timeout=10).read().decode()
            assert "replicas=3" in page
            assert "breaker=open" in page
        finally:
            server.stop()
            rs.close()


# -- persistent compilation cache -------------------------------------------
_CACHE_PROBE = """
import json, sys
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from znicz_tpu import compilecache
assert compilecache.enable(sys.argv[1]) == sys.argv[1]
from znicz_tpu.parallel import fused
def layer(act):
    return fused.LayerSpec(
        kind="fc", activation=act, include_bias=True,
        hypers=(0.1, 0.0, 0.0, 0.0), hypers_bias=(0.1, 0.0, 0.0, 0.0))
spec = fused.ModelSpec((layer("tanh"), layer("linear")), "softmax")
gen = np.random.default_rng(0)
params = [(gen.standard_normal((64, 128)).astype(np.float32),
           np.zeros(128, np.float32)),
          (gen.standard_normal((128, 10)).astype(np.float32),
           np.zeros(10, np.float32))]
vels = [tuple(np.zeros_like(a) for a in p) for p in params]
tr = fused.FusedTrainer(spec=spec, params=params, vels=vels)
data = gen.standard_normal((64, 64)).astype(np.float32)
labels = gen.integers(0, 10, 64).astype(np.int32)
tr.train_epoch(data, labels, np.arange(64), 16)
from znicz_tpu.telemetry import compilestats
print(json.dumps(compilestats.snapshot()["compile_cost"]))
"""


class TestPersistentCompileCache:
    def test_second_cold_start_is_cheaper(self, tmp_path):
        """Two PROCESSES, one cache dir: the second start's
        ``compile_time_ms{site="train.fused"}`` must come in below the
        first (its XLA compile is a disk hit; only trace + first run
        remain)."""
        cache = str(tmp_path / "xla-cache")
        env = {**os.environ, "JAX_PLATFORMS": "cpu",
               "PYTHONPATH": REPO + os.pathsep
               + os.environ.get("PYTHONPATH", "")}
        # a cache placed from outside would win over the argument
        env.pop("JAX_COMPILATION_CACHE_DIR", None)

        def cold_start():
            out = subprocess.run(
                [sys.executable, "-c", _CACHE_PROBE, cache],
                capture_output=True, text=True, timeout=240, env=env,
                cwd=REPO)
            assert out.returncode == 0, out.stderr[-2000:]
            cost = json.loads(out.stdout.strip().splitlines()[-1])
            return cost["train.fused"]["total_ms"]

        first = cold_start()
        assert os.listdir(cache), "first start persisted nothing"
        second = cold_start()
        assert second < first, (
            f"warm-cache start ({second:.0f} ms) not cheaper than the "
            f"cold one ({first:.0f} ms)")

    @pytest.fixture
    def config_updates(self, monkeypatch):
        """enable() with jax.config.update recorded instead of
        applied — the pytest process keeps its own cache setting."""
        import jax
        from znicz_tpu import compilecache
        calls = []
        monkeypatch.setattr(jax.config, "update",
                            lambda k, v: calls.append((k, v)))
        monkeypatch.setattr(compilecache, "_active_dir", None)
        return calls

    def test_placed_from_outside_sets_no_directory(self, monkeypatch,
                                                   config_updates):
        """With $JAX_COMPILATION_CACHE_DIR set the program sets no
        cache directory in code: neither the argument nor
        $ZNICZ_COMPILE_CACHE may override it (the floors still
        zero)."""
        from znicz_tpu import compilecache
        monkeypatch.setenv(compilecache.JAX_ENV_VAR, "/placed/outside")
        monkeypatch.setenv(compilecache.ENV_VAR, "/from/znicz/env")
        assert compilecache.enable("/from/flag") == "/placed/outside"
        keys = [k for k, _ in config_updates]
        assert "jax_compilation_cache_dir" not in keys
        assert "jax_persistent_cache_min_compile_time_secs" in keys

    def test_default_is_one_fixed_path_in_the_checkout(
            self, monkeypatch, config_updates):
        """Unconfigured, the cache is ON at <checkout>/.cache/xla —
        the same path in every process (the directory is part of the
        cache key, so a derived one would never hit)."""
        from znicz_tpu import compilecache
        monkeypatch.delenv(compilecache.JAX_ENV_VAR, raising=False)
        monkeypatch.delenv(compilecache.ENV_VAR, raising=False)
        want = os.path.join(REPO, ".cache", "xla")
        assert compilecache.enable(None) == want
        assert ("jax_compilation_cache_dir", want) in config_updates
        other = subprocess.run(
            [sys.executable, "-c", "from znicz_tpu import compilecache;"
             "print(compilecache.default_dir())"],
            capture_output=True, text=True, timeout=60, cwd=REPO,
            env={**os.environ, "JAX_PLATFORMS": "cpu",
                 "PYTHONPATH": REPO})
        assert other.stdout.strip() == want, other.stderr[-500:]


# -- census-driven warmup ---------------------------------------------------
class TestCensusWarmup:
    def _census(self, shapes):
        rec = FlightRecorder(capacity=64)
        for s in shapes:
            rec.record("request", duration_ms=1.0, shape=list(s),
                       rows=1, code=200)
        return rec

    def test_census_shapes_warm_every_bucket(self, tmp_path):
        path = str(tmp_path / "m.znn")
        _write_mlp_znn(path)
        engine = ServingEngine(path, buckets=(1, 4, 8))
        try:
            rec = self._census([(6,)] * 5)
            assert rec.shape_census() == [((6,), 5)]
            built = engine.warmup_from_census(recorder=rec)
            assert built == 3
            before = _site_compiles("serving.engine")
            rng = np.random.default_rng(0)
            for b in (1, 2, 4, 8):
                engine.predict(rng.standard_normal(
                    (b, 6)).astype(np.float32))
            after = _site_compiles("serving.engine")
            assert after.get("new_bucket", 0) == \
                before.get("new_bucket", 0)
            assert after.get("fallback", 0) == before.get("fallback", 0)
        finally:
            engine.close()

    def test_empty_census_falls_back_to_operator_shape(self, tmp_path):
        path = str(tmp_path / "m.znn")
        _write_mlp_znn(path)
        engine = ServingEngine(path, buckets=(1, 4))
        try:
            rec = FlightRecorder(capacity=8)
            assert engine.warmup_from_census(recorder=rec) == 0
            assert engine.warmup_from_census(
                recorder=rec, fallback_shape=(6,)) == 2
        finally:
            engine.close()

    def test_bad_operator_fallback_shape_fails_loud(self, tmp_path):
        """Census junk is skipped, but a --warmup-shape typo is the
        OPERATOR's input and must raise at startup, not silently warm
        nothing."""
        path = str(tmp_path / "m.znn")
        _write_mlp_znn(path)
        engine = ServingEngine(path, buckets=(1, 4))
        try:
            with pytest.raises(ValueError):
                engine.warmup_from_census(
                    recorder=FlightRecorder(capacity=8),
                    fallback_shape=(999,))
        finally:
            engine.close()

    def test_junk_census_shape_does_not_abort_warmup(self, tmp_path):
        path = str(tmp_path / "m.znn")
        _write_mlp_znn(path)
        engine = ServingEngine(path, buckets=(1, 4))
        try:
            rec = self._census([(999,), (6,), (6,)])
            assert engine.warmup_from_census(recorder=rec) == 2
        finally:
            engine.close()

    def test_reload_rewarms_from_census_zero_request_path_compiles(
            self, tmp_path):
        """The acceptance loop: traffic → hot reload (new generation,
        cache pruned) → census warmup re-covers the observed shape →
        the follow-up burst pays ZERO request-path compiles."""
        path = str(tmp_path / "m.znn")
        _write_mlp_znn(path)
        engine = ServingEngine(path, buckets=(1, 4))
        server = ServingServer(engine, port=0, max_wait_ms=1.0).start()
        try:
            rng = np.random.default_rng(0)
            for b in (1, 2, 4):
                code, _ = _post(server.url + "predict",
                                {"inputs": rng.standard_normal(
                                    (b, 6)).tolist()})
                assert code == 200
            path2 = str(tmp_path / "m2.znn")
            _write_mlp_znn(path2, seed=5)
            worker = server.reload_async(path2)
            assert worker is not None
            worker.join(60)
            assert engine.generation == 2
            before = _site_compiles("serving.engine")
            for b in (1, 2, 4):
                code, _ = _post(server.url + "predict",
                                {"inputs": rng.standard_normal(
                                    (b, 6)).tolist()})
                assert code == 200
            after = _site_compiles("serving.engine")
            assert after.get("new_bucket", 0) == \
                before.get("new_bucket", 0)
            assert after.get("fallback", 0) == before.get("fallback", 0)
        finally:
            server.stop()
            engine.close()
