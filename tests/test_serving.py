"""Serving subsystem tests (znicz_tpu/serving/): micro-batcher
coalescing/timeout/backpressure/deadlines, the shape-bucketed
executable cache, the .znn reader round-trip, and an end-to-end
``POST /predict`` against a trained Wine model — including the
acceptance contract: N concurrent requests complete in
≤ ceil(N/max_batch) engine forward calls, a full admission queue
returns 429 + Retry-After with no request silently dropped, and
/metrics stays self-consistent."""

import json
import math
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from znicz_tpu import prng
from znicz_tpu.backends import Device
from znicz_tpu.export import (ACT, KIND, _pack_layer, _write_header,
                              export_workflow, read_znn)
from znicz_tpu.serving import (DeadlineExceeded, MicroBatcher,
                               QueueFull, ServingEngine, ServingServer)
from znicz_tpu.serving.engine import output_features


# -- fakes / fixtures ------------------------------------------------------
class FakeEngine:
    """Counts forward calls; y = x @ ones → (B, 1)."""

    def __init__(self, delay: float = 0.0):
        self.calls = 0
        self.rows = []
        self.delay = delay
        self._lock = threading.Lock()

    def predict(self, x):
        with self._lock:
            self.calls += 1
            self.rows.append(len(x))
        if self.delay:
            time.sleep(self.delay)
        return np.asarray(x).reshape(len(x), -1).sum(
            axis=1, keepdims=True)


def _write_mlp_znn(path, fin=6, hidden=5, classes=3, seed=0):
    """Hand-written fc(tanh)+fc+softmax .znn with known weights."""
    gen = np.random.default_rng(seed)
    w1 = gen.standard_normal((fin, hidden)).astype(np.float32)
    b1 = gen.standard_normal(hidden).astype(np.float32)
    w2 = gen.standard_normal((hidden, classes)).astype(np.float32)
    with open(path, "wb") as fh:
        _write_header(fh, 3)
        _pack_layer(fh, KIND["fc"], ACT["tanh"], [fin, hidden], w1, b1)
        _pack_layer(fh, KIND["fc"], ACT["linear"], [hidden, classes], w2)
        _pack_layer(fh, KIND["softmax"], 0, [])
    return w1, b1, w2


def _mlp_reference(x, w1, b1, w2):
    h = 1.7159 * np.tanh(0.6666 * (x @ w1 + b1))
    logits = h @ w2
    e = np.exp(logits - logits.max(axis=1, keepdims=True))
    return e / e.sum(axis=1, keepdims=True)


@pytest.fixture(scope="module")
def wine_engine(tmp_path_factory):
    """A quickly-trained Wine workflow exported to .znn + a JAX
    serving engine over it (shared by the e2e tests)."""
    from znicz_tpu.models import wine
    prng.seed_all(1234)
    wf = wine.run(device=Device.create("xla"), epochs=8,
                  synthetic_sizes={"n_train": 90, "n_valid": 24,
                                   "n_test": 24, "noise": 0.5})
    path = str(tmp_path_factory.mktemp("serve") / "wine.znn")
    export_workflow(wf, path)
    engine = ServingEngine(path, buckets=(1, 2, 4, 8), cache_size=8)
    yield wf, engine
    engine.close()


# -- micro-batcher ---------------------------------------------------------
class TestMicroBatcher:
    def test_coalesces_concurrent_requests(self):
        """The acceptance shape: N concurrent 1-row requests finish in
        ≤ ceil(N/max_batch) forward calls."""
        fake = FakeEngine()
        mb = MicroBatcher(fake, max_batch=8, max_wait_ms=150,
                          max_queue=64)
        try:
            n = 24
            results, errors = [None] * n, [None] * n
            barrier = threading.Barrier(n)

            def worker(i):
                barrier.wait()
                try:
                    results[i] = mb.predict(
                        np.full((1, 4), float(i), np.float32),
                        timeout=30.0)
                except Exception as e:       # pragma: no cover
                    errors[i] = e
            threads = [threading.Thread(target=worker, args=(i,))
                       for i in range(n)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(30.0)
            assert errors == [None] * n
            for i, r in enumerate(results):
                np.testing.assert_allclose(r, [[4.0 * i]])
            assert fake.calls <= math.ceil(n / 8)
            m = mb.metrics()
            assert m["completed"] == n
            assert m["forward_calls"] == fake.calls
            assert sum(m["batch_size_histogram"].values()) == fake.calls
        finally:
            mb.close()

    def test_timeout_flushes_partial_batch(self):
        """A lone request doesn't wait for a full batch — it ships
        when max_wait_ms expires."""
        fake = FakeEngine()
        mb = MicroBatcher(fake, max_batch=32, max_wait_ms=20,
                          max_queue=64)
        try:
            t0 = time.monotonic()
            y = mb.predict(np.ones((3, 4), np.float32), timeout=10.0)
            assert time.monotonic() - t0 < 5.0
            assert y.shape == (3, 1) and fake.calls == 1
            assert mb.metrics()["batch_size_histogram"] == {"3": 1}
        finally:
            mb.close()

    def test_backpressure_rejects_when_queue_full(self):
        """Submissions beyond max_queue raise QueueFull with a
        retry_after estimate; nothing admitted is dropped."""
        fake = FakeEngine(delay=0.15)
        mb = MicroBatcher(fake, max_batch=2, max_wait_ms=1,
                          max_queue=4)
        try:
            admitted, rejected = [], 0
            for i in range(12):
                try:
                    admitted.append(mb.submit(
                        np.ones((1, 4), np.float32)))
                except QueueFull as e:
                    rejected += 1
                    assert e.retry_after >= 1
            assert rejected > 0
            for req in admitted:
                assert req.event.wait(30.0)
                assert req.error is None
            m = mb.metrics()
            assert m["completed"] == len(admitted)
            assert m["rejected"] == rejected
            assert m["completed"] + m["rejected"] == 12
        finally:
            mb.close()

    def test_oversized_request_admitted_when_idle(self):
        """A single request larger than max_queue must be served (the
        engine chunks it), not 429'd forever."""
        fake = FakeEngine()
        mb = MicroBatcher(fake, max_batch=4, max_wait_ms=1,
                          max_queue=8)
        try:
            y = mb.predict(np.ones((20, 3), np.float32), timeout=10.0)
            assert y.shape == (20, 1)
            assert mb.metrics()["rejected"] == 0
        finally:
            mb.close()

    def test_deadline_expires_in_queue(self):
        """A request whose deadline passes while queued fails with
        DeadlineExceeded instead of wasting a device call."""
        fake = FakeEngine(delay=0.3)
        mb = MicroBatcher(fake, max_batch=1, max_wait_ms=1,
                          max_queue=64)
        try:
            blocker = mb.submit(np.ones((1, 4), np.float32))
            doomed = mb.submit(np.ones((1, 4), np.float32),
                               deadline_ms=50)
            assert doomed.event.wait(30.0)
            assert isinstance(doomed.error, DeadlineExceeded)
            assert blocker.event.wait(30.0) and blocker.error is None
            assert mb.metrics()["expired"] == 1
        finally:
            mb.close()

    def test_short_deadline_dispatches_before_coalescing_window(self):
        """A lone request with deadline_ms shorter than max_wait_ms
        must be SERVED at its deadline, not expired waiting for
        co-riders that never come."""
        fake = FakeEngine()
        mb = MicroBatcher(fake, max_batch=32, max_wait_ms=5000,
                          max_queue=64)
        try:
            t0 = time.monotonic()
            y = mb.predict(np.ones((1, 4), np.float32),
                           deadline_ms=200, timeout=10.0)
            assert time.monotonic() - t0 < 2.0      # not the 5s window
            np.testing.assert_allclose(y, [[4.0]])
            assert mb.metrics()["expired"] == 0
        finally:
            mb.close()

    def test_predict_timeout_cancels_queued_request(self):
        """An abandoned (timed-out) request still in the queue is
        cancelled — it must not consume a device slot later."""
        fake = FakeEngine(delay=0.4)
        mb = MicroBatcher(fake, max_batch=1, max_wait_ms=1,
                          max_queue=64)
        try:
            blocker = mb.submit(np.ones((1, 4), np.float32))
            with pytest.raises(TimeoutError):
                mb.predict(np.ones((1, 4), np.float32), timeout=0.05)
            assert blocker.event.wait(30.0)
            time.sleep(0.6)               # give a slot the chance to run
            assert fake.calls == 1        # only the blocker ran
            assert mb.metrics()["cancelled"] == 1
        finally:
            mb.close()

    def test_mixed_shapes_are_not_coalesced(self):
        """Requests of different sample shapes never share a device
        call (they couldn't concatenate) but all complete."""
        fake = FakeEngine()
        mb = MicroBatcher(fake, max_batch=8, max_wait_ms=30,
                          max_queue=64)
        try:
            a = mb.submit(np.ones((1, 4), np.float32))
            b = mb.submit(np.ones((1, 6), np.float32))
            assert a.event.wait(10.0) and b.event.wait(10.0)
            assert a.error is None and b.error is None
            np.testing.assert_allclose(a.result, [[4.0]])
            np.testing.assert_allclose(b.result, [[6.0]])
            assert fake.calls == 2
        finally:
            mb.close()

    def test_engine_failure_propagates_to_every_request(self):
        def broken(x):
            raise RuntimeError("device fell over")
        mb = MicroBatcher(broken, max_batch=4, max_wait_ms=20,
                          max_queue=64)
        try:
            reqs = [mb.submit(np.ones((1, 2), np.float32))
                    for _ in range(3)]
            for r in reqs:
                assert r.event.wait(10.0)
                assert isinstance(r.error, RuntimeError)
            assert mb.metrics()["failed"] == 3
        finally:
            mb.close()


# -- engine: reader, buckets, executable cache -----------------------------
class TestServingEngine:
    def test_znn_reader_roundtrip(self, tmp_path):
        path = str(tmp_path / "m.znn")
        w1, b1, w2 = _write_mlp_znn(path)
        layers = read_znn(path)
        assert [la.kind for la in layers] == ["fc", "fc", "softmax"]
        np.testing.assert_array_equal(layers[0].w, w1)
        np.testing.assert_array_equal(layers[0].b, b1)
        assert layers[0].activation == "tanh"
        assert output_features(layers, (6,)) == 3

    def test_reader_rejects_bad_magic(self, tmp_path):
        bad = tmp_path / "bad.znn"
        bad.write_bytes(b"NOPE" + b"\0" * 32)
        with pytest.raises(IOError):
            read_znn(str(bad))
        # magic present but header cut short (crashed export): still
        # the documented IOError, never a raw struct.error
        stub = tmp_path / "stub.znn"
        stub.write_bytes(b"ZNN1\x02")
        with pytest.raises(IOError):
            read_znn(str(stub))

    def test_reader_rejects_dangling_depool_tie(self, tmp_path):
        """A depool row whose tie doesn't reference an earlier
        max_pool fails at load, not as a KeyError mid-forward."""
        path = tmp_path / "tie.znn"
        with open(path, "wb") as fh:
            _write_header(fh, 2)
            _pack_layer(fh, KIND["avg_pool"], 0,
                        [2, 2, 0, 0, 2, 2, 0, 0])
            _pack_layer(fh, KIND["depool"], 0,
                        [2, 2, 0, 0, 2, 2, 0, 0])   # ties to avg_pool
        with pytest.raises(IOError):
            read_znn(str(path))
        with open(path, "wb") as fh:
            _write_header(fh, 1)
            _pack_layer(fh, KIND["depool"], 0,
                        [2, 2, 0, 0, 2, 2, 0, 0])   # ties to itself
        with pytest.raises(IOError):
            read_znn(str(path))

    def test_server_rejects_batcher_plus_knobs(self, tmp_path):
        path = str(tmp_path / "m.znn")
        _write_mlp_znn(path)
        eng = ServingEngine(path, buckets=(1, 4))
        mb = MicroBatcher(eng, max_batch=4, max_wait_ms=1)
        try:
            with pytest.raises(ValueError):
                ServingServer(eng, batcher=mb, max_queue=512)
        finally:
            mb.close()

    def test_reader_rejects_bias_geometry_mismatch(self, tmp_path):
        """A corrupt bias blob fails at load (IOError), not as a
        broadcast error inside the first jitted forward."""
        import struct
        w = np.zeros((4, 3), np.float32)
        bad_bias = np.zeros(2, np.float32)       # fc fout=3 wants 3
        path = tmp_path / "badb.znn"
        path.write_bytes(
            b"ZNN1" + struct.pack("<I", 1) + struct.pack("<II", 0, 0)
            + struct.pack("<8i", 4, 3, 0, 0, 0, 0, 0, 0)
            + struct.pack("<Q", w.size) + w.tobytes()
            + struct.pack("<Q", bad_bias.size) + bad_bias.tobytes())
        with pytest.raises(IOError):
            read_znn(str(path))

    def test_reader_rejects_oversized_blob(self, tmp_path):
        import struct
        bad = tmp_path / "huge.znn"
        bad.write_bytes(b"ZNN1" + struct.pack("<I", 1)
                        + struct.pack("<II", 0, 0)
                        + struct.pack("<8i", 4, 4, 0, 0, 0, 0, 0, 0)
                        + struct.pack("<Q", 1 << 60))
        with pytest.raises(IOError):
            read_znn(str(bad))

    def test_predict_matches_reference_through_padding(self, tmp_path):
        """Outputs are identical no matter which bucket served the
        batch — padding rows never leak into real rows."""
        path = str(tmp_path / "m.znn")
        w1, b1, w2 = _write_mlp_znn(path)
        eng = ServingEngine(path, buckets=(1, 4, 16), cache_size=4)
        gen = np.random.default_rng(1)
        for b in (1, 2, 3, 4, 5, 16):
            x = gen.standard_normal((b, 6)).astype(np.float32)
            np.testing.assert_allclose(
                eng.predict(x), _mlp_reference(x, w1, b1, w2),
                rtol=1e-5, atol=1e-6)

    def test_bucket_cache_hits_and_eviction(self, tmp_path):
        path = str(tmp_path / "m.znn")
        _write_mlp_znn(path)
        eng = ServingEngine(path, buckets=(1, 4, 16), cache_size=2)
        x = np.ones((3, 6), np.float32)
        eng.predict(x)                       # bucket 4: miss
        eng.predict(x[:2])                   # bucket 4: hit
        m = eng.metrics()
        assert m["cache_misses"] == 1 and m["cache_hits"] == 1
        eng.predict(np.ones((1, 6), np.float32))    # bucket 1: miss
        eng.predict(np.ones((16, 6), np.float32))   # bucket 16: miss →
        m = eng.metrics()                           # evicts bucket 4
        assert m["cache_misses"] == 3
        assert m["cache_evictions"] == 1
        assert m["cached_executables"] == 2
        eng.predict(x)                       # bucket 4 again: recompile
        assert eng.metrics()["cache_misses"] == 4

    def test_oversized_batch_chunks_through_top_bucket(self, tmp_path):
        path = str(tmp_path / "m.znn")
        w1, b1, w2 = _write_mlp_znn(path)
        eng = ServingEngine(path, buckets=(1, 8), cache_size=4)
        x = np.random.default_rng(2).standard_normal(
            (21, 6)).astype(np.float32)
        y = eng.predict(x)
        np.testing.assert_allclose(y, _mlp_reference(x, w1, b1, w2),
                                   rtol=1e-5, atol=1e-6)
        assert eng.metrics()["forward_calls"] == math.ceil(21 / 8)

    def test_live_workflow_source(self, wine_engine):
        """ServingEngine(workflow) exports to a temp .znn internally
        and serves the trained forward chain."""
        wf, _ = wine_engine
        eng = ServingEngine(wf, buckets=(1, 8))
        try:
            x = np.asarray(wf.loader.original_data.mem[:5], np.float32)
            y = eng.predict(x)
            assert y.shape == (5, 3)
            np.testing.assert_allclose(y.sum(axis=1), 1.0, rtol=1e-5)
        finally:
            eng.close()

    def test_native_backend_matches_jax(self, tmp_path):
        """The no-JAX fallback serves the same numbers through
        native/libznicz_infer.so."""
        path = str(tmp_path / "m.znn")
        w1, b1, w2 = _write_mlp_znn(path)
        native = ServingEngine(path, backend="native")
        x = np.random.default_rng(3).standard_normal(
            (5, 6)).astype(np.float32)
        np.testing.assert_allclose(
            native.predict(x), _mlp_reference(x, w1, b1, w2),
            rtol=1e-4, atol=1e-5)
        assert native.metrics()["backend"] == "native"
        assert native.metrics()["forward_calls"] == 1

    def test_conv_pool_lrn_chain_matches_native(self, tmp_path):
        """The JAX forward agrees with the C++ engine on a conv +
        max-pool + LRN + fc chain (both consume the same .znn)."""
        gen = np.random.default_rng(7)
        cw = gen.standard_normal((3, 3, 2, 6)).astype(np.float32) * 0.3
        cb = gen.standard_normal(6).astype(np.float32) * 0.1
        # 8x8 input → conv(k=3, p=1) keeps 8x8 → pool 2x2/2 → 4x4x6
        fin = 4 * 4 * 6
        fw = gen.standard_normal((fin, 5)).astype(np.float32) * 0.2
        path = str(tmp_path / "conv.znn")
        with open(path, "wb") as fh:
            _write_header(fh, 4)
            _pack_layer(fh, KIND["conv"], ACT["tanh"],
                        [3, 3, 2, 6, 1, 1, 1, 1], cw, cb)
            _pack_layer(fh, KIND["max_pool"], 0,
                        [2, 2, 0, 0, 2, 2, 0, 0])
            _pack_layer(fh, KIND["lrn"], 0, [5],
                        np.asarray([1e-4, 0.75, 2.0], np.float32))
            _pack_layer(fh, KIND["fc"], ACT["sigmoid"], [fin, 5], fw)
        layers = read_znn(path)
        assert output_features(layers, (8, 8, 2)) == 5
        x = gen.standard_normal((3, 8, 8, 2)).astype(np.float32)
        jax_eng = ServingEngine(path, backend="jax", buckets=(4,))
        native = ServingEngine(path, backend="native")
        got, ref = jax_eng.predict(x), native.predict(x)
        assert got.shape == ref.shape == (3, 5)
        np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)

    def test_uneven_pool_depool_output_features(self, tmp_path):
        """A pool window that doesn't divide its input evenly: depool
        emits the RECORDED input extent (13, not the deconv-formula
        12), and output_features must agree with both engines or the
        native buffer sizing breaks."""
        gen = np.random.default_rng(17)
        path = str(tmp_path / "odd.znn")
        with open(path, "wb") as fh:
            _write_header(fh, 2)
            _pack_layer(fh, KIND["max_pool"], 0,
                        [2, 2, 0, 0, 2, 2, 0, 0])
            _pack_layer(fh, KIND["depool"], 0,
                        [2, 2, 0, 0, 2, 2, 0, 0])       # tie = layer 0
        layers = read_znn(path)
        assert output_features(layers, (13, 13, 2)) == 13 * 13 * 2
        x = gen.standard_normal((2, 13, 13, 2)).astype(np.float32)
        got = ServingEngine(path, backend="jax",
                            buckets=(2,)).predict(x)
        ref = ServingEngine(path, backend="native").predict(x)
        assert got.shape == ref.shape == (2, 13 * 13 * 2)
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)

    def test_decoder_chain_matches_native(self, tmp_path):
        """Depooling replays the tied max-pool's winner offsets and
        deconv reconstructs — the autoencoder serving path, JAX vs
        C++ on one .znn."""
        gen = np.random.default_rng(11)
        cw = gen.standard_normal((5, 5, 1, 4)).astype(np.float32) * 0.2
        cb = gen.standard_normal(4).astype(np.float32) * 0.1
        dw = gen.standard_normal((5, 5, 1, 4)).astype(np.float32) * 0.2
        path = str(tmp_path / "ae.znn")
        with open(path, "wb") as fh:
            _write_header(fh, 4)
            _pack_layer(fh, KIND["conv"], ACT["tanh"],
                        [5, 5, 1, 4, 1, 1, 2, 2], cw, cb)
            _pack_layer(fh, KIND["max_pool"], 0,
                        [2, 2, 0, 0, 2, 2, 0, 0])
            _pack_layer(fh, KIND["depool"], 0,
                        [2, 2, 1, 0, 2, 2, 0, 0])       # tie = layer 1
            _pack_layer(fh, KIND["deconv"], ACT["linear"],
                        [5, 5, 1, 4, 1, 1, 2, 2], dw)
        layers = read_znn(path)
        assert output_features(layers, (12, 12, 1)) == 12 * 12
        x = gen.standard_normal((2, 12, 12, 1)).astype(np.float32)
        got = ServingEngine(path, backend="jax",
                            buckets=(2,)).predict(x)
        ref = ServingEngine(path, backend="native").predict(x)
        assert got.shape == ref.shape == (2, 144)
        np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)


# -- end-to-end HTTP -------------------------------------------------------
def _post(url, payload, timeout=30.0):
    body = json.dumps(payload).encode()
    req = urllib.request.Request(url + "predict", data=body,
                                 method="POST")
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}"), dict(e.headers)


class TestServingEndToEnd:
    def test_predict_roundtrip_and_health(self, wine_engine):
        wf, engine = wine_engine
        server = ServingServer(engine, max_batch=8,
                               max_wait_ms=10).start()
        try:
            x = np.asarray(wf.loader.original_data.mem[:4], np.float32)
            status, out, _ = _post(server.url, {"inputs": x.tolist()})
            assert status == 200
            got = np.asarray(out["outputs"], np.float32)
            np.testing.assert_allclose(got, engine.predict(x),
                                       rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(got.sum(axis=1), 1.0, rtol=1e-5)
            with urllib.request.urlopen(server.url + "healthz",
                                        timeout=10) as r:
                health = json.loads(r.read())
            assert health["status"] == "ok"
            assert health["backend"] == "jax"
            # the devices behind that backend, as this process sees
            # them (conftest: 8 virtual CPU devices)
            assert (health["platform"], health["device_kind"],
                    health["device_count"]) == ("cpu", "cpu", 8)
            assert health["n_layers"] == 3     # fc + fc + softmax head
        finally:
            server.stop()

    def test_malformed_request_is_400(self, wine_engine):
        _, engine = wine_engine
        server = ServingServer(engine).start()
        try:
            status, out, _ = _post(server.url, {"wrong_key": [1, 2]})
            assert status == 400 and "error" in out
            status, _, _ = _post(server.url, {"inputs": "not numbers"})
            assert status == 400
            # junk deadline_ms is a client error, not an engine 503
            status, _, _ = _post(server.url, {
                "inputs": [[0.0] * 13], "deadline_ms": "soon"})
            assert status == 400
        finally:
            server.stop()

    def test_bad_bodies_get_json_400_never_500(self, wine_engine):
        """ISSUE 2 satellite pin: malformed JSON / wrong-shape input
        answers a parseable JSON 400 error body — no case may escape
        the parse guard and surface as a raw 500."""
        _, engine = wine_engine
        server = ServingServer(engine).start()
        try:
            # body that is not JSON at all
            req = urllib.request.Request(server.url + "predict",
                                         data=b"{definitely not json",
                                         method="POST")
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req, timeout=10)
            assert ei.value.code == 400
            assert "error" in json.loads(ei.value.read())
            # valid JSON whose top level is not an object
            status, out, _ = _post(server.url, [1, 2, 3])
            assert status == 400 and "error" in out
            status, out, _ = _post(server.url, "inputs")
            assert status == 400 and "error" in out
            # ragged rows cannot form an array
            status, out, _ = _post(
                server.url, {"inputs": [[1.0, 2.0], [3.0]]})
            assert status == 400 and "error" in out
            # wrong feature count for THIS model (wine wants 13)
            status, out, _ = _post(server.url, {"inputs": [[1.0, 2.0]]})
            assert status == 400 and "error" in out
            # null inputs
            status, out, _ = _post(server.url, {"inputs": None})
            assert status == 400 and "error" in out
            # the engine's breaker must not have charged any of this
            assert engine.metrics()["breaker"]["state"] == "closed"
            assert engine.metrics()["breaker"]["consecutive_failures"] \
                == 0
        finally:
            server.stop()

    def test_non_finite_outputs_are_500_not_invalid_json(self,
                                                         wine_engine):
        """NaN/Infinity tokens are not RFC 8259 JSON — a model blowing
        up must answer a parseable 500, not a 200 strict clients
        choke on."""
        _, engine = wine_engine

        class NanEngine:
            def predict(self, x):
                return np.full((len(x), 3), np.nan, np.float32)
        server = ServingServer(engine, batcher=MicroBatcher(
            NanEngine(), max_batch=4, max_wait_ms=1,
            max_queue=16)).start()
        try:
            status, out, _ = _post(server.url,
                                   {"inputs": [[0.0] * 13]})
            assert status == 500 and "non-finite" in out["error"]
        finally:
            server.stop()

    def test_oversized_body_is_413(self, wine_engine):
        """A huge declared body is refused before it is read — the
        bounded-admission story covers the wire, not just the queue."""
        _, engine = wine_engine
        server = ServingServer(engine, max_body_mb=0.001).start()
        try:
            status, out, _ = _post(
                server.url, {"inputs": [[0.0] * 13] * 100})
            assert status == 413 and "exceeds" in out["error"]
        finally:
            server.stop()

    def test_unknown_routes_are_404(self, wine_engine):
        """Routes match exactly — /livehealthz must not impersonate
        /healthz, nor /apppredict accept work."""
        _, engine = wine_engine
        server = ServingServer(engine).start()
        try:
            for path in ("livehealthz", "appmetrics", "nope"):
                with pytest.raises(urllib.error.HTTPError) as ei:
                    urllib.request.urlopen(server.url + path,
                                           timeout=10)
                assert ei.value.code == 404
            req = urllib.request.Request(
                server.url + "apppredict",
                data=json.dumps({"inputs": [[0.0] * 13]}).encode(),
                method="POST")
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req, timeout=10)
            assert ei.value.code == 404
        finally:
            server.stop()

    def test_deadline_zero_means_immediate_or_fail(self, wine_engine):
        """deadline_ms=0 is 'already due', not 'no deadline'."""
        _, engine = wine_engine
        server = ServingServer(engine, max_wait_ms=1).start()
        try:
            status, out, _ = _post(server.url, {
                "inputs": [[0.0] * 13], "deadline_ms": 0})
            assert status == 504 and "deadline" in out["error"]
        finally:
            server.stop()

    def test_dynamic_batching_e2e(self, wine_engine):
        """ISSUE acceptance: N concurrent /predict requests complete
        in ≤ ceil(N/max_batch) ENGINE forward calls."""
        wf, engine = wine_engine
        x1 = np.asarray(wf.loader.original_data.mem[:1], np.float32)
        # pre-compile the buckets this test will hit, so the first
        # batch isn't still compiling while the clock runs
        engine.predict(np.repeat(x1, 8, axis=0))
        engine.predict(np.repeat(x1, 4, axis=0))
        # a generous window: a full batch still flushes EARLY (as soon
        # as max_batch rows are queued), but under a loaded CI box a
        # straggler thread must not miss the coalescing window and buy
        # a third forward call
        server = ServingServer(engine, max_batch=8, max_wait_ms=2000,
                               max_queue=64).start()
        try:
            calls_before = engine.metrics()["forward_calls"]
            n = 12
            statuses = [None] * n
            barrier = threading.Barrier(n)

            def worker(i):
                barrier.wait()
                statuses[i], out, _ = _post(
                    server.url, {"inputs": x1.tolist()})
            threads = [threading.Thread(target=worker, args=(i,))
                       for i in range(n)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(60.0)
            assert statuses == [200] * n
            calls = engine.metrics()["forward_calls"] - calls_before
            assert calls <= math.ceil(n / 8), \
                f"{n} requests took {calls} forward calls"
            m = server.metrics()
            assert m["completed"] >= n
            assert sum(m["batch_size_histogram"].values()) \
                == m["forward_calls"]
        finally:
            server.stop()

    def test_backpressure_429_with_retry_after(self, wine_engine):
        """A full admission queue answers 429 + Retry-After; every
        request gets SOME answer (no silent drops)."""
        _, engine = wine_engine

        class Slow:
            def predict(self, x):
                time.sleep(0.25)
                return engine.predict(x)
        # the engine serves health/metrics; the batcher drives the
        # artificially slow path so the tiny queue actually fills
        server = ServingServer(engine, batcher=MicroBatcher(
            Slow(), max_batch=1, max_wait_ms=1, max_queue=2)).start()
        try:
            x = np.zeros((1, 13), np.float32)
            n = 10
            codes = [None] * n
            barrier = threading.Barrier(n)

            def worker(i):
                barrier.wait()
                codes[i], out, headers = _post(server.url,
                                               {"inputs": x.tolist()})
                if codes[i] == 429:
                    assert int(headers["Retry-After"]) >= 1
                    assert out["retry_after_s"] >= 1
            threads = [threading.Thread(target=worker, args=(i,))
                       for i in range(n)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(60.0)
            assert None not in codes          # nothing dropped
            assert codes.count(429) > 0       # backpressure engaged
            assert codes.count(200) > 0       # admitted work finished
            assert set(codes) <= {200, 429}
            m = server.batcher.metrics()
            assert m["rejected"] == codes.count(429)
            assert m["completed"] == codes.count(200)
        finally:
            server.stop()

    def test_deadline_is_504(self, wine_engine):
        _, engine = wine_engine

        class Slow:
            def predict(self, x):
                time.sleep(0.3)
                return engine.predict(x)
        server = ServingServer(engine, batcher=MicroBatcher(
            Slow(), max_batch=1, max_wait_ms=1, max_queue=64)).start()
        try:
            x = np.zeros((1, 13), np.float32).tolist()
            blocker = threading.Thread(
                target=_post, args=(server.url, {"inputs": x}))
            blocker.start()
            time.sleep(0.05)          # let the blocker reach the device
            status, out, _ = _post(server.url,
                                   {"inputs": x, "deadline_ms": 60})
            blocker.join(30.0)
            assert status == 504 and "deadline" in out["error"]
        finally:
            server.stop()

    def test_metrics_endpoint_consistency(self, wine_engine):
        wf, engine = wine_engine
        server = ServingServer(engine, max_batch=4,
                               max_wait_ms=5).start()
        try:
            x = np.asarray(wf.loader.original_data.mem[:3], np.float32)
            for _ in range(3):
                assert _post(server.url, {"inputs": x.tolist()})[0] \
                    == 200
            with urllib.request.urlopen(server.url + "metrics",
                                        timeout=10) as r:
                m = json.loads(r.read())
            assert m["completed"] >= 3
            assert sum(m["batch_size_histogram"].values()) \
                == m["forward_calls"]
            assert m["latency_p50_ms"] is not None
            assert m["latency_p99_ms"] >= m["latency_p50_ms"]
            eng = m["engine"]
            assert eng["cache_hits"] + eng["cache_misses"] \
                >= eng["forward_calls"] > 0
            assert eng["buckets"] == [1, 2, 4, 8]
        finally:
            server.stop()


class TestServeCLI:
    def test_serve_subcommand_parses_and_binds(self, tmp_path):
        """`python -m znicz_tpu serve` wires the sub-CLI (in-process:
        spawning a subprocess would re-import jax, too slow here)."""
        path = str(tmp_path / "m.znn")
        _write_mlp_znn(path)
        from znicz_tpu.serving.server import ServingServer as S
        started = {}
        orig = S.start

        def capture(self):
            started["server"] = self
            orig(self)
            raise KeyboardInterrupt     # unblock main()'s wait loop
        S.start = capture
        try:
            from znicz_tpu.__main__ import main
            rc = main(["serve", "--model", path, "--port", "0",
                       "--buckets", "1,4", "--max-batch", "4"])
            assert rc == 0
            assert started["server"].engine.n_layers == 3
        finally:
            S.start = orig
