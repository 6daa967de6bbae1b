"""Multi-tenant model-zoo serving (znicz_tpu/serving/zoo.py, ISSUE 11).

Pins the new subsystem's contracts end to end: routing (X-Model header
beats the body ``model`` field, absent → default, unknown → 404),
per-model reload isolation (reloading model A never bumps model B's
generation or touches its executable cache), the weight-residency LRU
(eviction + page-in byte-identity, and the single-flight page-in a
concurrent eviction must queue on instead of double-allocating —
pinned by counting real ``jax.device_put`` calls), token-bucket quotas
(429 + Retry-After), per-model criticality classes on the shed ladder
(a sheddable tenant browns out while critical tenants never shed, and
an explicit header still wins), the ``/healthz``/``/statusz``/
``/metrics`` per-model surfaces, and the CLI spec grammar.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from znicz_tpu.serving import zoo as zoo_mod
from znicz_tpu.serving.engine import ServingEngine
from znicz_tpu.serving.server import ServingServer
from znicz_tpu.serving.zoo import (DEMO_SHAPES, ModelEntry, ModelZoo,
                                   QuotaExceeded, TokenBucket,
                                   UnknownModel, make_demo_zoo,
                                   parse_model_spec, scan_zoo_dir)
from znicz_tpu.telemetry.registry import REGISTRY

X = {fam: [[0.1 * (i + 1)] * n for i in range(1)]
     for fam, n in DEMO_SHAPES.items()}
OUT_FEATURES = {"mnist": 10, "wine": 3, "kohonen": 4}


def _post(url, payload, headers=None, timeout=60.0):
    req = urllib.request.Request(
        url + "predict", json.dumps(payload).encode(),
        {"Content-Type": "application/json", **(headers or {})})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}"), dict(e.headers)


def _get(url, path, timeout=30.0):
    with urllib.request.urlopen(url + path, timeout=timeout) as r:
        body = r.read()
        ctype = r.headers.get("Content-Type", "")
        return (json.loads(body) if "json" in ctype
                else body.decode())


def _admin(url, payload, timeout=60.0):
    req = urllib.request.Request(
        url + "admin/reload", json.dumps(payload).encode(),
        {"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


@pytest.fixture(scope="module")
def zoo_paths(tmp_path_factory):
    d = tmp_path_factory.mktemp("zoo_artifacts")
    return make_demo_zoo(str(d))


def _build_zoo(zoo_paths, budget=None, **per_model):
    """Three-family zoo; ``per_model`` overrides add() kwargs per
    name (e.g. mnist={"criticality": "sheddable"})."""
    zoo = ModelZoo(memory_budget_bytes=budget)
    zoo.add("mnist", zoo_paths["mnist"], backend="jax", buckets=(1, 2),
            **per_model.get("mnist", {}))
    zoo.add("wine", zoo_paths["wine"], backend="jax", buckets=(1, 2),
            default=True, **per_model.get("wine", {}))
    zoo.add("kohonen", zoo_paths["kohonen"], backend="jax",
            buckets=(1, 2), **per_model.get("kohonen", {}))
    return zoo


@pytest.fixture(scope="module")
def routing_server(zoo_paths):
    """Shared read-only server for the routing/introspection tests
    (reload/eviction tests build their own)."""
    zoo = _build_zoo(zoo_paths,
                     mnist={"criticality": "sheddable"},
                     kohonen={"criticality": "critical",
                              "deadline_ms": 5000.0})
    server = ServingServer(zoo=zoo, max_wait_ms=1.0).start()
    yield server, zoo
    server.stop()
    zoo.close()


# -- routing ---------------------------------------------------------------

class TestRouting:
    def test_default_model_serves_nameless_requests(self,
                                                    routing_server):
        server, _zoo = routing_server
        status, body, headers = _post(server.url,
                                      {"inputs": X["wine"]})
        assert status == 200
        assert len(body["outputs"][0]) == OUT_FEATURES["wine"]
        assert "X-Request-Id" in headers          # PR-1/3 contract

    def test_header_routes_and_beats_body(self, routing_server):
        server, _zoo = routing_server
        status, body, _ = _post(server.url, {"inputs": X["mnist"]},
                                {"X-Model": "mnist"})
        assert status == 200
        assert len(body["outputs"][0]) == OUT_FEATURES["mnist"]
        # header wins over a conflicting body field (proxy contract)
        status, body, _ = _post(server.url,
                                {"inputs": X["mnist"],
                                 "model": "kohonen"},
                                {"X-Model": "mnist"})
        assert status == 200
        assert len(body["outputs"][0]) == OUT_FEATURES["mnist"]

    def test_body_field_routes(self, routing_server):
        server, _zoo = routing_server
        status, body, _ = _post(server.url, {"inputs": X["kohonen"],
                                             "model": "kohonen"})
        assert status == 200
        assert len(body["outputs"][0]) == OUT_FEATURES["kohonen"]

    def test_empty_header_is_unset_not_404(self, routing_server):
        """A proxy forwarding 'X-Model:' with an empty value clears
        the header — it must fall through to the body field / default
        model, never 404 on the literal name ''."""
        server, _zoo = routing_server
        status, body, _ = _post(server.url, {"inputs": X["wine"]},
                                {"X-Model": ""})
        assert status == 200
        assert len(body["outputs"][0]) == OUT_FEATURES["wine"]
        status, body, _ = _post(server.url, {"inputs": X["kohonen"],
                                             "model": "kohonen"},
                                {"X-Model": "  "})
        assert status == 200
        assert len(body["outputs"][0]) == OUT_FEATURES["kohonen"]

    def test_unknown_model_is_404(self, routing_server):
        server, _zoo = routing_server
        for req in ({"inputs": X["wine"], "model": "ghost"},):
            status, body, _ = _post(server.url, req)
            assert status == 404 and "ghost" in body["error"]
        status, body, _ = _post(server.url, {"inputs": X["wine"]},
                                {"X-Model": "ghost"})
        assert status == 404
        # junk model type is a 400 (client syntax), not a 404
        status, _b, _h = _post(server.url, {"inputs": X["wine"],
                                            "model": 7})
        assert status == 400

    def test_wrong_geometry_for_routed_model_is_400(self,
                                                    routing_server):
        server, _zoo = routing_server
        status, body, _ = _post(server.url, {"inputs": X["mnist"]},
                                {"X-Model": "wine"})
        assert status == 400

    def test_models_never_coalesce(self, routing_server):
        """Concurrent traffic for two models returns each tenant its
        own head's output — per-model batchers by construction."""
        server, _zoo = routing_server
        results = {}

        def client(fam):
            results[fam] = _post(server.url, {"inputs": X[fam]},
                                 {"X-Model": fam})

        threads = [threading.Thread(target=client, args=(f,))
                   for f in ("mnist", "wine", "kohonen") * 2]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60.0)
        for fam, (status, body, _h) in results.items():
            assert status == 200
            assert len(body["outputs"][0]) == OUT_FEATURES[fam]

    def test_single_engine_server_contract_unchanged(self, zoo_paths):
        """A plain ServingServer(engine) keeps the PR-1 surface: no
        models table, nameless routing works, and the implicit entry
        answers to 'default'."""
        engine = ServingEngine(zoo_paths["wine"], backend="jax",
                               buckets=(1, 2))
        server = ServingServer(engine, max_wait_ms=1.0).start()
        try:
            status, _b, _h = _post(server.url, {"inputs": X["wine"]})
            assert status == 200
            status, _b, _h = _post(server.url, {"inputs": X["wine"]},
                                   {"X-Model": "default"})
            assert status == 200
            status, _b, _h = _post(server.url, {"inputs": X["wine"]},
                                   {"X-Model": "nope"})
            assert status == 404
            # an empty criticality header is "unset" (the pre-zoo
            # `(header or "default")` reading), never a 400
            status, _b, _h = _post(server.url, {"inputs": X["wine"]},
                                   {"X-Criticality": ""})
            assert status == 200
            health = _get(server.url, "healthz")
            assert "models" not in health
            metrics = _get(server.url, "metrics")
            assert "zoo" not in metrics
            assert "model" not in metrics   # unnamed implicit batcher
            # no labeled zoo series may leak from the implicit
            # one-entry wrapper: a scraper pinned to the pre-zoo
            # single-model surface sees no new families
            for fam in ("model_requests_total", "model_resident",
                        "model_pagein_total"):
                snap = REGISTRY.as_dict().get(fam, 0)
                if isinstance(snap, dict):
                    assert not any("model=default" in k
                                   for k in snap), (fam, snap)
        finally:
            server.stop()
            engine.close()

    def test_engine_xor_zoo_required(self, zoo_paths):
        with pytest.raises(ValueError, match="exactly one"):
            ServingServer()
        engine = ServingEngine(zoo_paths["wine"], backend="jax")
        try:
            with pytest.raises(ValueError, match="exactly one"):
                ServingServer(engine, zoo=ModelZoo())
        finally:
            engine.close()


# -- introspection surfaces ------------------------------------------------

class TestIntrospection:
    def test_healthz_models_table(self, routing_server):
        server, _zoo = routing_server
        health = _get(server.url, "healthz")
        rows = {r["model"]: r for r in health["models"]}
        assert set(rows) == {"mnist", "wine", "kohonen"}
        assert health["default_model"] == "wine"
        assert rows["kohonen"]["criticality"] == "critical"
        assert rows["kohonen"]["deadline_ms"] == 5000.0
        assert rows["mnist"]["criticality"] == "sheddable"
        assert rows["wine"]["default"] is True
        for r in rows.values():
            assert r["generation"] >= 1
            assert isinstance(r["weight_bytes"], int)

    def test_statusz_renders_model_table(self, routing_server):
        server, _zoo = routing_server
        text = _get(server.url, "statusz")
        assert "model zoo" in text
        for fam in ("mnist", "wine", "kohonen"):
            assert fam in text
        assert "wine*" in text          # the default marker
        assert "critical" in text

    def test_metrics_zoo_block_and_prometheus_families(
            self, routing_server):
        server, _zoo = routing_server
        m = _get(server.url, "metrics")
        assert set(m["zoo"]["models"]) == {"mnist", "wine", "kohonen"}
        assert m["zoo"]["default_model"] == "wine"
        req = urllib.request.Request(
            server.url + "metrics?format=prometheus")
        with urllib.request.urlopen(req, timeout=30) as r:
            text = r.read().decode()
        for fam in ("model_resident{", "model_pagein_total{",
                    "model_requests_total{", "model_queue_depth{",
                    "model_weight_bytes{", "zoo_model_generation{"):
            assert fam in text, f"{fam} missing from text exposition"

    def test_model_requests_total_attributes_outcomes(
            self, routing_server):
        server, _zoo = routing_server
        before = REGISTRY.as_dict().get("model_requests_total", {})
        n200 = (before.get("code=200,model=kohonen", 0)
                if isinstance(before, dict) else 0)
        status, _b, _h = _post(server.url, {"inputs": X["kohonen"],
                                            "model": "kohonen"})
        assert status == 200
        # the handler counts the request after it has sent the answer:
        # the client may read the registry first (1 run in 3 did)
        deadline = time.monotonic() + 5.0
        while True:
            after = REGISTRY.as_dict()["model_requests_total"]
            if (after.get("code=200,model=kohonen", 0) > n200
                    or time.monotonic() > deadline):
                break
            time.sleep(0.01)
        assert after.get("code=200,model=kohonen", 0) == n200 + 1


# -- quotas ----------------------------------------------------------------

class TestQuota:
    def test_token_bucket_refill(self):
        clock = [0.0]
        tb = TokenBucket(rate_per_s=2.0, burst=2.0,
                         clock=lambda: clock[0])
        assert tb.try_take() is None
        assert tb.try_take() is None
        wait = tb.try_take()            # bucket empty
        assert wait == pytest.approx(0.5)
        clock[0] += 0.5                 # one token accrues
        assert tb.try_take() is None
        assert tb.try_take() is not None

    def test_token_bucket_validation(self):
        with pytest.raises(ValueError):
            TokenBucket(rate_per_s=0)
        with pytest.raises(ValueError):
            TokenBucket(rate_per_s=1.0, burst=0.5)

    def test_quota_breach_is_429_with_retry_after(self, zoo_paths):
        # kohonen: 1 burst token at a glacial refill — the second
        # request in a row must 429 with an honest Retry-After, and
        # the unquota'd default tenant stays unaffected
        zoo = _build_zoo(zoo_paths,
                         kohonen={"quota_rps": 0.01,
                                  "quota_burst": 1.0})
        server = ServingServer(zoo=zoo, max_wait_ms=1.0).start()
        try:
            reject_before = REGISTRY.as_dict().get(
                "model_quota_rejected_total", {})
            k0 = (reject_before.get("model=kohonen", 0)
                  if isinstance(reject_before, dict) else 0)
            status, _b, _h = _post(server.url,
                                   {"inputs": X["kohonen"],
                                    "model": "kohonen"})
            assert status == 200
            status, body, headers = _post(server.url,
                                          {"inputs": X["kohonen"],
                                           "model": "kohonen"})
            assert status == 429
            assert "quota" in body["error"]
            assert int(headers["Retry-After"]) >= 1
            # other tenants keep serving
            status, _b, _h = _post(server.url, {"inputs": X["wine"]})
            assert status == 200
            after = REGISTRY.as_dict()["model_quota_rejected_total"]
            assert after.get("model=kohonen", 0) == k0 + 1
        finally:
            server.stop()
            zoo.close()


# -- weight-residency LRU --------------------------------------------------

class TestResidency:
    def test_eviction_and_pagein_byte_identity(self, zoo_paths):
        """Budget below the combined weights: touching all three
        tenants evicts the coldest; the evicted model's next request
        pages back in and answers byte-identical outputs."""
        sizes = {}
        zoo = _build_zoo(zoo_paths)
        for e in zoo.entries():
            sizes[e.name] = e.engine.weight_nbytes()
        total = sum(sizes.values())
        # room for everything EXCEPT the largest model → churn
        zoo.memory_budget = total - max(sizes.values()) + 1
        server = ServingServer(zoo=zoo, max_wait_ms=1.0).start()
        try:
            s, body, _ = _post(server.url, {"inputs": X["wine"]})
            assert s == 200
            y0 = body["outputs"]
            wine = zoo.resolve("wine").engine
            pageins0 = wine.metrics()["weight_pageins"]
            # touch the other two: wine becomes the coldest and must
            # lose its device copy to fit the budget
            _post(server.url, {"inputs": X["mnist"]},
                  {"X-Model": "mnist"})
            _post(server.url, {"inputs": X["kohonen"]},
                  {"X-Model": "kohonen"})
            assert not wine.weights_resident()
            assert REGISTRY.as_dict()["model_resident"][
                "model=wine"] == 0
            # ...and the next wine request pages in, byte-identical
            s, body, _ = _post(server.url, {"inputs": X["wine"]})
            assert s == 200
            assert body["outputs"] == y0
            assert wine.weights_resident()
            assert wine.metrics()["weight_pageins"] == pageins0 + 1
            pageins = REGISTRY.as_dict()["model_pagein_total"]
            assert pageins.get("cause=evicted,model=wine", 0) >= 1
            evictions = REGISTRY.as_dict()["model_evictions_total"]
            assert evictions.get("model=wine", 0) >= 1
        finally:
            server.stop()
            zoo.close()

    def test_keep_model_never_self_evicts(self, zoo_paths):
        """A budget smaller than even one model still serves: the
        active model is exempt from its own eviction pass."""
        zoo = _build_zoo(zoo_paths, budget=1)
        server = ServingServer(zoo=zoo, max_wait_ms=1.0).start()
        try:
            for fam in ("wine", "mnist", "kohonen"):
                s, _b, _h = _post(server.url, {"inputs": X[fam]},
                                  {"X-Model": fam})
                assert s == 200
        finally:
            server.stop()
            zoo.close()

    def test_concurrent_eviction_queues_on_pagein_single_flight(
            self, zoo_paths, monkeypatch):
        """The ISSUE-11 bugfix pin: requests racing an eviction must
        park on the generation lock and adopt ONE materialization —
        never a double device allocation.  Counted against real
        ``jax.device_put`` calls: the wine demo model has exactly 3
        parameter arrays (fc1 w+b, fc2 w), so device_put calls must
        equal 3 × recorded page-ins, and recorded page-ins must match
        the successful-release count (strict alternation under the
        lock)."""
        import jax
        engine = ServingEngine(zoo_paths["wine"], backend="jax",
                               buckets=(1, 2))
        calls = [0]
        real_put = jax.device_put

        def counting_put(x, *a, **kw):
            calls[0] += 1
            return real_put(x, *a, **kw)

        monkeypatch.setattr(jax, "device_put", counting_put)
        x = np.asarray(X["wine"], np.float32)
        try:
            y0 = engine.predict(x)
            base_pageins = engine.metrics()["weight_pageins"]
            base_calls = calls[0]
            releases = [0]
            stop = threading.Event()
            errors = []

            def evictor():
                while not stop.is_set():
                    if engine.release_weights():
                        releases[0] += 1
                    time.sleep(0.001)

            def client():
                try:
                    for _ in range(25):
                        np.testing.assert_array_equal(
                            engine.predict(x), y0)
                except Exception as e:     # byte drift IS the failure
                    errors.append(e)

            ev = threading.Thread(target=evictor, daemon=True)
            clients = [threading.Thread(target=client, daemon=True)
                       for _ in range(6)]
            ev.start()
            for t in clients:
                t.start()
            for t in clients:
                t.join(120.0)
            stop.set()
            ev.join(10.0)
            assert not errors, f"byte drift under eviction: {errors}"
            pageins = (engine.metrics()["weight_pageins"]
                       - base_pageins)
            put_calls = calls[0] - base_calls
            # exactly-once materialization: every page-in is 3 puts,
            # and page-ins alternate strictly with releases (±1 for
            # whichever side the run ended on)
            assert put_calls == 3 * pageins
            assert releases[0] - 1 <= pageins <= releases[0] + 1
            assert pageins >= 1, "the evictor never actually evicted"
        finally:
            engine.close()


# -- per-model reload isolation --------------------------------------------

class TestReloadIsolation:
    def test_reload_one_model_leaves_others_untouched(self, zoo_paths,
                                                      tmp_path):
        zoo = _build_zoo(zoo_paths)
        server = ServingServer(zoo=zoo, max_wait_ms=1.0).start()
        try:
            # warm every tenant and pin baselines
            outs = {}
            for fam in ("mnist", "wine", "kohonen"):
                s, body, _ = _post(server.url, {"inputs": X[fam]},
                                   {"X-Model": fam})
                assert s == 200
                outs[fam] = body["outputs"]
            mnist = zoo.resolve("mnist").engine
            mnist_cache0 = mnist.metrics()["cached_executables"]
            v2 = str(tmp_path / "wine_v2.znn")
            zoo_mod.write_demo_model(v2, "wine", seed=321)
            status, rec = _admin(server.url, {"name": "wine",
                                              "model": v2,
                                              "wait": True})
            assert status == 200
            assert rec["model"] == "wine"
            assert rec["model_generation"] == 2
            assert (rec["last_reload"] or {})["outcome"] == "ok"
            # isolation: the other tenants' generations AND executable
            # caches are exactly where they were
            gens = {r["model"]: r["generation"] for r in zoo.status()}
            assert gens == {"mnist": 1, "wine": 2, "kohonen": 1}
            assert mnist.metrics()["cached_executables"] \
                == mnist_cache0
            # ...and their answers are byte-identical, while wine's
            # new weights actually took
            for fam in ("mnist", "kohonen"):
                s, body, _ = _post(server.url, {"inputs": X[fam]},
                                   {"X-Model": fam})
                assert s == 200 and body["outputs"] == outs[fam]
            s, body, _ = _post(server.url, {"inputs": X["wine"]})
            assert s == 200 and body["outputs"] != outs["wine"]
        finally:
            server.stop()
            zoo.close()

    def test_reload_unknown_name_is_404(self, routing_server):
        server, _zoo = routing_server
        status, body = _admin(server.url, {"name": "ghost",
                                           "wait": True})
        assert status == 404 and "ghost" in body["error"]


# -- per-model criticality on the shed ladder ------------------------------

class TestCriticalityShedding:
    def _escalate(self, batcher, levels=1):
        """Drive one tenant's CoDel ladder up deterministically: a
        standing above-target wait for `levels` full intervals."""
        sh = batcher.shedder
        sh.note_queue_wait(500.0)              # anchor
        for _ in range(levels):
            time.sleep(0.26)                   # a full interval
            sh.note_queue_wait(500.0)
        assert sh.level >= levels

    def test_sheddable_tenant_browns_out_before_critical(
            self, zoo_paths):
        zoo = _build_zoo(zoo_paths,
                         mnist={"criticality": "sheddable"},
                         kohonen={"criticality": "critical"})
        server = ServingServer(zoo=zoo, max_wait_ms=1.0,
                               shed_target_ms=30.0,
                               shed_interval_ms=250.0).start()
        try:
            # every tenant warm first (jit compiles must not stretch
            # the ladder's timing below)
            for fam in ("mnist", "wine", "kohonen"):
                s, _b, _h = _post(server.url, {"inputs": X[fam]},
                                  {"X-Model": fam})
                assert s == 200
            # the sheddable tenant's OWN queue stands above target →
            # its header-less traffic sheds at level 1
            self._escalate(zoo.resolve("mnist").batcher, levels=1)
            s, body, headers = _post(server.url,
                                     {"inputs": X["mnist"]},
                                     {"X-Model": "mnist"})
            assert s == 503 and "shed" in body["error"]
            assert "Retry-After" in headers
            # the other tenants' ladders are independent: both serve
            for fam in ("wine", "kohonen"):
                s, _b, _h = _post(server.url, {"inputs": X[fam]},
                                  {"X-Model": fam})
                assert s == 200
            # a cooperating client's explicit header still wins
            self._escalate(zoo.resolve("mnist").batcher, levels=1)
            s, _b, _h = _post(server.url, {"inputs": X["mnist"]},
                              {"X-Model": "mnist",
                               "X-Criticality": "critical"})
            assert s == 200
        finally:
            server.stop()
            zoo.close()

    def test_critical_tenant_never_sheds_even_at_level_2(
            self, zoo_paths):
        zoo = _build_zoo(zoo_paths,
                         kohonen={"criticality": "critical"})
        server = ServingServer(zoo=zoo, max_wait_ms=1.0,
                               shed_target_ms=30.0,
                               shed_interval_ms=250.0).start()
        try:
            s, _b, _h = _post(server.url, {"inputs": X["kohonen"]},
                              {"X-Model": "kohonen"})
            assert s == 200
            self._escalate(zoo.resolve("kohonen").batcher, levels=2)
            s, _b, _h = _post(server.url, {"inputs": X["kohonen"]},
                              {"X-Model": "kohonen"})
            assert s == 200            # critical is never shed
            # ...while a default-class tenant at level 2 would shed
            self._escalate(zoo.resolve("wine").batcher, levels=2)
            s, body, _h = _post(server.url, {"inputs": X["wine"]})
            assert s == 503 and "shed" in body["error"]
        finally:
            server.stop()
            zoo.close()


# -- registry policy + spec parsing ----------------------------------------

class TestRegistry:
    def test_effective_policy_defaults_and_overrides(self):
        class Eng:          # engine stand-in; policy is pure
            pass

        entry = ModelEntry("m", Eng(), criticality="sheddable",
                           deadline_ms=250.0)
        assert entry.effective_policy(None, None) \
            == ("sheddable", 250.0)
        assert entry.effective_policy("critical", None) \
            == ("critical", 250.0)
        assert entry.effective_policy(None, 50.0) \
            == ("sheddable", 50.0)
        plain = ModelEntry("p", Eng())
        assert plain.effective_policy(None, None) == ("default", None)

    def test_entry_validation(self):
        class Eng:
            pass

        with pytest.raises(ValueError, match="criticality"):
            ModelEntry("m", Eng(), criticality="vip")
        with pytest.raises(ValueError, match="name"):
            ModelEntry("bad name!", Eng())
        with pytest.raises(ValueError, match="deadline_ms"):
            ModelEntry("m", Eng(), deadline_ms=-1)

    def test_duplicate_and_unknown_names(self, zoo_paths):
        zoo = ModelZoo()
        try:
            zoo.add("wine", zoo_paths["wine"], backend="jax")
            with pytest.raises(ValueError, match="already"):
                zoo.add("wine", zoo_paths["wine"], backend="jax")
            with pytest.raises(UnknownModel):
                zoo.resolve("ghost")
            assert zoo.resolve().name == "wine"   # first = default
        finally:
            zoo.close()

    def test_default_flag_overrides_first(self, zoo_paths):
        zoo = ModelZoo()
        try:
            zoo.add("wine", zoo_paths["wine"], backend="jax")
            zoo.add("mnist", zoo_paths["mnist"], backend="jax",
                    default=True)
            assert zoo.default_name == "mnist"
            assert zoo.resolve().name == "mnist"
        finally:
            zoo.close()

    def test_admit_without_quota_is_free(self, zoo_paths):
        zoo = ModelZoo()
        try:
            entry = zoo.add("wine", zoo_paths["wine"], backend="jax")
            zoo.admit(entry)                      # no quota: no raise
            limited = zoo.add("mnist", zoo_paths["mnist"],
                              backend="jax", quota_rps=0.01,
                              quota_burst=1.0)
            zoo.admit(limited)
            with pytest.raises(QuotaExceeded):
                zoo.admit(limited)
            # a burst without a rate is a config error, not a silent
            # no-quota tenant
            with pytest.raises(ValueError, match="quota_burst"):
                zoo.add("kohonen", zoo_paths["kohonen"],
                        backend="jax", quota_burst=5.0)
        finally:
            zoo.close()


class TestSpecParsing:
    def test_bare_path_is_single_model(self):
        assert parse_model_spec("/tmp/model.znn") \
            == (None, "/tmp/model.znn", {})

    def test_named_spec_with_options(self):
        name, path, opts = parse_model_spec(
            "wine=/tmp/wine.znn,criticality=critical,"
            "deadline-ms=250,quota-rps=5,quota-burst=10,default")
        assert (name, path) == ("wine", "/tmp/wine.znn")
        assert opts == {"criticality": "critical",
                        "deadline_ms": 250.0, "quota_rps": 5.0,
                        "quota_burst": 10.0, "default": True}

    def test_bad_option_raises(self):
        with pytest.raises(ValueError, match="unknown option"):
            parse_model_spec("wine=/tmp/w.znn,flavor=dry")
        with pytest.raises(ValueError, match="bad option"):
            parse_model_spec("wine=/tmp/w.znn,critical")
        with pytest.raises(ValueError, match="empty path"):
            parse_model_spec("wine=")

    def test_scan_zoo_dir(self, zoo_paths, tmp_path):
        import os
        found = scan_zoo_dir(os.path.dirname(zoo_paths["wine"]))
        assert set(found) == {"mnist", "wine", "kohonen"}
        with pytest.raises(ValueError, match="no .znn"):
            scan_zoo_dir(str(tmp_path))


class TestServeCLIZoo:
    def test_serve_zoo_subcommand_parses_and_binds(self, zoo_paths):
        """`python -m znicz_tpu serve --zoo DIR` wires the multi-
        tenant CLI (in-process, same idiom as the single-model CLI
        test: subprocesses would re-import jax)."""
        import os
        started = {}
        orig = ServingServer.start

        def capture(self):
            started["server"] = self
            orig(self)
            raise KeyboardInterrupt     # unblock main()'s wait loop

        ServingServer.start = capture
        try:
            from znicz_tpu.__main__ import main
            rc = main([
                "serve", "--zoo", os.path.dirname(zoo_paths["wine"]),
                "--port", "0", "--buckets", "1,4",
                "--default-model", "wine",
                "--memory-budget-mb", "0.01",
                "--model", "kohonen="
                + zoo_paths["kohonen"]
                + ",criticality=critical,quota-rps=9"])
            assert rc == 0
            server = started["server"]
            assert server._zoo_explicit
            assert server.zoo.names() == ["kohonen", "mnist", "wine"]
            assert server.zoo.default_name == "wine"
            assert server.zoo.memory_budget == 10000
            entry = server.zoo.resolve("kohonen")
            assert entry.criticality == "critical"
            assert entry.quota is not None
            assert entry.quota.rate == 9.0
        finally:
            ServingServer.start = orig
