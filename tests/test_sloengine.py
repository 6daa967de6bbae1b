"""SLO burn-rate engine + per-tenant cost attribution (ISSUE 12).

Pins the observability substrate end to end: burn-rate arithmetic
(windowed counter deltas, latency-threshold snapping, the min-events
gate), budget exhaustion and recovery over a rolling compliance
window, alert hysteresis (fast AND slow windows must both exceed to
fire; the fast window de-asserts cleanly), the registry sample
builders, per-tenant device-ms attribution summing to what the
engines measured, the ``/alertz`` / ``/statusz`` /
``/debug/flightrecorder?model=`` surfaces, the ``bench.py serve``
transcript-row schema, the ``--slo`` spec grammar, and the promotion
controller's :class:`BurnRatePolicy` burn-rate canary watch.
"""

import importlib.util
import json
import math
import os
import urllib.error
import urllib.request

import numpy as np
import pytest

from znicz_tpu.promotion.slo import BurnRatePolicy, SLOSample
from znicz_tpu.serving import zoo as zoo_mod
from znicz_tpu.serving.engine import ServingEngine
from znicz_tpu.serving.server import ServingServer
from znicz_tpu.telemetry import sloengine as se
from znicz_tpu.telemetry.flightrecorder import (RECORDER, FlightRecorder,
                                                stage_breakdown)
from znicz_tpu.telemetry.registry import (DEFAULT_LATENCY_BUCKETS_MS,
                                          REGISTRY, MetricsRegistry)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "bench", os.path.join(_REPO, "bench.py"))
bench = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench)


def sample(at=0.0, req=0.0, err=0.0, lat=None, count=None):
    lat = dict(lat or {})
    if count is None:
        count = max(lat.values()) if lat else 0.0
    return se.TenantSample(at=at, requests=req, errors_5xx=err,
                           latency_cum=lat, latency_count=count)


def _labeled(name):
    snap = REGISTRY.as_dict().get(name, 0)
    return dict(snap) if isinstance(snap, dict) else {}


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


class ScriptedTenant:
    """A mutable counter source: tests push (good, bad) events and the
    engine samples the running totals, exactly like registry reads."""

    def __init__(self):
        self.req = 0.0
        self.err = 0.0

    def push(self, good=0, bad=0):
        self.req += good + bad
        self.err += bad

    def __call__(self, _model):
        return sample(req=self.req, err=self.err)


# -- burn arithmetic --------------------------------------------------------

class TestBurnArithmetic:
    def test_availability_burn_is_rate_over_budget(self):
        start = sample(req=100, err=1)
        end = sample(req=200, err=3)
        burn, events = se.burn_between(start, end, budget=0.001)
        assert events == 100
        # 2 bad of 100 -> 2% error rate over a 0.1% budget = 20x
        assert burn == pytest.approx(20.0)

    def test_latency_burn_snaps_threshold_to_bucket_edge(self):
        # edges 10 and 25: threshold 20 snaps UP to 25 — the registry
        # has bucket counts, not samples
        start = sample(lat={10.0: 0, 25.0: 0, math.inf: 0})
        end = sample(lat={10.0: 60, 25.0: 90, math.inf: 100})
        burn, events = se.burn_between(
            start, end, budget=0.1, objective="latency",
            threshold_ms=20.0)
        assert events == 100
        # good = cum(25) = 90 -> 10% bad over a 10% budget = burn 1.0
        assert burn == pytest.approx(1.0)

    def test_threshold_beyond_edges_reads_overflow_bucket(self):
        end = sample(lat={10.0: 5, math.inf: 8})
        good = se.latency_good(end.latency_cum, 99999.0)
        assert good == 8.0          # everything counts as good

    def test_min_events_gate_burns_zero(self):
        start = sample(req=0, err=0)
        end = sample(req=3, err=3)          # 100% errors, but 3 events
        burn, events = se.burn_between(start, end, budget=0.001,
                                       min_events=5)
        assert burn == 0.0 and events == 3

    def test_empty_window_burns_zero(self):
        s0 = sample(req=50, err=5)
        burn, events = se.burn_between(s0, s0, budget=0.01)
        assert burn == 0.0 and events == 0


class TestSpecValidation:
    def test_rejects_bad_shapes(self):
        with pytest.raises(ValueError):
            se.SLOSpec(name="x", objective="weird")
        with pytest.raises(ValueError):
            se.SLOSpec(name="x", target=99.9)      # percent, not frac
        with pytest.raises(ValueError):
            se.SLOSpec(name="x", objective="latency")   # no threshold
        with pytest.raises(ValueError):
            se.SLOSpec(name="x", fast_window_s=100, slow_window_s=10)
        with pytest.raises(ValueError):
            se.SLOSpec(name="x", severity="shrug")

    def test_budget_is_one_minus_target(self):
        assert se.SLOSpec(name="x", target=0.99).budget == \
            pytest.approx(0.01)

    def test_engine_rejects_duplicate_specs(self):
        spec = se.SLOSpec(name="a", model="m")
        with pytest.raises(ValueError):
            se.SLOEngine([spec, spec], lambda m: sample())


# -- windows, budget, hysteresis --------------------------------------------

def _build(spec, tenant, clock, recorder=None):
    return se.SLOEngine([spec], tenant, interval_s=1.0, clock=clock,
                        recorder=recorder or FlightRecorder())


def _tick(engine, clock, tenant, good=0, bad=0, n=1):
    events = []
    for _ in range(n):
        clock.t += 1.0
        tenant.push(good=good, bad=bad)
        events += engine.tick()
    return events


class TestWindows:
    def test_fast_window_recovers_before_slow(self):
        spec = se.SLOSpec(name="w", model="m", target=0.9,
                          fast_window_s=2.0, slow_window_s=10.0,
                          burn_threshold=1e9,     # alerts out of the way
                          min_events=5, budget_window_s=10.0)
        clock, tenant = FakeClock(), ScriptedTenant()
        eng = _build(spec, tenant, clock)
        _tick(eng, clock, tenant, good=5, bad=5, n=4)   # 50% errors
        st = eng.status()["slos"][0]
        assert st["burn_fast"] == pytest.approx(5.0)    # 0.5 / 0.1
        assert st["burn_slow"] == pytest.approx(5.0)
        # errors stop: the fast window drains to clean while the slow
        # window still remembers the burst
        _tick(eng, clock, tenant, good=10, n=4)
        st = eng.status()["slos"][0]
        assert st["burn_fast"] == 0.0
        assert st["burn_slow"] > 1.0

    def test_budget_exhaustion_then_recovery(self):
        spec = se.SLOSpec(name="b", model="m", target=0.9,
                          fast_window_s=1.0, slow_window_s=2.0,
                          burn_threshold=1e9, min_events=1,
                          budget_window_s=4.0)
        clock, tenant = FakeClock(), ScriptedTenant()
        eng = _build(spec, tenant, clock)
        _tick(eng, clock, tenant, good=0, bad=10, n=3)  # all errors
        st = eng.status()["slos"][0]
        assert st["budget_remaining"] <= 0.0            # exhausted
        # clean traffic long enough for the bad ticks to roll out of
        # the 4-second compliance window: the budget heals
        _tick(eng, clock, tenant, good=10, n=8)
        st = eng.status()["slos"][0]
        assert st["budget_remaining"] == pytest.approx(1.0)

    def test_gauges_exported_with_labels(self):
        spec = se.SLOSpec(name="gauged", model="gmodel", target=0.9,
                          fast_window_s=1.0, slow_window_s=2.0,
                          min_events=1, burn_threshold=1e9)
        clock, tenant = FakeClock(), ScriptedTenant()
        eng = _build(spec, tenant, clock)
        _tick(eng, clock, tenant, good=1, bad=1, n=2)
        burns = _labeled("slo_burn_rate")
        assert "model=gmodel,slo=gauged,window=fast" in burns
        assert "model=gmodel,slo=gauged,window=slow" in burns
        assert "model=gmodel,slo=gauged" in \
            _labeled("slo_budget_remaining")


class TestAlertHysteresis:
    def _spec(self):
        return se.SLOSpec(name="h", model="m", target=0.9,
                          fast_window_s=2.0, slow_window_s=10.0,
                          burn_threshold=5.0, min_events=5,
                          budget_window_s=100.0)

    def test_fast_spike_alone_does_not_fire(self):
        clock, tenant = FakeClock(), ScriptedTenant()
        rec = FlightRecorder()
        eng = _build(self._spec(), tenant, clock, recorder=rec)
        _tick(eng, clock, tenant, good=10, n=8)         # clean history
        # a 2-tick spike: fast window 100% bad (burn 10 >= 5) but the
        # slow window dilutes it (20 bad / 100 -> burn 2 < 5)
        events = _tick(eng, clock, tenant, good=0, bad=10, n=2)
        st = eng.status()["slos"][0]
        assert st["burn_fast"] >= 5.0
        assert st["burn_slow"] < 5.0
        assert events == [] and not st["firing"]

    def test_fire_once_then_clean_deassert(self):
        clock, tenant = FakeClock(), ScriptedTenant()
        rec = FlightRecorder()
        eng = _build(self._spec(), tenant, clock, recorder=rec)
        before = dict(_labeled("slo_alerts_total"))
        events = _tick(eng, clock, tenant, good=0, bad=10, n=8)
        fires = [e for e in events if e["transition"] == "fire"]
        assert len(fires) == 1                  # fired EXACTLY once
        assert fires[0]["slo"] == "h" and fires[0]["model"] == "m"
        assert eng.status()["slos"][0]["firing"]
        key = "model=m,severity=page,slo=h"
        after = _labeled("slo_alerts_total")
        assert after.get(key, 0) - before.get(key, 0) == 1
        # recovery: the fast window clears -> clean de-assert, and the
        # slow window (still hot) cannot hold the alert open
        events = _tick(eng, clock, tenant, good=10, n=3)
        resolves = [e for e in events if e["transition"] == "resolve"]
        assert len(resolves) == 1
        st = eng.status()["slos"][0]
        assert not st["firing"] and st["burn_slow"] >= 5.0
        # both transitions reached the flight recorder; the firing one
        # sits in the error ring (a busy burst must not flush it)
        kinds = [(r["transition"], r["outcome"])
                 for r in rec.snapshot()["recent"]
                 if r["kind"] == "slo_alert"]
        assert kinds == [("fire", "firing"), ("resolve", "ok")]
        assert any(r["kind"] == "slo_alert"
                   for r in rec.snapshot()["errors"])
        # de-asserts are not counted
        assert _labeled("slo_alerts_total").get(key) == after.get(key)

    def test_refire_counts_again(self):
        clock, tenant = FakeClock(), ScriptedTenant()
        eng = _build(self._spec(), tenant, clock)
        before = _labeled("slo_alerts_total").get(
            "model=m,severity=page,slo=h", 0)
        _tick(eng, clock, tenant, good=0, bad=10, n=8)      # fire
        _tick(eng, clock, tenant, good=10, n=12)            # resolve
        _tick(eng, clock, tenant, good=0, bad=10, n=8)      # re-fire
        after = _labeled("slo_alerts_total").get(
            "model=m,severity=page,slo=h", 0)
        assert after - before == 2


# -- registry sample builders -----------------------------------------------

class TestSampleBuilders:
    def test_model_sample_reads_labeled_families(self):
        reg = MetricsRegistry()
        c = reg.counter("model_requests_total")
        c.inc(7, model="a", code="200")
        c.inc(2, model="a", code="503")
        c.inc(9, model="b", code="200")     # another tenant: excluded
        h = reg.histogram("model_latency_ms",
                          buckets=DEFAULT_LATENCY_BUCKETS_MS)
        for v in (2.0, 30.0, 400.0):
            h.observe(v, model="a")
        s = se.model_sample("a", registry=reg)
        assert s.requests == 9 and s.errors_5xx == 2
        assert s.latency_count == 3
        assert s.latency_cum[2.5] == 1.0
        assert s.latency_cum[500.0] == 3.0

    def test_route_sample_reads_predict_route(self):
        reg = MetricsRegistry()
        c = reg.counter("requests_total")
        c.inc(5, route="/predict", code="200")
        c.inc(1, route="/predict", code="500")
        c.inc(3, route="/metrics", code="200")   # not the judged route
        reg.histogram("predict_latency_ms",
                      buckets=DEFAULT_LATENCY_BUCKETS_MS).observe(3.0)
        s = se.route_sample(registry=reg)
        assert s.requests == 6 and s.errors_5xx == 1
        assert s.latency_count == 1

    def test_latency_histogram_is_2xx_only(self):
        # a shed/quota refusal answers in microseconds; counting it as
        # a fast event would make a 503ing server look latency-HEALTHY
        # (found by the live drive with the CLI's default shed ladder)
        zoo_mod.note_model_request("lat2xx_pin", 200, 5.0)
        zoo_mod.note_model_request("lat2xx_pin", 503, 0.05)
        zoo_mod.note_model_request("lat2xx_pin", 429, 0.05)
        zoo_mod.note_model_request("lat2xx_pin", 400, 0.05)
        s = se.model_sample("lat2xx_pin")
        assert s.requests == 4                 # every outcome counted
        assert s.latency_count == 1            # only the served answer

    def test_4xx_is_not_an_availability_error(self):
        reg = MetricsRegistry()
        c = reg.counter("model_requests_total")
        c.inc(5, model="a", code="200")
        c.inc(5, model="a", code="400")
        s = se.model_sample("a", registry=reg)
        assert s.requests == 10 and s.errors_5xx == 0


# -- per-tenant device-time attribution -------------------------------------

@pytest.fixture(scope="module")
def zoo_paths(tmp_path_factory):
    d = tmp_path_factory.mktemp("slo_zoo")
    return zoo_mod.make_demo_zoo(str(d), families=("mnist", "wine"))


X = {"mnist": np.full((1, 16), 0.2, np.float32),
     "wine": np.full((1, 13), 0.1, np.float32)}


class TestDeviceAttribution:
    def test_engine_measures_and_fires_the_hook(self, zoo_paths):
        engine = ServingEngine(zoo_paths["wine"], backend="jax",
                               buckets=(1,))
        seen = []
        engine.on_device_time = seen.append
        try:
            engine.predict(X["wine"])
            engine.predict(X["wine"])
        finally:
            engine.close()
        total = engine.device_ms_total()
        assert total > 0.0
        assert sum(seen) == pytest.approx(total)

    def test_zoo_bills_the_tenant_that_spent_the_chip(self, zoo_paths):
        zoo = zoo_mod.ModelZoo()
        zoo.add("mnist", zoo_paths["mnist"], backend="jax",
                buckets=(1,))
        zoo.add("wine", zoo_paths["wine"], backend="jax", buckets=(1,))
        before = _labeled("model_device_ms_total")
        try:
            for _ in range(3):
                zoo.resolve("mnist").predict(X["mnist"])
            zoo.resolve("wine").predict(X["wine"])
            after = _labeled("model_device_ms_total")
            billed = {m: after.get(f"model={m}", 0.0)
                      - before.get(f"model={m}", 0.0)
                      for m in ("mnist", "wine")}
            measured = sum(e.engine.device_ms_total()
                           for e in zoo.entries())
            assert billed["mnist"] > 0.0 and billed["wine"] > 0.0
            # the ledger adds up: attribution == what was measured
            assert sum(billed.values()) == pytest.approx(measured,
                                                         rel=1e-6)
        finally:
            zoo.close()

    def test_implicit_single_model_zoo_stays_label_free(self,
                                                        zoo_paths):
        engine = ServingEngine(zoo_paths["wine"], backend="jax",
                               buckets=(1,))
        zoo = zoo_mod.ModelZoo(labeled_metrics=False)
        zoo.add("default", engine=engine)
        before = _labeled("model_device_ms_total")
        try:
            zoo.resolve().predict(X["wine"])
        finally:
            zoo.close()
        # the engine measured (process introspection)...
        assert engine.device_ms_total() > 0.0
        # ...but no model-labeled series appeared: a scraper pinned to
        # the pre-zoo single-model surface sees no new children
        assert _labeled("model_device_ms_total") == before


# -- HTTP surfaces ----------------------------------------------------------

def _get(url, path):
    with urllib.request.urlopen(url + path, timeout=30) as r:
        body = r.read()
        return (json.loads(body)
                if "json" in r.headers.get("Content-Type", "")
                else body.decode())


def _post(url, payload, headers=None):
    req = urllib.request.Request(
        url + "predict", json.dumps(payload).encode(),
        {"Content-Type": "application/json", **(headers or {})})
    try:
        with urllib.request.urlopen(req, timeout=60) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


@pytest.fixture(scope="class")
def served_zoo(zoo_paths):
    zoo = zoo_mod.ModelZoo()
    zoo.add("mnist", zoo_paths["mnist"], backend="jax", buckets=(1, 2))
    zoo.add("wine", zoo_paths["wine"], backend="jax", buckets=(1, 2),
            default=True)
    server = ServingServer(zoo=zoo, max_wait_ms=1.0).start()
    try:
        yield server, zoo
    finally:
        server.stop()
        zoo.close()


class TestHttpSurfaces:
    def test_alertz_disabled_without_engine(self, served_zoo):
        server, _zoo = served_zoo
        out = _get(server.url, "alertz")
        assert out == {"enabled": False, "alerts": []}

    def test_alertz_and_statusz_render_the_engine(self, served_zoo):
        server, _zoo = served_zoo
        spec = se.SLOSpec(name="avail", model="wine", target=0.99,
                          fast_window_s=1.0, slow_window_s=3.0,
                          min_events=1)
        eng = se.SLOEngine.for_server(server, [spec], interval_s=60.0)
        server.attach_slo(eng)
        try:
            for _ in range(3):
                code, _b = _post(server.url,
                                 {"inputs": X["wine"].tolist()})
                assert code == 200
            eng.tick()
            out = _get(server.url, "alertz")
            assert out["enabled"] is True
            rows = {r["slo"]: r for r in out["slos"]}
            assert rows["avail"]["model"] == "wine"
            assert rows["avail"]["firing"] is False
            assert rows["avail"]["burn_fast"] == 0.0
            assert out["alerts"] == []
            statusz = _get(server.url, "statusz")
            assert "slo burn rates" in statusz
            assert "avail" in statusz
            # the JSON /metrics view embeds the same judgment
            m = _get(server.url, "metrics")
            assert m["slo"]["slos"][0]["slo"] == "avail"
        finally:
            server.attach_slo(None)

    def test_flightrecorder_model_filter_and_device_stage(
            self, served_zoo):
        import time
        server, zoo = served_zoo
        t0 = time.time()
        for _ in range(2):
            assert _post(server.url, {"inputs": X["mnist"].tolist()},
                         {"X-Model": "mnist"})[0] == 200
        assert _post(server.url,
                     {"inputs": X["wine"].tolist()})[0] == 200
        # a request's record is written after its response is sent, and
        # the process-wide recorder also holds what earlier tests' servers
        # of the same worker left there: wait for THESE requests' records
        for _ in range(100):
            snap = _get(server.url, "debug/flightrecorder?model=mnist")
            ok = [r for r in snap["recent"]
                  if r["code"] == 200 and r["at"] >= t0]
            if len(ok) >= 2 and RECORDER.stage_breakdown(
                    model="wine")["requests"] >= 1:
                break
            time.sleep(0.02)
        assert snap["model"] == "mnist"
        assert snap["recent"], "model-scoped view lost the records"
        assert all(r["model"] == "mnist" for r in snap["recent"])
        # the per-request device-time share landed in the stages
        assert len(ok) >= 2 and all(
            r["stages"].get("device_ms", 0) > 0 for r in ok)
        # recorder-level aggregation scopes to the tenant too
        agg = RECORDER.stage_breakdown(model="mnist")
        assert agg["requests"] >= 2
        assert agg["stages"]["device_ms"]["total_ms"] > 0
        wine_agg = RECORDER.stage_breakdown(model="wine")
        assert wine_agg["requests"] >= 1
        # attribution sums within the acceptance's 10% of measured
        billed = _labeled("model_device_ms_total")
        measured = sum(e.engine.device_ms_total()
                       for e in zoo.entries())
        total_billed = sum(v for k, v in billed.items()
                           if k in ("model=mnist", "model=wine"))
        # other tests' zoos share these label children — compare
        # against every engine this process measured instead
        assert total_billed > 0 and measured > 0


class TestProRataSplit:
    def test_stage_breakdown_splits_device_ms_by_rows(self):
        spans = [{"name": "engine.forward", "duration_ms": 8.0,
                  "device_ms": 6.0, "rows": 4}]
        # a 1-row rider of a 4-row batch pays a quarter of the bill
        assert stage_breakdown(spans, rows=1)["device_ms"] == \
            pytest.approx(1.5)
        assert stage_breakdown(spans, rows=4)["device_ms"] == \
            pytest.approx(6.0)
        # no rows context: the whole span's figure (old behavior)
        assert stage_breakdown(spans)["device_ms"] == pytest.approx(6.0)
        # never more than the batch actually cost
        assert stage_breakdown(spans, rows=9)["device_ms"] == \
            pytest.approx(6.0)


# -- bench serve-mode row schema --------------------------------------------

class TestBenchServeRow:
    def test_row_schema_and_arithmetic(self):
        row = bench._serve_row(
            latencies_ms=[1.0, 2.0, 3.0, 4.0, 100.0],
            codes={200: 4, 429: 1}, duration_s=2.0, cores=8,
            device_ms_total=12.0)
        for key in ("requests", "ok", "codes", "duration_s", "cores",
                    "req_per_sec", "req_per_sec_per_core", "p50_ms",
                    "p99_ms", "device_ms_total",
                    "device_ms_per_request"):
            assert key in row, key
        assert row["requests"] == 5 and row["ok"] == 4
        assert row["req_per_sec"] == pytest.approx(2.0)     # 200s only
        assert row["req_per_sec_per_core"] == pytest.approx(0.25)
        assert row["p50_ms"] == 3.0 and row["p99_ms"] == 100.0
        assert row["device_ms_per_request"] == pytest.approx(3.0)
        assert json.loads(json.dumps(row)) == row           # JSON-able

    def test_no_traffic_row_degrades_honestly(self):
        row = bench._serve_row([], {}, 1.0, 4, 0.0)
        assert row["requests"] == 0
        assert row["p50_ms"] is None and row["p99_ms"] is None
        assert row["device_ms_per_request"] is None


# -- CLI spec grammar -------------------------------------------------------

class TestSpecGrammar:
    def test_full_spec(self):
        spec = se.parse_slo_spec(
            "lat,model=mnist,objective=latency,threshold-ms=100,"
            "target=99.9,fast-s=60,slow-s=600,burn=6,min-events=20,"
            "severity=ticket")
        assert spec.name == "lat" and spec.model == "mnist"
        assert spec.objective == "latency"
        assert spec.threshold_ms == 100.0
        assert spec.target == pytest.approx(0.999)   # percent reading
        assert spec.fast_window_s == 60.0
        assert spec.slow_window_s == 600.0
        assert spec.burn_threshold == 6.0
        assert spec.min_events == 20
        assert spec.severity == "ticket"

    def test_minimal_spec_defaults(self):
        spec = se.parse_slo_spec("availability")
        assert spec.model is None
        assert spec.objective == "availability"
        assert spec.target == pytest.approx(0.999)

    def test_fractional_target_passes_through(self):
        assert se.parse_slo_spec("a,target=0.95").target == \
            pytest.approx(0.95)

    def test_bad_specs_raise(self):
        for bad in ("", "model=x", "a,what=1", "a,objective=latency",
                    "a,threshold-ms=junk"):
            with pytest.raises(ValueError):
                se.parse_slo_spec(bad)


# -- the promotion burn-rate watch ------------------------------------------

def _slo_sample(at, req, err):
    return SLOSample(at=at, latency_cum={}, latency_count=0.0,
                     requests=req, errors_5xx=err)


class TestBurnRatePolicy:
    def test_controller_compatible_surface(self):
        pol = BurnRatePolicy(window_s=12.0, probe_interval_s=2.0)
        assert pol.window_s == 12.0 and pol.probe_interval_s == 2.0
        assert callable(pol.evaluate)

    def test_one_probe_blip_does_not_breach(self):
        pol = BurnRatePolicy(target=0.9, window_s=60.0,
                             probe_interval_s=2.0, fast_window_s=4.0,
                             max_burn_rate=5.0, min_samples=5)
        start = _slo_sample(0.0, 100, 0)
        # clean probes stretch the slow window out...
        for t in (2, 4, 6, 8, 10, 12, 14, 16):
            assert pol.evaluate(start,
                                _slo_sample(t, 100 + 5 * t, 0)) == []
        # ...then a short 100%-bad spike: fast burns hot, but the slow
        # window (the whole watch) dilutes it — no breach
        out = pol.evaluate(start, _slo_sample(18.0, 100 + 5 * 16 + 10,
                                              10))
        assert out == []

    def test_sustained_burn_breaches_both_windows(self):
        pol = BurnRatePolicy(target=0.9, window_s=60.0,
                             probe_interval_s=2.0, fast_window_s=4.0,
                             max_burn_rate=5.0, min_samples=5)
        start = _slo_sample(0.0, 100, 0)
        breaches = []
        req, err = 100, 0
        for t in (2, 4, 6, 8):
            req += 10
            err += 10                   # every new answer is a 5xx
            breaches = pol.evaluate(start, _slo_sample(t, req, err))
        assert breaches and breaches[0]["slo"] == "burn_rate"
        assert breaches[0]["value"] >= 5.0

    def test_new_watch_resets_the_probe_ring(self):
        pol = BurnRatePolicy(target=0.9, window_s=60.0,
                             probe_interval_s=2.0, fast_window_s=4.0,
                             max_burn_rate=5.0, min_samples=5)
        start1 = _slo_sample(0.0, 0, 0)
        for t in (2, 4, 6, 8):
            pol.evaluate(start1, _slo_sample(t, 10 * t, 10 * t))
        # a NEW watch (fresh start object) with clean traffic: the old
        # candidate's bad probes must not leak into this fast window
        start2 = _slo_sample(100.0, 1000, 80)
        out = pol.evaluate(start2, _slo_sample(104.0, 1040, 80))
        assert out == []

    def test_breaker_open_is_still_an_instant_breach(self):
        pol = BurnRatePolicy()
        start = _slo_sample(0.0, 0, 0)
        now = _slo_sample(2.0, 10, 0)
        now.breaker_state = "open"
        out = pol.evaluate(start, now)
        assert [b["slo"] for b in out] == ["breaker"]

    def test_latency_objective_needs_threshold(self):
        with pytest.raises(ValueError):
            BurnRatePolicy(objective="latency")
