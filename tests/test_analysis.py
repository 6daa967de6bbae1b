"""znicz_tpu.analysis ("zlint") — per-rule fixtures + the repo gate.

Each rule family gets a known-bad snippet that must fire and a
known-good twin that must stay silent (ISSUE 4 acceptance); suppression
and baseline handling get a full round-trip; and the whole-repo run is
the tier-1 gate (`pytest -m lint` runs it standalone).
"""

import json
import subprocess
import sys
import textwrap

import pytest

from znicz_tpu.analysis import (Analyzer, ConditionWaitPredicateRule,
                                DeadlineDisciplineRule,
                                DurationClockRule, EnvRoutingRule,
                                HandlerSafetyRule,
                                JaxHygieneRule, LockDisciplineRule,
                                LockLeakRule, LockOrderCycleRule,
                                MetricDriftRule, RetryAfterRule,
                                SpanNameDriftRule,
                                UnseededRandomRule, load_baseline,
                                run_repo, write_baseline)
from znicz_tpu.analysis import cli as zlint_cli


def lint(tmp_path, source, rules, rel="pkg/mod.py"):
    path = tmp_path / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(source))
    return Analyzer(rules, root=str(tmp_path)).run([rel])


def rules_of(findings):
    return sorted({f.rule for f in findings})


# -- lock discipline -------------------------------------------------------

LOCKED_BAD = """
    import threading

    class Box:
        def __init__(self):
            self._lock = threading.Lock()
            self._items = []

        def add(self, x):
            with self._lock:
                self._items.append(x)

        def peek(self):
            return self._items[-1]        # unguarded read
"""

LOCKED_GOOD = """
    import threading

    class Box:
        def __init__(self):
            self._lock = threading.Lock()
            self._items = []
            self.limit = 8                # config: never mutated

        def add(self, x):
            with self._lock:
                if len(self._items) < self.limit:
                    self._items.append(x)

        def peek(self):
            with self._lock:
                return self._items[-1]

        def capacity(self):
            return self.limit             # config read: not guarded
"""


class TestLockDiscipline:
    def test_unguarded_read_fires(self, tmp_path):
        found = lint(tmp_path, LOCKED_BAD, [LockDisciplineRule()])
        assert rules_of(found) == ["lock-discipline"]
        assert len(found) == 1
        assert "_items" in found[0].message
        assert found[0].path == "pkg/mod.py"

    def test_guarded_class_is_silent(self, tmp_path):
        assert lint(tmp_path, LOCKED_GOOD, [LockDisciplineRule()]) == []

    def test_unguarded_write_fires(self, tmp_path):
        src = LOCKED_BAD.replace(
            "return self._items[-1]        # unguarded read",
            "self._items = []              # unguarded write")
        found = lint(tmp_path, src, [LockDisciplineRule()])
        assert len(found) == 1 and "written" in found[0].message

    def test_init_is_exempt(self, tmp_path):
        # __init__ builds state before any other thread can see it
        found = lint(tmp_path, LOCKED_GOOD + """
    class Box2(Box):
        def __init__(self):
            super().__init__()
            with self._lock:
                self._items.append(0)
            self._items.append(1)         # still __init__: exempt
""", [LockDisciplineRule()])
        assert found == []

    def test_model_registry_torn_read_fires(self, tmp_path):
        # the zoo registry's exact mutable-state shape (ISSUE 11): an
        # LRU recency map + entries dict guarded in most methods, with
        # one scrape-path read outside the lock — the torn-read bug
        # PR 4 flagged in ServingEngine.metrics, re-pinned here so the
        # registry class stays honest
        found = lint(tmp_path, """
    import threading
    import time

    class Registry:
        def __init__(self):
            self._lock = threading.Lock()
            self._entries = {}
            self._last_used = {}

        def add(self, name, engine):
            with self._lock:
                self._entries[name] = engine
                self._last_used[name] = time.monotonic()

        def touch(self, name):
            with self._lock:
                self._last_used[name] = time.monotonic()

        def coldest(self):
            return min(self._last_used)   # unguarded scrape read
""", [LockDisciplineRule()])
        assert rules_of(found) == ["lock-discipline"]
        assert len(found) == 1 and "_last_used" in found[0].message

    def test_model_registry_guarded_is_silent(self, tmp_path):
        found = lint(tmp_path, """
    import threading
    import time

    class Registry:
        def __init__(self):
            self._lock = threading.Lock()
            self._entries = {}
            self._last_used = {}

        def add(self, name, engine):
            with self._lock:
                self._entries[name] = engine
                self._last_used[name] = time.monotonic()

        def touch(self, name):
            with self._lock:
                self._last_used[name] = time.monotonic()

        def coldest(self):
            with self._lock:
                return min(self._last_used)
""", [LockDisciplineRule()])
        assert found == []

    def test_lock_held_helper_inferred(self, tmp_path):
        # a private helper only ever called under the lock runs under
        # it by construction (the MicroBatcher._queued_rows idiom)
        found = lint(tmp_path, """
    import threading

    class Q:
        def __init__(self):
            self._lock = threading.Lock()
            self._rows = []

        def _count(self):
            return len(self._rows)        # callers hold the lock

        def add(self, r):
            with self._lock:
                if self._count() < 10:
                    self._rows.append(r)

        def size(self):
            with self._lock:
                return self._count()
""", [LockDisciplineRule()])
        assert found == []

    def test_helper_also_called_bare_is_flagged(self, tmp_path):
        found = lint(tmp_path, """
    import threading

    class Q:
        def __init__(self):
            self._lock = threading.Lock()
            self._rows = []

        def _count(self):
            return len(self._rows)

        def add(self, r):
            with self._lock:
                if self._count() < 10:
                    self._rows.append(r)

        def size(self):
            return self._count()          # bare call site
""", [LockDisciplineRule()])
        assert rules_of(found) == ["lock-discipline"]

    def test_annotated_assignment_is_a_mutation(self, tmp_path):
        # `self.x: int = v` must count as a write — an added type
        # annotation must not disarm the rule
        found = lint(tmp_path, """
    import threading

    class Box:
        def __init__(self):
            self._lock = threading.Lock()
            self.total = 0

        def bump(self):
            with self._lock:
                self.total += 1

        def reset(self):
            self.total: int = 0           # annotated unguarded write
""", [LockDisciplineRule()])
        assert len(found) == 1 and "written" in found[0].message

    def test_condition_counts_as_lock(self, tmp_path):
        found = lint(tmp_path, """
    import threading

    class W:
        def __init__(self):
            self._cond = threading.Condition()
            self._jobs = []

        def put(self, j):
            with self._cond:
                self._jobs.append(j)
                self._cond.notify_all()

        def depth(self):
            return len(self._jobs)        # unguarded
""", [LockDisciplineRule()])
        assert len(found) == 1 and "_jobs" in found[0].message


# -- JAX hygiene -----------------------------------------------------------

class TestJaxHygiene:
    def test_item_inside_jit_fires(self, tmp_path):
        found = lint(tmp_path, """
    import jax

    @jax.jit
    def step(x):
        return x.sum().item()
""", [JaxHygieneRule()])
        assert rules_of(found) == ["jit-host-sync"]

    def test_branch_on_traced_param_fires(self, tmp_path):
        found = lint(tmp_path, """
    import jax

    @jax.jit
    def step(x):
        if x > 0:
            return x
        return -x
""", [JaxHygieneRule()])
        assert rules_of(found) == ["jit-traced-branch"]

    def test_static_argnames_are_exempt(self, tmp_path):
        found = lint(tmp_path, """
    import functools
    import jax

    @functools.partial(jax.jit, static_argnames=("n",))
    def tile(x, n):
        if n > 1:                    # static at trace time
            return x * n
        return x
""", [JaxHygieneRule()])
        assert found == []

    def test_shape_and_none_tests_are_exempt(self, tmp_path):
        found = lint(tmp_path, """
    import jax

    @jax.jit
    def step(x, mask):
        if x.shape[0] > 2:
            x = x[:2]
        if mask is None:
            return x
        if len(x) > 4:
            return x * 2
        return x * mask
""", [JaxHygieneRule()])
        assert found == []

    def test_wrapped_local_function_is_scanned(self, tmp_path):
        found = lint(tmp_path, """
    import jax
    import numpy as np

    def build():
        def step(p, x):
            return p * np.asarray(x)
        return jax.jit(step, donate_argnums=(0,))
""", [JaxHygieneRule()])
        assert rules_of(found) == ["jit-host-sync"]

    def test_host_twin_of_jitted_name_not_scanned(self, tmp_path):
        # the FusedTrainer shape: a nested jitted `train_epoch` AND a
        # host-side method of the same name — scope resolution must
        # pin the jit to the nested def only
        found = lint(tmp_path, """
    import jax
    import numpy as np

    class T:
        def _build(self):
            def train_epoch(p, x):
                return p + x
            self._fn = jax.jit(train_epoch)

        def train_epoch(self, x):
            return np.asarray(self._fn(0, x))   # host code: fine
""", [JaxHygieneRule()])
        assert found == []

    def test_nested_def_shadows_traced_param(self, tmp_path):
        # a helper parameter reusing a traced param's name is a
        # concrete local, not the traced value
        found = lint(tmp_path, """
    import jax

    @jax.jit
    def f(x):
        def helper(x=3):
            if x > 0:
                return 1
            return 0
        return x * helper()
""", [JaxHygieneRule()])
        assert found == []

    def test_unjitted_function_is_ignored(self, tmp_path):
        found = lint(tmp_path, """
    def host(x):
        return x.sum().item()
""", [JaxHygieneRule()])
        assert found == []


class TestUnseededRandom:
    def test_global_numpy_rng_fires(self, tmp_path):
        found = lint(tmp_path, """
    import numpy as np

    def jitter():
        return np.random.uniform(0, 1)
""", [UnseededRandomRule()])
        assert rules_of(found) == ["unseeded-random"]

    def test_global_stdlib_rng_fires(self, tmp_path):
        found = lint(tmp_path, """
    import random

    def jitter():
        return random.random()
""", [UnseededRandomRule()])
        assert rules_of(found) == ["unseeded-random"]

    def test_seedless_generator_construction_fires(self, tmp_path):
        # default_rng()/Random() with no seed pulls OS entropy — just
        # as irreproducible as the global RNG
        found = lint(tmp_path, """
    import random
    import numpy as np

    def make():
        return np.random.default_rng(), random.Random()
""", [UnseededRandomRule()])
        assert len(found) == 2
        assert all(f.rule == "unseeded-random" for f in found)
        assert any("default_rng" in f.message for f in found)

    def test_seeded_generators_pass(self, tmp_path):
        found = lint(tmp_path, """
    import random
    import numpy as np

    def make(seed):
        gen = np.random.default_rng(seed)
        alt = np.random.Generator(np.random.PCG64(seed))
        py = random.Random(seed)
        return gen.uniform(), alt.normal(), py.random()
""", [UnseededRandomRule()])
        assert found == []


# -- handler safety --------------------------------------------------------

class TestHandlerSafety:
    def test_sleep_in_do_get_fires(self, tmp_path):
        found = lint(tmp_path, """
    import time

    class Handler:
        def do_GET(self):
            time.sleep(1.0)
            self.wfile.write(b"ok")
""", [HandlerSafetyRule()])
        assert rules_of(found) == ["handler-blocking"]
        assert "time.sleep" in found[0].message

    def test_blocking_helper_reachable_from_handler(self, tmp_path):
        found = lint(tmp_path, """
    import subprocess

    class Handler:
        def do_POST(self):
            self._work()

        def _work(self):
            subprocess.run(["convert", "img"])
""", [HandlerSafetyRule()])
        assert len(found) == 1 and "subprocess" in found[0].message

    def test_handler_file_io_fires(self, tmp_path):
        found = lint(tmp_path, """
    class Handler:
        def do_GET(self):
            with open("/var/log/x") as fh:
                self.wfile.write(fh.read().encode())
""", [HandlerSafetyRule()])
        assert len(found) == 1 and "file I/O" in found[0].message

    def test_capture_writer_shape_is_a_dispatch_path(self, tmp_path):
        """The online capture tap's writer-thread shape (ISSUE 15): a
        class pumping a queue from Thread(target=self._writer_loop) is
        a dispatch path — a sleep in its loop stalls every captured
        record behind it; the bounded Event.wait twin stays silent."""
        found = lint(tmp_path, """
    import threading
    import time

    class CaptureLog:
        def __init__(self):
            self._writer = threading.Thread(
                target=self._writer_loop)

        def _writer_loop(self):
            while True:
                time.sleep(0.2)          # unbounded pacing by sleep
                self._drain()

        def _drain(self):
            return []
""", [HandlerSafetyRule()])
        assert rules_of(found) == ["handler-blocking"]
        assert "dispatch-thread" in found[0].message
        assert lint(tmp_path, """
    import threading

    class CaptureLog:
        def __init__(self):
            self._wake = threading.Event()
            self._writer = threading.Thread(
                target=self._writer_loop)

        def _writer_loop(self):
            while True:
                self._wake.wait(0.2)     # bounded: interruptible
                self._drain()

        def _drain(self):
            return []
""", [HandlerSafetyRule()]) == []

    def test_unbounded_join_on_dispatch_thread(self, tmp_path):
        found = lint(tmp_path, """
    import threading

    class Pump:
        def __init__(self, worker):
            self.worker = worker
            self._thread = threading.Thread(target=self._loop)

        def _loop(self):
            self.worker.join()            # no timeout
""", [HandlerSafetyRule()])
        assert len(found) == 1 and ".join()" in found[0].message

    def test_bounded_waits_pass(self, tmp_path):
        found = lint(tmp_path, """
    import threading

    class Pump:
        def __init__(self):
            self._cond = threading.Condition()
            self._thread = threading.Thread(target=self._loop)

        def _loop(self):
            with self._cond:
                self._cond.wait(0.25)

        def do_GET(self):
            self.wfile.write(b"ok")

    class Pump2(Pump):
        def close(self):
            self._thread.join(timeout=5.0)
""", [HandlerSafetyRule()])
        assert found == []


# -- metric drift ----------------------------------------------------------

def _drift_repo(tmp_path, doc_names=("foo_total",),
                registered=("foo_total",), script_names=()):
    mod = tmp_path / "pkg" / "m.py"
    mod.parent.mkdir(parents=True, exist_ok=True)
    lines = ["from telemetry import REGISTRY", ""]
    for name in registered:
        lines.append(f'_c = REGISTRY.counter("{name}", "help")')
    mod.write_text("\n".join(lines) + "\n")
    doc = tmp_path / "docs" / "obs.md"
    doc.parent.mkdir(parents=True, exist_ok=True)
    rows = ["# metrics", "", "| metric | type |", "|---|---|"]
    rows += [f"| `{n}` | counter |" for n in doc_names]
    doc.write_text("\n".join(rows) + "\n")
    sh = tmp_path / "tools" / "smoke.sh"
    sh.parent.mkdir(parents=True, exist_ok=True)
    sh.write_text("\n".join(f'grep {n} /tmp/scrape'
                            for n in script_names) + "\n")
    rule = MetricDriftRule(doc_paths=("docs/obs.md",),
                           script_paths=("tools/smoke.sh",))
    return Analyzer([rule], root=str(tmp_path)).run(["pkg/m.py"])


class TestMetricDrift:
    def test_in_sync_is_silent(self, tmp_path):
        assert _drift_repo(tmp_path) == []

    def test_doc_reference_without_registration(self, tmp_path):
        found = _drift_repo(tmp_path,
                            doc_names=("foo_total", "gone_total"))
        assert len(found) == 1
        assert "gone_total" in found[0].message
        assert found[0].path == "docs/obs.md"

    def test_script_reference_without_registration(self, tmp_path):
        found = _drift_repo(tmp_path, script_names=("phantom_total",))
        assert len(found) == 1 and "phantom_total" in found[0].message
        assert found[0].path == "tools/smoke.sh"

    def test_histogram_suffixes_fold_to_base(self, tmp_path):
        found = _drift_repo(tmp_path,
                            doc_names=("lat_ms",),
                            registered=("lat_ms",),
                            script_names=("lat_ms_bucket",
                                          "lat_ms_count"))
        assert found == []

    def test_orphaned_registration(self, tmp_path):
        found = _drift_repo(tmp_path,
                            registered=("foo_total", "secret_total"))
        assert len(found) == 1
        assert "secret_total" in found[0].message
        assert found[0].path == "pkg/m.py"

    def test_collector_family_and_prefix(self, tmp_path):
        mod = tmp_path / "pkg" / "m.py"
        mod.parent.mkdir(parents=True, exist_ok=True)
        mod.write_text(textwrap.dedent("""
            def collect(self):
                fams = []
                for prefix, d in (("eng_", self.metrics()),):
                    for k, v in d.items():
                        fams.append(("gauge", prefix + k, "m", []))
                fams.append(("gauge", "pump_state", "s", []))
                return fams
        """))
        doc = tmp_path / "docs" / "obs.md"
        doc.parent.mkdir(parents=True, exist_ok=True)
        doc.write_text("`pump_state` is an enum; `eng_busy_ms` too\n")
        (tmp_path / "tools").mkdir(exist_ok=True)
        (tmp_path / "tools" / "smoke.sh").write_text("")
        rule = MetricDriftRule(doc_paths=("docs/obs.md",),
                               script_paths=("tools/smoke.sh",))
        assert Analyzer([rule],
                        root=str(tmp_path)).run(["pkg/m.py"]) == []

    def test_labeled_backtick_is_a_reference(self, tmp_path):
        # a backticked token WITH a label set is a metric reference
        # even when the bare name lacks a metric suffix — the zoo's
        # `model_resident{model=...}` idiom (ISSUE 11).  Registered →
        # silent AND counts as documentation; unregistered → drift.
        mod = tmp_path / "pkg" / "m.py"
        mod.parent.mkdir(parents=True, exist_ok=True)
        mod.write_text('from telemetry import REGISTRY\n'
                       '_g = REGISTRY.gauge("model_resident", "h")\n')
        doc = tmp_path / "docs" / "obs.md"
        doc.parent.mkdir(parents=True, exist_ok=True)
        (tmp_path / "tools").mkdir(exist_ok=True)
        (tmp_path / "tools" / "smoke.sh").write_text("")
        rule = MetricDriftRule(doc_paths=("docs/obs.md",),
                               script_paths=("tools/smoke.sh",))
        # registered + labeled-referenced: in sync, both directions
        doc.write_text('watch `model_resident{model="wine"}` flip\n')
        assert Analyzer([rule],
                        root=str(tmp_path)).run(["pkg/m.py"]) == []
        # the same labeled idiom naming a ghost family must fire —
        # before the label-set extension this drift was invisible
        doc.write_text('watch `model_resident{model="wine"}` and '
                       '`model_phantom{model="x"}`\n')
        found = Analyzer([rule],
                         root=str(tmp_path)).run(["pkg/m.py"])
        assert len(found) == 1 and "model_phantom" in found[0].message
        # a bare suffix-less token stays prose (no false positive)
        doc.write_text('`model_resident{model="w"}`; the resident '
                       'set and `some_config` are prose\n')
        assert Analyzer([rule],
                        root=str(tmp_path)).run(["pkg/m.py"]) == []

    def test_concat_built_prefix_registers(self, tmp_path):
        # dynamic family names built by string concatenation IN a
        # family tuple's name slot — ("gauge", "zoo_model_" + k, …) —
        # whitelist their prefix exactly like the ("prefix_", source)
        # fan-out tuple shape
        mod = tmp_path / "pkg" / "m.py"
        mod.parent.mkdir(parents=True, exist_ok=True)
        mod.write_text(textwrap.dedent("""
            def collect(self):
                fams = []
                for k, v in self.rows().items():
                    fams.append(("gauge", "zoo_model_" + k, "m", []))
                return fams
        """))
        doc = tmp_path / "docs" / "obs.md"
        doc.parent.mkdir(parents=True, exist_ok=True)
        doc.write_text("`zoo_model_generation{model=...}` per model\n")
        (tmp_path / "tools").mkdir(exist_ok=True)
        (tmp_path / "tools" / "smoke.sh").write_text("")
        rule = MetricDriftRule(doc_paths=("docs/obs.md",),
                               script_paths=("tools/smoke.sh",))
        assert Analyzer([rule],
                        root=str(tmp_path)).run(["pkg/m.py"]) == []

    def test_slo_labeled_families_in_sync(self, tmp_path):
        # the SLO engine's idiom (ISSUE 12): multi-label backticked
        # references — `slo_burn_rate{slo=,model=,window=}` — whose
        # bare names carry NO metric suffix (_rate / _remaining are
        # not in the suffix set).  Registered + label-referenced must
        # be silent in BOTH directions: the reference resolves, and
        # the labeled mention counts as documentation
        mod = tmp_path / "pkg" / "m.py"
        mod.parent.mkdir(parents=True, exist_ok=True)
        mod.write_text(
            'from telemetry import REGISTRY\n'
            '_b = REGISTRY.gauge("slo_burn_rate", "h")\n'
            '_r = REGISTRY.gauge("slo_budget_remaining", "h")\n'
            '_a = REGISTRY.counter("slo_alerts_total", "h")\n')
        doc = tmp_path / "docs" / "obs.md"
        doc.parent.mkdir(parents=True, exist_ok=True)
        doc.write_text(
            'watch `slo_burn_rate{slo="a",model="m",window="fast"}` '
            'against `slo_budget_remaining{slo="a",model="m"}`; '
            'firings count into '
            '`slo_alerts_total{slo="a",model="m",severity="page"}`\n')
        (tmp_path / "tools").mkdir(exist_ok=True)
        (tmp_path / "tools" / "smoke.sh").write_text("")
        rule = MetricDriftRule(doc_paths=("docs/obs.md",),
                               script_paths=("tools/smoke.sh",))
        assert Analyzer([rule],
                        root=str(tmp_path)).run(["pkg/m.py"]) == []

    def test_slo_labeled_ghost_family_fires(self, tmp_path):
        # the same labeled idiom naming a family nobody registers must
        # fire — a renamed slo_* gauge would otherwise leave the doc
        # asserting a series that no longer exists
        mod = tmp_path / "pkg" / "m.py"
        mod.parent.mkdir(parents=True, exist_ok=True)
        mod.write_text('from telemetry import REGISTRY\n'
                       '_b = REGISTRY.gauge("slo_burn_rate", "h")\n')
        doc = tmp_path / "docs" / "obs.md"
        doc.parent.mkdir(parents=True, exist_ok=True)
        doc.write_text(
            '`slo_burn_rate{slo="a",model="m",window="slow"}` is '
            'real; `slo_burn_velocity{slo="a",model="m"}` is not\n')
        (tmp_path / "tools").mkdir(exist_ok=True)
        (tmp_path / "tools" / "smoke.sh").write_text("")
        rule = MetricDriftRule(doc_paths=("docs/obs.md",),
                               script_paths=("tools/smoke.sh",))
        found = Analyzer([rule],
                         root=str(tmp_path)).run(["pkg/m.py"])
        assert len(found) == 1
        assert "slo_burn_velocity" in found[0].message

    def test_bare_concat_does_not_whitelist_namespace(self, tmp_path):
        # the guard on the extension: a prefix-shaped concat OUTSIDE
        # a family tuple (a filename, a log tag) must not whitelist
        # the namespace and mask real drift
        mod = tmp_path / "pkg" / "m.py"
        mod.parent.mkdir(parents=True, exist_ok=True)
        mod.write_text(textwrap.dedent("""
            def save(self, name):
                return open("model_" + name + ".znn", "wb")
        """))
        doc = tmp_path / "docs" / "obs.md"
        doc.parent.mkdir(parents=True, exist_ok=True)
        doc.write_text('`model_ghost{model="x"}` is watched\n')
        (tmp_path / "tools").mkdir(exist_ok=True)
        (tmp_path / "tools" / "smoke.sh").write_text("")
        rule = MetricDriftRule(doc_paths=("docs/obs.md",),
                               script_paths=("tools/smoke.sh",))
        found = Analyzer([rule],
                         root=str(tmp_path)).run(["pkg/m.py"])
        assert len(found) == 1 and "model_ghost" in found[0].message


# -- duration clock --------------------------------------------------------

CLOCK_BAD_DIRECT = """
    import time

    def wait_for(pred, deadline_s):
        deadline = time.time() + deadline_s          # wall deadline
        while time.time() < deadline:                # wall compare
            if pred():
                return True
        return False
"""

CLOCK_BAD_DATAFLOW = """
    import time

    def measure(fn):
        t0 = time.time()
        fn()
        return time.time() - t0
"""

CLOCK_GOOD = """
    import time

    def measure(fn):
        t0 = time.monotonic()
        fn()
        dt = time.monotonic() - t0
        return {"at": time.time(), "duration_s": dt}   # stamp only

    def record(recs):
        # a wall stamp stored, never entered into arithmetic
        started = time.time()
        recs.append(started)
"""


class TestDurationClock:
    def test_wall_deadline_fires(self, tmp_path):
        found = lint(tmp_path, CLOCK_BAD_DIRECT, [DurationClockRule()])
        assert rules_of(found) == ["duration-clock"]
        assert len(found) == 2          # the + line and the < line

    def test_stamp_subtraction_fires(self, tmp_path):
        found = lint(tmp_path, CLOCK_BAD_DATAFLOW, [DurationClockRule()])
        assert rules_of(found) == ["duration-clock"]
        # the `time.time() - t0` line fires once (direct arithmetic and
        # the t0 dataflow collapse to one finding per line)
        assert len(found) == 1

    def test_monotonic_and_bare_stamps_pass(self, tmp_path):
        assert lint(tmp_path, CLOCK_GOOD, [DurationClockRule()]) == []

    def test_from_import_is_resolved(self, tmp_path):
        found = lint(tmp_path, """
    from time import time as now

    def age_of(then):
        return now() - then
""", [DurationClockRule()])
        assert rules_of(found) == ["duration-clock"]

    def test_module_alias_is_resolved(self, tmp_path):
        found = lint(tmp_path, """
    import time as t

    def wait(pred):
        deadline = t.time() + 30
        while t.time() < deadline:
            if pred():
                return True
        return False
""", [DurationClockRule()])
        assert rules_of(found) == ["duration-clock"]
        assert len(found) == 2

    def test_nested_scope_stamp_does_not_leak(self, tmp_path):
        found = lint(tmp_path, """
    import time

    def outer():
        def stamp():
            t0 = time.time()
            return t0
        t0 = 17                  # outer t0 is NOT a wall stamp
        return stamp() - t0
""", [DurationClockRule()])
        assert found == []

    def test_inline_suppression(self, tmp_path):
        src = CLOCK_BAD_DATAFLOW.replace(
            "return time.time() - t0",
            "return time.time() - t0  # zlint: disable=duration-clock")
        assert lint(tmp_path, src, [DurationClockRule()]) == []

    def test_span_gap_on_wall_clock_fires(self, tmp_path):
        # the trace assembler's exact shape (ISSUE 18): per-stage
        # gaps between measured durations — wall-clock stamps entering
        # that arithmetic is precisely the cross-process clock bug
        # the stage split is designed to avoid
        found = lint(tmp_path, """
    import time

    def assemble_stages(pick_ms, forward_ms):
        t0 = time.time()
        total_ms = (time.time() - t0) * 1e3
        recv = max(0.0, total_ms - pick_ms - forward_ms)
        return {"router.recv": recv}
""", [DurationClockRule()])
        assert rules_of(found) == ["duration-clock"]

    def test_span_gap_on_monotonic_with_wall_stamp_passes(self,
                                                          tmp_path):
        # the assembler's real discipline: every DURATION from the
        # monotonic clock, the wall clock only as the trace's `at`
        # stamp, never in the gap arithmetic
        assert lint(tmp_path, """
    import time

    def assemble_stages(pick_ms, forward_ms):
        t0 = time.monotonic()
        total_ms = (time.monotonic() - t0) * 1e3
        recv = max(0.0, total_ms - pick_ms - forward_ms)
        return {"router.recv": recv, "at": time.time()}
""", [DurationClockRule()]) == []


# -- span-name drift -------------------------------------------------------

def _span_repo(tmp_path, code_names, doc_lines):
    mod = tmp_path / "pkg" / "m.py"
    mod.parent.mkdir(parents=True, exist_ok=True)
    lines = ["from telemetry import tracing", ""]
    for name in code_names:
        lines.append(f'_ = tracing.span("{name}")')
    mod.write_text("\n".join(lines) + "\n")
    doc = tmp_path / "docs" / "obs.md"
    doc.parent.mkdir(parents=True, exist_ok=True)
    doc.write_text("\n".join(doc_lines) + "\n")
    rule = SpanNameDriftRule(doc_paths=("docs/obs.md",))
    return Analyzer([rule], root=str(tmp_path)).run(["pkg/m.py"])


class TestSpanNameDrift:
    def test_in_sync_is_silent(self, tmp_path):
        assert _span_repo(
            tmp_path, ("engine.forward", "batcher.wait"),
            ["the `engine.forward` stage follows `batcher.wait`"]) == []

    def test_ghost_stage_fires(self, tmp_path):
        found = _span_repo(
            tmp_path, ("engine.forward",),
            ["| `engine.fwd` | the device stage |"])
        assert rules_of(found) == ["span-name-drift"]
        assert len(found) == 1
        assert "engine.fwd" in found[0].message
        assert found[0].path == "docs/obs.md"

    def test_stages_tuple_registers(self, tmp_path):
        # the tracestore STAGES tuple is a registration site even
        # with no span() call naming its entries
        mod = tmp_path / "pkg" / "m.py"
        mod.parent.mkdir(parents=True, exist_ok=True)
        mod.write_text('STAGES = ("router.recv", "net.hop")\n')
        doc = tmp_path / "docs" / "obs.md"
        doc.parent.mkdir(parents=True, exist_ok=True)
        doc.write_text("`router.recv` then `net.hop`\n")
        rule = SpanNameDriftRule(doc_paths=("docs/obs.md",))
        assert Analyzer([rule],
                        root=str(tmp_path)).run(["pkg/m.py"]) == []

    def test_prose_dotted_tokens_stay_out(self, tmp_path):
        # `np.asarray`, `lax.scan`, module paths: dotted but not
        # rooted in a stage namespace — never cross-checked
        found = _span_repo(
            tmp_path, ("engine.forward",),
            ["call `np.asarray` inside `lax.scan` via "
             "`znicz_tpu.telemetry.tracing`"])
        assert found == []

    def test_labeled_stage_reference(self, tmp_path):
        # `trace_stage_ms{stage=...}`-style prose often backticks the
        # stage with a label set attached — still a reference
        found = _span_repo(
            tmp_path, ("engine.forward",),
            ['slowest is `net.hop{stage="net.hop"}` today'])
        assert rules_of(found) == ["span-name-drift"]


# -- deadline discipline ---------------------------------------------------

DEADLINE_BAD = """
    import queue
    import threading
    import urllib.request

    def dispatch_loop(q, done, worker):
        item = q.get()                       # parks forever
        done.wait()                          # unbounded Event.wait
        worker.join()                        # unbounded join
        urllib.request.urlopen("http://x/")  # no timeout
        return item
"""

DEADLINE_GOOD = """
    import queue
    import urllib.request

    def dispatch_loop(q, done, worker, cfg):
        item = q.get(timeout=1.0)
        blocking = q.get(True, 0.5)          # positional timeout ok
        done.wait(0.25)
        worker.join(timeout=5.0)
        urllib.request.urlopen("http://x/", timeout=2.0)
        name = cfg.get("name")               # dict.get: has a key arg
        return item, blocking, name
"""


class TestDeadlineDiscipline:
    SERVING = "znicz_tpu/serving/mod.py"

    def test_unbounded_waits_fire_on_serving_paths(self, tmp_path):
        found = lint(tmp_path, DEADLINE_BAD, [DeadlineDisciplineRule()],
                     rel=self.SERVING)
        assert rules_of(found) == ["deadline-discipline"]
        assert len(found) == 4          # get / wait / join / urlopen

    def test_bounded_twins_stay_silent(self, tmp_path):
        assert lint(tmp_path, DEADLINE_GOOD, [DeadlineDisciplineRule()],
                    rel=self.SERVING) == []

    def test_out_of_scope_modules_not_patrolled(self, tmp_path):
        # the rule guards the REQUEST path; a training-side module
        # with a deliberate unbounded wait is not its business
        assert lint(tmp_path, DEADLINE_BAD, [DeadlineDisciplineRule()],
                    rel="znicz_tpu/ops/mod.py") == []

    def test_resilience_modules_in_scope(self, tmp_path):
        found = lint(tmp_path, DEADLINE_BAD, [DeadlineDisciplineRule()],
                     rel="znicz_tpu/resilience/mod.py")
        assert rules_of(found) == ["deadline-discipline"]

    def test_fleet_modules_in_scope(self, tmp_path):
        # the router tier's forward/probe hops are request path too —
        # an unbounded wait there wedges every backend behind it
        found = lint(tmp_path, DEADLINE_BAD, [DeadlineDisciplineRule()],
                     rel="znicz_tpu/fleet/mod.py")
        assert rules_of(found) == ["deadline-discipline"]
        assert len(found) == 4

    def test_online_modules_in_scope(self, tmp_path):
        # the live-data loop patrols too: the capture tap runs ON the
        # request path, and the replay tailer/trainer promise bounded
        # waits (ISSUE 15) — an unbounded wait there is the same bug
        found = lint(tmp_path, DEADLINE_BAD, [DeadlineDisciplineRule()],
                     rel="znicz_tpu/online/mod.py")
        assert rules_of(found) == ["deadline-discipline"]
        assert len(found) == 4

    # ISSUE 16: the autoscaler's spawn/retire path waits on real
    # subprocesses and polls real /healthz endpoints — exactly this
    # rule's target shape.  Pin that the new fleet modules are
    # patrolled with the shapes they actually use.

    AUTOSCALER_BAD = """
    import urllib.request

    def retire(proc, drained):
        drained.wait()                       # lost notify -> wedge
        proc.wait()                          # unbounded subprocess wait
        urllib.request.urlopen("http://b/healthz")   # prober, no bound
"""

    AUTOSCALER_GOOD = """
    import urllib.request

    def retire(proc, drained, deadline_s):
        drained.wait(deadline_s)
        try:
            proc.wait(timeout=deadline_s)    # bounded reap
        except Exception:
            proc.kill()
            proc.wait(timeout=5.0)
        with urllib.request.urlopen("http://b/healthz",
                                    timeout=2.0) as r:
            return r.read()
"""

    def test_autoscaler_subprocess_waits_patrolled(self, tmp_path):
        found = lint(tmp_path, self.AUTOSCALER_BAD,
                     [DeadlineDisciplineRule()],
                     rel="znicz_tpu/fleet/autoscaler.py")
        assert rules_of(found) == ["deadline-discipline"]
        assert len(found) == 3          # wait / proc.wait / urlopen

    def test_autoscaler_bounded_shapes_stay_silent(self, tmp_path):
        assert lint(tmp_path, self.AUTOSCALER_GOOD,
                    [DeadlineDisciplineRule()],
                    rel="znicz_tpu/fleet/autoscaler.py") == []

    def test_placement_module_patrolled(self, tmp_path):
        found = lint(tmp_path, DEADLINE_BAD,
                     [DeadlineDisciplineRule()],
                     rel="znicz_tpu/fleet/placement.py")
        assert rules_of(found) == ["deadline-discipline"]
        assert len(found) == 4

    # ISSUE 17: restart reconciliation waits on journaled orphan
    # processes and re-probes their /healthz — a single unbounded
    # wait there stretches the router's advertised Retry-After into
    # a lie.  Pin the statestore/reconcile shapes both ways.

    STATESTORE_BAD = """
    import urllib.request

    def reconcile(handle, settled):
        settled.wait()                       # unbounded settle wait
        handle.wait()                        # orphan reap, no bound
        urllib.request.urlopen("http://b/healthz")   # probe, no bound
"""

    STATESTORE_GOOD = """
    import subprocess
    import time
    import urllib.request

    def reconcile(handle, deadline_s, probe_timeout_s):
        deadline = time.monotonic() + deadline_s
        while time.monotonic() < deadline:   # the reconcile slice
            try:
                with urllib.request.urlopen(
                        "http://b/healthz",
                        timeout=probe_timeout_s) as r:
                    return r.read()
            except OSError:
                pass
            if handle.poll() is not None:
                break
            time.sleep(0.2)
        try:
            return handle.wait(timeout=deadline_s)   # bounded reap
        except subprocess.TimeoutExpired:
            handle.kill()
            return handle.wait(timeout=5.0)
"""

    def test_statestore_reconcile_waits_patrolled(self, tmp_path):
        found = lint(tmp_path, self.STATESTORE_BAD,
                     [DeadlineDisciplineRule()],
                     rel="znicz_tpu/fleet/statestore.py")
        assert rules_of(found) == ["deadline-discipline"]
        assert len(found) == 3          # wait / handle.wait / urlopen

    def test_statestore_bounded_reconcile_stays_silent(self, tmp_path):
        assert lint(tmp_path, self.STATESTORE_GOOD,
                    [DeadlineDisciplineRule()],
                    rel="znicz_tpu/fleet/statestore.py") == []

    def test_blocking_get_block_true_without_timeout(self, tmp_path):
        found = lint(tmp_path, """
    def loop(q):
        return q.get(block=True)
""", [DeadlineDisciplineRule()], rel=self.SERVING)
        assert len(found) == 1

    def test_contextvar_get_exempt(self, tmp_path):
        assert lint(tmp_path, """
    import contextvars
    _deadline_var = contextvars.ContextVar("d", default=None)

    def current():
        return _deadline_var.get()           # never blocks
""", [DeadlineDisciplineRule()], rel=self.SERVING) == []

    def test_inline_suppression(self, tmp_path):
        src = DEADLINE_BAD.replace(
            "item = q.get()                       # parks forever",
            "item = q.get()  # zlint: disable=deadline-discipline")
        found = lint(tmp_path, src, [DeadlineDisciplineRule()],
                     rel=self.SERVING)
        assert len(found) == 3          # the .get() finding is muted


# -- lock-order cycles (zsan static layer) ---------------------------------

ORDER_BAD = """
    import threading

    class Box:
        def __init__(self):
            self._lock = threading.Lock()
            self._cond = threading.Condition()

        def one(self):
            with self._lock:
                with self._cond:
                    pass

        def two(self):
            with self._cond:
                with self._lock:
                    pass
"""

ORDER_GOOD = """
    import threading

    class Box:
        def __init__(self):
            self._lock = threading.Lock()
            self._cond = threading.Condition()

        def one(self):
            with self._lock:
                with self._cond:
                    pass

        def two(self):
            with self._lock:        # same order everywhere
                with self._cond:
                    pass
"""

# the intra-class fixpoint: `two` acquires via a helper called under
# the other lock — the cycle is interprocedural
ORDER_HELPER_BAD = """
    import threading

    class Box:
        def __init__(self):
            self._a_lock = threading.Lock()
            self._b_lock = threading.Lock()

        def one(self):
            with self._a_lock:
                self._grab_b()

        def _grab_b(self):
            with self._b_lock:
                pass

        def two(self):
            with self._b_lock:
                with self._a_lock:
                    pass
"""

# the zoo->engine->zoo shape: each class's own order is consistent,
# the cycle only exists across the two objects
ORDER_CROSS_BAD = """
    import threading

    class DemoZoo:
        def __init__(self):
            self._lock = threading.Lock()
            self.engine = DemoEngine()

        def touch_resident(self):
            with self._lock:
                self.engine.swap_weights()

        def note_pages(self):
            with self._lock:
                pass

    class DemoEngine:
        def __init__(self):
            self._lock = threading.Lock()
            self.zoo = None

        def swap_weights(self):
            with self._lock:
                pass

        def observer_fire(self):
            with self._lock:
                self.zoo.note_pages()
"""

# same shape, engine calls back OUTSIDE its lock (the repo's actual
# discipline: "fire the observer lock-free") — no cycle
ORDER_CROSS_GOOD = """
    import threading

    class DemoZoo:
        def __init__(self):
            self._lock = threading.Lock()
            self.engine = DemoEngine()

        def touch_resident(self):
            with self._lock:
                self.engine.swap_weights()

        def note_pages(self):
            with self._lock:
                pass

    class DemoEngine:
        def __init__(self):
            self._lock = threading.Lock()
            self.zoo = None

        def swap_weights(self):
            with self._lock:
                pass

        def observer_fire(self):
            with self._lock:
                pass
            self.zoo.note_pages()       # outside the engine lock
"""


class TestLockOrderCycle:
    def test_direct_nesting_cycle_fires(self, tmp_path):
        fs = lint(tmp_path, ORDER_BAD, [LockOrderCycleRule()])
        assert rules_of(fs) == ["lock-order-cycle"]
        assert len(fs) == 1             # one finding per cycle
        assert "_lock" in fs[0].message and "_cond" in fs[0].message
        # provenance: both edges with path:line
        assert fs[0].message.count("pkg/mod.py:") == 2

    def test_consistent_order_is_clean(self, tmp_path):
        assert lint(tmp_path, ORDER_GOOD, [LockOrderCycleRule()]) == []

    def test_interprocedural_cycle_via_helper_fires(self, tmp_path):
        fs = lint(tmp_path, ORDER_HELPER_BAD, [LockOrderCycleRule()])
        assert rules_of(fs) == ["lock-order-cycle"]

    def test_cross_object_cycle_fires(self, tmp_path):
        fs = lint(tmp_path, ORDER_CROSS_BAD, [LockOrderCycleRule()])
        assert rules_of(fs) == ["lock-order-cycle"]
        assert "DemoZoo._lock" in fs[0].message
        assert "DemoEngine._lock" in fs[0].message

    def test_cross_object_lock_free_callback_is_clean(self, tmp_path):
        assert lint(tmp_path, ORDER_CROSS_GOOD,
                    [LockOrderCycleRule()]) == []

    def test_reentrant_reacquire_not_a_cycle(self, tmp_path):
        src = """
            import threading

            class Box:
                def __init__(self):
                    self._lock = threading.RLock()

                def outer(self):
                    with self._lock:
                        self.inner()

                def inner(self):
                    with self._lock:        # reentrant
                        pass
        """
        assert lint(tmp_path, src, [LockOrderCycleRule()]) == []


# -- lock leaks ------------------------------------------------------------

LEAK_BAD = """
    import threading

    class Box:
        def __init__(self):
            self._lock = threading.Lock()

        def work(self):
            self._lock.acquire()
            do_something()              # raises -> lock leaked
            self._lock.release()
"""

LEAK_GOOD = """
    import threading

    class Box:
        def __init__(self):
            self._lock = threading.Lock()

        def work(self):
            self._lock.acquire()
            try:
                do_something()
            finally:
                self._lock.release()

        def probe(self):
            # the engine-reload idiom: checked non-blocking probe
            if not self._lock.acquire(blocking=False):
                raise RuntimeError("busy")
            try:
                do_something()
            finally:
                self._lock.release()

        def inside_try(self):
            try:
                self._lock.acquire()
                do_something()
            finally:
                self._lock.release()
"""


class TestLockLeak:
    def test_unprotected_acquire_fires(self, tmp_path):
        fs = lint(tmp_path, LEAK_BAD, [LockLeakRule()])
        assert rules_of(fs) == ["lock-leak"]
        assert "self._lock" in fs[0].message

    def test_try_finally_and_probe_idioms_are_clean(self, tmp_path):
        assert lint(tmp_path, LEAK_GOOD, [LockLeakRule()]) == []

    def test_acquire_then_try_inside_if_is_clean(self, tmp_path):
        src = """
            import threading
            io_lock = threading.Lock()

            def work(flag):
                if flag:
                    io_lock.acquire()
                    try:
                        pass
                    finally:
                        io_lock.release()
        """
        assert lint(tmp_path, src, [LockLeakRule()]) == []

    def test_unchecked_probe_fires(self, tmp_path):
        src = """
            import threading
            io_lock = threading.Lock()

            def work():
                io_lock.acquire(blocking=False)     # result dropped
                io_lock.release()
        """
        fs = lint(tmp_path, src, [LockLeakRule()])
        assert rules_of(fs) == ["lock-leak"]


# -- condition-wait predicates ---------------------------------------------

WAIT_BAD = """
    import threading

    class Box:
        def __init__(self):
            self._cond = threading.Condition()
            self.ready = False

        def take(self):
            with self._cond:
                if not self.ready:
                    self._cond.wait(1.0)    # spurious wakeup -> torn
                return self.ready
"""

WAIT_GOOD = """
    import threading

    class Box:
        def __init__(self):
            self._cond = threading.Condition()
            self.ready = False

        def take(self):
            with self._cond:
                while not self.ready:
                    self._cond.wait(1.0)
                return self.ready

        def take_pred(self):
            with self._cond:
                self._cond.wait_for(lambda: self.ready, 1.0)
                return self.ready
"""


class TestConditionWaitPredicate:
    def test_if_guarded_wait_fires(self, tmp_path):
        fs = lint(tmp_path, WAIT_BAD, [ConditionWaitPredicateRule()])
        assert rules_of(fs) == ["condition-wait-predicate"]
        assert "_cond" in fs[0].message

    def test_while_loop_and_wait_for_are_clean(self, tmp_path):
        assert lint(tmp_path, WAIT_GOOD,
                    [ConditionWaitPredicateRule()]) == []

    def test_event_wait_not_flagged(self, tmp_path):
        # Event.wait has no predicate contract; a non-cond-ish
        # receiver must not fire
        src = """
            import threading

            class Box:
                def __init__(self):
                    self._stop = threading.Event()

                def run(self):
                    self._stop.wait(1.0)
        """
        assert lint(tmp_path, src,
                    [ConditionWaitPredicateRule()]) == []


# -- retry-after discipline ------------------------------------------------

RETRY_BAD = """
    class Handler:
        def _predict(self):
            try:
                work()
            except QueueFull as e:
                self._reply(429, {"error": str(e)})
            except Exception as e:
                self._reply(503, {"error": str(e)})
"""

RETRY_GOOD = """
    class Handler:
        def _predict(self):
            try:
                work()
            except QueueFull as e:
                self._reply(429, {"error": str(e)},
                            {"Retry-After": str(e.retry_after)})
            except Exception as e:
                ra = 1
                self._reply(503, {"error": str(e)},
                            {"Retry-After": str(ra)})

        def _passthrough(self, status, data, out):
            # variable status: the upstream tier enforced the literal
            out["Retry-After"] = "1"
            self._send(status, data, "application/json", out)

        def _built_headers(self):
            h = {}
            h["Retry-After"] = "2"
            self._reply(503, {"error": "x"}, h)
"""

RETRY_REL = "znicz_tpu/serving/mod.py"


class TestRetryAfter:
    def test_refusal_without_header_fires(self, tmp_path):
        fs = lint(tmp_path, RETRY_BAD, [RetryAfterRule()],
                  rel=RETRY_REL)
        assert rules_of(fs) == ["retry-after-discipline"]
        assert len(fs) == 2             # the 429 and the 503

    def test_header_shapes_are_clean(self, tmp_path):
        assert lint(tmp_path, RETRY_GOOD, [RetryAfterRule()],
                    rel=RETRY_REL) == []

    def test_out_of_scope_paths_ignored(self, tmp_path):
        # the rule pins the serving/ + fleet/ contract only
        assert lint(tmp_path, RETRY_BAD, [RetryAfterRule()],
                    rel="znicz_tpu/telemetry/mod.py") == []

    def test_send_error_for_refusal_codes_fires(self, tmp_path):
        src = """
            class Handler:
                def do_GET(self):
                    self.send_error(503, "nope")
        """
        fs = lint(tmp_path, src, [RetryAfterRule()], rel=RETRY_REL)
        assert rules_of(fs) == ["retry-after-discipline"]

    def test_send_response_with_send_header_is_clean(self, tmp_path):
        src = """
            class Handler:
                def do_GET(self):
                    self.send_response(429)
                    self.send_header("Retry-After", "1")
                    self.end_headers()
        """
        assert lint(tmp_path, src, [RetryAfterRule()],
                    rel=RETRY_REL) == []


# -- suppression + baseline ------------------------------------------------

class TestSuppression:
    def test_inline_disable(self, tmp_path):
        src = LOCKED_BAD.replace(
            "# unguarded read", "# zlint: disable=lock-discipline")
        assert lint(tmp_path, src, [LockDisciplineRule()]) == []

    def test_inline_disable_all(self, tmp_path):
        src = LOCKED_BAD.replace(
            "# unguarded read", "# zlint: disable=all")
        assert lint(tmp_path, src, [LockDisciplineRule()]) == []

    def test_wrong_rule_name_still_fires(self, tmp_path):
        src = LOCKED_BAD.replace(
            "# unguarded read", "# zlint: disable=metric-drift")
        assert len(lint(tmp_path, src, [LockDisciplineRule()])) == 1

    def test_standalone_comment_covers_next_line(self, tmp_path):
        src = LOCKED_BAD.replace(
            "            return self._items[-1]        # unguarded read",
            "            # zlint: disable=lock-discipline\n"
            "            return self._items[-1]")
        assert lint(tmp_path, src, [LockDisciplineRule()]) == []

    def test_def_line_disable_covers_body(self, tmp_path):
        src = LOCKED_BAD.replace(
            "def peek(self):",
            "def peek(self):  # zlint: disable=lock-discipline")
        assert lint(tmp_path, src, [LockDisciplineRule()]) == []

    def test_baseline_round_trip(self, tmp_path):
        """add → suppressed → removed-from-baseline → flagged again."""
        rel = "pkg/mod.py"
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(LOCKED_BAD))
        bl = tmp_path / "zlint_baseline.json"

        an = Analyzer([LockDisciplineRule()], root=str(tmp_path),
                      baseline_path=str(bl))
        found = an.run([rel])
        assert len(found) == 1 and an.new_findings(found) == found

        write_baseline(str(bl), found)       # add
        assert len(load_baseline(str(bl))) == 1
        an2 = Analyzer([LockDisciplineRule()], root=str(tmp_path),
                       baseline_path=str(bl))
        found2 = an2.run([rel])
        assert len(found2) == 1              # still reported raw...
        assert an2.new_findings(found2) == []   # ...but suppressed

        write_baseline(str(bl), [])          # removed from baseline
        an3 = Analyzer([LockDisciplineRule()], root=str(tmp_path),
                       baseline_path=str(bl))
        found3 = an3.run([rel])
        assert an3.new_findings(found3) == found3 and len(found3) == 1

    def test_write_baseline_preserves_handwritten_notes(self, tmp_path):
        """Regenerating must carry forward curated notes for entries
        that survive, not clobber them back to TODO."""
        rel = "pkg/mod.py"
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(LOCKED_BAD))
        bl = tmp_path / "bl.json"
        an = Analyzer([LockDisciplineRule()], root=str(tmp_path))
        found = an.run([rel])
        write_baseline(str(bl), found)
        data = json.loads(bl.read_text())
        data["entries"][0]["note"] = "deliberate: snapshot read"
        bl.write_text(json.dumps(data))
        write_baseline(str(bl), found)       # regenerate
        data2 = json.loads(bl.read_text())
        assert data2["entries"][0]["note"] == "deliberate: snapshot read"

    def test_baseline_invalidated_by_code_change(self, tmp_path):
        """Baseline entries match on the source line text: editing the
        flagged line re-arms the finding (no stale suppressions)."""
        rel = "pkg/mod.py"
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(LOCKED_BAD))
        bl = tmp_path / "bl.json"
        an = Analyzer([LockDisciplineRule()], root=str(tmp_path),
                      baseline_path=str(bl))
        write_baseline(str(bl), an.run([rel]))
        path.write_text(textwrap.dedent(LOCKED_BAD.replace(
            "self._items[-1]", "self._items[0]")))
        an2 = Analyzer([LockDisciplineRule()], root=str(tmp_path),
                       baseline_path=str(bl))
        assert len(an2.new_findings(an2.run([rel]))) == 1

    def test_parse_error_is_a_finding(self, tmp_path):
        found = lint(tmp_path, "def broken(:\n", [LockDisciplineRule()])
        assert rules_of(found) == ["parse-error"]

    def test_rerun_does_not_duplicate_parse_errors(self, tmp_path):
        rel = "pkg/mod.py"
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text("def broken(:\n")
        an = Analyzer([LockDisciplineRule()], root=str(tmp_path))
        assert len(an.run([rel])) == 1
        assert len(an.run([rel])) == 1      # reused Analyzer: still 1


@pytest.mark.lint
def test_path_subset_run_has_no_spurious_drift():
    """Linting ONE file must not turn every out-of-subset metric
    registration into an 'unregistered reference' — repo rules run
    over the full walk regardless of the per-module path subset."""
    findings, new, _ = run_repo(paths=["znicz_tpu/analysis/core.py"])
    drift = [f for f in new if f.rule == "metric-drift"]
    assert drift == [], "\n".join(f.render() for f in drift)


# -- CLI -------------------------------------------------------------------

class TestCli:
    def test_json_format_and_exit_codes(self, tmp_path, capsys):
        rel = "pkg/mod.py"
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(LOCKED_BAD))
        rc = zlint_cli.main([rel, "--root", str(tmp_path),
                             "--format", "json", "--no-baseline"])
        out = json.loads(capsys.readouterr().out)
        assert rc == 1 and not out["ok"]
        assert out["findings"][0]["rule"] == "lock-discipline"

        path.write_text(textwrap.dedent(LOCKED_GOOD))
        rc = zlint_cli.main([rel, "--root", str(tmp_path),
                             "--format", "json", "--no-baseline"])
        out = json.loads(capsys.readouterr().out)
        assert rc == 0 and out["ok"] and out["findings"] == []

    def test_write_baseline_refuses_path_subset(self, tmp_path):
        # a subset's findings would silently drop every entry for
        # unanalyzed files
        with pytest.raises(SystemExit) as exc:
            zlint_cli.main(["pkg/mod.py", "--root", str(tmp_path),
                            "--write-baseline"])
        assert exc.value.code == 2

    def test_list_rules_covers_every_default_rule(self, capsys):
        rc = zlint_cli.main(["--list-rules"])
        out = capsys.readouterr().out
        assert rc == 0
        for rule in zlint_cli.default_rules():
            assert rule.id in out, f"--list-rules missing {rule.id}"
        for rid in ("lock-order-cycle", "lock-leak",
                    "condition-wait-predicate",
                    "retry-after-discipline"):
            assert rid in out

    def test_changed_mode_scopes_to_git_diff(self, tmp_path):
        """--changed lints only walked files git reports as touched;
        a dirty file with a finding fails, a clean tree exits 0."""
        def git(*args):
            subprocess.run(["git", *args], cwd=tmp_path, check=True,
                           capture_output=True)

        pkg = tmp_path / "znicz_tpu"
        pkg.mkdir()
        (pkg / "clean.py").write_text("x = 1\n")
        (pkg / "dirty.py").write_text("x = 1\n")
        git("init", "-q")
        git("config", "user.email", "t@t")
        git("config", "user.name", "t")
        git("add", "-A")
        git("commit", "-qm", "seed")
        # clean tree: nothing to check
        rc = zlint_cli.main(["--changed", "--root", str(tmp_path),
                             "--no-baseline"])
        assert rc == 0
        # dirty a file with a finding; --changed must catch it
        (pkg / "dirty.py").write_text(textwrap.dedent(LOCKED_BAD))
        assert zlint_cli.changed_paths(str(tmp_path)) \
            == ["znicz_tpu/dirty.py"]
        rc = zlint_cli.main(["--changed", "--root", str(tmp_path),
                             "--no-baseline"])
        assert rc == 1
        # paths and --changed are mutually exclusive
        with pytest.raises(SystemExit) as exc:
            zlint_cli.main(["znicz_tpu/dirty.py", "--changed",
                            "--root", str(tmp_path)])
        assert exc.value.code == 2


# -- env-routing -------------------------------------------------------------

ENV_ROUTING_BAD = """
    import os
    from os import environ, getenv

    def pool_kernel():
        if os.environ.get("ZNICZ_TPU_POOL", "taps") == "window":
            return "window"
        if "ZNICZ_TPU_TIER" in os.environ or getenv("ZNICZ_TPU_ROUTE"):
            return environ["ZNICZ_TPU_TIER"]
        return dict(os.environ)
"""

ENV_ROUTING_ALLOWED = """
    import os

    _INTERPRET = os.environ.get("ZNICZ_TPU_PALLAS_INTERPRET", "0") == "1"

    def use_pallas():
        if os.getenv("ZNICZ_TPU_NO_PALLAS", "0") == "1":
            return False
        return os.environ["ZNICZ_TPU_PALLAS_INTERPRET"] != "1" \
            or "ZNICZ_TPU_NO_PALLAS" in os.environ
"""


class TestEnvRouting:
    def test_environment_read_under_ops_fires(self, tmp_path):
        found = lint(tmp_path, ENV_ROUTING_BAD, [EnvRoutingRule()],
                     rel="znicz_tpu/ops/mod.py")
        assert rules_of(found) == ["env-routing"]
        # get / in / getenv / subscript / the whole mapping
        assert len(found) == 5
        assert "'ZNICZ_TPU_POOL'" in found[0].message

    def test_two_switches_stay(self):
        """The Pallas interpreter and the XLA-tier fallback; the unit-graph
        matmul's precision follows the platform (``matmul._mxu_cast``)."""
        from znicz_tpu.analysis.envrouting import ALLOWED
        assert ALLOWED == {"ZNICZ_TPU_PALLAS_INTERPRET",
                           "ZNICZ_TPU_NO_PALLAS"}

    def test_the_switches_that_stay_are_silent(self, tmp_path):
        for rel in ("znicz_tpu/ops/tuning.py", "znicz_tpu/parallel/m.py"):
            assert lint(tmp_path, ENV_ROUTING_ALLOWED, [EnvRoutingRule()],
                        rel=rel) == []

    def test_other_packages_are_not_patrolled(self, tmp_path):
        for rel in ("znicz_tpu/serving/mod.py", "znicz_tpu/launcher.py",
                    "pkg/ops/mod.py"):
            assert lint(tmp_path, ENV_ROUTING_BAD, [EnvRoutingRule()],
                        rel=rel) == []

    def test_the_package_reads_only_the_switches_that_stay(self):
        """ops/ and parallel/ as they are: no finding, with one inline
        suppression (the coordinator's address in distributed.py)."""
        from znicz_tpu.analysis.core import default_root
        found = Analyzer([EnvRoutingRule()], root=default_root()).run()
        assert found == [], "\n".join(f.render() for f in found)


# -- the tier-1 gate -------------------------------------------------------

@pytest.mark.lint
class TestRepoGate:
    def test_whole_repo_has_no_new_findings(self):
        """THE gate: zlint over the real package must be clean (inline
        suppressions and justified baseline entries excepted)."""
        findings, new, _ = run_repo()
        assert not new, (
            "zlint found new issues (fix them, add an inline "
            "`# zlint: disable=RULE` with a comment, or baseline "
            "deliberately):\n" + "\n".join(f.render() for f in new))

    def test_baseline_entries_are_justified(self):
        """Every baseline entry must carry a real note — an
        unjustified entry is a muted bug, not a decision."""
        import os
        from znicz_tpu.analysis.core import default_root
        path = os.path.join(default_root(), "tools/zlint_baseline.json")
        with open(path) as fh:
            data = json.load(fh)
        for entry in data.get("entries", []):
            note = entry.get("note", "")
            assert note and "TODO" not in note, (
                f"baseline entry for {entry['path']} "
                f"[{entry['rule']}] has no justification: {entry}")

    def test_cli_gate_exits_zero(self):
        """`python -m znicz_tpu lint` is what tools/lint.sh and CI
        call; it must agree with the in-process gate."""
        proc = subprocess.run(
            [sys.executable, "-m", "znicz_tpu", "lint"],
            capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stdout + proc.stderr
