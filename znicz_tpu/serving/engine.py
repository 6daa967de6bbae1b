"""Forward-only inference engine with a shape-bucketed executable cache.

Parity target: the reference's snapshot-inference contract (PAPER.md /
SURVEY.md §2.3 — load a trained snapshot, serve its forward pass).  The
training side already exports ``.znn`` and runs it through the C++
engine; this engine runs the SAME container through JAX so serving gets
device acceleration and one model format covers both hosts.

Shape bucketing: XLA executables are shape-specialized, so serving raw
request batch sizes would compile once per distinct size and the cache
would grow without bound under organic traffic.  Requests are instead
padded up to a fixed bucket ladder (default 1/8/32/128) and the jitted
forward for each ``(bucket, sample_shape, dtype, device)`` is kept in a
bounded LRU — steady-state traffic hits a handful of executables, and
an evicted bucket simply recompiles on next use.  Oversized batches
chunk through the largest bucket.

Backend: ``auto`` uses JAX wherever JAX can be imported and
``export.NativeEngine`` (the C++ CPU engine) on a JAX-less host; a JAX
backend that fails to initialise is an error, not a reason to answer
from the CPU (``_jax_usable``).  The JAX forward deliberately
sticks to the XLA op tier (``ops/*.xla_*``) — serving wants the
portable, numerically-pinned path, not the Pallas training kernels.

Resilience (znicz_tpu.resilience): every jitted forward runs at the
``engine.forward`` fault site, transient failures retry under a
:class:`~znicz_tpu.resilience.RetryPolicy`, and a
:class:`~znicz_tpu.resilience.CircuitBreaker` guards the JAX engine —
after K consecutive forward failures it opens and ``predict`` degrades
to the SAME NativeEngine CPU fallback (one model format, so the
fallback serves identical semantics), or raises
:class:`~znicz_tpu.resilience.EngineUnavailable` (→ 503 + Retry-After
at the HTTP front) when the native engine cannot load.  Half-open
probes re-try JAX after ``cooldown_s`` and close the breaker on
success.  Deterministic errors (bad geometry → ValueError) bypass all
of this: retrying a bug hides it, and the front owes the client a 400.

Durability (znicz_tpu.durability): the artifact is verified on load
(sha256 manifest + deep format parse — a truncated/bit-flipped ``.znn``
raises ``ArtifactCorrupt`` at startup, never an XLA crash under
traffic), and weights are **generation-tracked**: :meth:`reload`
verifies + canaries a new artifact in the background and atomically
swaps it under the engine lock, rolling back on any failure while the
previous generation keeps serving (``model_reloads_total{outcome}``,
``model_generation``; state machine in docs/durability.md).
"""

from __future__ import annotations

import collections
import os
import tempfile
import threading
import time

import numpy as np

from .. import durability
from ..export import ZnnLayer, read_znn
from ..resilience import faults, overload
from ..resilience.breaker import CircuitBreaker, EngineUnavailable
from ..resilience.retry import RetryPolicy
from ..telemetry import compilestats, tracing
from ..telemetry.registry import REGISTRY

#: default pad-to-bucket ladder for request batch sizes
DEFAULT_BUCKETS = (1, 8, 32, 128)

#: int8 serving parity tolerances: the quantized forward must match
#: the fp32 engine on the verification batch within these bounds or
#: the generation serves fp32 (counted) — same shape of contract as
#: the BASELINE bf16 tolerance story (docs/performance.md): a speed
#: path may never silently change answers beyond a pinned bound
QUANT_RTOL = 5e-2
QUANT_ATOL = 5e-2

_reloads = REGISTRY.counter(
    "model_reloads_total",
    "hot-reload attempts, by outcome (ok | verify_failed | "
    "canary_failed | load_failed)")
_generation = REGISTRY.gauge(
    "model_generation",
    "generation number of the model currently serving (bumps on every "
    "successful hot reload; last engine to swap wins in a "
    "multi-engine process)")
_quant_fallbacks = REGISTRY.counter(
    "quantize_fallback_total",
    "int8 quantized-serving builds that fell back to fp32, by reason "
    "(unsupported = no quantizable fc chain or non-jax backend | "
    "tolerance = verification batch breached the parity tolerances | "
    "error = the quantized build/verify raised)")


class ReloadInProgress(RuntimeError):
    """A hot reload is already running — reloads are single-flight
    (the HTTP front answers 409)."""


class CanaryFailed(RuntimeError):
    """The candidate generation's canary forward produced a wrong
    shape, non-finite values, or raised — the swap is aborted and the
    previous generation keeps serving."""


class _Generation:
    """One loaded model generation: verified artifact path + parsed
    layers + their single device-resident parameter copy + the native
    CPU engine bound to the SAME artifact.  Immutable once published
    to the engine — a hot reload installs a NEW instance, and
    in-flight predicts finish on whichever generation they grabbed
    (including the degraded fallback leg: feats, layers, and the
    native model all come from one generation, so a mid-request swap
    can never mix two models)."""

    def __init__(self, number: int, path: str, layers, shardings=None):
        self.number = number
        self.path = path
        self.layers = layers
        #: per-layer (w, b) NamedShardings for tensor-parallel serving
        #: (None = single-device placement) — supplied by the engine
        #: at construction, before the first params() call, so the
        #: canary and every bucket executable see one consistent
        #: layout
        self.shardings = shardings
        #: per-layer int8 weight copies — ``None`` (fp32 serving) or a
        #: list aligned with ``layers`` whose quantized entries are
        #: ``(wq int8, scale f32 per-output-channel)`` and the rest
        #: ``None``.  Set by the engine AFTER verification against the
        #: fp32 forward, before the first ``params()`` call, so every
        #: bucket executable of this generation sees one consistent
        #: parameter layout.
        self.qlayers = None
        self._lock = threading.Lock()
        self._dev_params = None
        self._released = False        # evicted at least once before
        self.pageins = 0              # materializations (under _lock)
        #: pagein observer ``(cause, duration_ms)`` — the engine wires
        #: its own accounting hook here; fired AFTER the lock drops
        self.on_pagein = None
        self._native = None
        self._native_failed = False   # fallback tried and unavailable
        #: (cache key, jitted fn) compiled by the reload canary —
        #: seeded into the engine's LRU only if this generation swaps
        #: in, so a (possibly failing) reload never evicts the LIVE
        #: generation's executables
        self.warmed: tuple | None = None

    def _materialize(self):
        """Device-materialize the weights if absent, single-flight
        under the generation lock: a second caller racing the same
        page-in parks on the lock and adopts the first caller's copy —
        never a double device allocation (the weight-residency LRU's
        eviction/page-in contract, pinned by the concurrent-eviction
        test).  Returns ``(dev_params, pagein_info | None)`` where the
        info tuple is non-None iff THIS call did the materialization."""
        with self._lock:
            paged = None
            if self._dev_params is None:
                t0 = time.monotonic()
                import jax
                # device_put(x, None) is the default placement, so the
                # single-device case needs no separate branch
                sh = self.shardings or [(None, None)] * len(self.layers)
                ql = self.qlayers or [None] * len(self.layers)
                params = []
                for la, s, q in zip(self.layers, sh, ql):
                    if q is not None:
                        # quantized layer: the int8 copy + per-channel
                        # scale ride as a 3-tuple; jax_forward keys the
                        # int8 matmul off the third element.  tp>1 is
                        # rejected with quantize at construction, so
                        # no sharding to honor here.
                        wq, scale = q
                        params.append((
                            jax.device_put(wq),
                            None if la.b is None
                            else jax.device_put(la.b),
                            jax.device_put(scale)))
                    else:
                        params.append((
                            None if la.w is None
                            else jax.device_put(la.w, s[0]),
                            None if la.b is None
                            else jax.device_put(la.b, s[1])))
                self._dev_params = params
                self.pageins += 1
                paged = ("evicted" if self._released else "cold",
                         (time.monotonic() - t0) * 1e3)
            return self._dev_params, paged

    def _fire_pagein(self, paged) -> None:
        # outside the generation lock: the observer chain ends in the
        # zoo registry, which takes its own lock — holding this one
        # across foreign code is how lock-order cycles are born
        if paged is not None and self.on_pagein is not None:
            self.on_pagein(*paged)

    def params(self):
        """The weights, device-resident ONCE per generation and passed
        to every bucket executable as jit arguments — N cached
        executables must not mean N baked-in copies of the model.
        With tensor-parallel shardings set, each layer's weight lands
        pre-sharded over the ``model`` mesh axis (Megatron pairing),
        so every bucket executable computes on the sharded copies and
        XLA inserts the activation collectives between layers.
        Materialization is lazy AND revocable: :meth:`release_params`
        (the zoo's weight-residency LRU) drops the device copy and the
        next call here pages it back in from the retained host layers
        — byte-identical, because the host arrays never moved."""
        dev, paged = self._materialize()
        self._fire_pagein(paged)
        return dev

    def ensure(self) -> bool:
        """Page the weights in if evicted; True iff THIS call did the
        materialization (the zoo counts page-ins through it)."""
        _dev, paged = self._materialize()
        self._fire_pagein(paged)
        return paged is not None

    def release_params(self) -> bool:
        """Drop the device-resident weight copy (weight-residency LRU
        eviction).  The parsed host layers stay, so the next
        :meth:`params` call re-materializes the SAME bytes; an
        executable holding no baked-in weights (they ride as jit
        arguments) survives eviction untouched, which is what makes
        re-admission cheap.  True when a copy was actually resident."""
        with self._lock:
            had = self._dev_params is not None
            if had:
                self._dev_params = None
                self._released = True
            return had

    def params_resident(self) -> bool:
        with self._lock:
            return self._dev_params is not None

    def adopt_native(self, native) -> None:
        """Install an eagerly-loaded native model (backend="native"
        startup/reload, where a load failure must raise loudly instead
        of degrading)."""
        with self._lock:
            self._native = native

    def native_model(self):
        """This generation's CPU fallback model, lazily loaded from
        ITS OWN artifact path; None when the host cannot build/load
        the native engine (the degraded path is then 503, not a
        crash)."""
        with self._lock:
            if self._native is not None:
                return self._native
            if self._native_failed:
                return None
        try:
            from ..export import NativeEngine
            native = NativeEngine().load(self.path)
        except Exception:
            with self._lock:
                self._native_failed = True
            return None
        with self._lock:
            if self._native is None:
                self._native = native
            return self._native


# deliberate local twins of ops/geometry.out_size and
# ops/deconv.deconv_out_size: importing anything under znicz_tpu.ops
# pulls in jax (ops/__init__ imports every tier), and output_features
# must keep working on the JAX-less hosts the native fallback exists
# for.  tests/test_serving.py pins these against the real ops outputs.
def _conv_out(size: int, k: int, s: int, p: int) -> int:
    return (size + 2 * p - k) // s + 1


def _deconv_out(size: int, k: int, s: int, p: int) -> int:
    return s * (size - 1) + k - 2 * p


def output_features(layers: list[ZnnLayer], sample_shape) -> int:
    """Flat output feature count of the forward chain for one sample of
    ``sample_shape`` ((F,) or (H, W, C)) — pure arithmetic, no JAX, so
    the native fallback can size its output buffer too."""
    shape = tuple(int(d) for d in sample_shape)
    pool_in = {}       # export-stream index -> the pool's input (h, w)
    for li, lay in enumerate(layers):
        p = lay.p
        if lay.kind == "fc":
            feats = int(np.prod(shape))
            if feats != p[0]:
                raise ValueError(f"layer {li}: fc expects {p[0]} "
                                 f"features, chain carries {feats}")
            shape = (p[1],)
        elif lay.kind == "conv":
            h, w, _ = shape
            shape = (_conv_out(h, p[0], p[4], p[6]),
                     _conv_out(w, p[1], p[5], p[7]), p[3])
        elif lay.kind in ("max_pool", "avg_pool"):
            h, w, c = shape
            pool_in[li] = (h, w)
            shape = (_conv_out(h, p[0], p[4], p[6]),
                     _conv_out(w, p[1], p[5], p[7]), c)
        elif lay.kind == "deconv":
            h, w, _ = shape
            shape = (_deconv_out(h, p[0], p[4], p[6]),
                     _deconv_out(w, p[1], p[5], p[7]), p[2])
        elif lay.kind == "depool":
            # both engines emit the tied pool's RECORDED input extent,
            # which differs from the deconv formula whenever the pool
            # window didn't divide its input evenly
            h, w = pool_in[p[2]]
            shape = (h, w, shape[2])
        elif lay.kind == "kohonen":
            shape = (p[0],)
        # lrn / activation / dropout / softmax keep their shape
    return int(np.prod(shape))


def jax_forward(layers: list[ZnnLayer], x, params=None):
    """The .znn forward chain in jnp ops → (B, out_features) float32.

    Mirrors ``native/znicz_infer.cpp`` layer for layer: dropout is the
    inference identity, depooling replays the tied max-pool's winner
    offsets, the kohonen head emits negated squared distances.

    ``params`` (list of per-layer (w, b), e.g. already on device) lets
    the caller pass the weights as jit ARGUMENTS so every bucket
    executable shares one device copy instead of baking the full model
    in as compile-time constants; None falls back to the layers' own
    arrays.  LRN's 3 hyperparameters always come from the static layer
    (they parameterize the trace itself).

    Int8 serving (docs/serving.md "Int8 quantized serving"): an fc
    layer whose params entry is a 3-tuple ``(wq int8, b, scale)``
    takes the quantized path — the activations are dynamically
    quantized per row (symmetric, like the per-output-channel weight
    quantization), the int8×int8 matmul accumulates in fp32
    (``preferred_element_type``), and the product of the two scales
    dequantizes the result.  The tuple arity is part of the traced
    structure, so a quantized and an fp32 generation can never share
    an executable."""
    import jax
    import jax.numpy as jnp

    from ..ops import conv as conv_ops
    from ..ops import deconv as deconv_ops
    from ..ops import normalization as lrn_ops
    from ..ops import pooling as pool_ops
    from ..ops.activations import BY_NAME

    h = x
    pool_ctx = {}        # layer index -> (offsets, input shape, geometry)
    for li, lay in enumerate(layers):
        p = lay.p
        entry = (params[li] if params is not None else (lay.w, lay.b))
        w, b = entry[0], entry[1]
        qscale = entry[2] if len(entry) > 2 else None
        if lay.kind == "fc":
            h2 = h.reshape(h.shape[0], -1)
            if h2.shape[1] != p[0]:
                raise ValueError(f"layer {li}: fc expects {p[0]} "
                                 f"features, got {h2.shape[1]}")
            if qscale is not None:
                # int8 weight-and-activation matmul, fp32 accumulation:
                # rows quantize dynamically against their own absmax
                # (a zero row keeps scale 1 — 0/0 must not NaN the
                # batch), the per-output-channel weight scale pairs
                # with it to dequantize the accumulator
                amax = jnp.max(jnp.abs(h2), axis=1, keepdims=True)
                sx = jnp.where(amax > 0, amax / 127.0, 1.0)
                xq = jnp.clip(jnp.round(h2 / sx),
                              -127, 127).astype(jnp.int8)
                acc = jax.lax.dot_general(
                    xq, w, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                pre = acc * (sx * qscale[None, :])
            else:
                pre = h2 @ w
            if b is not None:
                pre = pre + b
            h = BY_NAME[lay.activation].fwd(pre, jnp)
        elif lay.kind == "conv":
            y = conv_ops.xla_conv2d(h, jnp.asarray(w),
                                    (p[4], p[5]), (p[6], p[7]))
            if b is not None:
                y = y + b
            h = BY_NAME[lay.activation].fwd(y, jnp)
        elif lay.kind == "max_pool":
            y, off = pool_ops.xla_max_pooling(
                h, (p[0], p[1]), (p[4], p[5]), (p[6], p[7]))
            pool_ctx[li] = (off, h.shape,
                            ((p[0], p[1]), (p[4], p[5]), (p[6], p[7])))
            h = y
        elif lay.kind == "avg_pool":
            h = pool_ops.xla_avg_pooling(
                h, (p[0], p[1]), (p[4], p[5]), (p[6], p[7]))
        elif lay.kind == "lrn":
            alpha, beta, k = (float(v) for v in lay.w)
            h = lrn_ops.xla_lrn(h, p[0], alpha, beta, k)[0]
        elif lay.kind == "activation":
            h = BY_NAME[lay.activation].fwd(h, jnp)
        elif lay.kind == "dropout":
            pass                        # inverted dropout: eval identity
        elif lay.kind == "softmax":
            h = jax.nn.softmax(h, axis=1)
        elif lay.kind == "deconv":
            y = deconv_ops.xla_deconv2d(h, jnp.asarray(w),
                                        (p[4], p[5]), (p[6], p[7]))
            if b is not None:
                y = y + b
            h = BY_NAME[lay.activation].fwd(y, jnp)
        elif lay.kind == "depool":
            off, in_shape, geom = pool_ctx[p[2]]
            h = pool_ops.xla_depooling(
                h, off, (h.shape[0],) + tuple(in_shape[1:]), *geom)
        elif lay.kind == "kohonen":
            h2 = h.reshape(h.shape[0], -1)
            d = ((h2[:, None, :] - w[None, :, :]) ** 2).sum(-1)
            h = -d
        else:
            raise NotImplementedError(
                f"serving does not cover layer kind {lay.kind!r}")
    return h.reshape(h.shape[0], -1)


def quantize_layers(layers: list[ZnnLayer]) -> tuple[list, int]:
    """Symmetric per-output-channel int8 copies of the fc weights.

    Returns ``(qlayers, n)`` where ``qlayers`` aligns with ``layers``
    (``(wq, scale)`` for each quantized fc layer, ``None`` elsewhere)
    and ``n`` counts quantized layers.  Only fc weights quantize — the
    FC-heavy families are where the bytes are; conv/LRN/pool/kohonen
    layers keep fp32 (a kohonen head's squared-distance arithmetic is
    not a matmul, and the conv chains fail the parity verification on
    the wrong side of the tolerance for no byte win)."""
    q, n = [], 0
    for lay in layers:
        w = lay.w
        if lay.kind == "fc" and w is not None \
                and getattr(w, "ndim", 0) == 2:
            scale = np.max(np.abs(w), axis=0) / 127.0
            # an all-zero output channel keeps scale 1: 0/0 would NaN
            # the whole dequantization for a column that is exactly 0
            scale = np.where(scale > 0.0, scale, 1.0).astype(np.float32)
            wq = np.clip(np.round(w / scale), -127, 127).astype(np.int8)
            q.append((wq, scale))
            n += 1
        else:
            q.append(None)
    return q, n


def _jax_usable() -> bool:
    """Whether ``backend="auto"`` serves through JAX.  False only on
    the JAX-less host the native engine exists for: JAX cannot be
    imported.  A JAX that imports but whose backend will not
    initialise — the chip is held by another process, the platform
    ``JAX_PLATFORMS`` names is missing — raises from here: answering
    from the CPU engine instead would hide the device."""
    try:
        import jax
    except ImportError:
        return False
    jax.devices()
    return True


def device_report(backend: str) -> dict:
    """``platform`` / ``device_kind`` / ``device_count`` of the devices
    behind an engine of ``backend``, for ``/healthz`` and ``/statusz``:
    the process's own JAX devices, or the host CPU with no JAX device
    for the native engine."""
    if backend == "jax":
        from ..backends import device_report as jax_report
        return jax_report()
    return {"platform": "cpu", "device_kind": "native",
            "device_count": 0}


class ServingEngine:
    """Load a ``.znn`` file or a live trained workflow and serve its
    forward pass with bucketed batching.

    ``predict(x)`` accepts (B, F) or (B, H, W, C) float arrays, pads B
    up to the smallest covering bucket (chunking batches larger than
    the top bucket), runs the per-bucket jitted executable, and returns
    the un-padded (B, out_features) float32 result.
    """

    def __init__(self, model, *, backend: str = "auto",
                 buckets=DEFAULT_BUCKETS, cache_size: int = 8,
                 retry: RetryPolicy | None = None,
                 breaker: CircuitBreaker | None = None,
                 tp: int = 1, quantize: str = "none"):
        if not buckets or list(buckets) != sorted(set(int(b)
                                                      for b in buckets)):
            raise ValueError(f"buckets must be unique ascending ints, "
                             f"got {buckets!r}")
        if not isinstance(tp, int) or isinstance(tp, bool) or tp < 1:
            raise ValueError(f"tp must be a positive int, got {tp!r}")
        if quantize not in ("none", "int8"):
            raise ValueError(f"quantize must be 'none' or 'int8', "
                             f"got {quantize!r}")
        if quantize != "none" and tp > 1:
            # the Megatron shardings split fp32 weight matrices; a
            # sharded int8 copy would need its own scale layout —
            # refuse loudly rather than silently serving fp32
            raise ValueError("quantize cannot combine with tensor-"
                             "parallel serving (tp > 1)")
        self.quantize = quantize
        self.buckets = tuple(int(b) for b in buckets)
        self.cache_size = int(cache_size)
        self.tp = tp
        self._tmpdir = None
        if isinstance(model, (str, os.PathLike)):
            path = os.fspath(model)
        else:                 # live workflow: one format serves both
            from ..export import export_workflow
            self._tmpdir = tempfile.TemporaryDirectory(
                prefix="znicz_serve_")
            path = os.path.join(self._tmpdir.name, "model.znn")
            export_workflow(model, path)
        # verify-on-load: a truncated/bit-flipped artifact must refuse
        # to serve HERE, as a typed error at startup — not as an XLA
        # shape crash under traffic (torn manifests heal, legacy
        # manifest-less files deep-parse; docs/durability.md)
        durability.verify_or_heal(path)
        if backend == "auto":
            backend = "jax" if _jax_usable() else "native"
        if backend not in ("jax", "native"):
            raise ValueError(f"unknown backend {backend!r}")
        self.backend = backend
        # tensor-parallel forward (docs/distributed.md): a (1, tp)
        # ("data", "model") mesh; weights of wide fc/conv layers land
        # pre-sharded (Megatron pairing, same rule as training's
        # shard_params), inputs replicate, XLA inserts the activation
        # collectives.  tp=1 (or the native backend, which has no
        # devices to shard over) is exactly the single-device path.
        self._mesh = None
        self._x_sharding = None
        if tp > 1:
            if backend != "jax":
                raise ValueError("tensor-parallel serving (tp > 1) "
                                 "needs the jax backend")
            from ..parallel import mesh as mesh_lib
            self._mesh = mesh_lib.resolve_mesh((1, tp), site="serve")
            self._x_sharding = mesh_lib.replicated(self._mesh)
        layers = read_znn(path)
        #: zoo residency hook ``(cause, duration_ms)`` — fired on every
        #: weight page-in of whichever generation is serving (set by
        #: ModelZoo.add; None outside a zoo)
        self.on_pagein = None
        #: per-tenant cost-attribution hook ``(duration_ms)`` — fired
        #: after every fenced forward with the measured device time
        #: (set by ModelZoo.add so ``model_device_ms_total{model}``
        #: bills the tenant whose batch spent the chip; None outside
        #: a labeled zoo)
        self.on_device_time = None
        self._gen = _Generation(1, path, layers,
                                self._tp_shardings(layers))
        self._gen.on_pagein = self._note_pagein
        if backend == "native":
            from ..export import NativeEngine
            self._gen.adopt_native(NativeEngine().load(path))
        # transient device errors retry briefly (default budget stays
        # well under the batcher's dispatch cadence); K consecutive
        # exhausted retries trip the breaker and predict degrades
        self.retry = retry if retry is not None else RetryPolicy(
            max_attempts=3, base_delay_s=0.02, max_delay_s=0.25)
        self.breaker = breaker if breaker is not None else \
            CircuitBreaker(failure_threshold=5, cooldown_s=10.0)
        self._lock = threading.Lock()
        self._cache = collections.OrderedDict()   # key -> jitted fwd
        self._stats = collections.Counter()       # bucket executables
        #: generation-independent (bucket, shape, dtype, device) keys
        #: whose executable COMPLETED a compile — classifies a
        #: request-path compile as "new_bucket" (never built) vs
        #: "fallback" (built before: LRU eviction or a generation swap
        #: re-exposed a cold executable).  Keys are added only once the
        #: first invocation succeeds (a build whose first call raised
        #: produced no executable), and the set is bounded: shape keys
        #: derive from client-controlled request shapes, so a public
        #: replica must not accrete one entry per adversarial shape
        #: forever.  Past the cap, novel shapes classify as new_bucket
        #: permanently — the conservative (stricter) cause.
        self._compiled_shapes: set = set()
        self._compiled_shapes_cap = 4096
        #: hot-reload bookkeeping: single-flight + last outcome for
        #: /healthz; the sample shape of live traffic feeds the canary
        self._reload_lock = threading.Lock()
        self.last_reload: dict | None = None
        self._last_sample_shape: tuple | None = None
        # int8 build rides construction, after the stats/locks exist
        # and BEFORE any params() materialization — the verification
        # runs eagerly on host copies, so a failed build costs nothing
        # on device and the generation simply serves fp32 (counted)
        self._try_quantize(self._gen)
        _generation.set(1)

    # -- int8 quantized serving -------------------------------------------
    def _try_quantize(self, gen: _Generation) -> None:
        """Build and VERIFY ``gen``'s int8 weight copy (engine
        ``quantize="int8"``): quantize the fc layers, run a seeded
        verification batch through the fp32 and quantized forwards
        eagerly, and publish ``gen.qlayers`` only when the outputs
        agree within :data:`QUANT_RTOL`/:data:`QUANT_ATOL`.  Any
        breach — no fc chain, non-jax backend, tolerance, a raise —
        falls back to fp32 for this generation and counts
        ``quantize_fallback_total{reason}``; serving never degrades
        below the fp32 contract because of a quantization knob."""
        if self.quantize != "int8":
            return
        reason = None
        try:
            qlayers, n = quantize_layers(gen.layers)
            first = gen.layers[0]
            if self.backend != "jax" or n == 0 \
                    or first.kind != "fc":
                # non-fc-first chains (conv H×W underivable from the
                # kernel alone) cannot build a verification batch —
                # and a model with nothing to quantize has no int8
                # path to verify
                reason = "unsupported"
            else:
                shape = (int(first.p[0]),)
                rng = np.random.default_rng(0)   # deterministic batch
                x = rng.standard_normal(
                    (self.buckets[0],) + shape).astype(np.float32)
                y32 = np.asarray(jax_forward(gen.layers, x))
                host = [((q[0], la.b, q[1]) if q is not None
                         else (la.w, la.b))
                        for la, q in zip(gen.layers, qlayers)]
                yq = np.asarray(jax_forward(gen.layers, x, host))
                if np.allclose(yq, y32, rtol=QUANT_RTOL,
                               atol=QUANT_ATOL):
                    gen.qlayers = qlayers
                else:
                    reason = "tolerance"
        except Exception:
            reason = "error"
        if reason is not None:
            with self._lock:
                self._stats["quantize_fallbacks"] += 1
            _quant_fallbacks.inc(reason=reason)

    def quantized_active(self) -> bool:
        """Whether the CURRENT serving generation holds a verified
        int8 weight copy (False on fp32 fallback or quantize='none')."""
        return self._current().qlayers is not None

    # -- tensor parallelism -----------------------------------------------
    @property
    def mesh_shape(self) -> tuple[int, int]:
        """(data, model) axis sizes of the serving layout — (1, 1) on
        the single-device path (healthz/statusz introspection)."""
        return (1, self.tp if self._mesh is not None else 1)

    def _tp_shardings(self, layers):
        """Per-layer (w, b) NamedShardings for one generation, or None
        without a mesh.  Megatron pairing over the PARAMETERIZED
        fc/conv/deconv layers only (same alternate-axis rule as
        training's ``shard_params``); everything else — including the
        lrn pseudo-weights that store hyperparameters in ``lay.w`` —
        replicates.  Biases replicate like training's."""
        if self._mesh is None:
            return None
        from ..parallel import mesh as mesh_lib
        repl = mesh_lib.replicated(self._mesh)
        shardings, pidx = [], 0
        for lay in layers:
            w = lay.w
            if lay.kind in ("fc", "conv", "deconv") and w is not None \
                    and getattr(w, "ndim", 0) >= 2:
                # plan_tp_sharding = THE shared Megatron policy (split
                # dim by pair parity, replicate + pair-restart when the
                # model axis doesn't divide) — one definition with the
                # trainer, so the two layouts can never drift
                sh, pidx = mesh_lib.plan_tp_sharding(self._mesh, pidx,
                                                     w.shape)
                shardings.append((sh, repl))
            else:
                shardings.append((repl, repl))
        return shardings

    def _replicate_input(self, x):
        """Pin a host batch to the replicated layout before a
        tensor-parallel executable consumes it — a bare np array next
        to mesh-committed params would fail jit's device check."""
        if self._x_sharding is None:
            return x
        import jax
        return jax.device_put(x, self._x_sharding)

    # -- weight residency (the zoo's memory-budget LRU) -------------------
    def _note_pagein(self, cause: str, dt_ms: float) -> None:
        """Every generation's pagein observer: count it and forward to
        the zoo hook (if any) so ``model_pagein_total{model,cause}``
        is exact even for page-ins the zoo did not initiate — e.g. a
        dispatch thread re-materializing a just-evicted straggler."""
        with self._lock:
            self._stats["weight_pageins"] += 1
        cb = self.on_pagein
        if cb is not None:
            cb(cause, dt_ms)

    # -- device-time cost attribution -------------------------------------
    def _note_device_time(self, dt_ms: float) -> None:
        """One fenced forward's measured wall time (the ``np.asarray``
        readback IS the block_until_ready fence, so this is dispatch +
        compute + readback — retry backoff sleeps and chaos-injected
        latency are outside the measurement).  Accumulated into
        ``device_ms_total`` and forwarded to the zoo hook so the
        tenant that spent the chip is the one billed."""
        with self._lock:
            self._stats["device_ms_total"] += dt_ms
        cb = self.on_device_time
        if cb is not None:
            cb(dt_ms)

    def device_ms_total(self) -> float:
        """Measured device milliseconds this engine has spent across
        every fenced forward (the zoo's per-tenant attribution and the
        server's ``engine_busy_ratio`` collector both read this)."""
        with self._lock:
            return float(self._stats["device_ms_total"])

    def weight_nbytes(self) -> int:
        """Host-side byte size of the serving generation's parameters
        — the device-resident copy costs the same (fp32 both sides),
        so this is what the zoo's residency budget accounts."""
        return sum((0 if la.w is None else la.w.nbytes)
                   + (0 if la.b is None else la.b.nbytes)
                   for la in self._current().layers)

    def weights_resident(self) -> bool:
        """Whether the serving generation currently holds its device
        weight copy (native backend: never — nothing to page)."""
        return self.backend == "jax" \
            and self._current().params_resident()

    def resident_weight_bytes(self) -> int:
        """Bytes actually on device right now — 0 when evicted (or on
        the native backend).  The zoo's budget arithmetic uses THIS,
        not :meth:`weight_nbytes`, so a replica set that is only
        partially re-materialized is billed for what it holds."""
        return self.weight_nbytes() if self.weights_resident() else 0

    def release_weights(self) -> int:
        """Evict the device weight copy (zoo LRU); returns the bytes
        freed (0 when nothing was resident or on the native backend).
        In-flight forwards pinned to the generation re-materialize on
        demand — eviction can cost a page-in, never correctness."""
        if self.backend != "jax":
            return 0
        gen = self._current()
        if not gen.release_params():
            return 0
        with self._lock:
            self._stats["weight_releases"] += 1
        return self.weight_nbytes()

    def ensure_weights(self) -> bool:
        """Page the serving generation's weights in if evicted; True
        iff this call did the materialization (single-flight: a
        concurrent caller parks on the generation lock instead of
        double-allocating)."""
        if self.backend != "jax":
            return False
        return self._current().ensure()

    # -- generation access ------------------------------------------------
    def _current(self) -> _Generation:
        """The generation currently serving (locked read: reload swaps
        it).  Callers grab it once per request and use that object
        throughout — a mid-request swap must never mix two models'
        layers and params."""
        with self._lock:
            return self._gen

    @property
    def layers(self) -> list[ZnnLayer]:
        return self._current().layers

    @property
    def path(self) -> str:
        return self._current().path

    @property
    def generation(self) -> int:
        return self._current().number

    # -- executable cache -------------------------------------------------
    def _device_key(self) -> str:
        import jax
        d = jax.devices()[0]
        key = f"{d.platform}:{getattr(d, 'id', 0)}"
        # the TP layout is part of the executable's identity: a tp=2
        # and a tp=1 engine in one process must never classify each
        # other's compiles as already-warm shapes.  Same rule for the
        # quantize mode — an int8 and an fp32 engine trace different
        # programs for one shape
        if self.quantize != "none":
            key = f"{key}:q-{self.quantize}"
        return key if self._mesh is None else f"{key}:tp{self.tp}"

    def _shape_key(self, bucket, sample_shape, dtype) -> tuple:
        """The generation-independent part of an executable-cache key
        — the ONE place the key layout lives: _executable, warmup and
        the reload canary must all build byte-identical keys or a
        'already warm' / seed-the-swap check silently never matches.
        The full cache key is ``(gen.number,) + _shape_key(...)``."""
        return (int(bucket), tuple(sample_shape), str(dtype),
                self._device_key())

    def _executable(self, gen: _Generation, bucket: int, sample_shape,
                    dtype, cause: str | None = None):
        """The jitted forward for one cache key, LRU-managed.  Each key
        gets its OWN ``jax.jit`` instance so evicting the entry actually
        releases the underlying executable.  Keys carry the generation
        number (and the swap clears the cache anyway): a stale
        executable from a previous generation must never serve.

        Compile accounting (telemetry.compilestats): every miss builds
        a fresh executable whose first invocation is timed into
        ``compile_time_ms{site="serving.engine"}``; ``cause`` defaults
        to the request-path classification (``new_bucket`` for a shape
        key never compiled, ``fallback`` for a re-compile after
        eviction / generation swap) — warmup passes ``cold``."""
        shape_key = self._shape_key(bucket, sample_shape, dtype)
        key = (gen.number,) + shape_key
        with self._lock:
            fn = self._cache.get(key)
            if fn is not None:
                self._cache.move_to_end(key)
                self._stats["cache_hits"] += 1
                compilestats.record_cache("serving.engine", hit=True)
                return fn
            self._stats["cache_misses"] += 1
            compilestats.record_cache("serving.engine", hit=False)
            if cause is None:
                cause = ("fallback" if shape_key in self._compiled_shapes
                         else "new_bucket")
            import jax
            layers = gen.layers
            fn = compilestats.first_call_timed(
                jax.jit(lambda params, x: jax_forward(layers, x,
                                                      params)),
                site="serving.engine", cause=cause,
                on_first=lambda: self._mark_compiled(shape_key))
            if gen is self._gen:
                # only the CURRENT generation may occupy cache slots:
                # an in-flight request pinned to a just-retired
                # generation would otherwise re-insert a key the
                # reload prune already removed — a dead entry that
                # pins the old layers alive and can evict a live
                # executable
                self._cache[key] = fn
                while len(self._cache) > self.cache_size:
                    self._cache.popitem(last=False)
                    self._stats["cache_evictions"] += 1
            return fn

    def _mark_compiled(self, shape_key) -> None:
        """A shape key's executable finished its first successful call
        (the FirstCallTimed hook — fires outside the engine lock)."""
        with self._lock:
            self._mark_compiled_locked(shape_key)

    def _mark_compiled_locked(self, shape_key) -> None:
        if len(self._compiled_shapes) < self._compiled_shapes_cap:
            self._compiled_shapes.add(shape_key)

    def bucket_for(self, b: int) -> int:
        for bucket in self.buckets:
            if b <= bucket:
                return bucket
        return self.buckets[-1]

    def warmup(self, sample_shape, dtype=np.float32,
               buckets=None) -> int:
        """Precompile the bucket executables for ``sample_shape``
        BEFORE traffic arrives, off the request path — the compiles
        record ``compiles_total{site="serving.engine", cause="cold"}``
        instead of ambushing the first request of each batch size as a
        ``new_bucket`` latency spike.  Returns the number of
        executables built (0 on the native backend, which has nothing
        to compile).  Serve CLI: ``--warmup-shape``."""
        if self.backend != "jax":
            return 0
        shape = tuple(int(d) for d in sample_shape)
        gen = self._current()
        built = 0
        for bucket in (buckets if buckets is not None else self.buckets):
            key = (gen.number,) + self._shape_key(bucket, shape,
                                                  np.dtype(dtype))
            with self._lock:
                if key in self._cache:
                    continue            # already warm: nothing to build
            fn = self._executable(gen, int(bucket), shape,
                                  np.dtype(dtype), cause="cold")
            x = np.zeros((int(bucket),) + shape, np.dtype(dtype))
            # force the lazy jit NOW — an un-invoked executable would
            # still pay its compile on the first request
            fn(gen.params(), self._replicate_input(x))
            built += 1
        return built

    def warmup_from_census(self, recorder=None, top: int = 4,
                           fallback_shape=None) -> int:
        """Census-driven warmup: precompile the bucket ladder for the
        sample shapes live traffic ACTUALLY sent — the flight
        recorder's request records carry each request's shape, so a
        reload or a restart-with-state can precompile what the
        operator could only guess at with ``--warmup-shape``.  The
        ``top`` most frequent shapes warm (shape cardinality is
        client-controlled; warming every shape ever probed would
        compile without bound); with no census yet (fresh process, no
        traffic) ``fallback_shape`` warms instead — the operator
        guess remains the bootstrap.  Returns executables built."""
        if self.backend != "jax":
            return 0
        from ..telemetry import flightrecorder
        rec = recorder if recorder is not None else flightrecorder.RECORDER
        # the warm set must FIT the LRU: warming top*len(buckets)
        # executables into a smaller cache would evict its own entries
        # — and the reload-seeded canary executable, whose slot stays
        # reserved here — re-exposing the very request-path compiles
        # this exists to prevent.  With cache_size <= len(buckets)
        # even ONE shape overflows, so census warming skips entirely
        # (the warning below names the knob)
        fit = (self.cache_size - 1) // len(self.buckets)
        top = min(max(0, int(top)), max(0, fit))
        census = rec.shape_census()
        shapes = [s for s, _ in census[:top]]
        if len(census) > top:
            # never a silent cap: a dropped shape's traffic will pay
            # request-path compiles after the next swap — tell the
            # operator which, and what knob fixes it
            import logging
            logging.getLogger("ServingEngine").warning(
                "census warmup: %d observed shape(s) beyond the "
                "cache-fit cap of %d not warmed (%s...); raise "
                "--cache-size to cover them",
                len(census) - top, top,
                [list(s) for s, _ in census[top:top + 3]])
        if not shapes and fallback_shape is not None:
            # the OPERATOR's shape fails loud: a --warmup-shape typo
            # must error at startup, not silently warm nothing and
            # hand every first request a compile spike
            return self.warmup(tuple(int(d) for d in fallback_shape))
        built = 0
        for s in shapes:
            try:
                built += self.warmup(s)
            except Exception:
                # the census records shapes CLIENTS sent, including
                # geometry the model rejects with a 400 — a junk shape
                # must not abort warming the legitimate ones
                continue
        return built

    # -- degraded path ----------------------------------------------------
    def _fallback_predict(self, x: np.ndarray, gen: _Generation,
                          cause=None) -> np.ndarray:
        """Serve ``x`` on the native CPU engine, or raise
        ``EngineUnavailable`` (→ 503 + Retry-After) — the two graceful
        outcomes the acceptance contract allows while JAX is down.
        Feats AND the native model both come from the request's pinned
        generation — a hot reload mid-request must not pair one
        model's geometry with the other's weights."""
        feats = output_features(gen.layers, x.shape[1:])
        native = gen.native_model()
        if native is None:
            raise EngineUnavailable(
                f"jax engine unavailable "
                f"({cause or 'circuit open'}) and the native CPU "
                f"fallback could not load",
                retry_after=self.breaker.retry_after())
        with self._lock:
            self._stats["fallback_calls"] += 1
            self._stats["rows_in"] += len(x)
        try:
            with tracing.span("engine.forward", backend="fallback",
                              rows=int(len(x))) as sp:
                t0 = time.monotonic()
                y = native.infer(x, feats)
                dt_ms = (time.monotonic() - t0) * 1e3
                sp.attrs["device_ms"] = round(dt_ms, 3)
            self._note_device_time(dt_ms)
            return y
        except Exception as e:
            raise EngineUnavailable(
                f"native fallback failed: {e!r}",
                retry_after=self.breaker.retry_after())

    def _forward_once(self, fn, gen: _Generation, padded: np.ndarray,
                      dev_acc: list | None = None) -> np.ndarray:
        faults.inject("engine.forward")
        # measure AFTER the fault site: injected latency is chaos, not
        # chip time, and must not pollute the cost attribution
        t0 = time.monotonic()
        y = np.asarray(fn(gen.params(), self._replicate_input(padded)))
        dt_ms = (time.monotonic() - t0) * 1e3
        if dev_acc is not None:
            dev_acc[0] += dt_ms
        self._note_device_time(dt_ms)
        return y

    def _count_retry(self, attempt, exc) -> None:
        with self._lock:
            self._stats["retries"] += 1

    # -- prediction -------------------------------------------------------
    def predict(self, x: np.ndarray) -> np.ndarray:
        x = np.ascontiguousarray(x, np.float32)
        if x.ndim < 2:
            raise ValueError(f"expected a batched input, got {x.shape}")
        if len(x) == 0:
            raise ValueError("empty batch")
        # deadline hop "forward": a batch whose every rider's budget
        # already ran out must not burn a device slot — the raise is
        # typed DeadlineExceeded (non-retryable, maps to 504), never
        # a breaker event (the engine is fine, the budget is not)
        overload.check_deadline("forward")
        # one generation per request: a hot reload mid-request must
        # never mix two models' layers/params (the canary also reuses
        # live traffic's sample shape, recorded here)
        with self._lock:
            gen = self._gen
            self._last_sample_shape = tuple(int(d) for d in x.shape[1:])
        if self.backend == "native":
            feats = output_features(gen.layers, x.shape[1:])
            native = gen.native_model()
            with self._lock:
                self._stats["forward_calls"] += 1
                self._stats["rows_in"] += len(x)
            with tracing.span("engine.forward", backend="native",
                              rows=int(len(x))) as sp:
                t0 = time.monotonic()
                y = native.infer(x, feats)
                dt_ms = (time.monotonic() - t0) * 1e3
                sp.attrs["device_ms"] = round(dt_ms, 3)
            self._note_device_time(dt_ms)
            return y
        if not self.breaker.allow():
            return self._fallback_predict(x, gen)
        top = self.buckets[-1]
        outs = []
        try:
            for start in range(0, len(x), top):
                chunk = x[start:start + top]
                bucket = self.bucket_for(len(chunk))
                if len(chunk) < bucket:
                    pad = np.zeros(
                        (bucket - len(chunk),) + chunk.shape[1:],
                        np.float32)
                    padded = np.concatenate([chunk, pad])
                else:
                    padded = chunk
                fn = self._executable(gen, bucket, chunk.shape[1:],
                                      chunk.dtype)
                # the span carries the chunk's measured device time so
                # flight-record stage breakdowns can split the chip
                # bill pro-rata across the batch's riders.  Accumulated
                # per CALL (not as a delta of the engine-global total):
                # a concurrent forward on the same engine — a hedge's
                # losing attempt, a replica straggler — must not leak
                # its chip time into this span's attribution
                dev_acc = [0.0]
                with tracing.span("engine.forward", backend="jax",
                                  bucket=bucket,
                                  rows=int(len(chunk))) as sp:
                    y = self.retry.call(self._forward_once, fn, gen,
                                        padded, dev_acc,
                                        on_retry=self._count_retry)
                    sp.attrs["device_ms"] = round(dev_acc[0], 3)
                with self._lock:
                    self._stats["forward_calls"] += 1
                    self._stats["rows_in"] += len(chunk)
                    self._stats["padded_rows"] += bucket - len(chunk)
                outs.append(y[:len(chunk)])
        except Exception as e:
            if not self.retry.retryable(e):
                # deterministic error (bad geometry, dtype bug): the
                # device is fine — free any probe slot and surface it
                self.breaker.abandon()
                raise
            with self._lock:
                self._stats["forward_failures"] += 1
            self.breaker.record_failure()
            return self._fallback_predict(x, gen, cause=e)
        self.breaker.record_success()
        return outs[0] if len(outs) == 1 else np.concatenate(outs)

    # -- hot reload -------------------------------------------------------
    def _canary_shape(self, layers) -> tuple | None:
        """Sample shape for the canary batch: live traffic's last seen
        shape when any, else derived from the first layer for flat
        models (fc/kohonen carry their input width; a conv chain's
        H×W cannot be recovered from kernels alone)."""
        with self._lock:
            if self._last_sample_shape is not None:
                return self._last_sample_shape
        first = layers[0]
        if first.kind == "fc":
            return (first.p[0],)
        if first.kind == "kohonen":
            return (first.p[1],)
        return None

    def _canary(self, gen: _Generation, native) -> str:
        """Run the candidate generation forward on a bucketed dummy
        batch BEFORE it may serve: a model that raises, returns the
        wrong feature count, or emits non-finite values must be
        rejected while the old generation still holds the traffic.
        Returns ``"ok"`` or ``"skipped"`` (shape underivable and no
        traffic seen yet); raises :class:`CanaryFailed`."""
        shape = self._canary_shape(gen.layers)
        if shape is None:
            return "skipped"
        bucket = self.buckets[0]
        x = np.zeros((bucket,) + tuple(shape), np.float32)
        try:
            feats = output_features(gen.layers, shape)
            if self.backend == "native":
                y = native.infer(x, feats)
            else:
                # compiled candidate-locally (NOT via _executable: an
                # insert into the shared LRU could evict a LIVE
                # generation's executable even when this reload rolls
                # back); a successful swap seeds it into the cache, so
                # the first post-swap request finds it warm
                import jax
                layers = gen.layers
                fn = jax.jit(lambda params, xx: jax_forward(layers, xx,
                                                            params))
                # compile accounting: a reload pays its compile HERE,
                # off the request path — cause="reload", and the swap
                # seeds the executable so traffic never re-pays it
                with compilestats.timed("serving.canary", "reload"):
                    y = np.asarray(fn(gen.params(),
                                      self._replicate_input(x)))
                gen.warmed = ((gen.number,)
                              + self._shape_key(bucket, shape, x.dtype),
                              fn)
        except Exception as e:
            raise CanaryFailed(f"canary forward raised: {e!r}") from e
        if y.shape != (bucket, feats):
            raise CanaryFailed(f"canary produced shape {y.shape}, "
                               f"expected {(bucket, feats)}")
        if not np.isfinite(y).all():
            raise CanaryFailed("canary produced non-finite outputs")
        return "ok"

    def reload(self, path: str | None = None, *,
               canary: bool = True) -> dict:
        """Zero-downtime hot reload: verify → parse → canary → atomic
        swap under the engine lock.  ``path=None`` re-reads the current
        artifact path (picking up newly exported weights in place).

        Any failure (verify, parse, canary) ROLLS BACK: nothing is
        swapped, the previous generation keeps serving, and the outcome
        lands in :attr:`last_reload` / ``model_reloads_total{outcome}``
        — the reload/rollback state machine in docs/durability.md.
        Single-flight; a concurrent attempt raises
        :class:`ReloadInProgress`."""
        if not self._reload_lock.acquire(blocking=False):
            raise ReloadInProgress("a hot reload is already running")
        try:
            old = self._current()
            target = os.fspath(path) if path is not None else old.path
            t0 = time.monotonic()
            outcome, error, canary_result = "ok", None, None
            candidate = native = None
            try:
                durability.verify_or_heal(target)
                # TP layout rides construction, before the first
                # params() touch: the canary compile must match the
                # serving executables
                layers = read_znn(target)
                candidate = _Generation(old.number + 1, target, layers,
                                        self._tp_shardings(layers))
                # the candidate's first materialization (the canary)
                # must count like any other page-in — the zoo's
                # residency accounting sees reloads too
                candidate.on_pagein = self._note_pagein
                # re-quantize PER GENERATION, verified against the
                # candidate's own fp32 forward: new weights get a
                # fresh int8 copy or a fresh (counted) fp32 fallback
                # — and the canary below then exercises whichever
                # path will actually serve
                self._try_quantize(candidate)
                if self.backend == "native":
                    from ..export import NativeEngine
                    native = NativeEngine().load(target)
                    candidate.adopt_native(native)
                if canary:
                    canary_result = self._canary(candidate, native)
            except durability.ArtifactCorrupt as e:
                outcome, error = "verify_failed", str(e)
            except CanaryFailed as e:
                outcome, error = "canary_failed", str(e)
            except Exception as e:
                outcome, error = "load_failed", repr(e)
            with self._lock:
                if outcome == "ok":
                    self._gen = candidate
                    self._stats["reloads"] += 1
                    keep = candidate.number
                else:
                    keep = old.number
                # stale generations' executables must never serve (and
                # must free their memory) — cache keys carry the
                # generation number, so this is just a filter
                for key in [k for k in self._cache if k[0] != keep]:
                    del self._cache[key]
                if outcome == "ok" and candidate.warmed is not None:
                    # seed the canary's compile: the first post-swap
                    # request must not pay the jit a second time (the
                    # shape key counts as compiled, so an eviction of
                    # this entry later classifies as "fallback")
                    key, fn = candidate.warmed
                    self._cache[key] = fn
                    self._mark_compiled_locked(key[1:])
            if outcome == "ok":
                _generation.set(candidate.number)
                # census-driven warmup belongs to the reload itself,
                # not to any one caller: POST /admin/reload, SIGHUP,
                # and a promotion controller's direct engine.reload
                # must all leave the new generation warm for the
                # shapes live traffic has been sending — the canary
                # only seeded ONE (shape, bucket) executable
                # (docs/serving.md zero-post-swap-compiles contract)
                try:
                    self.warmup_from_census()
                except Exception:
                    pass   # warmup is an optimization, never a failure
            record = {"outcome": outcome, "error": error,
                      "path": target, "canary": canary_result,
                      "generation": (candidate.number
                                     if outcome == "ok" else old.number),
                      "duration_ms": (time.monotonic() - t0) * 1e3,
                      "at": time.time()}
            with self._lock:
                self.last_reload = record
            _reloads.inc(outcome=outcome)
            return record
        finally:
            self._reload_lock.release()

    def reload_status(self) -> dict:
        """Generation + last reload outcome for /healthz."""
        with self._lock:
            return {"model_generation": self._gen.number,
                    "last_reload": dict(self.last_reload)
                    if self.last_reload else None}

    # -- introspection ----------------------------------------------------
    def resilience_state(self) -> str:
        """``ok`` (circuit closed) | ``degraded`` (open, native CPU
        fallback serving) | ``open`` (open and no fallback — requests
        get 503 + Retry-After).  /healthz surfaces this verbatim.

        ``degraded`` is only reported once the fallback has actually
        loaded — a balancer keeps a ``degraded`` replica in rotation,
        so the promise that it still serves 200s must be PROVEN, not
        assumed; the lazy load is attempted (and cached on the current
        generation) here if no request has triggered it yet."""
        if self.backend == "native" or self.breaker.state == "closed":
            return "ok"
        return "degraded" if self._current().native_model() is not None \
            else "open"

    def metrics(self) -> dict:
        with self._lock:
            m = dict(self._stats)
            # cache length must be read under the same lock that
            # guards insert/evict (zlint lock-discipline finding: a
            # scrape racing an eviction read torn LRU state)
            m["cached_executables"] = len(self._cache)
            m["generation"] = self._gen.number
        m.setdefault("reloads", 0)
        m.setdefault("cache_hits", 0)
        m.setdefault("cache_misses", 0)
        m.setdefault("cache_evictions", 0)
        m.setdefault("forward_calls", 0)
        m.setdefault("forward_failures", 0)
        m.setdefault("fallback_calls", 0)
        m.setdefault("retries", 0)
        m.setdefault("weight_pageins", 0)
        m.setdefault("weight_releases", 0)
        m.setdefault("device_ms_total", 0.0)
        m.setdefault("quantize_fallbacks", 0)
        m["quantize_mode"] = self.quantize
        m["quantized"] = self.quantized_active()
        m["weight_bytes"] = self.weight_nbytes()
        m["weights_resident"] = self.weights_resident()
        m["backend"] = self.backend
        m["buckets"] = list(self.buckets)
        m["tensor_parallel"] = self.tp if self._mesh is not None else 1
        m["mesh"] = "x".join(str(d) for d in self.mesh_shape)
        m["breaker"] = self.breaker.metrics()
        m["resilience_state"] = self.resilience_state()
        return m

    @property
    def n_layers(self) -> int:
        return len(self.layers)

    def close(self) -> None:
        if self._tmpdir is not None:
            self._tmpdir.cleanup()
            self._tmpdir = None
