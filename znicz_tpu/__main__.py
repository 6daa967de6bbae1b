"""CLI: ``python -m znicz_tpu <workflow> [<config.py>] [options]``.

Parity target: the reference ``veles/__main__.py`` (SURVEY.md §2.1 L7):
two-file workflow+config UX, snapshot resume, backend selection, config
overrides, distributed bootstrap flags.

Examples::

    python -m znicz_tpu znicz_tpu.models.mnist
    python -m znicz_tpu my_workflow.py my_config.py --backend=xla
    python -m znicz_tpu znicz_tpu.models.mnist --set mnist.minibatch_size=50
    python -m znicz_tpu znicz_tpu.models.mnist --snapshot snapshots/s_best.npz
    python -m znicz_tpu wf.py cfg.py --coordinator=host:1234 \
        --num-processes=4 --process-id=0        # multi-host SPMD
    python -m znicz_tpu serve --model model.znn --port 8100
        # batched inference serving of a .znn export (znicz_tpu.serving);
        # GET /metrics speaks JSON or Prometheus text (Accept header),
        # --profile-dir captures a jax.profiler trace, every
        # POST /predict carries an X-Request-Id (docs/observability.md),
        # and POST /admin/reload (or SIGHUP) hot-reloads the model with
        # verify + canary + rollback (docs/durability.md)
    python -m znicz_tpu serve --model model.znn \
            --quantize int8 --memoize 1024
        # request-path speed levers (docs/serving.md "Wire protocol"):
        # POST /predict also accepts/answers the zero-copy binary
        # tensor format (Content-Type/Accept:
        # application/x-znicz-tensor), --memoize answers repeat inputs
        # from a generation-keyed per-model cache without a device
        # call, and --quantize int8 serves verified per-channel int8
        # weight copies of the fc-heavy families (fp32 fallback,
        # counted, on tolerance breach)
    python -m znicz_tpu serve --zoo DIR --memory-budget-mb 64
        # multi-tenant model zoo: every *.znn in DIR becomes a routable
        # model (X-Model header / body "model" field; repeatable
        # --model name=path,criticality=...,quota-rps=... adds or
        # overrides entries) with per-model engines, batchers, quotas,
        # criticality/deadline classes, per-model /admin/reload, and a
        # weight-residency LRU under the memory budget
        # (docs/serving.md "Multi-tenant model zoo")
    python -m znicz_tpu serve --model model.znn \
            --slo availability,target=99.9 --slo-interval-s 10
        # declare per-model SLOs judged as rolling multi-window burn
        # rates (telemetry.sloengine): GET /alertz serves the firing
        # alerts + per-SLO burns/budgets, /statusz grows an SLO
        # section, and slo_burn_rate / slo_budget_remaining /
        # slo_alerts_total join the scrape
        # (docs/observability.md "SLO engine")
    python -m znicz_tpu route --backend http://127.0.0.1:8101 \
            --backend http://127.0.0.1:8102 --port 8200
        # fleet router tier (znicz_tpu.fleet; docs/fleet.md): spread
        # POST /predict over N independent `serve` backends with
        # weighted routing (live via POST /admin/weight), per-backend
        # circuit breakers + ejection/re-admission + failover, the
        # X-Deadline-Ms/X-Criticality/X-Request-Id wire contract
        # re-issued per hop (deadline decremented by hop latency),
        # JSON + binary payload pass-through, and aggregated
        # /healthz + /metrics (fleet_*{backend=...}) + /statusz
    python -m znicz_tpu route --backend ... --placement 1
        # + placement-aware zoo sharding: each zoo tenant is assigned
        # to a scored subset of backends (weighted rendezvous —
        # residency affinity, busy penalty, cache-warm consistency,
        # --placement N = replication factor), the router routes a
        # tenant only inside its set (failing over in-set first,
        # degrading to any-healthy rather than refusing), pushes
        # eviction hints down to every backend zoo, and re-places
        # live via POST /admin/placement (pin/rebalance; docs/fleet.md)
    python -m znicz_tpu autoscale --serve-arg=--zoo --serve-arg=DIR \
            --min-backends 1 --max-backends 4
        # elastic fleet (= route --autoscale): boots real `serve`
        # processes, scales OUT on sustained SLO burn at the router
        # tier (fleet_request_latency_ms + errors), scales IN through
        # the graceful drain, with hysteresis + cooldown so a
        # one-window blip never flaps the fleet; placement re-runs on
        # every membership change (fleet.autoscaler; docs/fleet.md)
    python -m znicz_tpu autoscale --serve-arg=--zoo --serve-arg=DIR \
            --state-dir /var/lib/znicz-router
        # + crash-safe control plane (fleet.statestore; docs/fleet.md
        # "Control-plane durability"): every admin weight, placement
        # pin, membership change and child boot/drain is journaled to
        # an fsync'd torn-tail-tolerant JSONL; a restarted router
        # replays its decisions, answers 503 + Retry-After while it
        # RECONCILES the journaled children — re-adopting live ones
        # in place (pid + start-time identity + healthz + a predict
        # canary), draining half-dead or unknown-generation ones —
        # and the SIGTERM default flips to journal-and-keep
        # (--teardown restores drain-everything).  Gray-failure
        # demotion rides the same bookkeeping: a probe-green backend
        # whose real predicts fail or stall is weight-decayed to
        # zero and ejected (disable with --no-gray-demotion)
    python -m znicz_tpu route --state-dir S --port 8200 &
    python -m znicz_tpu route --state-dir S --port 8201 \
            --standby-of http://127.0.0.1:8200/
        # highly-available fleet front (fleet.ha; docs/fleet.md
        # "Router high availability"): any --state-dir router holds
        # an fsync'd LEASE carrying a monotonically increasing epoch;
        # the hot standby tails the same journal (weights/pins/
        # members stay warm), probes the primary's /healthz, answers
        # its own traffic 503 + Retry-After, and on lease expiry —
        # or a dead holder pid — takes over: epoch bump, adopt the
        # journal's live children, serve.  Every journal mutation and
        # autoscaler boot/drain is epoch-FENCED: a deposed primary
        # waking from a GC pause/partition sees the newer epoch,
        # refuses its own stale mutations and demotes itself to
        # standby (never double-boots a backend).  --peer URL races
        # two symmetric routers for the lease instead; --lease-ttl-s
        # / --lease-renew-s tune the failover window
    python -m znicz_tpu promote --candidates DIR \
            --url http://127.0.0.1:8200/ --fleet
        # promote-one-then-fleet over a router: canary ONE backend
        # (weight-reduced), SLO-watch it, then walk the remaining
        # backends with weighted traffic splitting and fleet-wide
        # rollback on a mid-walk burn-rate breach (fleet.rollout)
    python -m znicz_tpu chaos \
            [--scenario reload|promote|overload|zoo|slo|wire|fleet|placement|controlplane|san|ha]
        # serving-under-fault smoke: boots the server under a canned
        # fault plan and checks graceful degradation (resilience.chaos);
        # --scenario reload drills corrupt-artifact rollback;
        # --scenario promote drives the closed promotion loop (N
        # train-while-serving promotions + an SLO-breaching candidate
        # auto-rolled-back, zero dropped requests; docs/promotion.md);
        # --scenario overload drills the overload defenses (deadlines,
        # retry budget, hedged dispatch, adaptive shedding, graceful
        # drain under 4x load with one slow replica; docs/resilience.md);
        # --scenario zoo drills multi-tenant serving (three families
        # under a memory budget forcing weight eviction, one tenant
        # latency-faulted, one reloaded mid-burst; docs/serving.md);
        # --scenario slo drills the burn-rate SLO engine (one tenant
        # latency-faulted => exactly one alert, the quiet tenant's
        # budget intact, per-tenant device-ms ledger sums;
        # docs/observability.md);
        # --scenario wire drills the binary wire protocol + response
        # memoization + int8 serving under a transient device fault
        # (zero raw 500s on either format, junk binary answers 400
        # fast, cross-format parity, reload swaps the memo key space;
        # docs/serving.md "Wire protocol");
        # --scenario controlplane drills the crash-safe control plane
        # (SIGKILL the router mid-burst, restart with --state-dir,
        # weights/pins restored, children re-adopted with zero
        # orphans/double-boots, 503+Retry-After while reconciling, a
        # healthz-green/predict-sick backend gray-demoted to ~zero
        # effective weight; docs/fleet.md);
        # --scenario ha drills the highly-available fleet front
        # (primary + hot standby over one state dir, primary
        # SIGKILLed mid-burst: one lease epoch bump, children
        # adopted, first 200 within 2x the lease TTL, the
        # resurrected old primary fenced to standby, zero raw 500s;
        # docs/fleet.md "Router high availability");
        # --scenario san replays the zoo drill with every package lock
        # wrapped by the runtime concurrency sanitizer — fails on any
        # observed lock-order inversion or an empty acquisition graph
        # (znicz_tpu.sanitizer; docs/static_analysis.md "Runtime
        # sanitizer"; tools/san_smoke.sh)
    python -m znicz_tpu promote --candidates DIR --url http://host:port/
        # closed-loop promotion controller sidecar: watch a trainer's
        # export directory, verify + canary-deploy each new candidate
        # to a running `serve` replica, SLO-watch the live telemetry,
        # auto-rollback on regression (znicz_tpu.promotion)
    python -m znicz_tpu serve --model m.znn --capture-dir cap
        # + traffic tap: every served /predict answer appends (input,
        # outputs) to a bounded fsync'd segment ring — fail-open (a
        # capture failure never fails an answer) and sampled
        # (--capture-sample); the continual trainer replays it
        # (docs/online.md)
    python -m znicz_tpu online-train --model m.znn \
            --capture-dir cap --candidates cands
        # continual trainer sidecar: fine-tune the served model (fc
        # chain, or Kohonen ONLINE mode for a SOM head) on replayed
        # capture traffic in bounded rounds, judge each round against
        # a held-back slice, export only blessed candidates — which
        # `promote [--fleet]` then canaries/watches/rolls out with
        # zero new promotion code (docs/online.md)
    python -m znicz_tpu lint [--format json|text] [--baseline ...] \
            [--changed] [--list-rules]
        # zlint: AST-based concurrency & JAX-hygiene analyzer over the
        # package (znicz_tpu.analysis; docs/static_analysis.md); exits
        # non-zero on new findings — tier-1 gates on it (pytest -m lint);
        # --changed scopes the per-module pass to git-modified files
        # (repo-wide rules like lock-order-cycle still see everything)
"""

from __future__ import annotations

import argparse
import sys

from .launcher import Launcher


def make_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="znicz_tpu",
        description="TPU-native unit/workflow training engine")
    p.add_argument("workflow",
                   help="workflow module: a .py path or dotted name")
    p.add_argument("config", nargs="?", default=None,
                   help="config file (python executed against `root`)")
    p.add_argument("--backend", default="auto",
                   choices=("auto", "numpy", "xla"))
    p.add_argument("--snapshot", default=None,
                   help="resume from a snapshot .npz")
    p.add_argument("--epochs", type=int, default=None)
    p.add_argument("--fused", action="store_true",
                   help="train via the fused whole-step path")
    p.add_argument("--seed", type=int, default=None)
    p.add_argument("--set", dest="overrides", action="append", default=[],
                   metavar="PATH=VALUE",
                   help="config override, e.g. --set mnist.layers=[...]")
    p.add_argument("--profile", default=None, metavar="DIR",
                   help="dump a jax.profiler trace of the run into DIR "
                        "(view with TensorBoard / xprof)")
    p.add_argument("--timeline-jsonl", default=None, metavar="PATH",
                   help="append one JSON line per fused host step with "
                        "the wall/device/host time split (also: "
                        "$ZNICZ_TIMELINE_JSONL; docs/observability.md)")
    p.add_argument("--coordinator", default=None,
                   help="host:port of process 0 (multi-host SPMD)")
    p.add_argument("--num-processes", type=int, default=1)
    p.add_argument("--process-id", type=int, default=0)
    p.add_argument("--mesh", default=None, metavar="DP[,TP]",
                   help="lay the fused train step out over a "
                        "(data, model) device mesh, e.g. '8' (pure "
                        "data parallel) or '4,2' (dp=4, tp=2); "
                        "implies --fused semantics on wf.train; "
                        "'1,1' or omitted = single-device jit "
                        "(docs/distributed.md)")
    p.add_argument("--compile-cache-dir", default=None, metavar="DIR",
                   help="where the persistent XLA compilation cache "
                        "lives (also: $ZNICZ_COMPILE_CACHE; default "
                        "<checkout>/.cache/xla).  A set "
                        "$JAX_COMPILATION_CACHE_DIR wins over both "
                        "(docs/performance.md)")
    return p


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if argv and argv[0] == "serve":
        # inference serving is its own sub-CLI (a .znn path, not a
        # workflow module) — see znicz_tpu/serving/server.py
        from .serving.server import main as serve_main
        return serve_main(argv[1:])
    if argv and argv[0] == "route":
        # the fleet router tier: spread /predict over N serve
        # backends — see znicz_tpu/fleet and docs/fleet.md
        from .fleet.router import main as route_main
        return route_main(argv[1:])
    if argv and argv[0] == "autoscale":
        # elastic fleet: `route --autoscale` under its own name —
        # boots/drains serve processes on the SLO burn signal — see
        # znicz_tpu/fleet/autoscaler.py and docs/fleet.md
        from .fleet.autoscaler import main as autoscale_main
        return autoscale_main(argv[1:])
    if argv and argv[0] == "chaos":
        # fault-injection smoke of the serving stack — see
        # znicz_tpu/resilience/chaos.py and tools/chaos_smoke.sh
        from .resilience.chaos import main as chaos_main
        return chaos_main(argv[1:])
    if argv and argv[0] == "promote":
        # the closed-loop promotion controller sidecar — see
        # znicz_tpu/promotion and docs/promotion.md
        from .promotion.cli import main as promote_main
        return promote_main(argv[1:])
    if argv and argv[0] == "online-train":
        # the continual trainer sidecar: replayed capture traffic →
        # bounded bless/refuse rounds → candidates for `promote` —
        # see znicz_tpu/online and docs/online.md
        from .online.cli import main as online_main
        return online_main(argv[1:])
    if argv and argv[0] == "lint":
        # static analysis gate — znicz_tpu/analysis, tools/lint.sh
        from .analysis.cli import main as lint_main
        return lint_main(argv[1:])
    args = make_parser().parse_args(argv)
    if args.mesh and not args.fused:
        # --mesh implies the fused path (the tick loop runs
        # single-device and would silently ignore the mesh — an
        # operator who asked for 4x2 must not benchmark 1x1)
        print("--mesh implies --fused: taking the fused train path",
              file=sys.stderr)
        args.fused = True
    launcher = Launcher(
        workflow=args.workflow, config=args.config, backend=args.backend,
        snapshot=args.snapshot, epochs=args.epochs, fused=args.fused,
        seed=args.seed, overrides=args.overrides,
        coordinator=args.coordinator, num_processes=args.num_processes,
        process_id=args.process_id, profile=args.profile,
        timeline_jsonl=args.timeline_jsonl, mesh=args.mesh,
        compile_cache_dir=args.compile_cache_dir)
    wf = launcher.run()
    decision = getattr(wf, "decision", None)
    if decision is not None and decision.epoch_metrics:
        for m in decision.epoch_metrics[-3:]:
            print(m)
    return 0


if __name__ == "__main__":
    sys.exit(main())
