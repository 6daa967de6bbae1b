"""Read bench.py transcripts (JSONL, one row per line) and print the
lever verdicts: the opt-in levers are DECIDED from the measured A/B,
not left as unmeasured debt (ROADMAP D3 keeps this tool until Speed 2
has ruled).

Round-5 semantics (the fused2 default FLIPPED this round, per VERDICT
r4 item 1, on the 1.78× on-chip b128 ablation):

* ``LRN_POOL fused2 vs fused1`` — fused2 is now the DEFAULT.  With
  both batches measured, the verdict is ``keep-default-fused2`` if
  fused2 beats fused1 by >3% mean with no loss at either batch (the
  original flip rule, now confirming the flip), ``revert-to-fused1``
  on a loss at EITHER batch (the symmetric promise in the shipped
  default's risk note), else ``marginal-keep``.  One surviving batch
  is ``insufficient-data`` — it can neither confirm nor revert.
* ``CONV1 s2d vs direct`` — still opt-in: ``flip-default`` on a >3%
  mean win with no loss at both batches, else ``keep-off``.  s2d is
  evaluated separately under each LRN_POOL context it was measured in
  (under fused2 only conv1 can take s2d; under fused1 the pair-fed
  convs can too), because the verdict may differ.

Rows are compared by their **resolved routing** (the ``resolved``
field bench.py stamped from round 5 to PR 31 — env levers + defaults
already applied; a later row ran the one routing the program has) and
their **code revision** (the ``rev`` sha stamped since
round 6): rows from different revisions neither average nor pair, so
a keep/revert verdict never mixes measurements of different code.
Pre-round-5 rows carry only explicit env levers; they are
canonicalized against the ROUND-4 defaults they actually ran under
(LRN_POOL=fused1, CONV1=direct, CONV=xla, PALLAS=on, MXU=bf16), so
"no levers" rows from a round-4 transcript keep meaning fused1 even
though today's default is fused2.

Prints one JSON line: {"decisions": {...}, "evidence": {...}} and a
human table on stderr.

Usage: python tools/decide_levers.py TRANSCRIPT.jsonl [...]
"""
import json
import sys

#: defaults pre-round-5 transcript rows (no ``resolved`` field) ran
#: under — the canonicalization target for legacy "levers"-only rows
_LEGACY_DEFAULTS = {"LRN_POOL": "fused1", "CONV1": "direct",
                    "CONV": "xla", "PALLAS": "on", "MXU": "bf16"}
_ROUTING_KEYS = tuple(_LEGACY_DEFAULTS)


def load(paths):
    """Rows from every transcript that can be read; a missing or
    unreadable file (fresh checkout, renamed burn output) warns on
    stderr and is skipped — it must not traceback into a
    silently-empty .decisions file."""
    rows = []
    for p in paths:
        try:
            f = open(p)
        except OSError as e:
            print(f"warning: cannot read transcript {p} ({e}), "
                  f"skipping", file=sys.stderr)
            continue
        with f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rows.append(json.loads(line))
                except json.JSONDecodeError:
                    print(f"skipping unparseable line in {p}: "
                          f"{line[:80]}", file=sys.stderr)
    return rows


def canonical(row):
    """Resolved routing config for a transcript row, as a hashable
    sorted-items tuple."""
    res = row.get("resolved")
    if not isinstance(res, dict):
        # unstamped: a pre-round-5 row, or (with the ``rev`` every row
        # carries since round 6) a row of the program without routing
        # levers, PR 32 on
        res = dict(_SHIPPED if row.get("rev") else _LEGACY_DEFAULTS)
        lv = row.get("levers", {})
        if "ZNICZ_TPU_LRN_POOL" in lv:
            val = lv["ZNICZ_TPU_LRN_POOL"]
            # legacy "fused" meant the then-default merge+fold phase-1
            res["LRN_POOL"] = "fused1" if val == "fused" else val
        if lv.get("ZNICZ_TPU_CONV1") == "s2d":
            res["CONV1"] = "s2d"
        if lv.get("ZNICZ_TPU_CONV") == "pallas":
            res["CONV"] = "pallas"
        if lv.get("ZNICZ_TPU_NO_PALLAS") == "1":
            res["PALLAS"] = "off"
        if lv.get("ZNICZ_TPU_MXU"):
            res["MXU"] = lv["ZNICZ_TPU_MXU"].lower()
    cfg = {k: res.get(k, _LEGACY_DEFAULTS[k]) for k in _ROUTING_KEYS}
    return tuple(sorted(cfg.items()))


def headline(rows):
    """{(config, minibatch, rev): mean images/sec} for AlexNet training
    rows on a real (non-cpu-fallback) device.  Repeated measurements of
    the same configuration (burn re-runs, multiple transcripts) AVERAGE
    — the ±15%-wobble argument behind the 3% threshold assumes means,
    not an arbitrary last sample.

    The code revision (the ``rev`` sha bench.py stamps since round 6)
    is part of the key: rows measured on different code must neither
    average together nor pair as an A/B — a lever verdict drawn across
    a code change measures the change, not the lever (ADVICE r5).
    Pre-stamp rows carry rev None and keep pairing among themselves."""
    acc = {}
    for r in rows:
        if r.get("metric") != "alexnet_train_images_per_sec_per_chip" \
                or r.get("value") is None:
            continue
        if "cpu" in str(r.get("device", "")).lower():
            continue                      # fallback rows decide nothing
        # the sharding scheme keys like the minibatch: a "4x2" mesh row
        # and a "1x1" row measure different programs and must neither
        # average nor pair (legacy rows predate the stamp and were all
        # single-device, so they canonicalize to "1x1")
        acc.setdefault((canonical(r), r.get("minibatch"),
                        r.get("rev"), r.get("sharding") or "1x1"),
                       []).append(r["value"])
    for key, vals in acc.items():
        if len(vals) > 1:
            cfg, mb, rev, sharding = key
            print(f"  averaging {len(vals)} samples for "
                  f"{_short(cfg)} b{mb} s{sharding}"
                  + (f" @{rev}" if rev else ""), file=sys.stderr)
    return {k: round(sum(v) / len(v), 1) for k, v in acc.items()}


#: what an old row's unset levers stood for when it was written (fused2
#: since round 5), the one copy in this module.  The program has no
#: routing levers since PR 32: every row it writes now ran this routing.
_SHIPPED = {"LRN_POOL": "fused2", "CONV1": "direct", "CONV": "xla",
            "PALLAS": "on", "MXU": "bf16"}


def _short(cfg):
    """Compact human tag: only the keys that differ from the shipped
    defaults."""
    parts = [f"{k}={v}" for k, v in sorted(dict(cfg).items())
             if _SHIPPED.get(k) != v]
    return ",".join(parts) or "default"


def compare(hl, key, challenger, baseline):
    """All (minibatch, context) pairs where a challenger-config row has
    a baseline twin differing ONLY in `key` — same minibatch, same
    code revision (a pair straddling a code change measures the code
    change, not the lever), and same sharding scheme (a mesh row and a
    single-device row measure different programs)."""
    pairs = []
    # rows without a minibatch field sort as 0, not TypeError
    for (cfg, mb, rev, sharding), v in sorted(
            hl.items(), key=lambda kv: (kv[0][1] or 0, kv[0][0],
                                        kv[0][2] or "", kv[0][3])):
        d = dict(cfg)
        if d.get(key) != challenger:
            continue
        d[key] = baseline
        bk = (tuple(sorted(d.items())), mb, rev, sharding)
        if bk in hl:
            ctx = {k: v2 for k, v2 in cfg if k != key}
            pairs.append({"minibatch": mb, "rev": rev,
                          "sharding": sharding, "context": _short(
                tuple(sorted(ctx.items()))),
                # decided against the cfg itself, not the display tag
                "shipped_context": all(
                    _SHIPPED.get(k) == v2 for k, v2 in ctx.items()),
                "baseline": hl[bk], "challenger": v,
                "gain_pct": round(100 * (v - hl[bk]) / hl[bk], 1)})
    return pairs


def rev_order(rows):
    """{rev: latest ISO ts} over headline-eligible rows — orders code
    revisions by when they were last measured (ISO timestamps sort
    lexicographically).  The rev=None pseudo-revision is never entered:
    unstamped rows must sort OLDEST regardless of their ts, or one
    fresh no-git row would let stale legacy pairs outrank a cleanly
    stamped revision's verdict."""
    order = {}
    for r in rows:
        if r.get("metric") != "alexnet_train_images_per_sec_per_chip" \
                or r.get("value") is None:
            continue
        if "cpu" in str(r.get("device", "")).lower():
            continue
        rev = r.get("rev")
        if rev is None:
            continue
        ts = str(r.get("ts") or "")
        if ts >= order.get(rev, ""):
            order[rev] = ts
    return order


def _qualified(pairs, order=None):
    """Pairs from ONE (revision, sharding) context that measured BOTH
    batches: the two-batch sufficiency rule must hold within one code
    revision AND one sharding scheme (a b128 pair from rev A plus a
    b256 pair from rev B is two single-batch observations of different
    code; a b128 1x1 pair plus a b256 4x2 pair is two single-batch
    observations of different PROGRAMS), and when several contexts
    each carry a complete A/B, the newest revision decides — with the
    single-device scheme preferred at equal recency, because lever
    defaults ship for the single-device program."""
    by_ctx = {}
    for p in pairs:
        by_ctx.setdefault((p.get("rev"), p.get("sharding") or "1x1"),
                          set()).add(p["minibatch"])
    full = [ctx for ctx, mbs in by_ctx.items() if len(mbs) >= 2]
    if not full:
        return []
    order = order or {}
    winner = max(full, key=lambda c: (
        order.get(c[0], ""),
        c[1] == "1x1",                               # shipped program
        sum(1 for p in pairs                         # deterministic
            if (p.get("rev"), p.get("sharding") or "1x1") == c),
        c[0] or ""))                                 # tie-breakers
    return [p for p in pairs
            if (p.get("rev"), p.get("sharding") or "1x1") == winner]


def _win(pairs, order=None):
    """The codified rule: >3% mean gain with no loss at either batch,
    and at least two measured batches (one surviving pair — the other
    bench run timed out — is not enough evidence) — within a single
    code revision (see _qualified)."""
    pairs = _qualified(pairs, order)
    if len({p["minibatch"] for p in pairs}) < 2:
        return None
    gains = [p["gain_pct"] / 100 for p in pairs]
    return min(gains) > 0 and sum(gains) / len(gains) > 0.03


def lrn_pool_verdict(pairs, order=None):
    """Verdict on the SHIPPED default, so only pairs measured in the
    shipped context (every other routing key at its default, i.e.
    CONV1=direct) decide it: the burn also measures fused2-vs-fused1
    under CONV1=s2d, and a loss in that opt-in context must not veto a
    default that wins where it ships (nor may a b128-s2d pair plus a
    b256-direct pair masquerade as "both batches measured")."""
    pairs = [p for p in pairs if p.get("shipped_context")]
    if not pairs:
        return "no-data (flip stands on the r4 ablation; re-run the " \
               "A/B)"
    # qualify ONCE: the win test and the revert evidence below must be
    # drawn from the same pair set (_qualified is idempotent, so the
    # nested call inside _win re-selects the same pairs)
    pairs = _qualified(pairs, order) or pairs
    win = _win(pairs, order)
    if win is None:
        # one surviving batch can neither confirm nor revert a
        # default — a single noisy pair is exactly the ±15% wobble the
        # two-batch rule exists to exclude
        return "insufficient-data (re-run the missing batch)"
    if win:
        return "keep-default-fused2 (confirmed)"
    # the revert is decided by the same evidence set the win rule uses:
    # the qualified (both-batch, newest-revision) pairs selected above
    losses = [p for p in pairs if p["gain_pct"] < 0]
    if losses:
        # the flip's own risk note promised a revert on a loss at
        # EITHER batch — symmetric with the no-loss-both-batches rule
        # that would have gated the flip
        return "revert-to-fused1 (loss at " + ", ".join(
            f"b{p['minibatch']}: {p['gain_pct']}%" for p in losses) + ")"
    return "marginal-keep (within wobble)"


def conv1_verdicts(pairs, order=None):
    """Per-context verdicts: under fused2 only conv1 can take s2d,
    under fused1 the pair-fed convs can too — pooling the contexts
    would let one context's loss veto the other's win."""
    if not pairs:
        return "no-data"
    out = {}
    for ctx in sorted({p["context"] for p in pairs}):
        cp = [p for p in pairs if p["context"] == ctx]
        win = _win(cp, order)
        out[ctx] = ("flip-default" if win
                    else "insufficient-data (re-run the missing batch)"
                    if win is None else "keep-off")
    return out


def main(argv):
    if not argv:
        print(__doc__, file=sys.stderr)
        return 2
    rows = load(argv)
    hl = headline(rows)
    if not hl:
        print(json.dumps({"decisions": {},
                          "error": "no on-device headline rows in "
                                   "transcript"}))
        return 1
    decisions, evidence = {}, {}
    order = rev_order(rows)

    pairs = compare(hl, "LRN_POOL", "fused2", "fused1")
    evidence["LRN_POOL fused2 vs fused1"] = pairs
    decisions["LRN_POOL"] = lrn_pool_verdict(pairs, order)

    pairs = compare(hl, "CONV1", "s2d", "direct")
    evidence["CONV1 s2d vs direct"] = pairs
    decisions["CONV1"] = conv1_verdicts(pairs, order)

    for (cfg, mb, rev, sharding), v in sorted(
            hl.items(), key=lambda kv: (kv[0][1] or 0,
                                        _short(kv[0][0]),
                                        kv[0][2] or "", kv[0][3])):
        print(f"  {_short(cfg):36s} b{mb}"
              + (f" s{sharding}" if sharding != "1x1" else "")
              + (f" @{rev}" if rev else "")
              + f": {v} img/s", file=sys.stderr)
    for lever, d in decisions.items():
        print(f"  {lever}: {d}", file=sys.stderr)
    print(json.dumps({"decisions": decisions, "evidence": evidence}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
