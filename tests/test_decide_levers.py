"""tools/decide_levers.py — the codified lever-decision rule.

Round 5 flipped the fused2 default, which silently re-aims any
transcript row tagged only by explicit env levers; the tool now
compares rows by resolved routing, canonicalizing pre-round-5 rows
against the round-4 defaults they actually ran under.  These tests pin
that canonicalization and the verdict rules, because a wrong verdict
here flips (or fails to revert) a shipped default."""

import importlib.util
import os
import sys

_TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools", "decide_levers.py")
_spec = importlib.util.spec_from_file_location("decide_levers", _TOOLS)
dl = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(dl)


def _row(value, mb, resolved=None, levers=None, device="TPU v5 lite",
         rev=None, sharding=None):
    r = {"metric": "alexnet_train_images_per_sec_per_chip",
         "value": value, "minibatch": mb, "device": device}
    if sharding is not None:
        r["sharding"] = sharding
    if resolved is not None:
        base = {"LRN_POOL": "fused2", "CONV1": "direct", "CONV": "xla",
                "PALLAS": "on", "MXU": "bf16"}
        base.update(resolved)
        r["resolved"] = base
    if levers is not None:
        r["levers"] = levers
    if rev is not None:
        r["rev"] = rev
    return r


class TestCanonical:
    def test_legacy_default_rows_mean_fused1(self):
        """Pre-round-5 rows with no levers ran under the fused1
        default — they must NOT be read as today's fused2 default."""
        cfg = dict(dl.canonical({"value": 1.0}))
        assert cfg["LRN_POOL"] == "fused1"
        assert cfg["CONV1"] == "direct"

    def test_legacy_fused_alias(self):
        cfg = dict(dl.canonical(
            {"levers": {"ZNICZ_TPU_LRN_POOL": "fused"}}))
        assert cfg["LRN_POOL"] == "fused1"

    def test_resolved_field_wins(self):
        cfg = dict(dl.canonical(_row(1.0, 128,
                                     resolved={"LRN_POOL": "fused2"})))
        assert cfg["LRN_POOL"] == "fused2"

    def test_cpu_fallback_rows_decide_nothing(self):
        hl = dl.headline([_row(9.9, 128, device="cpu-fallback (cpu)")])
        assert hl == {}


class TestVerdicts:
    def _hl(self, rows):
        return dl.headline(rows)

    def test_fused2_confirmed(self):
        hl = self._hl([
            _row(3700.0, 128, resolved={"LRN_POOL": "fused1"}),
            _row(3600.0, 256, resolved={"LRN_POOL": "fused1"}),
            _row(6500.0, 128, resolved={"LRN_POOL": "fused2"}),
            _row(6300.0, 256, resolved={"LRN_POOL": "fused2"}),
        ])
        pairs = dl.compare(hl, "LRN_POOL", "fused2", "fused1")
        assert len(pairs) == 2
        assert dl._win(pairs) is True

    def test_fused2_net_loss_means_revert(self):
        hl = self._hl([
            _row(3700.0, 128, resolved={"LRN_POOL": "fused1"}),
            _row(3600.0, 256, resolved={"LRN_POOL": "fused1"}),
            _row(3500.0, 128, resolved={"LRN_POOL": "fused2"}),
            _row(3400.0, 256, resolved={"LRN_POOL": "fused2"}),
        ])
        pairs = dl.compare(hl, "LRN_POOL", "fused2", "fused1")
        assert dl._win(pairs) is False
        assert sum(p["gain_pct"] for p in pairs) < 0

    def test_one_batch_is_insufficient(self):
        """One surviving pair (the other bench run timed out) must not
        confirm a default."""
        hl = self._hl([
            _row(3700.0, 128, resolved={"LRN_POOL": "fused1"}),
            _row(6500.0, 128, resolved={"LRN_POOL": "fused2"}),
        ])
        pairs = dl.compare(hl, "LRN_POOL", "fused2", "fused1")
        assert dl._win(pairs) is None

    def test_repeated_measurements_average(self):
        hl = self._hl([
            _row(3000.0, 128, resolved={"LRN_POOL": "fused1"}),
            _row(4000.0, 128, resolved={"LRN_POOL": "fused1"}),
        ])
        key = (dl.canonical(_row(1.0, 128,
                                 resolved={"LRN_POOL": "fused1"})),
               128, None, "1x1")
        assert hl[key] == 3500.0

    def test_s2d_compared_within_each_pair_context(self):
        """s2d rows only pair with a twin differing ONLY in CONV1 —
        the fused1 and fused2 contexts get separate evidence rows."""
        hl = self._hl([
            _row(6500.0, 128, resolved={"LRN_POOL": "fused2"}),
            _row(6700.0, 128, resolved={"LRN_POOL": "fused2",
                                        "CONV1": "s2d"}),
            _row(3700.0, 128, resolved={"LRN_POOL": "fused1"}),
            _row(3900.0, 128, resolved={"LRN_POOL": "fused1",
                                        "CONV1": "s2d"}),
        ])
        pairs = dl.compare(hl, "CONV1", "s2d", "direct")
        assert len(pairs) == 2
        contexts = {p["context"] for p in pairs}
        assert contexts == {"default", "LRN_POOL=fused1"}


class TestShardingDiscipline:
    """A mesh-sharded row and a single-device row measure different
    programs: they neither average nor pair, and legacy rows without
    the stamp canonicalize to single-device '1x1'."""

    def test_cross_sharding_rows_do_not_average(self):
        hl = dl.headline([
            _row(3000.0, 128, resolved={"LRN_POOL": "fused1"}),
            _row(9000.0, 128, resolved={"LRN_POOL": "fused1"},
                 sharding="4x2"),
        ])
        cfg = dl.canonical(_row(1.0, 128,
                                resolved={"LRN_POOL": "fused1"}))
        assert hl[(cfg, 128, None, "1x1")] == 3000.0
        assert hl[(cfg, 128, None, "4x2")] == 9000.0

    def test_cross_sharding_rows_do_not_pair(self):
        hl = dl.headline([
            _row(3700.0, 128, resolved={"LRN_POOL": "fused1"}),
            _row(6500.0, 128, resolved={"LRN_POOL": "fused2"},
                 sharding="4x2"),
        ])
        assert dl.compare(hl, "LRN_POOL", "fused2", "fused1") == []

    def test_same_sharding_rows_pair(self):
        hl = dl.headline([
            _row(3700.0, 128, resolved={"LRN_POOL": "fused1"},
                 sharding="4x2"),
            _row(6500.0, 128, resolved={"LRN_POOL": "fused2"},
                 sharding="4x2"),
        ])
        pairs = dl.compare(hl, "LRN_POOL", "fused2", "fused1")
        assert len(pairs) == 1 and pairs[0]["sharding"] == "4x2"

    def test_cross_sharding_pairs_do_not_jointly_qualify(self):
        """A b128 pair at 1x1 plus a b256 pair at 4x2 is two
        single-batch observations of different programs — together
        they must not satisfy the both-batches rule (the same
        discipline _qualified applies across code revisions)."""
        pairs = [
            {"minibatch": 128, "rev": "aaa", "sharding": "1x1",
             "gain_pct": 5.0},
            {"minibatch": 256, "rev": "aaa", "sharding": "4x2",
             "gain_pct": -4.0},
        ]
        assert dl._qualified(pairs) == []
        same = [dict(p, sharding="1x1") for p in pairs]
        assert dl._qualified(same) == same


class TestRevisionDiscipline:
    """Rows measured on different code revisions neither average nor
    pair (ADVICE r5 medium): a lever verdict drawn across a code change
    measures the change, not the lever."""

    def test_cross_revision_rows_do_not_average(self):
        hl = dl.headline([
            _row(3000.0, 128, resolved={"LRN_POOL": "fused1"},
                 rev="aaa111"),
            _row(4000.0, 128, resolved={"LRN_POOL": "fused1"},
                 rev="bbb222"),
        ])
        cfg = dl.canonical(_row(1.0, 128,
                                resolved={"LRN_POOL": "fused1"}))
        assert hl[(cfg, 128, "aaa111", "1x1")] == 3000.0
        assert hl[(cfg, 128, "bbb222", "1x1")] == 4000.0

    def test_cross_revision_rows_do_not_pair(self):
        hl = dl.headline([
            _row(3700.0, 128, resolved={"LRN_POOL": "fused1"},
                 rev="aaa111"),
            _row(6500.0, 128, resolved={"LRN_POOL": "fused2"},
                 rev="bbb222"),
        ])
        assert dl.compare(hl, "LRN_POOL", "fused2", "fused1") == []

    def test_same_revision_rows_pair(self):
        hl = dl.headline([
            _row(3700.0, 128, resolved={"LRN_POOL": "fused1"},
                 rev="aaa111"),
            _row(6500.0, 128, resolved={"LRN_POOL": "fused2"},
                 rev="aaa111"),
        ])
        pairs = dl.compare(hl, "LRN_POOL", "fused2", "fused1")
        assert len(pairs) == 1 and pairs[0]["rev"] == "aaa111"

    def test_two_single_batch_revisions_are_not_both_batches(self):
        """A b128 pair from rev A plus a b256 pair from rev B must NOT
        satisfy the two-batch sufficiency rule — each revision only
        measured one batch."""
        pairs = [
            {"minibatch": 128, "rev": "aaa111", "context": "default",
             "shipped_context": True, "baseline": 1000.0,
             "challenger": 1100.0, "gain_pct": 10.0},
            {"minibatch": 256, "rev": "bbb222", "context": "default",
             "shipped_context": True, "baseline": 1000.0,
             "challenger": 1100.0, "gain_pct": 10.0},
        ]
        assert dl._win(pairs) is None
        assert dl.lrn_pool_verdict(pairs).startswith(
            "insufficient-data")

    def test_one_full_revision_decides_despite_partial_other(self):
        """Rev A measured both batches (wins); rev B's lone extra pair
        neither blocks nor double-weights the verdict."""
        pairs = [
            {"minibatch": mb, "rev": "aaa111", "context": "default",
             "shipped_context": True, "baseline": 1000.0,
             "challenger": 1100.0, "gain_pct": 10.0}
            for mb in (128, 256)
        ] + [{"minibatch": 128, "rev": "bbb222", "context": "default",
              "shipped_context": True, "baseline": 1000.0,
              "challenger": 900.0, "gain_pct": -10.0}]
        # the single-batch rev B loss is wobble-class evidence, not a
        # revert trigger
        assert dl._win(pairs[:2]) is True
        assert dl.lrn_pool_verdict(pairs).startswith(
            "keep-default-fused2")

    def test_newest_full_revision_decides_alone(self):
        """When two revisions each carry a complete A/B, only the
        newest (by transcript ts) decides — an older revision's loss
        neither vetoes nor dilutes the current code's verdict."""
        def pair(mb, gain, rev):
            return {"minibatch": mb, "rev": rev, "context": "default",
                    "shipped_context": True, "baseline": 1000.0,
                    "challenger": 1000.0 * (1 + gain / 100),
                    "gain_pct": gain}
        pairs = [pair(128, -2.0, "old111"), pair(256, 1.0, "old111"),
                 pair(128, 10.0, "new222"), pair(256, 9.0, "new222")]
        order = {"old111": "2026-07-01T00:00:00Z",
                 "new222": "2026-08-01T00:00:00Z"}
        assert dl._win(pairs, order) is True
        assert dl.lrn_pool_verdict(pairs, order).startswith(
            "keep-default-fused2")
        # flipped recency: the old revision's loss now decides
        order = {"old111": "2026-08-02T00:00:00Z",
                 "new222": "2026-08-01T00:00:00Z"}
        assert dl.lrn_pool_verdict(pairs, order).startswith(
            "revert-to-fused1")

    def test_rev_order_tracks_latest_ts(self):
        rows = [
            _row(1.0, 128, resolved={}, rev="aaa"),
            _row(1.0, 128, resolved={}, rev="aaa"),
            _row(1.0, 256, resolved={}, rev="bbb"),
        ]
        rows[0]["ts"] = "2026-07-01T00:00:00Z"
        rows[1]["ts"] = "2026-07-03T00:00:00Z"
        rows[2]["ts"] = "2026-07-02T00:00:00Z"
        order = dl.rev_order(rows)
        assert order == {"aaa": "2026-07-03T00:00:00Z",
                         "bbb": "2026-07-02T00:00:00Z"}

    def test_unstamped_rows_never_outrank_a_stamped_revision(self):
        """One fresh rev-less row (no-git host) must not promote the
        legacy (rev=None) pair pool over a cleanly stamped revision:
        rev_order never records the None pseudo-revision."""
        fresh_none = _row(1.0, 128, resolved={})
        fresh_none["ts"] = "2026-08-02T00:00:00Z"
        stamped = _row(1.0, 128, resolved={}, rev="abc123")
        stamped["ts"] = "2026-07-30T00:00:00Z"
        order = dl.rev_order([fresh_none, stamped])
        assert None not in order
        assert order == {"abc123": "2026-07-30T00:00:00Z"}

        def pair(mb, gain, rev):
            return {"minibatch": mb, "rev": rev, "context": "default",
                    "shipped_context": True, "baseline": 1000.0,
                    "challenger": 1000.0 * (1 + gain / 100),
                    "gain_pct": gain}
        pairs = [pair(128, -12.0, None), pair(256, -10.0, None),
                 pair(128, 10.0, "abc123"), pair(256, 9.0, "abc123")]
        assert dl.lrn_pool_verdict(pairs, order).startswith(
            "keep-default-fused2")

    def test_unstamped_legacy_rows_still_pair_together(self):
        """Pre-stamp transcripts (rev absent → None) keep pairing among
        themselves — the discipline must not orphan history."""
        hl = dl.headline([
            _row(3700.0, 128, resolved={"LRN_POOL": "fused1"}),
            _row(6500.0, 128, resolved={"LRN_POOL": "fused2"}),
        ])
        assert len(dl.compare(hl, "LRN_POOL", "fused2", "fused1")) == 1


class TestLoadMissingFiles:
    def test_missing_transcript_warns_and_skips(self, tmp_path, capsys):
        """A fresh checkout without backlog_r4.jsonl must not
        traceback into an empty .decisions file."""
        real = tmp_path / "a.jsonl"
        real.write_text('{"metric": "x", "value": 1}\n')
        rows = dl.load([str(tmp_path / "missing.jsonl"), str(real)])
        assert rows == [{"metric": "x", "value": 1}]
        err = capsys.readouterr().err
        assert "missing.jsonl" in err and "skipping" in err


class TestVerdictRules:
    """The verdict branch ORDER matters: a single-batch loss must read
    insufficient-data (wobble), not trigger a revert; a both-batch
    mixed result with any loss must revert per the shipped default's
    risk note, even when the mean is positive."""

    def _pairs(self, *mb_gain):
        return [{"minibatch": mb, "context": "default",
                 "shipped_context": True,
                 "baseline": 1000.0, "gain_pct": g,
                 "challenger": 1000.0 * (1 + g / 100)}
                for mb, g in mb_gain]

    def test_single_batch_loss_is_insufficient_not_revert(self):
        v = dl.lrn_pool_verdict(self._pairs((128, -1.0)))
        assert v.startswith("insufficient-data")

    def test_loss_at_either_batch_reverts_even_with_positive_mean(self):
        v = dl.lrn_pool_verdict(self._pairs((128, 10.0), (256, -2.0)))
        assert v.startswith("revert-to-fused1")
        assert "b256" in v

    def test_small_gains_no_loss_is_marginal_keep(self):
        v = dl.lrn_pool_verdict(self._pairs((128, 1.0), (256, 2.0)))
        assert v.startswith("marginal-keep")

    def test_s2d_context_loss_cannot_veto_shipped_default(self):
        """The burn measures fused2-vs-fused1 under CONV1=s2d too; a
        loss in that opt-in context must not revert a default that
        wins in the context it actually ships in."""
        pairs = self._pairs((128, 10.0), (256, 9.0)) + [
            {"minibatch": 256, "context": "CONV1=s2d",
             "shipped_context": False,
             "baseline": 1000.0, "challenger": 980.0, "gain_pct": -2.0}]
        assert dl.lrn_pool_verdict(pairs).startswith(
            "keep-default-fused2")

    def test_conv1_contexts_get_separate_verdicts(self):
        pairs = (
            [{"minibatch": mb, "context": "LRN_POOL=fused1",
              "baseline": 1000.0, "challenger": 1110.0,
              "gain_pct": 11.0} for mb in (128, 256)]
            + [{"minibatch": mb, "context": "default",
                "baseline": 1000.0, "challenger": 950.0,
                "gain_pct": -5.0} for mb in (128, 256)])
        v = dl.conv1_verdicts(pairs)
        assert v["LRN_POOL=fused1"] == "flip-default"
        assert v["default"] == "keep-off"


class TestMixedTranscripts:
    def test_legacy_and_new_rows_compare(self):
        """A round-4 default row (legacy, = fused1) pairs with a
        round-5 resolved fused2 row at the same batch."""
        hl = dl.headline([
            _row(3688.6, 128),                     # legacy r4 headline
            _row(3576.1, 256),
            _row(6500.0, 128, resolved={"LRN_POOL": "fused2"}),
            _row(6300.0, 256, resolved={"LRN_POOL": "fused2"}),
        ])
        pairs = dl.compare(hl, "LRN_POOL", "fused2", "fused1")
        assert len(pairs) == 2
        assert dl._win(pairs) is True

    def test_row_without_stamp_but_with_rev_ran_the_shipped_routing(self):
        """bench.py stamps no ``resolved`` since PR 32 (the program has
        no routing lever left); such a row carries a ``rev`` and reads
        as the shipped routing, a rev-less one as round 4's."""
        assert dict(dl.canonical({"rev": "abc1234"})) == dl._SHIPPED
        assert dict(dl.canonical({}))["LRN_POOL"] == "fused1"
        off = dict(dl.canonical({"rev": "abc1234", "levers": {
            "ZNICZ_TPU_NO_PALLAS": "1"}}))
        assert off == {**dl._SHIPPED, "PALLAS": "off"}
