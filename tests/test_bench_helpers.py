"""bench.py's rev stamp and its exit-code contract: a row with an
``error`` exits non-zero."""

import importlib.util
import os

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "bench", os.path.join(_REPO, "bench.py"))
bench = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench)


class TestRevStamp:
    def test_git_rev_is_stamped_into_run_config(self, monkeypatch):
        """Transcript rows carry the code revision so decide_levers
        can keep cross-revision rows from contaminating verdicts."""
        rev = bench._git_rev()
        if rev is None:
            pytest.skip("not a git checkout")
        import re
        import subprocess
        # uncommitted CODE edits are DIFFERENT code: the stamp must
        # distinguish them from the bare sha AND from each other (the
        # suffix carries a hash of the diff itself) — same pathspec
        # as _git_rev
        paths = ["bench.py", "__graft_entry__.py", "znicz_tpu",
                 "native", "tools"]
        diff = subprocess.run(
            ["git", "diff", "HEAD", "--"] + paths,
            capture_output=True, cwd=_REPO).stdout.strip()
        untracked = subprocess.run(
            ["git", "ls-files", "--others", "--exclude-standard",
             "--"] + paths,
            capture_output=True, text=True, cwd=_REPO).stdout.strip()
        if diff or untracked:
            assert re.fullmatch(r"[0-9a-f]{7,40}-dirty\.[0-9a-f]{8}",
                                rev), rev
        else:
            assert re.fullmatch(r"[0-9a-f]{7,40}", rev), rev

        class Args:
            minibatch = 128
        result = {}
        bench._record_run_config(Args(), result)
        assert result["rev"] == rev
        assert result["minibatch"] == 128

    def test_git_rev_failure_is_none_not_raise(self, monkeypatch):
        import subprocess

        def boom(*a, **k):
            raise OSError("no git")
        monkeypatch.setattr(subprocess, "run", boom)
        assert bench._git_rev() is None
