"""Shared build driver for the native (C++) components.

Both data-plane libraries — ``native/libznr_reader.so`` (mmap record
gather, loader/records.py) and ``native/libznicz_infer.so`` (the C++
inference engine, export.py) — are compiled on first use from the repo's
``native/`` directory.  This module is the ONE implementation of the two
hazards that entails:

* **staleness** — the .so must be rebuilt when ANY of its build inputs
  changed, including shared headers (``parallel.h``), not just the
  primary .cpp.  Freshness is a sha256 of the sources recorded beside
  the .so (``<so>.digest``) at build time, not an mtime comparison: a
  tree that was copied, unpacked or checked out keeps no mtime order,
  and an ignored .so that travelled with it must not pass for a build
  of the sources it now sits next to;
* **cross-process exclusion** — concurrent workers must not run ``make``
  on the same target simultaneously (a partially written ELF would
  silently poison the dlopen).  flock() on an open fd: the kernel drops
  the lock when a builder dies, so there is no stale-lock takeover and
  no check-then-unlink TOCTOU.  Retrying is limited to EWOULDBLOCK /
  EAGAIN / EINTR — a filesystem where flock() fails outright (ENOLCK on
  some NFS mounts) falls through to one unlocked best-effort build
  attempt instead of spinning out the whole deadline.
"""

from __future__ import annotations

import errno
import hashlib
import os
import subprocess
import time


class NativeBuildError(RuntimeError):
    """``make`` failed, or the build lock never came free."""


def source_digest(srcs: list[str]) -> str:
    """sha256 over the names and bytes of ``srcs``, in order."""
    h = hashlib.sha256()
    for src in srcs:
        h.update(os.path.basename(src).encode() + b"\0")
        with open(src, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def is_fresh(so: str, srcs: list[str]) -> bool:
    """True when ``so`` exists and was built from exactly ``srcs`` as
    they are now."""
    try:
        with open(so + ".digest") as f:
            built_from = f.read().strip()
    except OSError:
        return False
    return os.path.exists(so) and built_from == source_digest(srcs)


def ensure_built(so: str, srcs: list[str], make_dir: str, target: str,
                 deadline_s: float = 180.0) -> None:
    """Build ``target`` under flock unless ``so`` is fresh.  Raises
    :class:`NativeBuildError` with make's output when the build fails
    — a caller with a pure-Python fallback catches it."""
    if is_fresh(so, srcs):
        return
    import fcntl
    lock = so + ".lock"
    try:
        fd = os.open(lock, os.O_CREAT | os.O_WRONLY, 0o644)
    except OSError:
        fd = None                       # unwritable dir: try bare build
    try:
        got = fd is None                # no lock file → best-effort bare
        if fd is not None:
            # monotonic deadline: an NTP step mid-wait must not turn a
            # 180 s build lock into an instant give-up (or a forever
            # wait) — zlint duration-clock
            deadline = time.monotonic() + deadline_s
            while time.monotonic() < deadline:
                try:
                    fcntl.flock(fd, fcntl.LOCK_EX | fcntl.LOCK_NB)
                    got = True
                    break
                except OSError as e:
                    if e.errno not in (errno.EWOULDBLOCK, errno.EAGAIN,
                                       errno.EINTR):
                        got = True      # flock unsupported: build bare
                        break
                    time.sleep(0.1)
        if is_fresh(so, srcs):          # another process built it
            return
        if not got:
            raise NativeBuildError(
                f"{target}: build lock {lock} busy for {deadline_s:.0f}s")
        digest = source_digest(srcs)
        # -B: make judges by mtime too, and would call a stale .so
        # whose mtime happens to be newest up to date
        try:
            proc = subprocess.run(["make", "-B", "-C", make_dir, target],
                                  capture_output=True, text=True)
        except OSError as e:
            raise NativeBuildError(f"cannot run make: {e}") from e
        if proc.returncode != 0:
            raise NativeBuildError(
                f"`make -C {make_dir} {target}` failed "
                f"(rc={proc.returncode}):\n{proc.stderr[-2000:]}")
        tmp = f"{so}.digest.{os.getpid()}"
        with open(tmp, "w") as f:
            f.write(digest + "\n")
        os.replace(tmp, so + ".digest")
    finally:
        if fd is not None:
            os.close(fd)                # releases the flock if held
