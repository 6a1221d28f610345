"""Build and locate the ctypes libraries under ``native/``.

Every load asks ``make`` first (a no-op when the library is newer than its
source), so a stale ``.so`` copied along with a tree is rebuilt from the
committed ``.cpp`` instead of loaded as-is.
"""

from __future__ import annotations

import fcntl
import os
import subprocess

NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native",
)


def so_name(stem: str) -> str:
    """``stem``'s library file: the ASAN+UBSAN build (``make sanitize``)
    under ``GW_SANITIZED_NATIVE=1``, which the sanitizer harness sets to
    run the same python callers against it."""
    if os.environ.get("GW_SANITIZED_NATIVE") == "1":
        return f"{stem}.san.so"
    return f"{stem}.so"


def build(name: str) -> str | None:
    """Bring ``native/<name>`` up to date; its path, or None when it cannot
    be built (no toolchain, compile error)."""
    # the sanitizer runtimes preloaded into a harness must not leak into
    # make and the compiler
    env = {k: v for k, v in os.environ.items() if k != "LD_PRELOAD"}
    try:
        # one builder at a time across processes (test workers, cluster
        # components): a library half-written by one is never loaded by
        # another
        with open(os.path.join(NATIVE_DIR, ".build.lock"), "w") as lock:
            fcntl.flock(lock, fcntl.LOCK_EX)
            subprocess.run(["make", "-C", NATIVE_DIR, "-s", name],
                           check=True, capture_output=True, timeout=120,
                           env=env)
    except (OSError, subprocess.SubprocessError):
        return None
    return os.path.join(NATIVE_DIR, name)
