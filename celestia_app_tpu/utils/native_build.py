"""On-demand builds of the native/ sources, safe across processes.

The artefacts are git-ignored, so the first caller on a cold checkout
builds them — and under ``pytest -n 6`` six workers are that first caller
at once. Two things keep that safe: the Makefile links every target under
a temporary name and renames it into place (a reader never sees a
half-written file, whoever runs make), and ``make()`` here holds an
exclusive flock on ``native/.build.lock`` so concurrent callers build once
and the rest find the target fresh.
"""

from __future__ import annotations

import fcntl
import os
import subprocess

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NATIVE_DIR = os.path.join(REPO, "native")
LOCK_NAME = ".build.lock"


def make(target: str, *, native_dir: str = NATIVE_DIR,
         timeout: int = 180) -> str:
    """Bring ``native/<target>`` up to date (a no-op when fresh: make's
    dependency tracking is what keeps a stale binary from outliving an
    edit to its source). Returns the artefact's path; raises
    subprocess.CalledProcessError / OSError when it cannot be built."""
    with open(os.path.join(native_dir, LOCK_NAME), "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        subprocess.run(
            ["make", "-C", native_dir, target],
            check=True, capture_output=True, text=True, timeout=timeout,
        )
    return os.path.join(native_dir, target)
