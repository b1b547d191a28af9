"""Build the program's native encoder once for every process that asks.

``cedar_tpu/native/build.py`` compiles the encoder on first use into one
temporary file beside the library and renames it into place, under a lock
that holds within a process only. Several processes that find no library
compile at once into that same temporary file, and every one whose rename
comes after the first finds it gone (``FileNotFoundError``): that process
falls back to the Python path for good, and its native tests skip. Taking a
lock on a file in the build directory around ``ensure_built`` makes the
first process build and the others wait, then find the library there.
"""

from __future__ import annotations

import fcntl
import pathlib


def build_native_once(lock: pathlib.Path | None = None) -> pathlib.Path:
    """``ensure_built()`` with no other process of this machine inside it."""
    from cedar_tpu.native import build

    lock = lock or build.library_path().parent / ".build.lock"
    lock.parent.mkdir(parents=True, exist_ok=True)
    with open(lock, "w") as f:  # closing the file releases the lock
        fcntl.flock(f, fcntl.LOCK_EX)
        return build.ensure_built()
