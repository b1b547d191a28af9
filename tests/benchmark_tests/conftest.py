"""The native encoder, built once before the suite's workers ask for it.

The benchmark's rehearsals serve through the program's native encoder, as
do many tests beside them, and each test process builds it on first use
when the checkout has none (``cedar_tpu/native/_build/``). Under
``pytest -n 6`` on a fresh checkout the six workers did so at the same
moment and raced on one temporary file: one or more of them lost, fell
back to the Python path, and skipped their native tests
(``native_once.py``). pytest collects ``tests/benchmark_tests`` before the
``tests/test_*.py`` files (one directory's entries are collected in name
order), so every worker loads this file before any module that asks for
the encoder; the first builds it under a lock on a file, the others wait
and find it built. Where there is no toolchain the native tests skip as
they always did.
"""

from native_once import build_native_once

try:
    build_native_once()
except Exception:  # noqa: BLE001 - no toolchain: the native tests skip
    pass
