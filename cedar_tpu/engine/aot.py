"""AOT-compiled, disk-serialized serving executables.

The engine's warm ladder (evaluator._warm_shape_plan) traces and compiles
every serving shape at load time, which makes the FIRST load of a policy
set — a fresh worker process after a rolling restart, a fanout revive, a
100k-rule cold start — pay the full jit trace+compile bill before it can
serve. The fanout tier papers over that window with peer cache fills;
this module removes the window instead.

Every jitted match/bits entry point (ops/match.py) dispatches through
:func:`dispatch`, which:

* computes a cache key from everything that determines the compiled
  artifact: jax/jaxlib versions, backend platform + device kind + device
  count, the entry-point name, the static-argument values, and the
  abstract shapes/dtypes of every dynamic argument (``None`` slots
  included — they are part of the pytree signature);
* on a disk hit, loads the COMPILED executable via
  ``jax.experimental.serialize_executable.deserialize_and_load`` — no
  trace (ops.match's ``kernel_trace_count()`` does not move;
  tests/test_aot.py pins this) and no fresh XLA compile either, which is
  what makes a 100k-rule cold start a disk read;
* on a miss, AOT-compiles (``jit_fn.lower(*args).compile()`` — one trace,
  exactly what the jit path would have paid), serializes the executable
  to disk for the NEXT process, and serves the call from the same
  compiled object;
* on ANY mismatch or failure — a meta header naming a different jaxlib or
  topology, a truncated blob, an unserializable computation — logs,
  counts it, and falls back to the jit path. A stale or foreign cache
  entry can recompile loudly; it can never deserialize wrong.

The loaded executable takes ONLY the dynamic arguments (statics are baked
into the compilation; ``None``-valued dynamic args keep their pytree
slot) and refuses mismatched shapes/pytrees with a TypeError — a refusal,
never a wrong answer.

Security note: entries deserialize via pickle (the treedefs) and load
native code (the executable image). The cache directory must be
trusted — same bar as the python environment itself; see
docs/Operations.md.

The cache is enabled when a directory resolves (``CEDAR_TPU_AOT_CACHE``
env or :func:`set_cache_dir`, the ``--aot-cache-dir`` CLI flag) and
``CEDAR_TPU_AOT`` is not ``0``. With no directory, dispatch is a
zero-overhead passthrough to the jit function. docs/Operations.md has
the runbook (layout, invalidation, rolling-restart impact).

File format (one file per key, written atomically via tmp + rename)::

    CDRAOT1\\n | u32be meta_len | meta json (the key fields) | payload

where payload = pickle((executable blob, in_treedef, out_treedef)). The
meta header repeats the key's inputs verbatim so a loader can refuse an
entry whose filename collides but whose environment differs (defense
against hand-copied caches between heterogeneous hosts).
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import pickle
import struct
import threading
import warnings
from typing import Callable, Optional, Sequence, Tuple

import numpy as np

from ..obs.trace import note_launch, profiler_scope

log = logging.getLogger("cedar_tpu.aot")

_MAGIC = b"CDRAOT1\n"

# static-argument positions per entry-point family, matching the
# POSITIONAL call convention used by evaluator.match_arrays_launch.
# jax.export bakes statics out of the Exported signature, so dispatch
# must split args into (statics -> key material) and (dynamics ->
# Exported.call operands). None-valued DYNAMIC args (n_valid when not
# want_bits) keep their pytree slot and are passed through.
STATICS = {
    # (codes, extras, act_rows, W_chunks, thresh_c, group_c, policy_c,
    #  n_tiers, want_full, want_bits, n_valid, has_gate, segs)
    "codes": (7, 8, 9, 11, 12),
    # (codes8, codes_w, lo8, extras, act_rows, W_chunks, thresh_c,
    #  group_c, policy_c, n_tiers, want_full, want_bits, n_valid,
    #  has_gate, segs)
    "wire": (9, 10, 11, 13, 14),
    # (codes, extras, act_rows, W_chunks, thresh_c, group_c, policy_c)
    "bits": (),
}

_lock = threading.Lock()
# key -> ("aot", callable) | ("jit", None): resolved dispatch decisions.
# "jit" entries mean the disk was already consulted (miss, stale, or
# error) and the original function should be called without further IO.
_resolved: dict = {}
_counters = {
    "hits": 0,        # dispatches served via a deserialized executable
    "misses": 0,      # first-time keys AOT-compiled (and exported)
    "stale": 0,       # disk entries refused (meta/env mismatch, corrupt)
    "errors": 0,      # compile/serialize/deserialize failures (fell back)
    "exports": 0,     # entries successfully serialized to disk
}
_cache_dir: Optional[str] = None


def set_cache_dir(path: Optional[str]) -> None:
    """Point the executable cache at ``path`` (``--aot-cache-dir``);
    ``None`` or ``""`` disables it. Clears resolved-dispatch state so a
    redirected cache is actually consulted."""
    global _cache_dir
    with _lock:
        _cache_dir = str(path) if path else None
        _resolved.clear()


def reset_counters() -> None:
    with _lock:
        for k in _counters:
            _counters[k] = 0


def stats() -> dict:
    """Counter snapshot plus the resolved cache-dir (None = disabled)."""
    with _lock:
        out = dict(_counters)
    out["cache_dir"] = cache_dir()
    out["enabled"] = enabled()
    return out


def cache_dir() -> Optional[str]:
    if _cache_dir is not None:
        return _cache_dir
    return os.environ.get("CEDAR_TPU_AOT_CACHE") or None


def enabled() -> bool:
    """AOT serving is on when a cache dir resolves and CEDAR_TPU_AOT is
    not explicitly 0 (the byte-differential escape hatch)."""
    if os.environ.get("CEDAR_TPU_AOT", "1") == "0":
        return False
    return cache_dir() is not None


# ----------------------------------------------------------------- keying


def _env_fields() -> dict:
    import jax

    try:
        import jaxlib

        jaxlib_version = getattr(jaxlib, "__version__", "?")
    except Exception:  # noqa: BLE001 — version probing must not fail hot
        jaxlib_version = "?"
    devs = jax.devices()
    return {
        # 2: a want_bits launch returns one packed buffer, not four arrays
        # (ops/match.py _pack_out) — a format-1 executable of the same
        # name, statics and operand shapes must never serve that call
        "format": 2,
        "jax": jax.__version__,
        "jaxlib": jaxlib_version,
        "platform": devs[0].platform if devs else "none",
        "device_kind": getattr(devs[0], "device_kind", "?") if devs else "?",
        "n_devices": len(devs),
    }


def _aval_sig(args: Sequence, static_argnums: Tuple[int, ...]) -> list:
    """Stable signature of the DYNAMIC arguments: (shape, dtype) per
    array-like, "none" for None slots (which stay in the pytree)."""
    import numpy as np

    statics = set(static_argnums)
    sig = []
    for i, a in enumerate(args):
        if i in statics:
            continue
        if a is None:
            sig.append("none")
        else:
            sig.append([list(a.shape), np.dtype(a.dtype).str])
    return sig


def _key_meta(
    name: str, args: Sequence, static_argnums: Tuple[int, ...]
) -> dict:
    meta = _env_fields()
    meta["name"] = name
    meta["statics"] = repr(
        tuple(args[i] for i in static_argnums if i < len(args))
    )
    meta["avals"] = _aval_sig(args, static_argnums)
    return meta


def _key(meta: dict) -> str:
    canon = json.dumps(meta, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(canon.encode()).hexdigest()[:32]


def _path(name: str, key: str) -> str:
    d = cache_dir()
    assert d is not None
    safe = "".join(c if c.isalnum() or c in "-_" else "_" for c in name)
    return os.path.join(d, f"{safe}-{key}.jexp")


# ------------------------------------------------------------ disk format


def _write_entry(path: str, meta: dict, blob: bytes) -> None:
    meta_b = json.dumps(meta, sort_keys=True).encode()
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "wb") as f:
        f.write(_MAGIC)
        f.write(struct.pack(">I", len(meta_b)))
        f.write(meta_b)
        f.write(blob)
    os.replace(tmp, path)


def _read_entry(path: str) -> Tuple[dict, bytes]:
    with open(path, "rb") as f:
        magic = f.read(len(_MAGIC))
        if magic != _MAGIC:
            raise ValueError(f"bad magic in {path!r}")
        (meta_len,) = struct.unpack(">I", f.read(4))
        meta = json.loads(f.read(meta_len).decode())
        blob = f.read()
    if not blob:
        raise ValueError(f"empty executable blob in {path!r}")
    return meta, blob


# --------------------------------------------------------------- dispatch


def _dynamic(args: Sequence, static_argnums: Tuple[int, ...]) -> tuple:
    statics = set(static_argnums)
    return tuple(a for i, a in enumerate(args) if i not in statics)


def _count(field: str) -> None:
    with _lock:
        _counters[field] += 1


def _load_aot(name: str, key: str, meta: dict) -> Optional[Callable]:
    """Try to resolve ``key`` from disk. Returns the loaded executable on
    success, None on miss/stale/error (counted + logged)."""
    import jax
    from jax.experimental import serialize_executable as se

    path = _path(name, key)
    if not os.path.exists(path):
        return None
    try:
        disk_meta, payload = _read_entry(path)
    except Exception as e:  # noqa: BLE001 — corrupt entry: refuse, recompile
        _count("stale")
        log.warning("aot cache entry %s unreadable (%r); recompiling", path, e)
        return None
    if disk_meta != meta:
        # the filename hash matched but the recorded environment does not
        # — a hand-copied cache from a different jaxlib/topology. Loudly
        # recompile; never deserialize a foreign executable.
        _count("stale")
        drift = {
            k: (disk_meta.get(k), meta.get(k))
            for k in set(disk_meta) | set(meta)
            if disk_meta.get(k) != meta.get(k)
        }
        log.warning(
            "aot cache entry %s is stale (mismatched fields: %s); "
            "recompiling", path, sorted(drift),
        )
        return None
    try:
        blob, in_tree, out_tree, device_ids = pickle.loads(payload)
        # loads the ALREADY-COMPILED executable: no trace (the python
        # kernel body never runs — kernel_trace_count() stays flat) and
        # no XLA compile, so warm-from-disk cost is IO + linking only.
        # It must load onto the devices it was compiled for: left to its
        # default, jax binds EVERY visible device and then wants one
        # argument shard per device on each call.
        by_id = {d.id: d for d in jax.devices()}
        return se.deserialize_and_load(
            blob, in_tree, out_tree,
            execution_devices=[by_id[i] for i in device_ids],
        )
    except Exception as e:  # noqa: BLE001 — deserialize failure: fall back
        _count("errors")
        log.warning("aot deserialize failed for %s (%r); recompiling", path, e)
        return None


def _compile_and_export(name, key, meta, jit_fn, args) -> Optional[Callable]:
    """AOT-compile ``jit_fn`` for ``args`` and serialize the executable.
    Returns the compiled callable (serving the miss in-process), or None
    when even AOT compilation fails (caller falls back to plain jit)."""
    from jax.experimental import serialize_executable as se

    try:
        with warnings.catch_warnings():
            # donated twins warn "Some donated buffers were not usable"
            # for the buffers XLA cannot reuse (all of them on backends
            # that cannot donate) — the donation is dropped (an
            # optimization, not a semantic), which is fine
            warnings.simplefilter("ignore")
            compiled = jit_fn.lower(*args).compile()
    except Exception as e:  # noqa: BLE001 — lowering quirk: plain jit path
        _count("errors")
        log.warning("aot compile failed for %s/%s (%r)", name, key, e)
        return None
    try:
        blob, in_tree, out_tree = se.serialize(compiled)
        device_ids = [
            d.id for d in compiled.runtime_executable().local_devices()
        ]
        payload = pickle.dumps((blob, in_tree, out_tree, device_ids))
        _write_entry(_path(name, key), meta, payload)
        _count("exports")
    except Exception as e:  # noqa: BLE001 — export is best-effort
        _count("errors")
        log.warning("aot export failed for %s/%s (%r)", name, key, e)
    return compiled


# an argument the jitted call has to send up on its own: a host array or
# a numpy scalar (what is already on the device is a jax.Array), told by
# its type — a set lookup, half an isinstance's cost on a launch's 15
_HOST = frozenset([np.ndarray, *np.sctypeDict.values()])


# the jitted call alone — the runtime's uploads, its execute enqueue, the
# call's return — is ``cedar.dispatch.call`` on a running profiler's clock,
# inside the caller's ``cedar.dispatch.launch``
_CALL = "cedar.dispatch.call"


def dispatch(
    name: str,
    jit_fn: Callable,
    args: tuple,
    static_argnums: Tuple[int, ...],
):
    """Call ``jit_fn(*args)`` through the executable cache.

    ``name`` identifies the entry-point family (a STATICS key or any
    distinct label); ``static_argnums`` are the positions jax.jit treats
    as static. Disabled cache = straight passthrough. The host arrays
    among ``args`` and their bytes go onto the calling batch's record
    (obs.trace.note_launch: cedar_launch_uploads_total and
    cedar_launch_upload_bytes_total)."""
    uploads = upload_bytes = 0
    for a in args:
        if type(a) in _HOST:
            uploads += 1
            upload_bytes += a.nbytes
    note_launch(uploads, upload_bytes)
    if not enabled():
        with profiler_scope(_CALL, uploads=uploads, upload_bytes=upload_bytes):
            return jit_fn(*args)
    try:
        meta = _key_meta(name, args, static_argnums)
        key = _key(meta)
    except Exception as e:  # noqa: BLE001 — keying must never break serving
        _count("errors")
        log.warning("aot keying failed for %s (%r); jit path", name, e)
        return jit_fn(*args)
    with _lock:
        hit = _resolved.get(key)
    if hit is None:
        fn = _load_aot(name, key, meta)
        if fn is not None:
            with _lock:
                _resolved[key] = ("aot", fn)
            hit = ("aot", fn)
        else:
            # miss (or refused entry): AOT-compile once (the same single
            # trace the jit path would have paid), serialize for the
            # next process, and serve this call from the compiled object
            _count("misses")
            fn = _compile_and_export(name, key, meta, jit_fn, args)
            if fn is None:
                with _lock:
                    _resolved[key] = ("jit", None)
                return jit_fn(*args)
            with _lock:
                _resolved[key] = ("aot", fn)
            try:
                return fn(*_dynamic(args, static_argnums))
            except Exception as e:  # noqa: BLE001 — never 500 on a cache
                _count("errors")
                log.warning(
                    "aot compiled call failed for %s (%r); jit fallback",
                    name, e,
                )
                with _lock:
                    _resolved[key] = ("jit", None)
                return jit_fn(*args)
    kind, fn = hit
    if kind == "jit":
        with profiler_scope(_CALL, uploads=uploads, upload_bytes=upload_bytes):
            return jit_fn(*args)
    _count("hits")
    try:
        dynamic = _dynamic(args, static_argnums)
        with profiler_scope(_CALL, uploads=uploads, upload_bytes=upload_bytes):
            return fn(*dynamic)
    except Exception as e:  # noqa: BLE001 — a bad executable must not 500
        _count("errors")
        log.warning(
            "aot executable call failed for %s (%r); jit fallback", name, e
        )
        with _lock:
            _resolved[key] = ("jit", None)
        return jit_fn(*args)
