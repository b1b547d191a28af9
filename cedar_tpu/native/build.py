"""On-demand build of the native encoder library.

Compiles encoder.cpp with the system C++ toolchain into a shared library
cached under ``cedar_tpu/native/_build/`` keyed by a hash of the source, the
target arch and — for the default ``-march=native`` — the host CPU's feature
flags, so edits to the .cpp transparently rebuild, repeated imports are
free, and a library built on a different machine is never picked up. No
pip dependencies: plain g++ (or $CXX) + ctypes."""

from __future__ import annotations

import hashlib
import os
import pathlib
import subprocess
import threading

_HERE = pathlib.Path(__file__).resolve().parent
_SRC = _HERE / "encoder.cpp"
_BUILD_DIR = _HERE / "_build"
_LOCK = threading.Lock()


def _glue_include() -> str:
    """Python include dir when this interpreter's headers are present
    (enables the *_pylist zero-packing entries), else ''."""
    import sysconfig

    inc = sysconfig.get_paths().get("include")
    if inc and os.path.exists(os.path.join(inc, "Python.h")):
        return inc
    return ""


def _host_cpu_id() -> str:
    """What ``-march=native`` resolves to on THIS host: the CPU's feature
    flags (Linux) or, failing that, its architecture and model name. Part
    of the cache key, so a library built on another machine — a copied
    checkout, a baked image — is never loaded here: it may hold
    instructions this CPU lacks and die of SIGILL inside the server."""
    import platform

    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    return " ".join(sorted(line.split(":", 1)[1].split()))
    except OSError:
        pass
    return f"{platform.machine()}|{platform.processor()}"


def _source_hash(with_glue: bool) -> str:
    import sysconfig

    arch = os.environ.get("CEDAR_NATIVE_ARCH", "native")
    h = hashlib.sha256(_SRC.read_bytes())
    h.update(arch.encode())
    if arch == "native":
        h.update(b"cpu:")
        h.update(_host_cpu_id().encode())
    if with_glue:
        # the glue compiles PyList/PyObject struct-offset macros for THIS
        # interpreter's ABI: key the cache on it so a different
        # interpreter (or a headers-appeared-later host) rebuilds
        h.update(b"pyglue:")
        h.update(str(sysconfig.get_config_var("SOABI")).encode())
    return h.hexdigest()[:16]


def library_path(with_glue: bool = None) -> pathlib.Path:
    """The cache path for a (source, arch, glue?) build. The glue state is
    part of the FILENAME, so a glueless fallback build can never occupy
    the glue-tagged slot: a transient toolchain failure leaves the glue
    path absent and the next import retries the full glue compile instead
    of being pinned to the slower packed-buffer entries forever."""
    if with_glue is None:
        with_glue = bool(_glue_include())
    tag = "glue_" if with_glue else ""
    return _BUILD_DIR / f"libcedar_native_{tag}{_source_hash(with_glue)}.so"


def _compile(out: pathlib.Path, glue_inc: str) -> None:
    cxx = os.environ.get("CXX", "g++")
    # CEDAR_NATIVE_ARCH=x86-64 (etc.) builds a portable binary — set it
    # for container images so the .so survives a host-CPU change; the
    # default tunes for the build machine
    arch = os.environ.get("CEDAR_NATIVE_ARCH", "native")
    tmp = out.with_suffix(".so.tmp")
    cmd = [
        cxx,
        "-O3",
        f"-march={arch}",
        "-fno-plt",
        "-std=c++17",
        "-shared",
        "-fPIC",
        "-pthread",
    ]
    if glue_inc:
        cmd += ["-DCEDAR_PY_GLUE", f"-I{glue_inc}"]
    cmd += [str(_SRC), "-o", str(tmp)]
    subprocess.run(cmd, check=True, capture_output=True, text=True)
    os.replace(tmp, out)


def ensure_built() -> pathlib.Path:
    """Compile (once) and return the shared-library path.

    CPython glue (the *_pylist zero-packing entries) is best-effort:
    compiled in when this interpreter's headers are present, dropped on
    compile failure — the ctypes loader probes for the symbols and falls
    back to the packed-buffer entries (native/__init__.py). The fallback
    build is cached under the GLUELESS filename, so the glue compile is
    retried on the next import rather than permanently pinned off."""
    out = library_path()
    if out.exists():
        return out
    with _LOCK:
        if out.exists():
            return out
        _BUILD_DIR.mkdir(exist_ok=True)
        inc = _glue_include()
        try:
            _compile(out, inc)
        except subprocess.CalledProcessError:
            if not inc:
                raise
            # glue compile failed (e.g. transient toolchain breakage):
            # build without it at the glueless cache slot
            out = library_path(with_glue=False)
            if not out.exists():
                _compile(out, "")
        # drop stale builds of older source revisions — but keep the
        # glueless fallback alongside a glue request, and vice versa: the
        # two names can legitimately coexist across retry cycles
        keep = {library_path(with_glue=False), library_path(with_glue=True)}
        for old in _BUILD_DIR.glob("libcedar_native_*.so"):
            if old not in keep:
                try:
                    old.unlink()
                except OSError:
                    pass
    return out
