"""Process-level JAX set-up: platform selection and the compile cache.

One home for the decisions a process makes about JAX before its first
jit: which platform it is allowed to run on (``require_tpu`` for device
paths, ``force_cpu`` for tools that are cpu-only by design), where the
persistent compilation cache lives (``configure_compile_cache``), and the
multi-process bring-up of the pod tier (``distributed_initialize``).
"""

from __future__ import annotations

import os
import pathlib
import threading

# <repo>/.jax_cache: a FIXED path (the cache key includes the directory, so
# a location that moves between runs never hits). Git-ignored.
_REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
DEFAULT_COMPILE_CACHE_DIR = _REPO_ROOT / ".jax_cache"


def require_tpu() -> dict:
    """Raise unless JAX's default device is a TPU; returns the device as
    JAX reports it ({"platform", "kind", "count"}). The one platform check
    behind every device path (``--backend tpu`` serving, ``bench.py``):
    with no chip ``jax.devices()`` quietly
    returns the CPU, and a device path that continued there would answer
    correctly from the wrong place."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"no TPU: jax.devices()[0] is {dev.platform!r} "
            f"({dev.device_kind}); the device path does not fall back to "
            "another platform. Set JAX_PLATFORMS=cpu to run the CPU plane "
            "on purpose."
        )
    return {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(devices),
    }


def cpu_requested() -> bool:
    """True when the environment names the CPU platform explicitly
    (``JAX_PLATFORMS=cpu``): the operator asked for the CPU plane by
    name, so device paths skip ``require_tpu``."""
    return os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip() == "cpu"


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a stable directory
    before the first jit; returns the directory in effect.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads it itself and the
    directory is left alone: the cache lives where it was placed from
    outside. Otherwise it goes to ``<repo>/.jax_cache`` — never a temp dir,
    pid or timestamp. Either way the minimum compile time is dropped to
    zero (unless ``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS`` says
    otherwise) so the warm ladder's small shapes are kept too: a cold
    server start is ~80 compiles per engine, many of them short."""
    import jax

    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_COMPILE_CACHE_DIR))
    return str(DEFAULT_COMPILE_CACHE_DIR)


def force_cpu() -> None:
    """cpu-only set-up for standalone scripts (soaks, probes, the cpu-only
    bench scenarios): pin JAX_PLATFORMS + jax_platforms to cpu and default
    the engines' background warm-up off."""
    os.environ.setdefault("CEDAR_TPU_WARM_DEFAULT", "off")
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")


_dist_lock = threading.Lock()
_dist_params: tuple | None = None


class DistributedInitError(RuntimeError):
    """Raised for mis-wired multi-host bring-up: a second initialize with
    different coordinates, or a coordinator that never answers within the
    bounded timeout. Callers (cli/webhook.py pod mode, pod/spawn.py) exit
    nonzero on it instead of hanging in ``jax.distributed.initialize``."""


def enable_cpu_collectives() -> None:
    """Switch jax's CPU client to the gloo collectives implementation.

    The default CPU client has NO cross-process collectives ("Multiprocess
    computations aren't implemented on the CPU backend"), so any pod-mode
    run on the cpu platform — the CI simulation of a multi-host slice —
    must flip this BEFORE the backend initializes."""
    import jax

    jax.config.update("jax_cpu_collectives_implementation", "gloo")


def _probe_coordinator(address: str, timeout_s: float) -> None:
    """Bounded TCP reachability check of ``host:port``; raises
    DistributedInitError when nothing accepts within ``timeout_s``."""
    import socket
    import time as _time

    host, _, port_s = address.rpartition(":")
    try:
        port = int(port_s)
    except ValueError:
        raise DistributedInitError(
            f"malformed coordinator address {address!r} (want host:port)"
        ) from None
    deadline = _time.monotonic() + max(1.0, timeout_s)
    last = "unreachable"
    while _time.monotonic() < deadline:
        try:
            with socket.create_connection((host or "127.0.0.1", port), 1.0):
                return
        except OSError as e:
            last = str(e)
            _time.sleep(0.2)
    raise DistributedInitError(
        f"coordinator {address} unreachable within {timeout_s:.0f}s "
        f"({last}) — wrong --pod-coordinator or the leader never started"
    )


def distributed_initialize(
    coordinator_address: str,
    num_processes: int,
    process_id: int,
    timeout_s: float | None = None,
) -> bool:
    """Idempotent, loudly-failing ``jax.distributed.initialize``.

    Returns True when this call performed the initialization, False when
    an identical one already did (idempotent re-entry: the CLI and the
    pod bootstrap may both run). Raises DistributedInitError — within
    ``timeout_s`` (env ``CEDAR_POD_INIT_TIMEOUT_S``, default 60s) — for
    every mis-wiring instead of hanging:

      * process_id outside [0, num_processes) or num_processes < 1
        (caught before jax is even touched);
      * a prior initialize under DIFFERENT coordinates (address/count/id
        mismatch — two configs are fighting over one process);
      * a coordinator that cannot be reached or never sees all
        ``num_processes`` workers before the deadline (wrong address or
        wrong count somewhere in the fleet — jax's own barrier timeout
        is re-raised as this error so supervisors see one exit path).
    """
    if num_processes < 1 or not (0 <= process_id < num_processes):
        raise DistributedInitError(
            f"pod coordinates out of range: process_id={process_id} "
            f"num_processes={num_processes}"
        )
    if timeout_s is None:
        timeout_s = float(os.environ.get("CEDAR_POD_INIT_TIMEOUT_S", "60"))
    params = (str(coordinator_address), int(num_processes), int(process_id))
    global _dist_params
    with _dist_lock:
        if _dist_params is not None:
            if _dist_params == params:
                return False
            raise DistributedInitError(
                f"jax.distributed already initialized as "
                f"addr={_dist_params[0]} n={_dist_params[1]} "
                f"pid={_dist_params[2]}; refusing conflicting "
                f"addr={params[0]} n={params[1]} pid={params[2]}"
            )
        if process_id != 0:
            # Probe the coordinator's TCP endpoint before handing control
            # to jax: its C++ distributed client LOG(FATAL)s (SIGABRT) on
            # a RegisterTask deadline, so a dead/mis-addressed
            # coordinator would abort the process instead of raising.
            # Retry until timeout_s — the leader may still be binding.
            _probe_coordinator(params[0], timeout_s)
        import jax

        # Platform check WITHOUT touching backends (default_backend()
        # would initialize them — after which neither gloo nor
        # jax.distributed can take effect).
        platforms = (
            os.environ.get("JAX_PLATFORMS")
            or getattr(jax.config, "jax_platforms", None)
            or ""
        )
        if "cpu" in platforms or platforms in ("", None):
            enable_cpu_collectives()
        try:
            jax.distributed.initialize(
                coordinator_address=params[0],
                num_processes=params[1],
                process_id=params[2],
                initialization_timeout=int(max(1, timeout_s)),
            )
        except Exception as e:  # noqa: BLE001 — one loud exit path
            raise DistributedInitError(
                f"jax.distributed.initialize failed within {timeout_s:.0f}s "
                f"(addr={params[0]} n={params[1]} pid={params[2]}): {e}"
            ) from e
        _dist_params = params
        return True


def distributed_params() -> tuple | None:
    """(coordinator_address, num_processes, process_id) once initialized
    through distributed_initialize, else None."""
    return _dist_params
