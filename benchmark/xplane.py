"""From a JAX profiler trace to the numbers the per-layer metrics read.

Two halves. ``dump`` opens the ``.xplane.pb`` with ``jax.profiler.
ProfileData`` and writes the events this benchmark reads as plain JSON; it
runs as a process of its own (``python benchmark/xplane.py DIR OUT``) after
the server has gone, with JAX held to the CPU, because the benchmark's
parent never imports JAX. Everything else works on that JSON and needs
nothing but Python, so the tests run it on a small recorded trace.

The JSON: ``{"planes": [{"name", "lines": [{"name", "events": [[name,
start_ns, duration_ns], ...]}]}]}``. Device planes (``/device:TPU:n``) keep
every event of their ``XLA Ops`` and ``XLA Modules`` lines; host planes
keep only the events that say a program was being launched on the device
(``HOST_LAUNCH``), which is what idle gaps are attributed by.
"""

from __future__ import annotations

import json
import pathlib
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# host-side runtime events during which a batch is on its way to or from
# the device: the jitted call and its transfers
HOST_LAUNCH = re.compile(
    r"PjitFunction|PjRt.*Execut|ExecuteHelper|ExecuteSharded|TransferTo|"
    r"TransferFrom|BufferFromHost|CopyToHost|ToLiteral|DevicePut|copy_to_host"
)
MAX_HOST_EVENTS = 200_000


# ------------------------------------------------------------------- dump

def find_xplane(trace_dir: pathlib.Path) -> pathlib.Path:
    found = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def dump(trace_dir: pathlib.Path) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(find_xplane(trace_dir)))
    planes = []
    host_names: dict = {}
    for plane in data.planes:
        is_device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            if is_device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = []
            for ev in line.events:
                name = ev.name
                if not is_device:
                    host_names[name] = host_names.get(name, 0) + 1
                    if not HOST_LAUNCH.search(name):
                        continue
                    if len(events) >= MAX_HOST_EVENTS:
                        break
                events.append([name, int(ev.start_ns), int(ev.duration_ns)])
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    top_host = sorted(host_names.items(), key=lambda kv: -kv[1])[:60]
    return {"planes": planes, "host_event_names": top_host}


# ----------------------------------------------------------------- reduce

def merge(intervals: list) -> list:
    """Sorted, disjoint [start, end] from any [start, end] list."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def device_planes(trace: dict) -> list:
    return [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]


def line_events(plane: dict, line_name: str) -> list:
    for line in plane["lines"]:
        if line["name"] == line_name:
            return line["events"]
    return []


def busy(plane: dict) -> list:
    """The merged intervals (ns) in which an operation ran on this device."""
    return merge([[s, s + d] for _, s, d in line_events(plane, OPS_LINE) if d > 0])


def busy_seconds(trace: dict) -> float | None:
    """Seconds in which an operation ran, averaged over the devices traced;
    None where the trace holds no device plane."""
    planes = device_planes(trace)
    if not planes:
        return None
    per = [sum(e - s for s, e in busy(p)) for p in planes]
    return sum(per) / len(per) / 1e9


def idle_share(trace: dict, window_s: float) -> float | None:
    b = busy_seconds(trace)
    if b is None or window_s <= 0:
        return None
    return 100.0 * (1.0 - b / window_s)


def module_runs(trace: dict, pattern: str) -> list:
    """Durations (ns) of the executions of the XLA modules whose name the
    pattern finds, over every device plane."""
    rx = re.compile(pattern)
    return [
        d for p in device_planes(trace)
        for name, _, d in line_events(p, MODULES_LINE) if rx.search(name)
    ]


def top_ops(trace: dict, n: int = 10) -> list:
    """[[name, seconds], ...]: the device operations that took most time."""
    totals: dict = {}
    for p in device_planes(trace):
        for name, _, d in line_events(p, OPS_LINE):
            totals[name] = totals.get(name, 0) + d
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[re.sub(r"[^A-Za-z0-9_.\-]", "_", name)[:64], d / 1e9] for name, d in ranked]


def host_launch_intervals(trace: dict) -> list:
    spans = []
    for p in trace["planes"]:
        if DEVICE_PLANE.match(p["name"]):
            continue
        for line in p["lines"]:
            spans.extend([s, s + d] for _, s, d in line["events"] if d > 0)
    return merge(spans)


def _overlap(lo: int, hi: int, spans: list) -> int:
    return sum(max(0, min(hi, e) - max(lo, s)) for s, e in spans if s < hi and e > lo)


def idle_gaps(trace: dict, n: int = 10) -> list:
    """[[what the host was doing, seconds], ...]: the longest gaps between
    device operations on the first device. A gap during most of which the
    host was inside a launch or a transfer is ``batch_in_flight_host_
    dispatch``; any other is ``no_batch_in_flight`` (the host had nothing
    for the device: it was receiving, encoding, decoding or answering)."""
    planes = device_planes(trace)
    if not planes:
        return []
    spans = busy(planes[0])
    launches = host_launch_intervals(trace)
    gaps = sorted(
        ((spans[i + 1][0] - spans[i][1], spans[i][1], spans[i + 1][0])
         for i in range(len(spans) - 1)),
        reverse=True,
    )[:n]
    out = []
    for length, lo, hi in gaps:
        inside = _overlap(lo, hi, launches)
        what = ("batch_in_flight_host_dispatch" if inside * 2 > length
                else "no_batch_in_flight")
        out.append([what, length / 1e9])
    return out


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 2:
        print("usage: xplane.py TRACE_DIR OUT.json", file=sys.stderr)
        return 2
    pathlib.Path(argv[1]).write_text(json.dumps(dump(pathlib.Path(argv[0]))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
