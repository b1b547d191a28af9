#!/usr/bin/env python3
"""Put a profiler trace's idle gaps down to the program's own stages.

    JAX_PLATFORMS=cpu python tools/xplane_stages.py TRACE_DIR [OUT.json]

Reads the newest ``.xplane.pb`` under TRACE_DIR with
``jax.profiler.ProfileData`` (nothing else) and prints one JSON document:

  ``host_events``   every ``cedar.*`` event name on the host planes (the
                    program's ``TraceAnnotation``s: obs/trace.py
                    ``batch_stage`` / ``sub_stage`` / ``profiler_scope``),
                    with its count, total and mean milliseconds
  ``idle_gaps``     the ten longest gaps between operations on the first
                    device, each with the milliseconds of it that every
                    ``cedar.*`` event name overlaps (threads united) and
                    ``stage``: the most specific name that covers at least
                    half of the gap, or ``none``
  ``device_ops``    the ten device operations with most time, each with its
                    ``source`` line and the ``cedar.match.*`` scope its
                    metadata names (the scopes of ops/match.py; a ``while``
                    has none of its own), or ``""`` where the executable was
                    compiled before the scopes existed — JAX's persistent
                    cache keys on the program without its metadata, so an
                    old entry is loaded as it was compiled

It is a builder's tool: benchmark/xplane.py keeps only the runtime's own
host events and no device-op metadata, and editing it takes a benchmark PR
(PERF.md, section 7).
"""

from __future__ import annotations

import json
import pathlib
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
SCOPE = re.compile(r"cedar\.match\.[a-z_]+")
# most specific first: a gap goes to the first of these that covers half of it
SPECIFICITY = (
    "cedar.dispatch.launch", "cedar.dispatch.stage", "cedar.dispatch.readback",
    "cedar.decode.device_wait", "cedar.batch.encode", "cedar.batch.decode",
    "cedar.batch.dispatch", "cedar.http.request",
)


def merge(intervals: list) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def overlap(lo: float, hi: float, spans: list) -> float:
    return sum(max(0.0, min(hi, e) - max(lo, s)) for s, e in spans if s < hi and e > lo)


# ---- the xplane's own protobuf, as far as the scopes need it. ProfileData
# shows an event's own stats but not its metadata's, and a device op's
# framework name (``tf_op``: "jit(f)/cedar.match.scan/while/…") is a stat of
# its XEventMetadata. Field numbers: tsl/profiler/protobuf/xplane.proto.

def _varint(buf: bytes, i: int):
    val = shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        shift += 7
        if not b & 0x80:
            return val, i


def _fields(buf: bytes):
    """(field number, wire type, value) of one serialized message."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val = buf[i:i + size]
            i += size
        elif wire == 1:
            val = buf[i:i + 8]
            i += 8
        elif wire == 5:
            val = buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"wire type {wire}")
        yield num, wire, val


def _map_entry(buf: bytes):
    key, value = 0, b""
    for num, _w, val in _fields(buf):
        if num == 1:
            key = val
        elif num == 2:
            value = val
    return key, value


def op_scopes(pb_path: pathlib.Path) -> dict:
    """{device op name: (its cedar.match scopes, outermost first, joined
    by '/', its ``source`` file:line)} from the event metadata of the
    device planes. A ``while`` op carries no framework name of its own:
    its source line says which loop it is."""
    out: dict = {}
    for num, _w, plane in _fields(pathlib.Path(pb_path).read_bytes()):
        if num != 1:
            continue
        name, event_meta, stat_names = "", [], {}
        for f, _w2, val in _fields(plane):
            if f == 2:
                name = val.decode(errors="replace")
            elif f == 4:
                event_meta.append(_map_entry(val)[1])
            elif f == 5:
                key, meta = _map_entry(val)
                for g, _w3, v in _fields(meta):
                    if g == 2:
                        stat_names[key] = v.decode(errors="replace")
        if not DEVICE_PLANE.match(name):
            continue
        for meta in event_meta:
            op, texts, source = "", [], ""
            for f, _w2, val in _fields(meta):
                if f == 2:
                    op = val.decode(errors="replace")
                elif f == 5:  # XStat
                    stat = dict((g, v) for g, _w3, v in _fields(val))
                    text = ""
                    if 5 in stat:
                        text = stat[5].decode(errors="replace")
                    elif 6 in stat:
                        text = stat[6].decode(errors="replace")
                    elif 7 in stat:  # a reference to a stat metadata's name
                        text = stat_names.get(stat[7], "")
                    texts.append(text)
                    if stat_names.get(stat.get(1)) == "source":
                        source = text.rsplit("/", 1)[-1]
            found: list = []
            for text in texts:
                for scope in SCOPE.findall(text):
                    if scope not in found:
                        found.append(scope)
            out[op] = ("/".join(found), source)
    return out


def analyse(trace_dir: pathlib.Path) -> dict:
    from jax.profiler import ProfileData

    pbs = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    if not pbs:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(str(pbs[-1]))
    scopes = op_scopes(pbs[-1])
    host: dict = {}      # cedar.* name -> [[start, end], ...]
    device_ops: dict = {}  # op name -> [ns, scope]
    busy: list = []
    first_device = None
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            if first_device is None:
                first_device = plane.name
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    if ev.duration_ns <= 0:
                        continue
                    if plane.name == first_device:
                        busy.append([ev.start_ns, ev.start_ns + ev.duration_ns])
                    cell = device_ops.setdefault(
                        ev.name, [0.0, *scopes.get(ev.name, ("", ""))])
                    cell[0] += ev.duration_ns
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("cedar."):
                    host.setdefault(ev.name, []).append(
                        [ev.start_ns, ev.start_ns + ev.duration_ns])
    busy = merge(busy)
    united = {name: merge(spans) for name, spans in host.items()}
    gaps = sorted(
        ((busy[i + 1][0] - busy[i][1], busy[i][1], busy[i + 1][0])
         for i in range(len(busy) - 1)),
        reverse=True,
    )[:10]
    out_gaps = []
    for length, lo, hi in gaps:
        inside = {name: overlap(lo, hi, spans) for name, spans in united.items()}
        stage = next((n for n in SPECIFICITY if inside.get(n, 0.0) * 2 >= length), "none")
        out_gaps.append({
            "ms": length / 1e6, "stage": stage,
            "overlap_ms": {n: round(v / 1e6, 3) for n, v in
                           sorted(inside.items(), key=lambda kv: -kv[1]) if v > 0},
        })
    ranked = sorted(device_ops.items(), key=lambda kv: -kv[1][0])[:10]
    return {
        "xplane": str(pbs[-1]),
        "device_busy_ms": sum(e - s for s, e in busy) / 1e6,
        "host_events": {
            name: {"count": len(spans),
                   "total_ms": sum(e - s for s, e in spans) / 1e6,
                   "mean_ms": sum(e - s for s, e in spans) / 1e6 / len(spans)}
            for name, spans in sorted(host.items())
        },
        "idle_gaps": out_gaps,
        "device_ops": [{"name": name[:96], "seconds": ns / 1e9, "scope": scope,
                        "source": source}
                       for name, (ns, scope, source) in ranked],
    }


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if not 1 <= len(argv) <= 2:
        print(__doc__, file=sys.stderr)
        return 2
    text = json.dumps(analyse(pathlib.Path(argv[0])), indent=1)
    if len(argv) == 2:
        pathlib.Path(argv[1]).write_text(text + "\n")
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
