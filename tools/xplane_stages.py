#!/usr/bin/env python3
"""Put a profiler trace's idle gaps down to the program's own stages, or
open its launches.

    JAX_PLATFORMS=cpu python tools/xplane_stages.py TRACE_DIR [OUT.json]
    JAX_PLATFORMS=cpu python tools/xplane_stages.py launch TRACE_DIR [OUT.json]

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

``launch`` puts every batch of the trace on one clock by the ``seq`` its
``cedar.*`` annotations carry (obs/trace.py): ``cedar.dispatch.launch`` and
``cedar.dispatch.call`` on the dispatch thread, the runtime's host events
inside the call (uploads, execute, the rest by name), the batch's ``XLA
Modules`` run on the first device, ``cedar.dispatch.readback``, the
runtime's D2H events and ``cedar.decode.device_wait`` on the decode thread.
It prints the medians of the consecutive terms of a batch's round trip and
the share of launch + wait the named events and the gaps between them
cover (``launch_anatomy`` below).

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


# ---- launch: a batch's round trip on one clock

MODULES_LINE = "XLA Modules"
MATCH_MODULE = re.compile(r"match_rules_codes")
# the runtime's own host events (host_tracer_level 2). An upload is a
# synchronous call on the launching thread; the execute is looked for on
# every host line, because a PJRT plugin's own events come on a line with
# no thread name; the rest of the runtime (linearize, the H2D and D2H
# dispatches, the completion events) runs on threads of its own
UPLOAD = re.compile(r"BufferFromHost|TransferToDevice|DevicePut")
EXECUTE = re.compile(r"Executable(::|_)Execute")
D2H = re.compile(r"CopyToHost|TransferFrom|copy_to_host|D2H")
DONE = re.compile(r"Execute=>Done")  # the host learns that an execute ended
# the consecutive terms of a launch, of its wait, and of the round trip
# from the launch's end to the wait's end (the device's times put on the
# host's clock by CLOCK_SHIFTS' pairing, below)
LAUNCH_TERMS = (
    "launch_python_before_call", "call_args", "uploads", "upload_gaps",
    "execute", "call_return", "launch_after_call",
)
WAIT_TERMS = ("wait_to_d2h_end", "d2h_end_to_wait_end")
ROUND_TRIP_TERMS = (
    "launch_end_to_device_start", "device_run", "device_end_to_d2h_end",
    "d2h_end_to_wait_end",
)
CLOCK_SHIFTS = range(-3, 4)


def _median(values):
    xs = sorted(v for v in values if v is not None)
    if not xs:
        return None
    n = len(xs)
    return xs[n // 2] if n % 2 else (xs[n // 2 - 1] + xs[n // 2]) / 2


def _top(events: list) -> list:
    """The events not nested inside an earlier one (name, start, end)."""
    out: list = []
    for ev in sorted(events, key=lambda e: (e[1], -e[2])):
        if out and ev[2] <= out[-1][2]:
            continue
        out.append(ev)
    return out


def _read_launch_trace(trace_dir: pathlib.Path):
    """(host lines: {thread: [(name, start, end)]}, cedar events: [(name,
    thread, start, end, stats)], the first device's module runs: [(name,
    start, end)] on the device's clock, the xplane's path)."""
    from jax.profiler import ProfileData

    pbs = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    if not pbs:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(str(pbs[-1]))
    lines: dict = {}
    cedar: list = []
    modules: list = []
    first_device = None
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            if first_device is None:
                first_device = plane.name
            if plane.name != first_device:
                continue
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules.extend(
                        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events if MATCH_MODULE.search(ev.name))
            continue
        if not plane.name.startswith("/host:"):
            continue
        for k, line in enumerate(plane.lines):
            thread = f"{plane.name}#{k}:{line.name}"
            events = []
            for ev in line.events:
                name, t0 = ev.name, ev.start_ns
                t1 = t0 + ev.duration_ns
                if name.startswith("cedar."):
                    cedar.append((name, thread, t0, t1, dict(ev.stats)))
                else:
                    events.append((name, t0, t1))
            lines[thread] = events
    modules.sort(key=lambda m: m[1])
    return lines, cedar, modules, str(pbs[-1])


def _inside(lines: dict, t0: int, t1: int, pattern=None) -> list:
    """(name, start, end, thread) of every host event within [t0, t1]."""
    return [(n, a, b, thread) for thread, events in lines.items()
            for n, a, b in events
            if t0 <= a and b <= t1 and (pattern is None or pattern.search(n))]


def _clock_offset(execs: list, modules: list, dones: list):
    """The device's clock against the host's, from the executes in launch
    order paired with the module runs in order: the pairing (a shift of a
    few runs, for the trace's edges) that keeps the device's start after
    the host's execute steadiest, and the offset ``delta`` (device time =
    host time + delta) bounded by each pair — the device starts after the
    execute began, and ends before the host learns it ended (``dones``:
    the host's time of that, one per execute, or None). Returns
    ``(pairs {execute index: module index}, delta, (lo, hi))`` or None."""
    best = None
    for shift in sorted(CLOCK_SHIFTS, key=abs):  # a tie goes to the nearest
        ks = [k for k in range(len(execs)) if 0 <= k + shift < len(modules)]
        if len(ks) < max(3, len(execs) // 2):
            continue
        gaps = sorted(modules[k + shift][1] - execs[k] for k in ks)
        spread = gaps[3 * len(gaps) // 4] - gaps[len(gaps) // 4]
        if best is None or spread < best[0]:
            best = (spread, shift, ks)
    if best is None:
        return None
    _, shift, ks = best
    hi = min(modules[k + shift][1] - execs[k] for k in ks)
    ends = [modules[k + shift][2] - dones[k] for k in ks if dones[k] is not None]
    lo = max(ends) if ends else hi
    delta = (lo + hi) / 2 if lo <= hi else hi
    return {k: k + shift for k in ks}, delta, (lo, hi)


def launch_anatomy(trace_dir: pathlib.Path, examples: int = 3) -> dict:
    """Every batch of the trace by ``seq``: its launch opened (the call,
    the runtime's events inside it), its device run and its wait, as the
    medians of consecutive terms in ms — LAUNCH_TERMS add up to the
    launch, WAIT_TERMS to the wait, ROUND_TRIP_TERMS run from the launch's
    end to the wait's end — and the share of launch + wait that named
    events and the gaps between them cover. A batch with no launch (its
    rows answered at the encoder's gate), one of several chunks, and one
    the trace's edges cut are counted apart."""
    lines, cedar, modules, path = _read_launch_trace(trace_dir)
    calls: dict = {}      # thread -> [(start, end, stats)]
    readbacks: dict = {}  # thread -> [(start, end)]
    by_seq: dict = {}
    all_launches = []
    for name, thread, t0, t1, stats in cedar:
        if name == "cedar.dispatch.call":
            calls.setdefault(thread, []).append((t0, t1, stats))
            continue
        if name == "cedar.dispatch.readback":
            readbacks.setdefault(thread, []).append((t0, t1))
        if name == "cedar.dispatch.launch":
            all_launches.append((t0, t1, thread))
        seq = stats.get("seq")
        if seq is not None:
            by_seq.setdefault(int(seq), {}).setdefault(name, []).append(
                (thread, t0, t1, stats))
    # the runtime's D2H events, less the copy_to_host_async calls made
    # inside cedar.dispatch.readback (they start a copy; they do not wait)
    d2h = []
    for thread, events in lines.items():
        starts = readbacks.get(thread, [])
        d2h.extend((n, a, b) for n, a, b in events if D2H.search(n)
                   and not any(r0 <= a and b <= r1 for r0, r1 in starts))
    dones = sorted(a for events in lines.values() for n, a, _ in events
                   if DONE.search(n))

    # every launch's call and the runtime's execute inside it, in order
    opened: dict = {}  # (thread, launch start) -> (call, execute (x0, x1))
    execs = []
    for l0, l1, thread in sorted(all_launches):
        call = next(((c0, c1, cs) for c0, c1, cs in calls.get(thread, [])
                     if l0 <= c0 and c1 <= l1), None)
        x = None
        if call is not None:
            top = _top([e[:3] for e in _inside(lines, call[0], call[1], EXECUTE)])
            if top:
                x = (top[0][1], top[-1][2])
                execs.append(x[0])
        opened[thread, l0] = (call, x)
    # each execute's completion as the host learns it: the first DONE
    # after the execute began and after the previous execute's (they
    # complete in order; at saturation one lands after the next begins)
    done_after, j = [], 0
    for x0 in execs:
        while j < len(dones) and dones[j] < x0:
            j += 1
        done_after.append(dones[j] if j < len(dones) else None)
        j += 1
    clock = _clock_offset(execs, modules, done_after) if modules else None
    exec_index = {x0: k for k, x0 in enumerate(execs)}

    counts = {"launched": 0, "no_launch": 0, "chunked": 0, "cut_by_window": 0}
    rows: list = []
    in_call: dict = {}
    threads_ok = True
    for seq in sorted(by_seq):
        rec = by_seq[seq]
        launches = rec.get("cedar.dispatch.launch", [])
        waits = rec.get("cedar.decode.device_wait", [])
        if not launches:
            full = rec.get("cedar.batch.dispatch") and rec.get("cedar.batch.decode")
            counts["no_launch" if full else "cut_by_window"] += 1
            continue
        if not waits or not rec.get("cedar.batch.decode"):
            counts["cut_by_window"] += 1
            continue
        if len(launches) > 1:
            counts["chunked"] += 1
            continue
        counts["launched"] += 1
        (l_thread, l0, l1, _), = launches
        w_thread, w0, w1, _ = waits[0]
        threads_ok &= l_thread != w_thread
        call, x = opened.get((l_thread, l0), (None, None))
        row = {"seq": seq, "launch": (l1 - l0) / 1e6, "wait": (w1 - w0) / 1e6,
               "launch_end_to_wait_start": (w0 - l1) / 1e6}
        readback = rec.get("cedar.dispatch.readback")
        if readback:
            row["readback"] = (readback[0][2] - readback[0][1]) / 1e6
        covered = 0
        if call is not None:
            c0, c1, cstats = call
            row["uploads_stat"] = cstats.get("uploads")
            row["upload_bytes_stat"] = cstats.get("upload_bytes")
            totals: dict = {}
            for n, a, b, _t in _inside(lines, c0, c1):
                totals[n] = totals.get(n, 0) + b - a
            for n, ns in totals.items():
                in_call.setdefault(n, []).append(ns / 1e6)
        if x is not None:
            x0, x1 = x
            ups = _top([(n, a, b) for n, a, b in lines.get(l_thread, [])
                        if c0 <= a and b <= x0 and UPLOAD.search(n)])
            first = ups[0][1] if ups else x0
            up_ns = sum(b - a for _, a, b in ups)
            row.update({
                "launch_python_before_call": (c0 - l0) / 1e6,
                "call_args": (first - c0) / 1e6,
                "uploads": up_ns / 1e6,
                "upload_gaps": (x0 - first - up_ns) / 1e6,
                "execute": (x1 - x0) / 1e6,
                "call_return": (c1 - x1) / 1e6,
                "launch_after_call": (l1 - c1) / 1e6,
                "upload_events": [(n, (b - a) / 1e6) for n, a, b in ups],
            })
            covered += l1 - l0
            k = exec_index.get(x0)
            if clock is not None and k in clock[0]:
                _, m0, m1 = modules[clock[0][k]]
                d0, d1 = m0 - clock[1], m1 - clock[1]
                row["launch_end_to_device_start"] = (d0 - l1) / 1e6
                row["device_run"] = (m1 - m0) / 1e6
                row["module"] = modules[clock[0][k]][0]
        ends = [b for _, a, b in d2h if a >= l1 and b <= w1]
        if ends:
            end = max(ends)
            row["d2h_end_to_wait_end"] = (w1 - end) / 1e6
            row["wait_to_d2h_end"] = (max(end, w0) - w0) / 1e6
            row["d2h_events"] = sorted({n for n, a, b in d2h if a >= l1 and b <= w1})
            if "device_run" in row:
                row["device_end_to_d2h_end"] = (end - d1) / 1e6
            covered += w1 - w0
        row["covered_share"] = covered / max(1, (l1 - l0) + (w1 - w0))
        rows.append(row)

    def med(key):
        return _median(r.get(key) for r in rows)

    uploads_by_index: dict = {}
    for r in rows:
        for i, (_n, ms) in enumerate(r.get("upload_events", [])):
            uploads_by_index.setdefault(i, []).append(ms)
    total = sum(r["launch"] + r["wait"] for r in rows)
    covered_ms = sum(r["covered_share"] * (r["launch"] + r["wait"]) for r in rows)
    return {
        "xplane": path,
        "batches": counts,
        "launch_and_wait_on_own_threads": threads_ok,
        "medians_ms": {
            "launch": med("launch"), "wait": med("wait"),
            "launch_plus_wait": _median(r["launch"] + r["wait"] for r in rows),
            "readback": med("readback"),
            "launch_end_to_wait_start": med("launch_end_to_wait_start"),
            **{t: med(t) for t in LAUNCH_TERMS + WAIT_TERMS + ROUND_TRIP_TERMS},
        },
        "uploads_per_launch": med("uploads_stat"),
        "upload_bytes_per_launch": med("upload_bytes_stat"),
        "upload_ms_by_order": [_median(v) for _, v in sorted(uploads_by_index.items())],
        "covered_share": covered_ms / total if total else None,
        # the device's clock against the host's: device = host + delta;
        # the launch_end_to_device_start and device_end_to_d2h_end terms
        # are good to half the bounds' width
        "clock_offset_ms": None if clock is None else {
            "delta": clock[1] / 1e6, "lo": clock[2][0] / 1e6, "hi": clock[2][1] / 1e6},
        # every runtime event inside the call, on any host line, by name:
        # the batches it came in, and its median ms a batch (nested events
        # count in their own name and in their parent's)
        "in_call_by_name": {
            n: {"batches": len(v), "median_ms": _median(v)}
            for n, v in sorted(in_call.items(), key=lambda kv: -_median(kv[1]))
            if len(v) * 2 >= len(rows)
        },
        "d2h_events": sorted({n for r in rows for n in r.get("d2h_events", [])}),
        "examples": rows[:examples],
        "by_seq": {
            seq: {name: [(t, (e - s) / 1e6) for t, s, e, _ in evs]
                  for name, evs in by_seq[seq].items()}
            for seq in sorted(by_seq)
        },
    }


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    mode = analyse
    if argv[:1] == ["launch"]:
        mode, argv = launch_anatomy, argv[1:]
    if not 1 <= len(argv) <= 2:
        print(__doc__, file=sys.stderr)
        return 2
    doc = mode(pathlib.Path(argv[0]))
    if len(argv) == 2:
        pathlib.Path(argv[1]).write_text(json.dumps(doc, indent=1) + "\n")
    doc.pop("by_seq", None)  # the file keeps it; the screen gets the summary
    print(json.dumps(doc, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
