"""BENCHMARK.json and the data files it names: loading and validation.

A cell ``<config>.<traffic>`` is one entry of ``workloads`` plus
``configs/<config>.json``, ``traffic/<traffic>.json`` and
``cells/<cell>.json``; a per-layer metric is one entry of ``per_layer``
plus ``metrics/<name>.json`` (the same entry, with its reader and the
reader's parameters). Code comes in as files too: a corpus generator
(``corpora/``), a reader (``readers/``), a request kind (``kinds/``).
Everything is found by name, so a later change adds files and entries and
edits nothing that is here.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import pathlib
import re

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
# the directories of code that is found by name, and what each holds
CODE = {"corpora": "corpus generator", "readers": "reader", "kinds": "request kind"}
DEFAULT_KIND = "sar"  # the kind of a mix that names none


class ManifestError(Exception):
    pass


def load_json(path: pathlib.Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise ManifestError(f"missing file {path}") from None
    except ValueError as e:
        raise ManifestError(f"{path}: {e}") from None


class Manifest:
    """BENCHMARK.json of ``root`` with the files under ``bench_dir``."""

    def __init__(self, root: pathlib.Path = ROOT, bench_dir: pathlib.Path | None = None):
        self.root = pathlib.Path(root)
        self.dir = pathlib.Path(bench_dir) if bench_dir else self.root / "benchmark"
        self.doc = load_json(self.root / "BENCHMARK.json")

    # -- lookups by name
    def workload(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise ManifestError(
            f"no workload {name!r}; have {[w['name'] for w in self.doc['workloads']]}"
        )

    def config(self, name: str) -> dict:
        for c in self.doc["configs"]:
            if c["name"] == name:
                return load_json(self.root / c["file"])
        raise ManifestError(f"no configuration {name!r}")

    def traffic(self, name: str) -> dict:
        return load_json(self.dir / "traffic" / f"{name}.json")

    def cell(self, name: str) -> dict:
        return load_json(self.dir / "cells" / f"{name}.json")

    def metric_file(self, name: str) -> dict:
        return load_json(self.dir / "metrics" / f"{name}.json")

    def kind_of(self, traffic: str) -> str:
        """The name of the request kind that a mix sends."""
        return self.traffic(traffic).get("kind", DEFAULT_KIND)

    def metrics_for(self, workload: str, group: str) -> list:
        """The entries of ``end_to_end`` or ``per_layer`` that this cell
        reports: those that list it, and those that list no cells."""
        cell_e2e = {
            m["name"] for m in self.doc["end_to_end"]
            if workload in m.get("workloads", [workload])
        }
        if group == "end_to_end":
            return [m for m in self.doc["end_to_end"] if m["name"] in cell_e2e]
        out = []
        for m in self.doc["per_layer"]:
            if "workloads" in m:
                if workload in m["workloads"]:
                    out.append(m)
            elif m["moves"] in cell_e2e:
                out.append(m)
        return out


_FROM_ROOT: dict = {}  # path -> module, for code that came in under --root


def code_path(sub: str, name: str, bench_dir=None):
    """Where ``<sub>/<name>.py`` is: in this package, or — for a name the
    package does not have — under ``bench_dir``; None if in neither. The
    package's own comes first, so code under ``--root`` adds and never
    replaces: the yardstick is what is here."""
    if not isinstance(name, str) or not NAME.match(name):
        raise ManifestError(f"bad {CODE[sub]} name {name!r}")
    for base in (HERE, bench_dir):
        if base is not None:
            path = pathlib.Path(base) / sub / f"{name}.py"
            if path.is_file():
                return path
    return None


def code_module(sub: str, name: str, bench_dir=None):
    """``<sub>/<name>.py``, found by name (``code_path``'s rule)."""
    path = code_path(sub, name, bench_dir)
    if path is None:
        raise ManifestError(f"no {CODE[sub]} {name!r}: no {sub}/{name}.py")
    if path.parent.parent == HERE:
        return importlib.import_module(f"benchmark.{sub}.{name}")
    path = path.resolve()
    if path not in _FROM_ROOT:
        spec = importlib.util.spec_from_file_location(
            f"benchmark_root.{sub}.{name.replace('.', '_').replace('-', '_')}", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        _FROM_ROOT[path] = module
    return _FROM_ROOT[path]


def corpus_module(name: str, bench_dir=None):
    """``corpora/<name>.py``: ``build(params, seed)``."""
    return code_module("corpora", name, bench_dir)


def reader_module(name: str, bench_dir=None):
    """``readers/<name>.py``: ``read(ctx, params)``."""
    return code_module("readers", name, bench_dir)


def kind_module(name: str, bench_dir=None):
    """``kinds/<name>.py``: what one request is (``kinds/sar.py``)."""
    return code_module("kinds", name, bench_dir)


# ------------------------------------------------------------- validation

def _check_name(problems: list, what: str, value) -> None:
    if not isinstance(value, str) or not NAME.match(value):
        problems.append(f"{what}: {value!r} is not a name")


def _check_line(problems: list, what: str, value) -> None:
    if (not isinstance(value, str) or not 1 <= len(value) <= 200
            or "\n" in value or "\t" in value):
        problems.append(f"{what}: not one line of 1 to 200 characters")


def _check_code(problems: list, what: str, sub: str, name, bench_dir) -> None:
    try:
        if code_path(sub, name, bench_dir) is None:
            problems.append(f"{what}: no {sub}/{name}.py")
    except ManifestError as e:
        problems.append(f"{what}: {e}")


def validate(m: Manifest) -> list:
    """Every breach of the benchmark's contract that can be seen without a
    run, as a list of sentences; empty when the manifest holds."""
    d = m.doc
    problems: list = []
    if set(d) != TOP_KEYS:
        problems.append(f"top-level keys {sorted(d)} are not {sorted(TOP_KEYS)}")
        return problems
    if not (isinstance(d["run_seconds"], int) and 1 <= d["run_seconds"] <= 51):
        problems.append("run_seconds is not a whole number from 1 to 51")
    paths = d["paths"]
    if not 1 <= len(paths) <= 16:
        problems.append("paths: 1 to 16 directories")
    for p in paths:
        if not re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", p) or p.startswith("/") or ".." in p:
            problems.append(f"paths: bad directory {p!r}")
    if not 1 <= len(d["command"]) <= 32:
        problems.append("command: 1 to 32 words")
    for word in d["command"]:
        _check_line(problems, "command word", word)
        if word.startswith("/") or ".." in word.split("/"):
            problems.append(f"command word {word!r} leaves the repo")
        if "/" in word and not any(word.startswith(p + "/") for p in paths):
            problems.append(f"command word {word!r} names a file outside paths")

    def under_paths(f: str) -> bool:
        return any(f.startswith(p + "/") for p in paths)

    # configurations
    configs = {}
    files = set()
    if not 1 <= len(d["configs"]) <= 24:
        problems.append("configs: 1 to 24")
    for c in d["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            problems.append(f"config {c.get('name')}: keys {sorted(c)}")
            continue
        _check_name(problems, "config name", c["name"])
        _check_line(problems, f"config {c['name']} source", c["source"])
        _check_line(problems, f"config {c['name']} why", c["why"])
        if c["name"] in configs:
            problems.append(f"config {c['name']} appears twice")
        configs[c["name"]] = c
        if not under_paths(c["file"]) or c["file"] in files:
            problems.append(f"config {c['name']}: file {c['file']!r}")
        files.add(c["file"])
        try:
            generator = (load_json(m.root / c["file"]).get("corpus") or {}).get("generator")
            _check_code(problems, f"config {c['name']}", "corpora", generator, m.dir)
        except ManifestError as e:
            problems.append(f"config {c['name']}: {e}")
        if len(c["reduced"]) > 16:
            problems.append(f"config {c['name']}: more than 16 reduced keys")
        for key in c["reduced"]:
            _check_name(problems, f"config {c['name']} reduced key", key)
    # cells
    cells = {}
    pairs = set()
    if not 1 <= len(d["workloads"]) <= 24:
        problems.append("workloads: 1 to 24")
    for w in d["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            problems.append(f"workload {w.get('name')}: keys {sorted(w)}")
            continue
        _check_name(problems, "workload name", w["name"])
        _check_name(problems, "workload traffic", w["traffic"])
        _check_line(problems, f"workload {w['name']} why", w["why"])
        if w["name"] in cells:
            problems.append(f"workload {w['name']} appears twice")
        cells[w["name"]] = w
        if w["config"] not in configs:
            problems.append(f"workload {w['name']}: unknown config {w['config']!r}")
        if (w["config"], w["traffic"]) in pairs:
            problems.append(f"workload {w['name']}: pair appears twice")
        pairs.add((w["config"], w["traffic"]))
        if w["chips"] not in (1, 4):
            problems.append(f"workload {w['name']}: chips is 1 or 4")
        for sub in ("traffic/" + w["traffic"], "cells/" + w["name"]):
            if not (m.dir / f"{sub}.json").is_file():
                problems.append(f"workload {w['name']}: no {sub}.json")
        try:
            _check_code(problems, f"workload {w['name']}: traffic/{w['traffic']}.json",
                        "kinds", m.kind_of(w["traffic"]), m.dir)
        except ManifestError:
            pass  # no such mix: named above
    for name in configs:
        if not any(w.get("config") == name for w in d["workloads"]):
            problems.append(f"config {name} has no cell")
    four = sum(1 for w in d["workloads"] if w.get("chips") == 4)
    if four > max(1, len(d["workloads"]) // 2):
        problems.append("more than half of the cells ask for 4 chips")
    # metrics
    names = set()
    e2e = {}

    def check_metric(x: dict, allowed: set, what: str) -> bool:
        if not ({"name", "unit", "better", "source"} <= set(x) <= allowed):
            problems.append(f"{what} {x.get('name')}: keys {sorted(x)}")
            return False
        _check_name(problems, f"{what} name", x["name"])
        if not UNIT.match(x["unit"]):
            problems.append(f"{what} {x['name']}: bad unit {x['unit']!r}")
        if x["better"] not in ("lower", "higher"):
            problems.append(f"{what} {x['name']}: better is lower or higher")
        if x["source"] not in SOURCES:
            problems.append(f"{what} {x['name']}: bad source {x['source']!r}")
        if x["name"] in names:
            problems.append(f"metric {x['name']} appears twice")
        names.add(x["name"])
        for wl in x.get("workloads", []):
            if wl not in cells:
                problems.append(f"{what} {x['name']}: unknown workload {wl!r}")
        return True

    if not 1 <= len(d["end_to_end"]) <= 16:
        problems.append("end_to_end: 1 to 16 metrics")
    for x in d["end_to_end"]:
        if not check_metric(
            x, {"name", "unit", "better", "source", "bound", "workloads"}, "end_to_end"
        ):
            continue
        e2e[x["name"]] = x
        if x["source"] not in ("host_clock", "device_trace"):
            problems.append(f"end_to_end {x['name']}: source must be host_clock or device_trace")
        b = x.get("bound")
        if not isinstance(b, (int, float)) or not 0.01 <= b <= 0.25:
            problems.append(f"end_to_end {x['name']}: bound {b!r} is not in 0.01..0.25")
    if "setup_s" not in e2e:
        problems.append("end_to_end has no setup_s")
    elif "workloads" in e2e["setup_s"]:
        problems.append("setup_s is reported by every cell and lists none")
    if not 1 <= len(d["per_layer"]) <= 128:
        problems.append("per_layer: 1 to 128 metrics")
    for x in d["per_layer"]:
        if not check_metric(
            x, {"name", "unit", "better", "source", "layer", "moves", "workloads"},
            "per_layer",
        ):
            continue
        _check_line(problems, f"per_layer {x['name']} layer", x.get("layer"))
        if x.get("moves") not in e2e:
            problems.append(f"per_layer {x['name']}: moves unknown metric {x.get('moves')!r}")
            continue
        moved = e2e[x["moves"]]
        for wl in x.get("workloads", []):
            if wl not in moved.get("workloads", [wl]):
                problems.append(
                    f"per_layer {x['name']}: cell {wl} does not report {x['moves']}"
                )
        if "_roofline" in x["name"] and x["unit"] != "%":
            problems.append(f"per_layer {x['name']}: a roofline share has the unit %")
        try:
            spec = m.metric_file(x["name"])
        except ManifestError as e:
            problems.append(f"per_layer {x['name']}: {e}")
            continue
        if {k: v for k, v in spec.items() if k not in ("reader", "params")} != x:
            problems.append(f"per_layer {x['name']}: metrics/{x['name']}.json and its entry differ")
        _check_code(problems, f"per_layer {x['name']}", "readers", spec.get("reader"), m.dir)
    for wl in cells:
        got = [x["name"] for x in m.metrics_for(wl, "end_to_end")]
        if "setup_s" not in got or len(got) < 2:
            problems.append(f"cell {wl} reports {got}: setup_s and one more are needed")
        if not m.metrics_for(wl, "per_layer"):
            problems.append(f"cell {wl} reports no per-layer metric")
    if len(json.dumps(d)) > 64 * 1024:
        problems.append("BENCHMARK.json is over 64 KiB")
    return problems
