"""BENCHMARK.json against the benchmark's contract, and the data files the
harness finds by name."""

import copy
import json
import pathlib
import shutil

import pytest

from benchmark import manifest as mf

ROOT = pathlib.Path(__file__).resolve().parents[2]
DOC = json.loads((ROOT / "BENCHMARK.json").read_text())
SAT, LONE = "synth-10k.sar-saturate", "selector-1k.sar-lone"
PER_LAYER = [m["name"] for m in DOC["per_layer"]]


def problems_of(doc, tmp_path):
    """validate() of a changed BENCHMARK.json over the real data files."""
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    return mf.validate(mf.Manifest(tmp_path, ROOT / "benchmark"))


def test_the_manifest_holds():
    assert mf.validate(mf.Manifest()) == []


def test_the_cells_the_issue_asked_for_in_their_order():
    """What is contract: the first two cells, the end-to-end names, the
    command and the paths. Cells after the second are not named here; the
    parametrised checks below hold each to what it reports."""
    assert [w["name"] for w in DOC["workloads"][:2]] == [SAT, LONE]
    assert [m["name"] for m in DOC["end_to_end"]] == [
        "decisions_per_s", "latency_p50_ms", "latency_p95_ms", "setup_s"]
    assert all(w["chips"] == 1 for w in DOC["workloads"][:2])
    cells = {m["name"]: m.get("workloads") for m in DOC["end_to_end"]}
    assert cells["setup_s"] is None
    assert SAT in cells["decisions_per_s"]
    assert SAT not in cells["latency_p50_ms"] + cells["latency_p95_ms"]
    assert LONE in cells["latency_p50_ms"] and LONE in cells["latency_p95_ms"]
    assert DOC["command"] == ["python3", "benchmark/run.py"]
    assert DOC["paths"] == ["benchmark", "tests/benchmark_tests"]


def _drop(key):
    def change(d):
        del d[key]
    return change


def _set(path, value):
    def change(d):
        node = d
        for k in path[:-1]:
            node = node[k]
        node[path[-1]] = value
    return change


@pytest.mark.parametrize(
    "change,expect",
    [
        (_drop("per_layer"), "top-level keys"),
        (_set(["extra"], 1), "top-level keys"),
        (_set(["run_seconds"], 52), "run_seconds"),
        (_set(["run_seconds"], 2.5), "run_seconds"),
        (_set(["paths"], ["/abs"]), "bad directory"),
        (_set(["paths"], ["a/../b"]), "bad directory"),
        (_set(["command"], ["python3", "bench.py/x"]), "outside paths"),
        (_set(["command"], ["python3", "/usr/bin/x"]), "leaves the repo"),
        (_set(["configs", 0, "name"], "has space"), "not a name"),
        (_set(["configs", 0, "why"], "two\nlines"), "not one line"),
        (_set(["configs", 0, "extra"], 1), "keys"),
        (_set(["configs", 0, "file"], "cedar_tpu/x.json"), "file"),
        (_set(["configs", 1, "file"], "benchmark/configs/synth-10k.json"), "file"),
        (_set(["workloads", 0, "chips"], 2), "chips is 1 or 4"),
        (_set(["workloads", 0, "config"], "nope"), "unknown config"),
        (_set(["workloads", 0, "rate_per_s"], 350), "keys"),
        (_set(["workloads", 0, "traffic"], "no-such-mix"), "no traffic/no-such-mix.json"),
        (_set(["workloads", 0, "why"], "x" * 201), "not one line"),
        (_set(["end_to_end", 0, "unit"], "per second"), "bad unit"),
        (_set(["end_to_end", 0, "unit"], "µs"), "bad unit"),
        (_set(["end_to_end", 0, "unit"], "x" * 17), "bad unit"),
        (_set(["end_to_end", 0, "name"], "a/b"), "not a name"),
        (_set(["end_to_end", 0, "name"], "x" * 65), "not a name"),
        (_set(["end_to_end", 0, "better"], "faster"), "lower or higher"),
        (_set(["end_to_end", 0, "bound"], 0.3), "bound"),
        (_set(["end_to_end", 0, "bound"], 0.001), "bound"),
        (_set(["end_to_end", 0, "source"], "program_counter"), "host_clock or device_trace"),
        (_set(["end_to_end", 0, "why"], "because"), "keys"),
        (_set(["per_layer", 0, "moves"], "nothing"), "moves unknown metric"),
        (_set(["per_layer", 0, "name"], DOC["per_layer"][1]["name"]), "appears twice"),
        (_set(["per_layer", 0, "why"], "because"), "keys"),
        (_set(["per_layer", PER_LAYER.index("ingress_ms.lone"), "workloads"], [SAT]),
         "does not report latency_p50_ms"),
        (_set(["per_layer", PER_LAYER.index("ingress_ms.saturate"), "workloads"], [SAT, LONE]),
         "does not report decisions_per_s"),
        (_set(["per_layer", 0, "layer"], "two\nlines"), "not one line"),
        (_set(["per_layer", 0, "unit"], "s"), "and its entry differ"),
        (_set(["per_layer", 0, "name"], "no_such_metric_ms"), "missing file"),
    ],
)
def test_a_breach_of_the_contract_is_named(change, expect, tmp_path):
    doc = copy.deepcopy(DOC)
    change(doc)
    found = problems_of(doc, tmp_path)
    assert any(expect in p for p in found), found


def test_setup_s_is_required(tmp_path):
    doc = copy.deepcopy(DOC)
    doc["end_to_end"] = [m for m in doc["end_to_end"] if m["name"] != "setup_s"]
    doc["per_layer"] = [m for m in doc["per_layer"] if m["moves"] != "setup_s"]
    assert any("no setup_s" in p for p in problems_of(doc, tmp_path))


def test_a_configuration_without_a_cell_is_refused(tmp_path):
    doc = copy.deepcopy(DOC)
    doc["workloads"] = [w for w in doc["workloads"] if w["config"] != "selector-1k"]
    found = problems_of(doc, tmp_path)
    assert any("selector-1k has no cell" in p for p in found)


def test_a_roofline_share_has_the_unit_percent(tmp_path):
    doc = copy.deepcopy(DOC)
    for m in doc["per_layer"]:
        if "_roofline" in m["name"]:
            m["unit"] = "ratio"
    assert any("roofline share has the unit %" in p for p in problems_of(doc, tmp_path))


# What every per-layer metric, every cell and every configuration owes, as
# functions of the manifest they are given: the real one here, and the copy
# a later PR's files make of it in the door test below.
LAYERS_OF_EVERY_CELL = ("fallback_row_share", "match_roofline", "device_idle_share",
                        "ingress_ms", "dispatch_ms_per_batch")


def check_per_layer_metric(m: mf.Manifest, metric: str) -> None:
    doc = m.doc
    entry = next(x for x in doc["per_layer"] if x["name"] == metric)
    spec = m.metric_file(metric)
    assert {k: v for k, v in spec.items() if k not in ("reader", "params")} == entry
    assert callable(mf.reader_module(spec["reader"], m.dir).read)
    moved = next(x for x in doc["end_to_end"] if x["name"] == entry["moves"])
    for cell in entry.get("workloads", []):
        assert cell in moved.get("workloads", [cell])
    reporting = [w["name"] for w in doc["workloads"]
                 if entry in m.metrics_for(w["name"], "per_layer")]
    assert reporting, "no cell reports this metric"


def check_cell(m: mf.Manifest, cell: str) -> None:
    """What a cell owes follows from what it reports, not from its name."""
    e2e = {x["name"] for x in m.metrics_for(cell, "end_to_end")}
    layer = {x["name"] for x in m.metrics_for(cell, "per_layer")}
    assert "setup_s" in e2e and len(e2e) >= 2
    if cell == SAT:   # its 95th percentile is a per-layer metric (PERF.md, section 2)
        assert "decisions_per_s" in e2e
        assert "client_latency_p95_ms.saturate" in layer and "latency_p95_ms" not in e2e
    assert {"ready_s", "ladder_s"} <= layer
    # each layer between the socket and the device has a witness of the
    # cell's own: ingress_ms.admit serves a cell on another path, and
    # ingress_ms.lone is not demanded of it
    for base in LAYERS_OF_EVERY_CELL:
        assert any(n == base or n.startswith(base + ".") for n in layer), base
    # the stops' witnesses and the compile count are read in every traced run
    assert any(n.startswith("over_deadline_share") for n in layer)
    assert any(n.startswith("gc_pause_max_ms") for n in layer)
    assert any(n.startswith("window_compiles") for n in layer)
    # no cell reports a layer metric without the end-to-end metric it moves
    assert {x["moves"] for x in m.metrics_for(cell, "per_layer")} <= e2e
    # the cell's data files, and the kind its mix names, are found by name
    w = m.workload(cell)
    assert m.config(w["config"])["corpus"]["generator"]
    assert m.traffic(w["traffic"])["loop"] in ("open", "closed")
    kind = mf.kind_module(m.kind_of(w["traffic"]), m.dir)
    assert kind.PATH.startswith("/")
    for stated in ("body", "distinct", "expected", "verdict", "gave_up"):
        assert callable(getattr(kind, stated)), stated
    assert isinstance(m.cell(cell), dict)


def check_configuration(m: mf.Manifest, config: str) -> None:
    cfg = m.config(config)
    assert cfg["guarantees"]["answers"] and cfg["guarantees"]["reload"]
    assert "never Allow" in cfg["guarantees"]["deadline"]
    assert "failure, not an answer" in cfg["guarantees"]["failures"]
    assert "Price:" in cfg["assumed"]["request_timeout_ms"]
    assert cfg["assumed"]["placement"] and cfg["assumed"]["seed"]
    assert cfg["source"] == next(c["source"] for c in m.doc["configs"] if c["name"] == config)
    assert cfg["assumed"]["generator"]
    # every serving flag is the program's default but --max-batch and the
    # per-request deadline: an answer that the host held up comes late and
    # is timed as late, and each departure is listed under ``assumed``
    assert cfg["server_args"] == ["--max-batch", "512", "--request-timeout-ms", "30000"]
    assert cfg["assumed"]["max_batch"] and cfg["assumed"]["request_timeout_ms"]
    assert callable(mf.corpus_module(cfg["corpus"]["generator"], m.dir).build)


@pytest.mark.parametrize("metric", [m["name"] for m in DOC["per_layer"]])
def test_every_per_layer_metric_has_a_file_a_reader_and_cells_that_report_what_it_moves(metric):
    check_per_layer_metric(mf.Manifest(), metric)


@pytest.mark.parametrize("cell", [w["name"] for w in DOC["workloads"]])
def test_every_cell_reports_what_the_acceptance_asks(cell):
    check_cell(mf.Manifest(), cell)


@pytest.mark.parametrize("config", [c["name"] for c in DOC["configs"]])
def test_a_configuration_states_its_guarantees_and_what_it_assumed(config):
    check_configuration(mf.Manifest(), config)


def test_unknown_names_are_errors():
    m = mf.Manifest()
    with pytest.raises(mf.ManifestError):
        m.workload("synth-10k.nope")
    with pytest.raises(mf.ManifestError):
        m.config("nope")
    with pytest.raises(mf.ManifestError):
        m.traffic("nope")
    with pytest.raises(mf.ManifestError):
        mf.reader_module("../evil")
    for find in (mf.reader_module, mf.corpus_module, mf.kind_module):
        with pytest.raises(mf.ManifestError, match="no_such_module.py"):
            find("no_such_module")


DOOR = pathlib.Path(__file__).resolve().parent / "door"
DOOR_CELL = "urls-200.url-trickle"


def extended_root(tmp_path) -> pathlib.Path:
    """A copy of the benchmark's data extended as a later PR would extend
    it, by files and entries only (the steps of benchmark/README.md): the
    files of ``door/benchmark`` — a configuration with a corpus generator
    of its own, a traffic mix with a request kind of its own, a cell, and
    per-layer files of the cell's own suffix — laid over the copy, and
    ``door/entries.json`` appended to BENCHMARK.json."""
    tmp_path.mkdir(parents=True, exist_ok=True)
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for sub in ("configs", "traffic", "cells", "metrics"):
        shutil.copytree(ROOT / "benchmark" / sub, tmp_path / "benchmark" / sub)
    before = {p: p.read_bytes() for p in (tmp_path / "benchmark").rglob("*.json")}
    added = [p.relative_to(DOOR) for p in (DOOR / "benchmark").rglob("*") if p.is_file()]
    # code is looked for in the package first: a file that is here cannot be replaced
    assert not [p for p in added if (ROOT / p).exists()]
    shutil.copytree(DOOR / "benchmark", tmp_path / "benchmark", dirs_exist_ok=True)
    entries = json.loads((DOOR / "entries.json").read_text())
    doc = json.loads((tmp_path / "BENCHMARK.json").read_text())
    doc["configs"] += entries["configs"]
    doc["workloads"] += entries["workloads"]
    for name, cells in entries["end_to_end"].items():
        next(m for m in doc["end_to_end"] if m["name"] == name)["workloads"] += cells
    for f in sorted((DOOR / "benchmark" / "metrics").glob("*.json")):
        spec = json.loads(f.read_text())
        doc["per_layer"].append(
            {k: v for k, v in spec.items() if k not in ("reader", "params")})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    # nothing that was there has been edited
    assert all(p.read_bytes() == data for p, data in before.items())
    return tmp_path


def test_a_configuration_a_mix_a_cell_and_a_metric_are_added_as_files_only(tmp_path):
    root = extended_root(tmp_path)
    m = mf.Manifest(root)
    assert mf.validate(m) == []
    assert m.cell(DOOR_CELL)["rate_per_s"] == 40
    assert m.traffic("url-trickle")["aimed_share"] == 0.5
    assert m.config("urls-200")["corpus"]["params"]["policies"] == 200
    names = {x["name"] for x in m.metrics_for(DOOR_CELL, "per_layer")}
    assert "allow_ms.url" in names
    assert {"ready_s", "ladder_s"} <= names            # unlisted: every cell
    assert "ingress_ms.lone" not in names              # listed: its own cells
    assert {x["name"] for x in m.metrics_for(DOOR_CELL, "end_to_end")} == {
        "latency_p50_ms", "latency_p95_ms", "setup_s"}


def test_the_door_a_later_prs_files_pass_every_check_the_manifest_is_held_to(tmp_path):
    """The door itself: on the copy, every cell-, configuration- and
    metric-parametrised check of this file, over the copy's own lists."""
    m = mf.Manifest(extended_root(tmp_path))
    assert mf.validate(m) == []
    assert [w["name"] for w in m.doc["workloads"]][-1] == DOOR_CELL
    for w in m.doc["workloads"]:
        check_cell(m, w["name"])
    for c in m.doc["configs"]:
        check_configuration(m, c["name"])
    for x in m.doc["per_layer"]:
        check_per_layer_metric(m, x["name"])
    from test_benchmark_phase_metrics import check_phase_entries

    check_phase_entries(m)


def test_code_that_comes_in_as_a_file_is_found_under_the_root_it_came_in(tmp_path):
    """One rule for kinds, corpora and readers: the package's own first,
    then ``<root>/benchmark/<kinds|corpora|readers>/<name>.py``."""
    m = mf.Manifest(extended_root(tmp_path))
    kind = mf.kind_module(m.kind_of("url-trickle"), m.dir)
    assert kind.PATH == "/v1/authorize?timeout=30s"
    assert mf.kind_module(m.kind_of("sar-lone"), m.dir) is mf.kind_module("sar")
    assert mf.kind_module("sar").PATH == "/v1/authorize"
    assert mf.kind_module("sar_url", m.dir) is kind              # loaded once
    with pytest.raises(mf.ManifestError, match="kinds/sar_url.py"):
        mf.kind_module("sar_url")                                # not without its root
    corpus = mf.corpus_module("urls", m.dir).build({"policies": 20}, 5)
    assert list(corpus.files) == ["urls.cedar"]
    # a reader under the root, and a name the package has is the package's
    (m.dir / "readers").mkdir()
    (m.dir / "readers" / "always_one.py").write_text("def read(ctx, params):\n    return 1.0\n")
    (m.dir / "readers" / "harness_value.py").write_text("def read(ctx, params):\n    return -1\n")
    assert mf.reader_module("always_one", m.dir).read(None, {}) == 1.0
    from benchmark.readers import harness_value

    assert mf.reader_module("harness_value", m.dir) is harness_value


@pytest.mark.parametrize("change,expect", [
    (lambda b: (b / "traffic" / "url-trickle.json").write_text(
        json.dumps({"loop": "open", "connections": 8, "processes": 2, "kind": "no_such_kind"})),
     "no kinds/no_such_kind.py"),
    (lambda b: (b / "traffic" / "url-trickle.json").write_text(
        json.dumps({"loop": "open", "connections": 8, "processes": 2, "kind": "../sar"})),
     "bad request kind name"),
    (lambda b: (b / "kinds" / "sar_url.py").unlink(), "no kinds/sar_url.py"),
    (lambda b: (b / "corpora" / "urls.py").unlink(), "no corpora/urls.py"),
    (lambda b: (b / "metrics" / "allow_ms.url.json").write_text(json.dumps(dict(
        json.loads((b / "metrics" / "allow_ms.url.json").read_text()), reader="no_such_reader"))),
     "no readers/no_such_reader.py"),
], ids=["unknown-kind", "bad-kind-name", "kind-file-gone", "corpus-file-gone", "unknown-reader"])
def test_code_that_is_named_and_not_there_is_named_by_validate(change, expect, tmp_path):
    root = extended_root(tmp_path)
    change(root / "benchmark")
    found = mf.validate(mf.Manifest(root))
    assert any(expect in p for p in found), found


@pytest.mark.parametrize("mix,problem", [
    ({"connections": 64, "processes": 5}, "do not divide"),
    ({"connections": 4, "processes": 0}, "do not divide"),
    ({"connections": 4, "processes": 2, "loop": "ring"}, "unknown loop"),
])
def test_a_mix_that_cannot_be_driven_is_refused(mix, problem):
    from benchmark import traffic

    with pytest.raises(ValueError, match=problem):
        traffic.Plan(None, {"loop": "closed", **mix}, {}, 1, 1.0)
