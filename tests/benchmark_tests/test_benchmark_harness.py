"""The harness's own decisions: where each process runs, when a server
counts as warm, what the collector's hook reads, and the result's line."""

import gc
import json

import pytest

from benchmark import run, serve
from benchmark.kinds import sar


@pytest.mark.parametrize(
    "processes,cores,server,generators,parent",
    [
        # the one-chip machine: 13 cores
        (4, range(13), list(range(8)), [8, 9, 10, 11], [12]),
        (1, range(13), list(range(11)), [11], [12]),
        # the four-chip host, and a set of cores that does not start at 0
        (4, range(30), list(range(25)), [25, 26, 27, 28], [29]),
        (1, [2, 3, 5, 7], [2, 3], [5], [7]),
        (4, range(7), [0, 1], [2, 3, 4, 5], [6]),
    ],
)
def test_every_process_gets_cores_of_its_own(processes, cores, server, generators, parent):
    plan = run.plan_cores(processes, cores)
    assert (plan["server"], plan["generators"], plan["parent"]) == (server, generators, parent)
    assert not set(server) & set(generators) and not set(parent) & set(server + generators)
    assert plan["cores"] == len(list(cores))


@pytest.mark.parametrize("processes,cores", [(4, range(6)), (1, range(3)), (1, [0])])
def test_a_host_too_small_for_the_generators_fails_the_run(processes, cores):
    with pytest.raises(run.RunFailure, match="too few"):
        run.plan_cores(processes, cores)
    # a rehearsal, whose numbers mean nothing, goes on with nothing pinned
    plan = run.plan_cores(processes, cores, rehearsal=True)
    assert plan["server"] is None and plan["parent"] is None
    assert plan["generators"] == [None] * processes


WARM = {"running": False, "shapes": 52, "compiled": 52, "failures": 0}


@pytest.mark.parametrize(
    "docs,done",
    [
        ({"authorization": {"warm": WARM}, "admission": {"warm": WARM}}, True),
        ({"authorization": {"warm": WARM}}, False),                      # one engine only
        ({"authorization": {"warm": WARM}, "admission": {"warm": {}}}, False),
        ({"authorization": {"warm": WARM},
          "admission": {"warm": dict(WARM, running=True)}}, False),
        ({"authorization": {"warm": WARM},
          "admission": {"warm": dict(WARM, compiled=40)}}, False),
        ({"authorization": {"warm": dict(WARM, shapes=0, compiled=0)},
          "admission": {"warm": WARM}}, False),                          # no ladder ran
        # a failed shape completes the ladder; the run then fails on the failure
        ({"authorization": {"warm": dict(WARM, compiled=51, failures=1)},
          "admission": {"warm": WARM}}, True),
    ],
)
def test_both_ladders_have_to_be_complete(docs, done):
    assert run.ladders_done(docs) is done


def test_the_collectors_hook_reads_the_longest_pause_since_the_last_reading():
    pauses = serve.GcPauses()
    gc.callbacks.append(pauses)
    try:
        junk = [[i] for i in range(200_000)]
        gc.collect()
        gc.collect(0)
        first = pauses.take()
        del junk
    finally:
        gc.callbacks.remove(pauses)
    assert first["gc_collections"] >= 2
    assert 0 < first["gc_pause_max_ms"] <= first["gc_pause_sum_ms"]
    # a reading starts the next: a window without a collection reads 0
    assert pauses.take() == {"gc_collections": 0, "gc_pause_sum_ms": 0.0,
                             "gc_pause_max_ms": 0.0}


def test_a_stop_without_a_start_is_not_a_pause():
    pauses = serve.GcPauses()
    pauses("stop", {})
    assert pauses.take()["gc_collections"] == 0


@pytest.mark.parametrize(
    "status,verdict,want",
    [
        (200, (True, False, frozenset({"p"})), True),
        (200, (False, False, frozenset()), True),
        (200, (False, False, frozenset({"evaluationError: deadline exceeded"})), False),
        (200, None, False),          # a body that did not read as a verdict
        (503, None, False),
        (0, None, False),            # a transport error
    ],
)
def test_what_counts_as_an_answer(status, verdict, want):
    assert run.answered((0, 0.0, 0.0, 0.1, status, verdict, ""), sar) is want


def test_the_runs_options_are_the_contracts_and_the_rehearsals():
    args = run.parse_args(["--workload", "x.y", "--seed", "2147483999",
                           "--seconds", "51", "--trace", "1"])
    assert (args.workload, args.seed, args.seconds, args.trace) == ("x.y", 2147483999, 51.0, 1)
    assert vars(args).keys() == {"workload", "seed", "seconds", "trace", "allow_cpu",
                                 "policies", "server_arg", "out", "root"}
    with pytest.raises(SystemExit):
        run.parse_args(["--workload", "x.y", "--trace", "2"])


def test_bench_run_in_the_environment_changes_nothing():
    """The driver sets BENCH_RUN for its own use; no file of the benchmark reads it."""
    assert not [p for p in run.HERE.rglob("*.py") if "BENCH_RUN" in p.read_text()]


def test_peaks_name_their_source():
    table = json.loads((run.HERE / "peaks.json").read_text())
    assert table["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    assert all(entry["source"] for entry in table.values())
