"""Each reader of a per-layer metric, on recorded /metrics text and on a
small recorded device trace (TPU v5 lite; see recorded_trace.json)."""

import json
import pathlib

import pytest

from benchmark import prom, xplane
from benchmark.manifest import Manifest, reader_module
from benchmark.run import Context

HERE = pathlib.Path(__file__).resolve().parent
AUTH = {"path": "authorization"}
PER_LAYER = [m["name"] for m in Manifest().doc["per_layer"]]


@pytest.fixture(scope="module")
def ctx():
    c = Context()
    c.prom_before = prom.parse((HERE / "recorded_metrics_before.txt").read_text())
    c.prom_after = prom.parse((HERE / "recorded_metrics_after.txt").read_text())
    c.trace = json.loads((HERE / "recorded_trace.json").read_text())
    c.trace_window_s = c.trace["span_ns"] / 1e9
    c.engine_after = {
        "authorization": {"L": 6144, "R": 10240, "warm": {"seconds": 31.5, "shapes": 52}},
        "admission": {"L": 6144, "R": 10240, "warm": {"seconds": 27.0, "shapes": 52}},
    }
    c.loadgen = {"client_latency_p99_ms": 101.5, "client_latency_max_ms": 612.0,
                 "over_deadline_share": 0.0}
    c.harness = {"ready_s": 80.8, "window_compiles": 0, "gc_pause_max_ms": 41.25}
    c.device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    return c


def read(ctx, metric):
    spec = Manifest().metric_file(metric)
    return reader_module(spec["reader"]).read(ctx, spec["params"])


def test_prom_parse_reads_names_labels_and_values():
    samples = prom.parse(
        '# HELP x y\nx_total{a="1",b="two"} 3\nplain 4.5\nbad{a="1"} nan-ish\n'
    )
    assert ("x_total", {"a": "1", "b": "two"}, 3.0) in samples
    assert ("plain", {}, 4.5) in samples
    assert len(samples) == 2


def test_prom_total_matches_label_alternatives():
    samples = prom.parse('r{c="a"} 1\nr{c="b"} 2\nr{c="c"} 4\n')
    assert prom.total(samples, "r") == 7
    assert prom.total(samples, "r", {"c": "b"}) == 2
    assert prom.total(samples, "r", {"c": ["a", "c"]}) == 5


@pytest.mark.parametrize(
    "metric,want",
    [
        # (78.40+9.98+24.27 - 39.39-5.46-12.73) s over (2257 - 1057) requests
        ("ingress_ms.saturate", 1e3 * (112.655344585 - 57.586422595) / 1200),
        ("batch_rows.saturate", (2273 - 1070) / (695 - 328)),
        ("dispatch_ms_per_batch.saturate", 1e3 * (4.564613588996053 - 2.1457226660018023) / 366),
        ("queue_wait_ms.saturate", 1e3 * (4.64070162099506 - 2.3156340089981313) / 366),
        ("encode_us_per_row.saturate", 1e6 * (1.0658978240103352 - 0.5191422210050405) / 1203),
        ("decode_us_per_row.saturate", 1e6 * (1.5883580040044762 - 0.837624087005679) / 1203),
        ("fallback_row_share.saturate", 0.0),
        # the lone cell's readers are the same arithmetic under its own names
        ("ingress_ms.lone", 1e3 * (112.655344585 - 57.586422595) / 1200),
        ("batch_rows.lone", (2273 - 1070) / (695 - 328)),
        ("dispatch_ms_per_batch.lone", 1e3 * (4.564613588996053 - 2.1457226660018023) / 366),
        ("queue_wait_ms.lone", 1e3 * (4.64070162099506 - 2.3156340089981313) / 366),
        ("decode_us_per_row.lone", 1e6 * (1.5883580040044762 - 0.837624087005679) / 1203),
        ("fallback_row_share.lone", 0.0),
    ],
)
def test_prometheus_readers_on_recorded_metrics(ctx, metric, want):
    assert read(ctx, metric) == pytest.approx(want, rel=1e-6, abs=1e-12)


def test_a_reader_with_nothing_to_read_returns_nothing():
    empty = Context()
    for metric in PER_LAYER:
        assert read(empty, metric) is None, metric


def test_fallback_rows_are_a_share_of_all_rows():
    c = Context()
    c.prom_before = []
    c.prom_after = prom.parse(
        'cedar_authorizer_row_routing_total{path="authorization",row_class="clean_native"} 90\n'
        'cedar_authorizer_row_routing_total{path="authorization",row_class="gated"} 6\n'
        'cedar_authorizer_row_routing_total{path="authorization",row_class="encoder_fallback"} 4\n'
        'cedar_authorizer_row_routing_total{path="admission",row_class="gated"} 50\n'
    )
    assert read(c, "fallback_row_share.saturate") == pytest.approx(10.0)


@pytest.mark.parametrize(
    "metric,want",
    [("ladder_s", 31.5), ("ready_s", 80.8), ("window_compiles", 0.0),
     ("client_latency_p99_ms", 101.5), ("client_latency_max_ms", 612.0),
     # a window without a slow reply or a collection reads 0, not nothing
     ("over_deadline_share", 0.0), ("gc_pause_max_ms", 41.25)],
)
def test_engine_harness_and_loadgen_readers(ctx, metric, want):
    assert read(ctx, metric) == want


# ------------------------------------------------------- the recorded trace

def test_trace_holds_eight_batches_of_the_match_module(ctx):
    runs = xplane.module_runs(ctx.trace, "match_rules")
    assert len(runs) == 8
    assert xplane.module_runs(ctx.trace, "no_such_module") == []


def test_kernel_time_per_batch(ctx):
    assert read(ctx, "device_ms_per_batch.saturate") == pytest.approx(0.11848025)


def test_idle_share_is_one_minus_the_union_of_op_intervals(ctx):
    busy_s = xplane.busy_seconds(ctx.trace)
    assert busy_s == pytest.approx(0.000941858)
    # nested operations (a while loop and its body) are counted once
    ops = xplane.line_events(xplane.device_planes(ctx.trace)[0], "XLA Ops")
    assert sum(d for _, _, d in ops) / 1e9 > 1.5 * busy_s
    assert read(ctx, "device_idle_share.saturate") == pytest.approx(
        100.0 * (1 - busy_s / ctx.trace_window_s)
    )


def test_roofline_is_the_plane_read_once_over_the_kernel_time(ctx):
    # batches of (2273-1070)/(695-328) = 3.3 rows ride the 4-row bucket;
    # memory bounds: L*R + 2*B*L + B*R/8 bytes at 819 GB/s
    least = (6144 * 10240 + 2 * 4 * 6144 + 4 * 1280) / 819e9
    assert read(ctx, "match_roofline.saturate") == pytest.approx(
        100.0 * least / 0.11848025e-3, rel=1e-6
    )
    assert 50.0 < read(ctx, "match_roofline.saturate") < 100.0


def test_roofline_of_an_unknown_device_is_an_error(ctx):
    import copy

    from benchmark import kernels

    other = copy.copy(ctx)
    other.device = {"kind": "TPU v9"}
    with pytest.raises(kernels.UnknownDevice):
        read(other, "match_roofline.saturate")


def test_gaps_are_named_by_what_the_host_was_doing(ctx):
    gaps = xplane.idle_gaps(ctx.trace, 5)
    assert len(gaps) == 5
    assert all(name in ("batch_in_flight_host_dispatch", "no_batch_in_flight")
               for name, _ in gaps)
    assert gaps == sorted(gaps, key=lambda g: -g[1])
    assert gaps[0][1] == pytest.approx(0.007013946)
    # with the host's launch events taken away nothing is in flight
    bare = {"planes": xplane.device_planes(ctx.trace)}
    assert {name for name, _ in xplane.idle_gaps(bare, 5)} == {"no_batch_in_flight"}


def test_top_ops_have_names_the_ledger_can_hold(ctx):
    top = xplane.top_ops(ctx.trace, 3)
    assert len(top) == 3 and top[0][1] >= top[1][1] >= top[2][1]
    for name, seconds in top:
        assert len(name) <= 64 and " " not in name and "," not in name


@pytest.mark.parametrize(
    "intervals,want",
    [
        ([[0, 10], [5, 12], [20, 30]], [[0, 12], [20, 30]]),
        ([[5, 6], [0, 10]], [[0, 10]]),          # nested
        ([[0, 1], [1, 2]], [[0, 2]]),            # touching
        ([], []),
    ],
)
def test_merge(intervals, want):
    assert xplane.merge(intervals) == want


def test_a_trace_without_a_device_plane_reads_nothing():
    host_only = {"planes": [{"name": "/host:CPU", "lines": []}]}
    assert xplane.busy_seconds(host_only) is None
    assert xplane.idle_share(host_only, 3.0) is None
    assert xplane.idle_gaps(host_only) == []
