"""The match kernel's operation and byte counts, and the table of peaks."""

import json

import pytest

from benchmark import kernels


@pytest.mark.parametrize(
    "L,R,B,w,ops,nbytes",
    [
        (6144, 10240, 1, 1, 2 * 6144 * 10240, 6144 * 10240 + 2 * 6144 + 1280),
        (6144, 10240, 8, 1, 16 * 6144 * 10240, 6144 * 10240 + 16 * 6144 + 8 * 1280),
        (1024, 2048, 4, 2, 8 * 1024 * 2048, 2 * 1024 * 2048 + 16 * 1024 + 4 * 256),
        (128, 256, 512, 1, 2 * 512 * 128 * 256, 128 * 256 + 2 * 512 * 128 + 512 * 32),
    ],
)
def test_match_cost_from_shapes(L, R, B, w, ops, nbytes):
    assert kernels.match_cost(L, R, B, w) == {"ops": ops, "bytes": nbytes}


@pytest.mark.parametrize("bad", [(0, 10, 1), (10, 0, 1), (10, 10, 0)])
def test_match_cost_refuses_empty_shapes(bad):
    with pytest.raises(ValueError):
        kernels.match_cost(*bad)


def test_v5e_peaks_are_the_published_ones():
    p = kernels.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
    assert "TPU v5e" in p["source"]


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "", "NVIDIA H100"])
def test_unknown_device_is_an_error_not_a_default(kind):
    with pytest.raises(kernels.UnknownDevice):
        kernels.peaks(kind)


def test_small_batches_are_bound_by_memory_large_by_compute():
    peak = kernels.peaks("TPU v5 lite")
    small = kernels.match_least_seconds(6144, 10240, 4, peak)
    assert small["bound"] == "memory"
    assert small["seconds"] == pytest.approx(6144 * 10240 / 819e9, rel=0.01)
    large = kernels.match_least_seconds(6144, 10240, 8192, peak)
    assert large["bound"] == "compute"
    assert large["seconds"] == pytest.approx(2 * 8192 * 6144 * 10240 / 393e12)


def test_bf16_plane_uses_the_bf16_peak(tmp_path):
    table = tmp_path / "peaks.json"
    table.write_text(json.dumps({"x": {"bf16_flops_per_s": 1e12, "int8_ops_per_s": 4e12,
                                       "hbm_bytes_per_s": 1e15, "hbm_bytes": 1}}))
    peak = kernels.peaks("x", table)
    assert kernels.match_least_seconds(100, 100, 100, peak, weight_bytes=2)["seconds"] == \
        pytest.approx(2e6 / 1e12)
    assert kernels.match_least_seconds(100, 100, 100, peak, weight_bytes=1)["seconds"] == \
        pytest.approx(2e6 / 4e12)
