"""The roofline's byte count comes from shapes alone, and its peaks from
the published table keyed by device kind."""
import pytest

from bench_testkit import ROOT  # noqa: F401  (puts the checkout on the path)

from bench import cells, roofline


def test_bytes_of_a_fig5_replica():
    p = cells.load_cell("fig5-grid").points[0]          # alock, T=80, K=100
    draws = 3 * 4 * 150_000
    operands = 80 * 4 + 80 * 4 + 10 * 4 + 4 + 4 + 8 + 32 + 10 * 4 + 4
    results = 80 * 4 + 32768 * 8 + 4 + 8 + 4 + 4
    assert roofline.replica_bytes(p, 150_000, 32768) == \
        draws + operands + results == 2_063_256


def test_bytes_of_a_sweep_add_over_points_and_seeds():
    cell = cells.load_cell("fig4-budget")
    one = roofline.replica_bytes(cell.points[0], cell.n_events, 32768)
    assert roofline.sweep_bytes(cell.points, cell.n_seeds, cell.n_events,
                                32768) == one * cell.replicas
    rw = dict(cell.points[0], alg="alock-rw")
    assert roofline.replica_bytes(rw, 1000, 32768) - one + 150_000 * 12 \
        == 4 * 1000 + 240 * 4 + 1000 * 12


def test_published_peaks_and_unknown_device():
    v5e = roofline.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["hbm_bytes"] == 16e9
    with pytest.raises(KeyError, match="no published peaks"):
        roofline.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        roofline.peaks("cpu")
