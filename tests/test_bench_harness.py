"""bench.py's result gates (scripts/bench_diff.py). That bench.py refuses
to run without a TPU is pinned in tests/test_chip_smoke.py."""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bench_diff_gates_e2e_rate_and_p99():
    """ISSUE 9 satellite: ``e2e_rate_req_s`` and ``e2e_p99_ms`` are
    first-class direction-aware headline gates — a 20% rate drop or p99
    rise regresses; improvements never do."""
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    try:
        import bench_diff as bd
    finally:
        sys.path.pop(0)
    base = {"e2e_rate_req_s": 10000.0, "e2e_p99_ms": 100.0}
    worse = {"e2e_rate_req_s": 8000.0, "e2e_p99_ms": 130.0}
    rows = bd.diff(base, worse, threshold=0.10)
    flags = {r["metric"]: r["regressed"] for r in rows}
    assert flags == {"e2e_rate_req_s": True, "e2e_p99_ms": True}
    better = {"e2e_rate_req_s": 13000.0, "e2e_p99_ms": 60.0}
    rows = bd.diff(base, better, threshold=0.10)
    assert not any(r["regressed"] for r in rows)
    # Direction-awareness: a HIGHER rate with a higher p99 regresses only
    # on the p99 axis.
    mixed = {"e2e_rate_req_s": 13000.0, "e2e_p99_ms": 130.0}
    flags = {r["metric"]: r["regressed"]
             for r in bd.diff(base, mixed, threshold=0.10)}
    assert flags == {"e2e_rate_req_s": False, "e2e_p99_ms": True}


def test_bench_diff_gates_placement_blackout_and_accounting():
    """ISSUE 11 satellite: the placement-soak rows gate direction-aware
    (lower is better). lost/dup have a zero baseline on a healthy run, so
    any meaningful nonzero fresh value regresses via the base==0 rule."""
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    try:
        import bench_diff as bd
    finally:
        sys.path.pop(0)
    base = {"placement_blackout_ms_max": 200.0,
            "placement_blackout_ms_mean": 80.0,
            "placement_lost": 0, "placement_dup": 0}
    worse = {"placement_blackout_ms_max": 400.0,
             "placement_blackout_ms_mean": 90.0,
             "placement_lost": 3, "placement_dup": 1}
    flags = {r["metric"]: r["regressed"]
             for r in bd.diff(base, worse, threshold=0.10)}
    assert flags == {"placement_blackout_ms_max": True,
                     "placement_blackout_ms_mean": True,
                     "placement_lost": True,
                     "placement_dup": True}
    better = {"placement_blackout_ms_max": 150.0,
              "placement_blackout_ms_mean": 60.0,
              "placement_lost": 0, "placement_dup": 0}
    assert not any(r["regressed"]
                   for r in bd.diff(base, better, threshold=0.10))


def test_hbm_peak_is_keyed_by_device_kind():
    """The roofline's peak comes from one cited table; a device kind that
    is not in it is an error, never a silent v5e default."""
    import pytest

    sys.path.insert(0, REPO)
    try:
        import bench
    finally:
        sys.path.pop(0)
    assert bench.hbm_peak("TPU v5 lite") == 819e9
    with pytest.raises(SystemExit):
        bench.hbm_peak("cpu")
