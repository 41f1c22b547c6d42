"""The readers of the host-side per-layer metrics (response egress, the
engine's collection, the event loop's lag) on synthetic run records: the
value from the counters ``serve.py`` copies into ``readings["window"]``,
and None where the program has no such counter or no window was counted.
"""

import numpy as np
import pytest

from benchmark.run import read_metric
from benchmark.serve import RunRecord


def record(categories=None, spans=None) -> RunRecord:
    window = {"seconds": 8.0, "settled": 100, "statuses": {},
              "categories": categories or {}, "spans": spans or {},
              "lanes_padded": 0, "compiles": 0}
    return RunRecord(setup_s=1.0, seconds=10.0, attempted=0,
                     due=np.zeros(0), match_ms=np.zeros(0),
                     matches_in_window=0, failed=0, data=None,
                     readings={"window": window})


def test_egress_is_its_site_time_per_window():
    run = record({"egress": {"total_s": 0.4, "traces": 2000}},
                 {"windows": 100})
    assert read_metric("egress_ms_per_window.paired", run) == \
        pytest.approx(4.0)


def test_collect_is_its_span_counter_per_window():
    run = record(spans={"windows": 50, "collect_s": 0.025})
    assert read_metric("collect_ms_per_window.search", run) == \
        pytest.approx(0.5)


@pytest.mark.parametrize("name", ["loop_lag_ms_mean.search",
                                  "loop_lag_ms_mean.paired"])
def test_loop_lag_is_its_mean_per_tick(name):
    run = record({"loop_lag": {"total_s": 0.6, "traces": 3000}})
    assert read_metric(name, run) == pytest.approx(0.2)


@pytest.mark.parametrize("name,run", [
    # A program without the counters (the parent of this change).
    ("egress_ms_per_window.paired", record(spans={"windows": 100})),
    ("collect_ms_per_window.search", record(spans={"windows": 100})),
    ("loop_lag_ms_mean.search", record()),
    ("loop_lag_ms_mean.paired", record()),
    # Counters there, but no window (or tick) counted.
    ("egress_ms_per_window.paired",
     record({"egress": {"total_s": 0.0, "traces": 0}}, {"windows": 0})),
    ("collect_ms_per_window.search",
     record(spans={"windows": 0, "collect_s": 0.01})),
    ("loop_lag_ms_mean.search",
     record({"loop_lag": {"total_s": 0.0, "traces": 0}})),
    ("loop_lag_ms_mean.paired",
     record({"loop_lag": {"total_s": 0.0, "traces": 0}})),
])
def test_nothing_to_read_reads_none(name, run):
    assert read_metric(name, run) is None
