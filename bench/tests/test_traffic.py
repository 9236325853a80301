"""CPU rehearsal of each traffic mix, through the whole run after the
look for a chip, at tiny sizes."""

import json

import pytest

from bench import run as harness
from bench.tests import tiny

CELLS = ["solo_nu_1m", "libsvm_steady", "libsvm_overload",
         "mesh_points_1m_x8"]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_and_is_correct(cell):
    res = tiny.run(cell)
    json.dumps(res)                        # the result line is JSON
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    spec = harness.load_cell(cell)[0]
    names = {m["name"] for m in harness.metrics_of(spec, cell, "end_to_end")}
    assert set(res["metrics"]) == names and "setup_s" in names
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-2:] == ["checks", "notes"]
    assert res["checks"]["compiles_in_window"]["value"] == 0


def test_throughput_counts_the_work_of_fits_in_flight():
    """A fit in flight at the window's end counts by the share of its
    lane time inside the window, so the rate is no count of whole fits;
    the fits in flight are followed to their results and judged."""
    res = tiny.run("libsvm_overload")
    notes, seconds = res["notes"], tiny.SECONDS["libsvm_overload"]
    work = notes["window_work_fits"]
    assert res["metrics"]["fits_per_s"]["value"] * seconds == \
        pytest.approx(work)
    assert notes["completed_by_window_end"] < work < res["attempted"]
    assert res["checks"]["answers_judged"]["value"] == res["attempted"]
    assert notes["step_ms"]["calls"] > 0 and notes["slowest_steps"]


def test_traced_run_reports_device_block():
    res = tiny.run("libsvm_steady", trace=True)
    assert res["correct"]
    assert "window_s" in res["device"] and "busy_s" in res["device"]
    # on the CPU no TPU operation is traced: the device readers stay
    # silent rather than read 0
    assert res["metrics"] == {}
    assert res["breakdown"]["device_ops"] == []


def test_schedule_is_part_of_the_mix():
    from bench import load
    tr = {"rate": 2.0, "burst": 24, "schedule_seed": 1}
    a = load.schedule(tr, 3, 50.0)
    assert a == load.schedule(dict(tr), 3, 50.0)
    c = load.schedule(dict(tr, schedule_seed=2), 3, 50.0)
    assert a != c
    # any schedule seed gives the same multiset of gaps and of sets
    assert sorted(s for _, s in a) == sorted(s for _, s in c)
    gaps = lambda sch: sorted(sch[i + 24][0] - sch[i][0]       # noqa: E731
                              for i in range(0, len(sch) - 24, 24))
    assert len(gaps(a)) == len(gaps(c))
    assert a[0][0] == 0.0 and len({t for t, _ in a[:24]}) == 1
