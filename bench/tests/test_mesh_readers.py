"""The five readers of the meshed service cell, on a recorded excerpt
of a four-chip trace, against a plain recount; and silent on the solo
cell's excerpt and without a trace."""

import json
import os
import re

import pytest

from bench import program_trace as pt
from bench import roofline, trace
from bench import run as harness

DATA = os.path.join(os.path.dirname(__file__), "data")
READERS = ("step_roofline_pct.mesh", "collective_pct.mesh",
           "projection_pct.mesh", "idle_pct.mesh", "host_ms_per_step.mesh")
MODULE = "jit_local_fn"


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "mesh_trace.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(DATA, "..", "..", "configs",
                           "dense_1m_nu_mesh4.json")) as f:
        return json.load(f)


def summary_of(rec):
    return trace.reduce({"device": [r[:6] for r in rec["device"]],
                         "host": rec["host"]}, rec["window_s"])


def program_of(rec):
    return pt.reduce({"spans": rec["spans"],
                      "device": [r[:5] + [r[6]] for r in rec["device"]]})


def read(name, summary, prog, cfg, monkeypatch):
    monkeypatch.setattr(pt, "of_cell",
                        lambda cell: prog if cell == "mesh_points_1m_x8"
                        else None)
    ctx = harness.Context(summary, {}, cfg, roofline.peaks("TPU v5 lite"))
    return harness.reader(name)(ctx)


def per_chip(rec, pick):
    """Per device: the union of the picked ops' intervals in the module
    runs over the device's module time; the mean over devices."""
    shares = []
    for dev in sorted({r[0] for r in rec["device"]}):
        mods = [(r[3], r[3] + r[4]) for r in rec["device"]
                if r[0] == dev and r[2].startswith(MODULE)]
        ops = [(r[3], r[3] + r[4]) for r in rec["device"]
               if r[0] == dev and r[1] == "XLA Ops" and pick(r)
               and any(a <= r[3] <= b for a, b in mods)]
        covered, end = 0.0, -1.0
        for a, b in sorted(ops):
            covered += max(0.0, b - max(a, end))
            end = max(end, b)
        shares.append(covered / sum(b - a for a, b in mods))
    return 100.0 * sum(shares) / len(shares)


def test_excerpt_holds_four_chips_of_three_chunks(recorded):
    mods = [r for r in recorded["device"] if r[1] == "XLA Modules"]
    assert len(mods) == 12 and len({r[0] for r in mods}) == 4
    dispatch = [c for n, *_, c in recorded["spans"] if n == "svc.dispatch"]
    assert [(c["lanes"], c["slots"]) for c in dispatch] == [(8, 8)] * 3


def test_step_roofline(recorded, cfg, monkeypatch):
    # 3 chunks x 8 lanes x 64 steps on a chip's 2^18 points of each lane
    need = 3 * 8 * 64 * (2 * 128 * (1 << 18) * 4 + 10 * (1 << 18) * 4
                         + 2 * 128 * 4)
    chip_s = sum(r[4] for r in recorded["device"]
                 if r[1] == "XLA Modules") * 1e-9 / 4
    got = read("step_roofline_pct.mesh", summary_of(recorded),
               program_of(recorded), cfg, monkeypatch)
    assert got == pytest.approx(100 * need / 819e9 / chip_s)
    assert 10.0 < got < 20.0


def test_collective_share(recorded, cfg, monkeypatch):
    want = per_chip(recorded,
                    lambda r: re.match(r"%(psum|pmax)\.", r[2]) is not None)
    assert read("collective_pct.mesh", summary_of(recorded),
                program_of(recorded), cfg, monkeypatch) == \
        pytest.approx(want)
    assert 0 < want < 1


def test_projection_share(recorded, cfg, monkeypatch):
    want = per_chip(recorded, lambda r: "vmap(nu_projection)" in (r[6] or ""))
    assert read("projection_pct.mesh", summary_of(recorded),
                program_of(recorded), cfg, monkeypatch) == \
        pytest.approx(want)


def test_idle_and_host_step(recorded, cfg, monkeypatch):
    s = summary_of(recorded)
    assert read("idle_pct.mesh", s, None, cfg, monkeypatch) == \
        pytest.approx(100 * (1 - s.busy_s / recorded["window_s"]))
    assert read("host_ms_per_step.mesh", s, None, cfg, monkeypatch) == \
        pytest.approx(trace.host_ms_per_span(s, "bench.step"))


@pytest.mark.parametrize("name", READERS)
def test_silent_on_the_solo_excerpt(name, cfg, monkeypatch):
    with open(os.path.join(DATA, "program_trace.json")) as f:
        solo = json.load(f)["solo"]
    summary = trace.reduce({"device": [r[:5] + [None]
                                       for r in solo["device"]],
                            "host": []}, 6.0)
    assert read(name, summary, pt.reduce(solo), cfg, monkeypatch) is None
    assert read(name, None, None, cfg, monkeypatch) is None
