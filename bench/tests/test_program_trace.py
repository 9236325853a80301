"""The program's spans and scoped device ops, from trace events to the
seven per-layer readers that read them."""

import importlib.util
import json
import os

import pytest

from bench import program_trace as pt

HERE = os.path.dirname(__file__)
DEV = "/device:TPU:0"
READERS = ("split_ms.solo", "preprocess_ms.solo", "projection_pct.solo",
           "projection_pct.steady", "dispatch_ms.steady",
           "lane_occupancy_pct.steady", "queue_depth.steady")


def reader(name):
    path = os.path.join(HERE, "..", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "t_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read(name, prog, monkeypatch):
    """A reader's value with ``prog`` as its cell's trace."""
    mod = reader(name)
    monkeypatch.setattr(pt, "of_cell",
                        lambda cell: prog if cell == mod.CELL else None)
    return mod.read(None)


def test_innermost_scope():
    assert pt.innermost_scope(
        "jit(run_chunk_slots)/while/body/vmap(nu_projection)/while/body/"
        "closed_call/min") == "nu_projection"
    assert pt.innermost_scope(
        "jit(run_solve_slots)/while/body/while/body/momentum_pass/dot_general"
    ) == "momentum_pass"
    assert pt.innermost_scope("jit(f)/gap_check/health_check/x") == \
        "health_check"
    assert pt.innermost_scope("jit(f)/while/body/add") is None
    assert pt.innermost_scope(None) is None
    assert pt.innermost_scope("jit(f)/nu_projection_x/add") is None


def ms(x):
    return x * 1e6


def test_ops_go_to_their_module_run_and_loops_are_left_out():
    events = {"spans": [], "device": [
        [DEV, "XLA Modules", "jit_run_chunk_slots(1)", ms(0), ms(10), None],
        [DEV, "XLA Ops", "%while.1", ms(0), ms(10), "jit(a)/while"],
        [DEV, "XLA Ops", "%fusion.1", ms(1), ms(2),
         "jit(a)/while/body/vmap(nu_projection)/exp"],
        [DEV, "XLA Ops", "%fusion.2", ms(2), ms(2),
         "jit(a)/while/body/vmap(nu_projection)/while/body/min"],
        [DEV, "XLA Ops", "%fusion.3", ms(6), ms(1),
         "jit(a)/while/body/vmap(mwu_pass)/exp"],
        [DEV, "XLA Modules", "jit_other(2)", ms(20), ms(4), None],
        [DEV, "XLA Ops", "%fusion.4", ms(20), ms(4),
         "jit(b)/nu_projection/mul"]]}
    prog = pt.reduce(events)
    assert prog.exec_s == pytest.approx({"run_chunk_slots": 0.010,
                                         "other": 0.004})
    assert [(k, sc) for k, sc, _, _ in prog.ops] == [
        ("run_chunk_slots", "nu_projection"),
        ("run_chunk_slots", "nu_projection"),
        ("run_chunk_slots", "mwu_pass"), ("other", "nu_projection")]
    # overlapping ops count once: [1, 4) ms of 10 ms
    assert pt.scope_pct(prog, "run_chunk_slots", "nu_projection") == \
        pytest.approx(30.0)
    assert pt.scope_pct(prog, "run_chunk_slots", "gap_check") is None
    assert pt.scope_pct(prog, "run_solve_slots", "nu_projection") is None


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "program_trace.json")) as f:
        return json.load(f)


def test_event_metadata_decodes_the_profile(tmp_path):
    """The protobuf decoder against a CPU profile: the metadata plane
    holds each executable's HLO proto, named like its module runs."""
    import glob

    import jax
    import jax.numpy as jnp

    @jax.jit
    def double_exp(x):
        return jnp.exp(x) * 2.0

    double_exp(jnp.ones(8)).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        double_exp(jnp.ones(8)).block_until_ready()
    path, = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    meta = pt.event_metadata(path, ("/host:metadata",))
    assert list(meta) == ["/host:metadata"]
    mods = meta["/host:metadata"]
    name, = [k for k in mods if k.startswith("jit_double_exp(")]
    assert isinstance(mods[name]["Hlo Proto"], bytes)
    assert pt.event_metadata(path, ("/device:TPU:",)) == {}


def test_readers_on_a_recorded_chip_excerpt(recorded, monkeypatch):
    solo = pt.reduce(recorded["solo"])
    steady = pt.reduce(recorded["steady"])

    def rows(part):
        return [d for d in recorded[part]["device"]
                if d[1] == "XLA Ops" and "vmap(nu_projection)" in (d[5] or "")]

    # the traced fit's spans: one split, one preprocess
    assert read("split_ms.solo", solo, monkeypatch) == \
        pytest.approx(1143.402325)
    assert read("preprocess_ms.solo", solo, monkeypatch) == \
        pytest.approx(27.38136)
    # one block step: 84 of its 1427 ops run under nu_projection,
    # 634,504 ns in all, against the solve's 4,698,400,805 ns
    assert len(rows("solo")) == 84
    assert sum(d[4] for d in rows("solo")) == 634504
    assert read("projection_pct.solo", solo, monkeypatch) == \
        pytest.approx(100 * 634504 / 4698400805)
    # two service steps, each dispatching 5 of 8 lanes with 3 queued
    assert read("dispatch_ms.steady", steady, monkeypatch) == \
        pytest.approx((0.675110 + 0.670649) / 2)
    assert read("lane_occupancy_pct.steady", steady, monkeypatch) == \
        pytest.approx(100 * (5 + 5) / (8 + 8))
    assert read("queue_depth.steady", steady, monkeypatch) == \
        pytest.approx((3 + 3) / 2)
    # the chunk's first 40 ops: 25 under nu_projection, 44,710 ns, of a
    # 23,252,747 ns chunk
    assert len(rows("steady")) == 25
    assert sum(d[4] for d in rows("steady")) == 44710
    assert read("projection_pct.steady", steady, monkeypatch) == \
        pytest.approx(100 * 44710 / 23252747)


@pytest.mark.parametrize("name", READERS)
def test_readers_are_silent_without_program_spans(recorded, monkeypatch,
                                                  name):
    """The parent commit's trace: the same device ops with no scope
    path and no program span; and no trace at all."""
    part = "solo" if name.endswith(".solo") else "steady"
    bare = {"spans": [],
            "device": [d[:5] + [None] for d in recorded[part]["device"]]}
    assert read(name, pt.reduce(bare), monkeypatch) is None
    assert read(name, None, monkeypatch) is None
