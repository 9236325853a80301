"""The program's host spans and device scopes (``repro.utils.spans``,
``jax.named_scope`` in the engine), read back the way a profile reader
sees them: host spans from a CPU ``jax.profiler`` trace, scopes from the
op metadata of the lowered chunk executables."""

import glob
import re

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from repro.core import engine, saddle
from repro.core.svm import SaddleNuSVC
from repro.data import synthetic
from repro.serve.solver_service import FitRequest, SolverService

PREFIXES = ("svm.", "saddle.", "svc.")


def _profile(tmp_path, fn):
    """Host spans of the program written while ``fn`` runs under the
    profiler: ``(name, start_ns, end_ns, stats)`` by start."""
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        fn()
    path, = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
              dict(e.stats))
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith(PREFIXES)]
    return sorted(spans, key=lambda sp: (sp[1], -sp[2]))


def _parent(spans, child):
    """The innermost span that holds ``child``, by name (None at top)."""
    holders = [sp for sp in spans if sp is not child
               and sp[1] <= child[1] and child[2] <= sp[2]]
    if not holders:
        return None
    return min(holders, key=lambda sp: sp[2] - sp[1])[0]


def test_fit_spans_nest(tmp_path):
    ds = synthetic.blobs(30, 34, 8, gap=1.0, spread=0.2, seed=1)
    m = SaddleNuSVC(alpha=0.85, num_iters=64, block_size=1, seed=3)
    m.fit(ds.x, ds.y)                      # compile outside the profile
    spans = _profile(tmp_path, lambda: m.fit(ds.x, ds.y))
    names = [sp[0] for sp in spans]
    assert names == ["svm.fit", "svm.split", "svm.preprocess",
                     "saddle.solve", "saddle.pack", "saddle.run",
                     "svm.recover"]
    assert {sp[0]: _parent(spans, sp) for sp in spans} == {
        "svm.fit": None, "svm.split": "svm.fit",
        "svm.preprocess": "svm.fit", "saddle.solve": "svm.fit",
        "saddle.pack": "saddle.solve", "saddle.run": "saddle.solve",
        "svm.recover": "svm.fit"}
    run = spans[names.index("saddle.run")]
    assert run[3] == {"steps": 64, "pallas": 0}     # jnp off the chip


def test_service_step_spans_and_dispatch_counters(tmp_path):
    ds = synthetic.blobs(40, 50, 16, gap=1.2, spread=0.15, seed=0)
    chunk = 8
    svc = SolverService(num_slots=2, chunk_steps=chunk)
    # the first fit ends after one chunk; the third waits for its lane
    budgets = (chunk, 3 * chunk, 3 * chunk)
    warm = SolverService(num_slots=2, chunk_steps=chunk)
    warm.submit(FitRequest(x=ds.x, y=ds.y, num_iters=chunk, seed=1,
                           nu=0.05))
    warm.run()                             # compile outside the profile

    def two_steps():
        for i, n in enumerate(budgets):
            svc.submit(FitRequest(x=ds.x, y=ds.y, num_iters=n, seed=i,
                                  nu=0.05))
        svc.step()
        svc.step()

    spans = _profile(tmp_path, two_steps)
    by = {}
    for sp in spans:
        by.setdefault(sp[0], []).append(sp)
    assert [sp[3] for sp in by["svc.submit"]] == [
        {"rid": r, "n": 90, "d": 16} for r in range(3)]
    assert all(_parent(spans, sp) == "svc.submit"
               for sp in by["svc.preprocess"])
    assert len(by["svc.step"]) == 2
    for name, parent in (("svc.admit", "svc.step"),
                         ("svc.dispatch", "svc.step"),
                         ("svc.harvest", "svc.step"),
                         ("svc.evict", "svc.step"),
                         ("svc.wait", "svc.harvest"),
                         ("svc.recover", "svc.harvest")):
        assert by[name] and all(_parent(spans, sp) == parent
                                for sp in by[name]), name
    # step 1 admits rids 0 and 1, step 2 admits rid 2 into the lane the
    # finished rid 0 left
    assert [sp[3] for sp in by["svc.admit"]] == [
        {"rid": 0, "lane": 0, "warm": 0}, {"rid": 1, "lane": 1, "warm": 0},
        {"rid": 2, "lane": 0, "warm": 0}]
    assert [sp[3] for sp in by["svc.dispatch"]] == [
        {"lanes": 2, "slots": 2, "queued": 1, "n_pad": 128},
        {"lanes": 2, "slots": 2, "queued": 0, "n_pad": 128}]
    assert [sp[3] for sp in by["svc.recover"]] == [{"rid": 0}]


def _slot_args(num_slots=2, n_pad=128, d=16):
    state = engine.init_slot_state(num_slots, n_pad, d)
    row = engine.slot_params_row(
        saddle.make_params(100, d, 1e-3, 0.1, nu=0.05), gap_tol=0.05)
    sp = engine.SlotParams(*(jnp.full((num_slots,), v) for v in row))
    x_t = jnp.zeros((num_slots, d, n_pad), jnp.float32)
    sign = jnp.ones((num_slots, n_pad), jnp.float32)
    return state, x_t, sign, sp


@pytest.mark.parametrize("executable", ["run_solve_slots",
                                        "run_chunk_slots"])
def test_scopes_name_the_step_phases(executable):
    state, x_t, sign, sp = _slot_args()
    statics = dict(chunk_steps=8, d=16, block_size=1, project=True,
                   check_gap=True)
    if executable == "run_solve_slots":
        low = engine.run_solve_slots.lower(state, x_t, sign, sp, 32,
                                           num_chunks=4, **statics)
    else:
        low = engine.run_chunk_slots.lower(state, x_t, sign, sp, 8,
                                           **statics)
    locs = re.findall(r'loc\("([^"]*)"', low.as_text(debug_info=True))
    parts = {re.sub(r"^(?:\w+\()+|\)+$", "", p)
             for loc in locs for p in loc.split("/")}
    for scope in ("momentum_pass", "mwu_pass", "nu_projection",
                  "gap_check", "health_check"):
        assert scope in parts, scope
