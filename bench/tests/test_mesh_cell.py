"""The meshed service cell: the service built from the configuration,
and the whole run on four devices, where the points of each fit span
the chips and the exchange between them can be left out."""

import json
import os
import subprocess
import sys

import pytest

from bench import load
from bench.tests import tiny

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))

# four host devices in a child process: the device count is fixed when
# JAX starts
CHILD = r"""
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path[:0] = [os.getcwd(), os.path.join(os.getcwd(), "src")]
import jax
from bench.tests import tiny
from repro.core import engine

def line(res):
    return {"correct": res["correct"], "checks": res["checks"],
            "count": res["device"]["count"], "notes": res["notes"]}

print(json.dumps(line(tiny.run("mesh_points_1m_x8", chips=4))), flush=True)
# the exchange between chips left out: every round of Algorithm 4 (the
# psums and pmaxes of the step, the objective and the health agreement)
# keeps each chip's own partial
engine._all_sum = lambda x, axis_name: x
engine._all_max = lambda x, axis_name: x
engine._sharded_slot_runner.cache_clear()
jax.clear_caches()
print(json.dumps(line(tiny.run("mesh_points_1m_x8", chips=4))), flush=True)
"""


@pytest.fixture(scope="module")
def four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", CHILD], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=900)
    lines = [json.loads(x) for x in out.stdout.splitlines()
             if x.startswith("{")]
    assert len(lines) == 2, out.stdout + out.stderr[-4000:]
    return lines


def test_four_devices_sound_run_is_correct(four_devices):
    sound = four_devices[0]
    assert sound["count"] == 4
    assert sound["correct"], sound["checks"]
    assert sound["checks"]["compiles_in_window"]["value"] == 0
    assert set(sound["notes"]["peak_bytes_by_device"]) == {"0", "1", "2",
                                                           "3"}


def test_four_devices_exchange_left_out_is_incorrect(four_devices):
    cut = four_devices[1]
    assert not cut["correct"], cut["checks"]
    gap = cut["checks"]["gap_max"]
    assert gap["value"] > gap["limit"]


def test_service_without_a_service_object(monkeypatch):
    """A configuration without ``service`` gets ``SolverService()``, with
    no argument, whatever the cell's chips."""
    from repro.serve import solver_service
    calls = []
    monkeypatch.setattr(solver_service, "SolverService",
                        lambda *a, **k: calls.append((a, k)) or "svc")
    assert load.make_service({"sets": {}}, 1) == "svc"
    assert load.make_service({"sets": {}}, 4) == "svc"
    assert calls == [((), {}), ((), {})]


def test_service_object_builds_the_meshed_service():
    cfg = dict(tiny.CFG["mesh_points_1m_x8"])
    svc = load.make_service(cfg, 1)
    assert svc.mesh is not None and svc.mesh.size == 1
    assert (svc.num_slots, svc.chunk_steps, svc.shard_points_above,
            svc.shard_num_slots) == (8, 64, 256, 8)
    with pytest.raises(ValueError, match="spans 1 chips, the cell 4"):
        load.make_service(cfg, 4)


def test_fit_requests_take_the_block_size():
    from repro.serve.solver_service import FitRequest
    cfg = {"eps": 1e-3, "beta": 0.1, "gap_tol": 0.05}
    req = load._fit_request(dict(cfg, block_size=1), None, None, 0.1, 3)
    assert req == FitRequest(x=None, y=None, nu=0.1, eps=1e-3, beta=0.1,
                             gap_tol=0.05, seed=3)
    assert load._fit_request(dict(cfg, block_size=128), None, None, 0.1,
                             3).block_size == 128
