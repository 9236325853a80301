"""Tiny versions of the cells, for CPU rehearsals of the whole run."""

import time

from bench import run as harness

SETS = {"phishing": {"n1": 300, "n2": 240, "d": 12},
        "a9a": {"n1": 200, "n2": 600, "d": 20},
        "ijcnn1": {"n1": 100, "n2": 900, "d": 6}}

CFG = {
    "solo_nu_1m": {"n1": 2048, "n2": 2048, "d": 64, "block_size": 8},
    "libsvm_steady": {"sets": SETS},
    # shorter budgets, so that fits finish inside a short CPU window
    "libsvm_overload": {"sets": SETS, "eps": 0.01},
    "mesh_points_1m_x8": {"n1": 512, "n2": 512, "d": 32, "block_size": 8,
                          "service": {"mesh_chips": 1, "num_slots": 8,
                                      "shard_points_above": 256,
                                      "shard_num_slots": 8,
                                      "chunk_steps": 64}},
}
TRAFFIC = {
    "solo_nu_1m": {},
    "libsvm_steady": {"rate": 4.0, "drain_s": 60, "trace_s": 0.5},
    "libsvm_overload": {"rate": 24.0},
    "mesh_points_1m_x8": {"trace_s": 0.5},
}
# the limits at these sizes, between what the tiny program reads on the
# CPU at the tests' seed (solo 1.9e-3, service 5.3e-2, 0.12 at eps 0.01)
# and what the faults read there (solo: half the points left out 0.100,
# the state left unchanged 0.68; service: 1.4 and 2.0; an answer with
# its sign flipped reads infinity everywhere)
LIMITS = {"solo_nu_1m": {"gap": 0.02}, "libsvm_steady": {"gap": 0.08},
          "libsvm_overload": {"gap": 0.3}, "mesh_points_1m_x8": {"gap": 0.02}}


SECONDS = {"libsvm_overload": 8.0}


def run(cell_name: str, seed: int = 2 ** 33 + 17,
        trace: bool = False, chips: int = 1) -> dict:
    """The cell at its tiny size; a meshed service spans ``chips``
    devices, which the process has to hold."""
    spec, cell, cfg, traffic, _ = harness.load_cell(cell_name)
    cfg = dict(cfg, **CFG[cell_name])
    if "service" in cfg:
        cfg["service"] = dict(cfg["service"], mesh_chips=chips)
        cell = dict(cell, chips=chips)
    traffic = dict(traffic, **TRAFFIC[cell_name])
    return harness.run_cell(spec, cell, cfg, traffic, LIMITS[cell_name], seed,
                            SECONDS.get(cell_name, 2.0), trace,
                            t0=time.time())
