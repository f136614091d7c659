"""The benchmark's own checks, on the CPU at a size a test run holds.

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests -q

Each test drives a whole run of a cell (set-up, warm-up, the served
window, the comparison with the plain reference) with the TPU check
skipped and the overlay shrunk to a few hundred peers.  A sound run,
every answer of its window compared, must come out ``correct``; the
lower-precision control (the program at float32) must not, nor must a
run with a fault planted in the served path, checked on the cell's own
sample of answers.
"""
from __future__ import annotations

import copy
import os
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

from harness import bench, cell, overlay  # noqa: E402

PEERS = 400
SEED = 2**33 + 5          # a --seed past 32 bits, as real runs use
CELLS = tuple(w["name"] for w in bench.load()["workloads"])


def run(workload: str, precision=None, seconds: float = 3.0,
        **traffic) -> dict:
    """One run of ``workload`` on a ``PEERS``-peer overlay of its family;
    ``traffic`` replaces keys of the cell's traffic (by default every
    answer is checked)."""
    spec = bench.cell(bench.load(), workload)
    fam = spec["config"]["overlay"]["family"]
    ov = overlay.FAMILIES[fam](PEERS, 11)
    small = {"family": fam, "peers": PEERS, "topology_seed": 11,
             "edges": ov.n_edges, "origins": overlay.pick_origins(ov, 2, 11)}
    return cell.run_cell(workload, SEED, seconds, False,
                         t_start=time.perf_counter(), precision=precision,
                         config_override={"overlay": small},
                         traffic_override=dict(
                             {"check_sample": 10**6}, **traffic))


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload):
    out = run(workload)
    assert out["correct"], out["check"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out) >= {"correct", "attempted", "failed", "metrics",
                        "device"}
    assert list(out)[-1] == "check"
    spec = bench.cell(bench.load(), workload)
    assert set(out["metrics"]) == {m["name"] for m in spec["end_to_end"]}


@pytest.mark.parametrize("workload", CELLS)
def test_f32_control_fails(workload):
    out = run(workload, precision="f32")
    assert not out["correct"]
    nums = out["check"]
    assert nums["value_rel_gap"]["value"] > 1e3 * nums["value_rel_gap"][
        "limit"]


def _altered_answer(orig):
    def run_entries_jax(*a, **kw):
        out = orig(*a, **kw)
        n = a[5]
        out["owners"][:, -1] = (out["owners"][:, -1] + 1) % n
        return out
    return run_entries_jax


def _half_batch(orig):
    def run_entries_jax(*a, **kw):
        out = orig(*a, **kw)
        E = len(a[4])
        half = (E + 1) // 2
        for v in out.values():
            if isinstance(v, np.ndarray) and v.shape[:1] == (E,) and E > 1:
                v[half:] = v[:E - half]
        return out
    return run_entries_jax


def _stale_state(orig_run_many):
    first = {}

    def run_many(self, specs, policies="fd-dynamic", **kw):
        if "res" not in first:
            first["res"] = orig_run_many(self, specs[:1], policies, **kw)[0]
        return [copy.deepcopy(first["res"]) for _ in specs]
    return run_many


@pytest.mark.parametrize("fault", ("altered_answer", "half_batch",
                                   "stale_state"))
@pytest.mark.parametrize("workload", CELLS)
def test_fault_is_caught(workload, fault, monkeypatch):
    """A fault planted in the served path makes ``correct`` false on the
    cell's own sample of answers: an answer altered where it is
    produced; half of every batch left out and answered with the other
    half's results; an engine that hands back the state of its first
    call unchanged."""
    from repro.engine import SimEngine, sim_jax
    if fault == "stale_state":
        monkeypatch.setattr(SimEngine, "run_many",
                            _stale_state(SimEngine.run_many))
    else:
        wrap = _altered_answer if fault == "altered_answer" else _half_batch
        monkeypatch.setattr(sim_jax, "run_entries_jax",
                            wrap(sim_jax.run_entries_jax))
    # at the small size the CPU answers far faster than the cell's
    # rate, so an open loop is driven hard enough to fill batches
    tr = bench.cell(bench.load(), workload)["traffic"]
    load = {"rate_per_s": 400.0} if tr["loop"] == "open" else {}
    out = run(workload, check_sample=tr["check_sample"], **load)
    assert not out["correct"], out["check"]
