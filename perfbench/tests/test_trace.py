"""The trace reduction, checked on the CPU.

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests/test_trace.py -q

A hand-made trace whose numbers are worked out below, with the plane
and line names a TPU v5e trace has (``/device:TPU:0``, ``XLA Ops``,
``XLA Modules``), and the per-layer readers over it.
"""
from __future__ import annotations

import math
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

from harness import bench, cell, roofline, traffic  # noqa: E402
from harness import trace as tracing  # noqa: E402

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def ev(plane, line, name, start, dur):
    return tracing.Event(plane, line, name, float(start), float(dur))


def hand_made():
    return [
        ev(HOST, "main", "bench.window", 100, 1000),
        ev(HOST, "srv", "bench.engine.run_many", 150, 400),
        # device ops: two overlap (200-300 and 250-350), one outside the
        # window (50-120, clipped to 100-120), one late (900-1200,
        # clipped to 900-1100)
        ev(DEV, "XLA Ops", "fusion.1", 200, 100),
        ev(DEV, "XLA Ops", "fusion.2", 250, 100),
        ev(DEV, "XLA Ops", "copy.3", 50, 70),
        ev(DEV, "XLA Ops", "fusion.1", 900, 300),
        ev(DEV, "XLA Modules", "jit__fd_sweep_impl(1)", 200, 150),
        ev(DEV, "XLA Modules", "jit_other(2)", 900, 300),
    ]


def test_hand_made_trace():
    red = tracing.reduce(hand_made(), "_fd_sweep_impl")
    assert red["window_s"] == pytest.approx(1000e-9)
    # busy: 100-120, 200-350, 900-1100
    assert red["busy_s"] == pytest.approx((20 + 150 + 200) * 1e-9)
    assert red["sweep_device_s"] == pytest.approx(150e-9)
    assert red["sweeps"] == 1
    # an op counts in full when it starts inside the window
    assert red["device_ops"][0] == ["fusion.1", pytest.approx(400e-9)]
    # idle: 120-200 (80, in run_many), 350-900 (550, middle 625: idle
    # since run_many ended at 550)
    assert red["idle_gaps"][0] == ["idle", pytest.approx(550e-9)]
    assert red["idle_gaps"][1] == ["bench.engine.run_many",
                                   pytest.approx(80e-9)]


def test_no_device_plane_is_an_error():
    with pytest.raises(RuntimeError):
        tracing.reduce([ev(HOST, "main", "bench.window", 0, 10)], "x")


@pytest.mark.parametrize("workload",
                         [w["name"] for w in bench.load()["workloads"]])
def test_every_per_layer_metric_reads(workload):
    """Each per-layer metric of the cell reads a number from a traced
    run (the hand-made trace, one sweep answering one request), and
    leaves itself out of an untraced one."""
    spec = bench.cell(bench.load(), workload)
    red = tracing.reduce(hand_made(), "_fd_sweep_impl")
    done = traffic.Request(1, 7, 0.0, done_s=0.5,
                           result=type("R", (), {"queue_s": 0.01}))
    run = cell.Run(spec["config"], spec["traffic"], "TPU v5 lite", 80.0,
                   [done], [(0.0, 0.3)], [[7]], red)
    got = bench.read_metrics(spec["per_layer"], run)
    assert set(got) == {m["name"] for m in spec["per_layer"]}
    for m in spec["per_layer"]:
        assert got[m["name"]]["unit"] == m["unit"]
        assert 0 < got[m["name"]]["value"] < math.inf
    run.trace = None
    untraced = bench.read_metrics(spec["per_layer"], run)
    assert not set(untraced) - {"queue_wait_s.steady",
                                "latency_p50_s.steady",
                                "latency_p90_s.steady"}


def test_least_bytes():
    # per peer: in 20*8 + 3*8 + 8 (death) + 8 (lambda), out 20*12 + 16 + 1
    assert roofline.sweep_least_bytes(2, 10, 20, churn=True,
                                      strategy1=True) == 2 * 10 * 457
    assert roofline.sweep_least_bytes(1, 1, 20, churn=False,
                                      strategy1=False) == 440


def test_unknown_device_is_an_error():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")
