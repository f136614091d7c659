"""The benchmark's own checks, on the CPU at a size a test run holds.

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests -q

Each test drives a whole run of a cell (set-up, warm-up, the served
window, the comparison with the plain reference) with the TPU check
skipped and the overlay shrunk to a few hundred peers.  A sound run,
every answer of its window compared, must come out ``correct``; the
lower-precision control (the program at float32) must not, nor must a
run with a fault planted in the served path, checked on the cell's own
sample of answers.  A configuration's overlay generator and plain
reference are files found by name, so a new deployment is new files.
"""
from __future__ import annotations

import copy
import hashlib
import json
import os
import shutil
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

from harness import bench, cell, check, overlay  # noqa: E402

PEERS = 400
SEED = 2**33 + 5          # a --seed past 32 bits, as real runs use
CELLS = tuple(w["name"] for w in bench.load()["workloads"])
CONFIGS = tuple(c["name"] for c in bench.load()["configs"])


def small_overlay(overlay_cfg: dict, root: str = bench.CHECKOUT) -> dict:
    """``overlay_cfg`` cut to ``PEERS`` peers of topology seed 11, with
    its edge count and two origins of the median degree."""
    small = dict(overlay_cfg, peers=PEERS, topology_seed=11)
    ov = overlay.build(small, check=False, root=root)
    return dict(small, edges=ov.n_edges,
                origins=overlay.pick_origins(ov, 2, 11))


def run(workload: str, precision=None, seconds: float = 3.0,
        **traffic) -> dict:
    """One run of ``workload`` on a ``PEERS``-peer overlay of its family;
    ``traffic`` replaces keys of the cell's traffic (by default every
    answer is checked)."""
    spec = bench.cell(bench.load(), workload)
    small = small_overlay(spec["config"]["overlay"])
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


def _config(name: str) -> dict:
    conf = {c["name"]: c for c in bench.load()["configs"]}[name]
    with open(os.path.join(bench.CHECKOUT, conf["file"])) as f:
        return json.load(f)


def _digest(ov) -> str:
    h = hashlib.sha256()
    for a in ov.neighbors:
        a = np.asarray(a, np.int32)
        h.update(np.int64(len(a)).tobytes())
        h.update(a.tobytes())
    if ov.coords is not None:
        h.update(np.ascontiguousarray(ov.coords, np.float64).tobytes())
    return h.hexdigest()


# adjacency (and coordinates) of each configuration's family at 400
# peers, topology seed 11, as the generators gave them before they
# moved out of harness/overlay.py into overlays/<family>.py
FROZEN = {
    "paper-ba-100k": (797, [140, 143], "04b89aa327d0ca380c62527ad0bbfbbb"
                      "012cb58f4b1af4b694933ba09e45e00a"),
    "gnutella-churn-100k": (801, [99, 395], "6bcde4049b2f1744cf747190a4027"
                            "50e12230992ca65e2d5c52947db1a03a3ac"),
}


@pytest.mark.parametrize("config", CONFIGS)
def test_family_and_reference_found_by_name(config):
    """Each configuration's generator and reference are the files its
    ``overlay.family`` and ``reference`` name; the generator gives the
    overlay it gave before the move, bit for bit."""
    cfg = _config(config)
    fam = cfg["overlay"]["family"]
    gen = bench.module("overlays", fam)
    assert gen.__file__ == os.path.join(HERE, "overlays", fam + ".py")
    small = dict(cfg["overlay"], peers=PEERS, topology_seed=11)
    ov = overlay.build(small, check=False)
    edges, origins, digest = FROZEN[config]
    assert (ov.kind, ov.n_edges) == (fam, edges)
    assert overlay.pick_origins(ov, 2, 11) == origins
    assert _digest(ov) == digest
    query = check.load_reference(cfg)
    assert query.__module__ == bench.module("references",
                                            cfg["reference"]).__name__


@pytest.mark.parametrize("kind,known", (("overlays", ["ba", "gnutella"]),
                                        ("references", ["fd"])))
def test_unknown_name_lists_what_exists(kind, known):
    with pytest.raises(ValueError) as err:
        bench.module(kind, "no-such-file")
    msg = str(err.value)
    assert "no-such-file" in msg
    assert all(repr(k) in msg for k in known)


@pytest.mark.parametrize("block,key,value", (
    ("params", "latency_model", "edge"),   # the program knows it
    ("policy", "z", 0.8),
    ("policy", "name", "cn"),
))
def test_key_the_reference_does_not_know_fails_before_window(
        block, key, value, monkeypatch):
    def window(*a, **kw):
        raise AssertionError("the window opened")
    monkeypatch.setattr(cell.Session, "window", window)
    cfg = _config("paper-ba-100k")
    bad = {block: dict(cfg[block], **{key: value}),
           "overlay": small_overlay(cfg["overlay"])}
    with pytest.raises(ValueError, match=key if key != "name" else "cn"):
        cell.run_cell("ba100k-steady", SEED, 1.0, False,
                      t_start=time.perf_counter(), config_override=bad)


RING = '''"""A ring with seeded chords: a family only this test has."""
import numpy as np

from harness.overlay import Overlay


def build(n, seed, chords=0):
    adj = [{(u - 1) % n, (u + 1) % n} for u in range(n)]
    for u, v in np.random.default_rng(seed).integers(0, n, (chords, 2)):
        if u != v:
            adj[u].add(int(v))
            adj[v].add(int(u))
    return Overlay("ring", [np.array(sorted(a), np.int32) for a in adj])
'''

# the fd reference under another name; ``wrong`` swaps the owners of
# the first two places of every answer
REFERENCE = '''from harness import check, reference

WRONG = {wrong}


def params(config):
    return reference.Params(**config["params"])


def query(ov, origin, seed, config):
    pol = config["policy"]
    ans = reference.fd_query(ov.neighbors, origin, seed, params(config),
                             strategy=pol["strategy"],
                             dynamic=pol["dynamic"],
                             lifetime_mean_s=check.lifetime(pol))
    if WRONG:
        ans.owners[[0, 1]] = ans.owners[[1, 0]]
    return ans
'''


@pytest.mark.parametrize("wrong", (False, True))
def test_new_deployment_is_new_files(wrong, tmp_path, capsys):
    """A family and a reference that exist only under another root are
    built, served and checked end to end by an unedited harness: the
    named reference decides ``correct``."""
    root = str(tmp_path)
    base = os.path.join(root, "perfbench")
    for sub in ("configs", "overlays", "references"):
        os.makedirs(os.path.join(base, sub))
    with open(os.path.join(base, "overlays", "ring.py"), "w") as f:
        f.write(RING)
    with open(os.path.join(base, "references", "fd-copy.py"), "w") as f:
        f.write(REFERENCE.format(wrong=wrong))
    cfg = _config("paper-ba-100k")
    cfg["overlay"] = small_overlay(
        {"family": "ring", "family_args": {"chords": PEERS // 2}}, root)
    cfg["reference"] = "fd-copy"
    with open(os.path.join(base, "configs", "ring-400.json"), "w") as f:
        json.dump(cfg, f)
    b = bench.load()
    b["configs"].append(dict(b["configs"][0], name="ring-400",
                             file="perfbench/configs/ring-400.json"))
    b["workloads"].append(dict(b["workloads"][0], name="ring-closed",
                               config="ring-400", traffic="closed-8"))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)
    try:
        out = cell.run_cell("ring-closed", SEED, 3.0, False,
                            t_start=time.perf_counter(), root=root,
                            traffic_override={"check_sample": 10**6})
    finally:
        shutil.rmtree(root, ignore_errors=True)
    assert f"overlay ring n={PEERS}" in capsys.readouterr().out
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["correct"] is not wrong, out["check"]
    assert (out["check"]["owner_mismatch"]["value"] > 0) is wrong
