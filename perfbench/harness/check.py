"""The comparison that decides ``correct``.

Every answer the window produced is one query's top-k and its traffic
figures.  After the window a sample of the answered requests, drawn
from ``--seed``, is recomputed on the same overlay, origin, seed and
parameters by the plain reference the configuration names
(``references/<reference>.py``: ``query`` returns a
``harness.reference.Answer``, whose fields are the contract), and each
compared number is held to its limit from the configuration's
``check`` block:

``unanswered``
    Requests due in the window that were shed, failed, or not answered
    by the drain limit.
``owner_mismatch``
    Positions of the sampled top-k lists whose owner differs.
``value_rel_gap``
    Largest relative gap of a top-k score.
``traffic_mismatch``
    Sampled (request, figure) pairs whose message or byte count, or
    number of peers reached, differs.
``accuracy_gap``
    Largest gap of the accuracy (share of the true top-k returned).
``response_rel_gap``
    Largest relative gap of the simulated response time.
"""
from __future__ import annotations

import math
import sys
from typing import Callable, List

import numpy as np

from harness import bench

COUNTS = ("n_reached", "m_fw", "b_fw", "m_bw", "b_bw", "m_rt", "b_rt")
ORDER = ("unanswered", "owner_mismatch", "value_rel_gap",
         "traffic_mismatch", "accuracy_gap", "response_rel_gap")


def lifetime(policy: dict) -> float:
    """Mean peer lifetime of a configuration's policy (inf: no churn)."""
    life = policy.get("lifetime_mean_s")
    return math.inf if life is None else float(life)


def sample(answered: list, batches: List[List[int]], count: int,
           seed: int) -> list:
    """``count`` answered requests (all when fewer): every answered
    request of the window's largest dispatch, so that a fault in how a
    batch is split or padded cannot go unseen, and the rest drawn from
    ``seed``.  ``batches`` holds the request seeds of each dispatch
    (the engine's ``run_many`` calls); the largest is the first of the
    largest ones."""
    if len(answered) <= count:
        return list(answered)
    by_seed = {r.seed: i for i, r in enumerate(answered)}
    big = max(batches, key=len, default=[])
    keep = [by_seed[s] for s in big if s in by_seed][:count]
    rest = np.setdiff1d(np.arange(len(answered)), keep)
    rng = np.random.default_rng([7, int(seed)])
    keep += list(rng.choice(rest, count - len(keep), replace=False))
    return [answered[i] for i in sorted(keep)]


def load_reference(config: dict, root: str = bench.CHECKOUT) -> Callable:
    """``query`` of the reference ``config`` names, once its ``params``
    has accepted the configuration: a key it does not know fails here,
    at set-up, not after the window."""
    mod = bench.module("references", config["reference"], root)
    mod.params(config)
    return mod.query


def _worst(a: float, b: float) -> float:
    """The larger of two gaps; NaN, which no limit holds, wins."""
    return b if (math.isnan(b) or b > a) else a


def _field(result, name: str):
    return getattr(result.metrics, name).reshape(-1)[0]


def compare(requests: List, sampled: List, ov, config: dict,
            limits: dict, query: Callable) -> dict:
    """Each compared number beside its limit; ``ok`` when all hold.
    ``query`` is the configuration's reference (``load_reference``), asked
    for each sampled request on the overlay ``ov``."""
    nums = dict.fromkeys(ORDER, 0)
    nums["unanswered"] = sum(1 for r in requests
                             if math.isinf(r.latency_s))
    for r in sampled:
        ref = query(ov, r.origin, r.seed, config)
        got = r.result
        vals = np.asarray(got.values, np.float64).reshape(-1)
        owns = np.asarray(got.indices).reshape(-1)
        nums["owner_mismatch"] += int(np.sum(owns != ref.owners))
        gap = np.abs(vals - ref.values) / np.abs(ref.values)
        nums["value_rel_gap"] = _worst(nums["value_rel_gap"],
                                       float(np.max(gap)))
        nums["traffic_mismatch"] += sum(
            int(_field(got, f) != getattr(ref, f)) for f in COUNTS)
        nums["accuracy_gap"] = _worst(
            nums["accuracy_gap"],
            abs(float(_field(got, "accuracy")) - ref.accuracy))
        t = float(_field(got, "response_time_s"))
        nums["response_rel_gap"] = _worst(
            nums["response_rel_gap"],
            abs(t - ref.response_time_s) / abs(ref.response_time_s))
    out = {k: {"value": v, "limit": limits[k]} for k, v in nums.items()}
    ok = all(math.isfinite(v["value"]) and v["value"] <= v["limit"]
             for v in out.values()) and len(sampled) > 0
    return {"ok": ok, "sampled": len(sampled), "numbers": out}


def print_lines(check: dict) -> None:
    """The compared numbers beside their limits, as the last lines of
    standard error."""
    print(f"check: sampled {check['sampled']} answers, "
          f"correct={check['ok']}", file=sys.stderr)
    for name, v in check["numbers"].items():
        print(f"check {name} = {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
