"""The plain reference of the FD family (``reference: "fd"``):
``harness.reference.fd_query``, the scalar event simulator.

It answers the FD policies (``fd-basic``, ``fd-st1``, ``fd-st1+2``,
``fd-dynamic``, with or without churn) under Table-1 ``params`` with
i.i.d. link latencies.  A configuration outside that scope, such as a
``params`` key it has no field for, is refused at set-up rather than
answered wrongly.
"""
import dataclasses

from harness import check, reference

POLICIES = ("fd-basic", "fd-st1", "fd-st1+2", "fd-dynamic")
POLICY_KEYS = ("name", "strategy", "dynamic", "lifetime_mean_s")


def params(config: dict) -> reference.Params:
    """The reference's parameters from ``config["params"]``; raises on
    a key of ``params`` or ``policy`` it does not know, or a policy
    outside the FD family."""
    fields = {f.name for f in dataclasses.fields(reference.Params)}
    pol = config["policy"]
    unknown = ([f"params.{k}" for k in sorted(config["params"])
                if k not in fields]
               + [f"policy.{k}" for k in sorted(pol)
                  if k not in POLICY_KEYS])
    if unknown:
        raise ValueError(f"the fd reference does not know {unknown}")
    if pol["name"] not in POLICIES:
        raise ValueError(f"the fd reference answers {POLICIES}, not "
                         f"{pol['name']!r}")
    return reference.Params(**config["params"])


def query(ov, origin: int, seed: int, config: dict) -> reference.Answer:
    """One query from ``origin`` with seed ``seed`` on overlay ``ov``."""
    pol = config["policy"]
    return reference.fd_query(
        ov.neighbors, origin, seed, params(config),
        strategy=pol["strategy"], dynamic=pol["dynamic"],
        lifetime_mean_s=check.lifetime(pol))
