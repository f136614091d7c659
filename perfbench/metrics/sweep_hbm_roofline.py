"""Share of the HBM roofline the FD sweep reaches: the least bytes its
answered queries have to move (``harness.roofline.sweep_least_bytes``)
over the chip's peak HBM bandwidth, against the sweep's device time."""
from harness import check, roofline


def read(run):
    if run.trace is None or not run.trace["sweep_device_s"]:
        return None
    cfg = run.config
    least = roofline.sweep_least_bytes(
        len(run.answered), int(cfg["overlay"]["peers"]),
        int(cfg["params"]["k"]),
        churn=check.lifetime(cfg["policy"]) != float("inf"),
        strategy1=cfg["policy"]["strategy"] != "basic")
    bw = roofline.peaks(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least / bw / run.trace["sweep_device_s"]
