from repro.kernels.sweep.ops import wait_propagate  # noqa: F401
from repro.kernels.sweep.ref import arrivals_ref, wait_ref  # noqa: F401
from repro.kernels.sweep.sweep import wait_pallas  # noqa: F401
