from repro.kernels.merge import merge_pallas, merge_ref, merge_scorelists  # noqa: F401
from repro.kernels.sweep import wait_propagate  # noqa: F401
from repro.kernels.topk import local_topk, topk_pallas, topk_ref  # noqa: F401
