from repro.kernels.mwem_step.ops import (mwem_step, mwem_step_batch,
                                         mwem_step_supported, mwu_apply)
from repro.kernels.mwem_step.ref import (UPDATE_RULES, mwem_step_ref,
                                         mwu_apply_ref)

__all__ = [
    "mwem_step",
    "mwem_step_batch",
    "mwem_step_supported",
    "mwu_apply",
    "mwem_step_ref",
    "mwu_apply_ref",
    "UPDATE_RULES",
]
