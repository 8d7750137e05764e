"""Public entry for fused rejection features: Pallas on TPU, interpret mode
on the CPU (`repro.kernels.backend`)."""
from __future__ import annotations

from repro.kernels import backend
from repro.kernels.logit_features.logit_features import logit_features as _kernel
from repro.kernels.logit_features.ref import logit_features_ref


def logit_features_op(logits, *, blk=2048):
    interpret = backend.interpret()
    return _kernel(logits, blk=blk, interpret=interpret)


__all__ = ["logit_features_op", "logit_features_ref"]
