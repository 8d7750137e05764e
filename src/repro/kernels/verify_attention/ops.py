"""Public entry for verification attention: Pallas on TPU, interpret mode
(same kernel body, Python-evaluated) on the CPU (`repro.kernels.backend`)."""
from __future__ import annotations

from repro.kernels import backend
from repro.kernels.verify_attention.verify_attention import verify_attention as _kernel
from repro.kernels.verify_attention.ref import verify_attention_ref


def verify_attention_op(q, k, v, lengths, *, softcap=0.0, window=0, blk_kv=512):
    interpret = backend.interpret()
    return _kernel(
        q, k, v, lengths,
        softcap=softcap, window=window, blk_kv=blk_kv, interpret=interpret,
    )


__all__ = ["verify_attention_op", "verify_attention_ref"]
