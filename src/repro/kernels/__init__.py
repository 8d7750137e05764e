"""Pallas TPU kernels for WISP's compute hot spots.

  verify_attention — small-Q x long-KV flash attention for batched
                     verification (the server hot path)
  paged_attention  — decode attention over paged KV with scalar-prefetched
                     block tables (PagedAttention, TPU-native)
  logit_features   — fused single-pass rejection-predictor features

Each kernel ships <name>.py (pl.pallas_call + BlockSpec), ops.py (public
jit'd wrapper) and ref.py (pure-jnp oracle); ``backend.interpret()`` picks
compiled (TPU) or interpreted (CPU) execution for all three.
"""
from repro.kernels.verify_attention.ops import verify_attention_op, verify_attention_ref
from repro.kernels.paged_attention.ops import paged_attention_op, paged_attention_ref
from repro.kernels.logit_features.ops import logit_features_op, logit_features_ref

__all__ = [
    "verify_attention_op",
    "verify_attention_ref",
    "paged_attention_op",
    "paged_attention_ref",
    "logit_features_op",
    "logit_features_ref",
]
