"""Prompt tokens served by the prefix cache over prompt tokens, for the
prompts whose prefill finished during the run (the engine's
``prefix_cached_tokens`` counter)."""
import math


def read(w):
    hit = w.stats1["prefix_cached_tokens"] - w.stats0["prefix_cached_tokens"]
    total = sum(len(s.prompt) for s in w.sessions.values()
                if not math.isnan(s.t_first))
    return 100.0 * hit / total if total else None
