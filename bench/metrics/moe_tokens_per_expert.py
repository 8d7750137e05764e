"""Routed tokens each expert multiplies per pass, over the run's window:
the engine's ``moe_assignments`` (real tokens x top-k x layers) over its
``moe_expert_passes`` (program calls x layers) times the expert count.
The weight reuse that batching buys an expert.  An engine without those
counters reads nothing."""


def read(w):
    def delta(key):
        return w.stats1.get(key, 0) - w.stats0.get(key, 0)

    passes, experts = delta("moe_expert_passes"), w.config.get(
        "n_routed_experts")
    if not passes or not experts:
        return None
    return delta("moe_assignments") / (passes * experts)
