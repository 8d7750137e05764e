"""Verifier fleet: prefix-locality routing, heartbeat failover, hedged
re-dispatch (docs/ARCHITECTURE.md §7, DESIGN.md §10).

``build_verifier_fleet`` constructs N independent `WISPServer` verifiers
— same target params, same engine seed, so they are functionally
interchangeable under rng-tagged verification — behind one `FleetRouter`;
`FleetRuntime` drives the ensemble on the cluster's virtual clock with
deterministic failure/straggler injection (`ClusterConfig.fail_at` /
``straggle``).
"""
from __future__ import annotations

import jax

from repro.fleet.router import FleetCapacityError, FleetRouter
from repro.fleet.runtime import FleetRuntime


def build_verifier_fleet(
    model_cfg,
    tparams,
    n_verifiers: int,
    coeffs,
    *,
    max_slots: int,
    max_len: int,
    method: str = "residual",
    policy="wisp",
    sched_cfg=None,
    network=None,
    prefill: str = "monolithic",
    prefill_chunk_tokens: int = 256,
    slo_classes=None,
    ttft_slo=None,
    engine_seed: int = 0,
    heartbeat_timeout: float = 0.15,
    hedge_factor: float = 8.0,
    hedge_guard: float = 0.01,
    kv_tier_pages: int = 0,
    spill_quantize: bool = False,
    spill_idle_epochs: int = 2,
    tenants=None,
    devices=None,
) -> FleetRouter:
    """N same-seed verifiers (each its own engine + page pool + scheduler
    instance) behind a prefix-locality router.  ``max_slots`` is PER
    VERIFIER — the fleet's aggregate capacity is ``n_verifiers x
    max_slots`` — and every verifier shares ``tparams`` (one trained
    target model, replicated), which is what makes migration lossless.

    ``tenants`` (a `TenantRegistry`, or an iterable of `TenantSpec` /
    CLI spec strings) is instantiated ONCE and shared by every verifier:
    tenant budgets and fair-share accounting are fleet-global, which is
    what a fleet-wide SLO means (DESIGN.md §13).

    Replica i lives on ``devices[i % len(devices)]`` (default
    ``jax.devices()``): its copy of the params, its page pools, staging
    buffers and rng keys, so on a multi-chip host each verifier holds its
    own chip.  Replicas on the device that already holds ``tparams`` share
    them (``device_put`` there makes no copy)."""
    from repro.serving.engine import VerificationEngine
    from repro.serving.server import WISPServer
    from repro.tenancy import TenantRegistry

    if tenants is not None and not isinstance(tenants, TenantRegistry):
        tenants = TenantRegistry(tenants)
    registry = tenants if tenants is not None else TenantRegistry()

    devices = list(devices or jax.devices())
    verifiers = {}
    for i in range(int(n_verifiers)):
        device = devices[i % len(devices)]
        engine = VerificationEngine(
            model_cfg, jax.device_put(tparams, device),
            max_slots=max_slots, max_len=max_len,
            method=method, seed=engine_seed,
            kv_tier_pages=kv_tier_pages, spill_quantize=spill_quantize,
            spill_idle_epochs=spill_idle_epochs, device=device,
        )
        verifiers[f"v{i}"] = WISPServer(
            engine, coeffs, policy=policy, sched_cfg=sched_cfg,
            network=network, prefill=prefill,
            prefill_chunk_tokens=prefill_chunk_tokens,
            slo_classes=slo_classes, ttft_slo=ttft_slo,
            tenants=registry,
        )
    return FleetRouter(verifiers, heartbeat_timeout=heartbeat_timeout,
                       hedge_factor=hedge_factor, hedge_guard=hedge_guard)


__all__ = [
    "FleetCapacityError",
    "FleetRouter",
    "FleetRuntime",
    "build_verifier_fleet",
]
