"""Serving-side scheduling: continuous (in-flight) batching over a fixed
pool of KV-cache slots (``transformer_tpu/serve/scheduler.py``),
speculative decoding — draft/verify/rollback on that pool
(``transformer_tpu/serve/speculative.py``) — the cross-request prefix
KV cache — radix-trie prompt reuse feeding slot admission
(``transformer_tpu/serve/prefix_cache.py``) — and the fault-tolerance
surface: deterministic fault injection, request deadlines/cancellation,
and the circuit-breaker degradation ladder
(``transformer_tpu/serve/resilience.py``, docs/ROBUSTNESS.md) — plus the
multi-replica serving tier: a prefix-affinity front-end router with
zero-loss failover over replica worker processes
(``transformer_tpu/serve/router.py`` / ``replica.py``,
docs/SERVING.md "Multi-replica router") — and the live-weights control
plane: router-coordinated rolling checkpoint swaps with canary gating and
SLO-driven auto-rollback (``transformer_tpu/serve/upgrade.py``,
docs/SERVING.md "Live-weights rollout")."""

import importlib

# Resolved on first attribute access (PEP 562), not at import: the router
# parent imports ``transformer_tpu.serve.router`` and must stay off JAX —
# one process per chip, and a parent that has touched JAX holds the chip —
# while the scheduler, the prefix cache and the drafters all import jax.
_EXPORTS = {
    "CircuitBreaker": "resilience",
    "ContinuousScheduler": "scheduler",
    "FaultPlane": "resilience",
    "InjectedFault": "resilience",
    "ModelDrafter": "speculative",
    "NgramDrafter": "speculative",
    "PrefixCache": "prefix_cache",
    "PrefixCorruptionError": "prefix_cache",
    "PrefixHit": "prefix_cache",
    "ReplicaLink": "router",
    "ReplicaProcess": "router",
    "Router": "router",
    "SlotPool": "scheduler",
    "TransientError": "resilience",
    "UpgradeCoordinator": "upgrade",
    "UpgradeError": "upgrade",
    "drafter_from_flags": "speculative",
    "speculative_generate": "speculative",
}

__all__ = sorted(_EXPORTS)


def __getattr__(name: str):
    try:
        module = _EXPORTS[name]
    except KeyError:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}") from None
    value = getattr(importlib.import_module(f"{__name__}.{module}"), name)
    globals()[name] = value
    return value
