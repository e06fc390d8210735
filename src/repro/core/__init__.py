"""REFT core: the paper's contribution (in-memory fault tolerance).

Names are resolved on first use (PEP 562), so importing a numpy-only
submodule — the SMP process imports `repro.core.smp` — does not pull in
the coordinator, the saving pipeline or JAX.
"""
import importlib

_EXPORTS = {
    "NodeState": "coordinator", "Reft": "coordinator",
    "ReftGroup": "coordinator",
    "ReftConfig": "snapshot", "SnapshotEngine": "snapshot",
    "LoadPlan": "loader", "LoadStats": "loader", "build_plan": "loader",
    "RecoveryError": "recovery", "restore_from_checkpoint": "recovery",
    "restore_state": "recovery",
    "FrequencyPlan": "policy", "ckpt_survival": "policy",
    "optimal_interval": "policy", "plan_frequencies": "policy",
    "reft_fail_rate": "policy", "reft_survival": "policy",
    "safe_horizon": "policy", "weibull_survival": "policy",
}

__all__ = list(_EXPORTS)


def __getattr__(name):
    mod = _EXPORTS.get(name)
    if mod is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    value = getattr(importlib.import_module(f"{__name__}.{mod}"), name)
    globals()[name] = value
    return value
