"""Shape/cost extraction for kernel builds — the ops-side half of the
analytic cost model (racon_tpu/obs/costmodel.py).

``device_keyed_cache`` calls :func:`record_build` on every builder cache
miss, and ``poa_driver._build_kernel`` does the same for its
topology-keyed front.  The hook maps the builder's shape arguments onto
the closed-form per-unit estimates, so every retroactive ``kernel.build``
span carries ``pred_flops`` / ``pred_hbm_bytes`` / ``pred_serial_steps``
args — the predicted bill for ONE window/job through that kernel, right
next to the measured build wall in the same trace row.

Gated on ``RACON_TPU_COST_MODEL`` (default on) and a no-op whenever obs
is disarmed; anything unrecognized returns ``{}`` rather than guessing.
The in-process registry (:func:`builds`) is what tests read back.
"""

from __future__ import annotations

from typing import Dict, List, Optional

from .. import config, obs
from ..obs import costmodel

ENV_COST_MODEL = "RACON_TPU_COST_MODEL"

#: Build records this process accumulated: {builder, shape, estimate}.
_BUILDS: List[dict] = []


def enabled() -> bool:
    return obs.enabled() and config.get_bool(ENV_COST_MODEL)


def reset() -> None:
    del _BUILDS[:]


def builds() -> List[dict]:
    return list(_BUILDS)


def _poa_estimate(cfg, tier: str) -> costmodel.CostEstimate:
    # max_backbone is already the 128-ceiled window class (make_config)
    return costmodel.poa_window_cost(cfg.depth, cfg.max_backbone, tier)


def estimate(builder: str, args: tuple,
             kwargs: dict) -> Optional[costmodel.CostEstimate]:
    """Closed-form per-unit cost for a recognized builder signature, or
    None.  Signatures mirror the @device_keyed_cache builders:

    * ``build_align_kernel(cap, band)`` — xla moves-matrix aligner
    * ``build_poa_kernel(cfg)`` — XLA twin
    * ``build_pallas_poa_kernel(cfg, ...)`` / \
      ``build_lockstep_poa_kernel(cfg, ...)`` — v2 / ls tiers
    * ``_build_edge_kernel(rcap, K, ...)`` / ``_build_base_kernel(K,
      ...)`` — Hirschberg pieces (billed as one hirschberg job at the
      kernel's row capacity and band)
    """
    try:
        if builder == "build_align_kernel":
            return costmodel.align_job_cost(int(args[0]), int(args[1]),
                                            "xla")
        if builder == "build_poa_kernel":
            return _poa_estimate(args[0], "xla")
        if builder == "build_pallas_poa_kernel":
            return _poa_estimate(args[0], "v2")
        if builder == "build_lockstep_poa_kernel":
            return _poa_estimate(args[0], "ls")
        if builder == "_build_edge_kernel":
            return costmodel.align_job_cost(int(args[0]), int(args[1]),
                                            "hirschberg")
        if builder == "_build_base_kernel":
            return costmodel.align_job_cost(int(args[0]), int(args[0]),
                                            "hirschberg")
    except (IndexError, TypeError, ValueError, AttributeError):
        return None
    return None


def record_build(builder: str, args: tuple = (),
                 kwargs: Optional[dict] = None) -> Dict[str, float]:
    """Called by the kernel-cache seams on a build.  Returns the span
    args to stamp onto the ``kernel.build`` event ({} when the cost
    model is off or the builder is unrecognized)."""
    if not enabled():
        return {}
    est = estimate(builder, args, kwargs or {})
    if est is None:
        return {}
    _BUILDS.append({"builder": builder, "estimate": est})
    obs.count(f"cost_model.builds.{builder}")
    return {"pred_flops": round(est.flops),
            "pred_hbm_bytes": round(est.hbm_bytes),
            "pred_serial_steps": round(est.serial_steps)}
