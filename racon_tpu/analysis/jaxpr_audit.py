"""Static jaxpr audit: trace the device kernels abstractly and enforce
TPU invariants that no unit test exercises.

Two properties are checked over the *whole* compile grid (every
(depth bucket, window class) the consensus driver can request), using
`jax.make_jaxpr` — abstract tracing only, no device, no compilation:

* **forbidden primitives** — host callbacks (`pure_callback`,
  `io_callback`, ...), infeed/outfeed and explicit `device_put`
  transfers must never appear inside a kernel jaxpr: on TPU each one is
  a device->host round-trip that serializes the pipeline.  float64
  intermediates are likewise rejected (TPUs emulate f64 at ~1/10th
  throughput; the kernels are specified in i32/f32).

* **recompile budget** — the number of distinct jit input signatures
  across the audited grid must not exceed the budget declared next to
  the geometry (`POA_RECOMPILE_BUDGET`).  Every signature is one XLA
  compile at serving time; a geometry change that silently splits
  signatures is the biggest TPU latency cliff this repo has hit, so
  widening the grid must consciously raise the literal.

The audit traces through `jax.jit` wrappers (the pjit equation's inner
jaxpr is walked recursively), so it sees exactly what XLA would lower.
"""

from __future__ import annotations

from typing import Iterable, List, Optional, Sequence, Set, Tuple

from .lint import Violation

#: Primitive names that must never appear in a device kernel jaxpr.
#: Callbacks/infeed are host round-trips; device_put inside a jaxpr is
#: an implicit transfer the caller did not ask for.
FORBIDDEN_PRIMITIVES = {
    "pure_callback": "host callback",
    "io_callback": "host callback",
    "debug_callback": "host callback",
    "callback": "host callback",
    "infeed": "host infeed",
    "outfeed": "host outfeed",
    "device_put": "implicit transfer",
}

_POA_PATH = "racon_tpu/ops/poa.py"


# --------------------------------------------------------------------------
# jaxpr walking (duck-typed: survives jax-internal module moves)
# --------------------------------------------------------------------------

def _as_jaxpr(obj):
    """Unwrap ClosedJaxpr-likes (have .jaxpr) to the raw Jaxpr-like
    (has .eqns); None when obj is neither."""
    if hasattr(obj, "eqns"):
        return obj
    inner = getattr(obj, "jaxpr", None)
    if inner is not None and hasattr(inner, "eqns"):
        return inner
    return None


def _sub_jaxprs(value) -> Iterable:
    """Jaxpr-likes reachable from one eqn.params value."""
    if isinstance(value, (list, tuple)):
        for v in value:
            yield from _sub_jaxprs(v)
        return
    j = _as_jaxpr(value)
    if j is not None:
        yield j


def iter_eqns(jaxpr, _seen: Optional[Set[int]] = None):
    """Every equation in `jaxpr` and (recursively) in any sub-jaxpr of
    its equations' params — scan/while/cond bodies, pjit inners, vmap'd
    closed jaxprs all included."""
    seen = _seen if _seen is not None else set()
    root = _as_jaxpr(jaxpr)
    if root is None or id(root) in seen:
        return
    seen.add(id(root))
    for eqn in root.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in _sub_jaxprs(value):
                yield from iter_eqns(sub, seen)


def _aval_dtypes(eqn) -> Iterable[str]:
    for var in list(eqn.invars) + list(eqn.outvars):
        aval = getattr(var, "aval", None)
        dtype = getattr(aval, "dtype", None)
        if dtype is not None:
            yield str(dtype)


def check_jaxpr(jaxpr, path: str, label: str) -> List[Violation]:
    """Forbidden-primitive + float64 scan of one traced kernel."""
    out: List[Violation] = []
    seen_prims: Set[str] = set()
    f64_hit = False
    for eqn in iter_eqns(jaxpr):
        name = getattr(eqn.primitive, "name", str(eqn.primitive))
        if name in FORBIDDEN_PRIMITIVES and name not in seen_prims:
            seen_prims.add(name)
            out.append(Violation(
                "jaxpr-forbidden-primitive", path, 0,
                f"{label}: primitive `{name}` "
                f"({FORBIDDEN_PRIMITIVES[name]}) in kernel jaxpr"))
        if not f64_hit and any("float64" in d for d in _aval_dtypes(eqn)):
            f64_hit = True
            out.append(Violation(
                "jaxpr-float64", path, 0,
                f"{label}: float64 intermediate in kernel jaxpr "
                f"(TPU-emulated; kernels are specified in i32/f32)"))
    return out


def _signature(avals) -> Tuple:
    """Hashable jit signature: the (shape, dtype) of every input aval —
    exactly what triggers an XLA recompile when it changes."""
    return tuple((tuple(a.shape), str(a.dtype)) for a in avals)


# --------------------------------------------------------------------------
# POA consensus kernel grid
# --------------------------------------------------------------------------

def audit_poa(window_lengths: Optional[Sequence[int]] = None,
              match: int = 3, mismatch: int = -5,
              gap: int = -4) -> List[Violation]:
    """Trace the XLA consensus kernel over the full bucket grid the
    driver can request and enforce POA_RECOMPILE_BUDGET."""
    import jax
    import numpy as np

    from ..ops import poa, poa_driver

    wls = tuple(window_lengths if window_lengths is not None
                else poa_driver.AUDIT_WINDOW_LENGTHS)
    out: List[Violation] = []
    signatures: Set[Tuple] = set()
    for depth_bucket, wl_class, rung in poa_driver.audit_grid(wls):
        cfg = poa_driver.make_config(wl_class, depth_bucket,
                                     match, mismatch, gap, rung)
        # Bypass the topology cache: the audit must not touch
        # jax.devices() (stays runnable with no backend configured) and
        # must not pollute the production cache with audit entries.
        kernel = poa.build_poa_kernel.__wrapped__(cfg)
        u8, i32 = np.uint8, np.int32
        args = [
            jax.ShapeDtypeStruct((1, cfg.max_backbone), u8),   # bb codes
            jax.ShapeDtypeStruct((1, cfg.max_backbone), i32),  # bb weights
            jax.ShapeDtypeStruct((1,), i32),                   # bb_len
            jax.ShapeDtypeStruct((1,), i32),                   # n_layers
            jax.ShapeDtypeStruct((1, cfg.depth, cfg.max_len), u8),
            jax.ShapeDtypeStruct((1, cfg.depth, cfg.max_len), i32),
            jax.ShapeDtypeStruct((1, cfg.depth), i32),         # lens
            jax.ShapeDtypeStruct((1, cfg.depth), i32),         # begins
            jax.ShapeDtypeStruct((1, cfg.depth), i32),         # ends
        ]
        label = (f"poa d={depth_bucket} w={wl_class} "
                 f"rung={poa_driver.NODE_RUNGS[rung]}")
        try:
            closed = jax.make_jaxpr(kernel)(*args)
        except Exception as e:  # noqa: BLE001 — audit reports, not raises
            out.append(Violation(
                "jaxpr-trace-error", _POA_PATH, 0,
                f"{label}: abstract trace failed: "
                f"{type(e).__name__}: {e}"))
            continue
        # a node rung changes no input shape and is a program of its own
        # all the same, and so is each width the lockstep kernel runs
        # the geometry at: max_nodes and the width ride the signature
        signatures |= {(cfg.max_nodes, width) + _signature(closed.in_avals)
                       for width in poa_driver.audit_widths(cfg)}
        out.extend(check_jaxpr(closed, _POA_PATH, label))
    budget = poa_driver.POA_RECOMPILE_BUDGET
    if len(signatures) > budget:
        out.append(Violation(
            "recompile-budget", _POA_PATH, 0,
            f"POA grid compiles {len(signatures)} distinct jit "
            f"signatures over depths={tuple(poa_driver.DEPTH_BUCKETS)} "
            f"x windows={wls} x node rungs, exceeding "
            f"POA_RECOMPILE_BUDGET="
            f"{budget}; raise the declared budget only after sizing "
            f"the serving-latency cost"))
    return out


def run_audit() -> List[Violation]:
    """Full static jaxpr audit: the consensus kernel's grid."""
    return sorted(audit_poa(),
                  key=lambda v: (v.path, v.rule, v.message))
