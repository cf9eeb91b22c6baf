"""Hardware mesh discovery + the Partitioner that shards grid kernels.

This is the multi-chip half of ROADMAP item 2: a real partitioning
subsystem in the T5X mold sized for this repo's
two embarrassingly parallel hot paths.  Three layers:

* **mesh discovery** — ``mesh_shape()`` resolves the
  ``RACON_TPU_MESH_SHAPE`` knob against the live device set;
  ``build_mesh()`` materializes a 2-D ``jax.sharding.Mesh`` over
  ``axes.MESH_AXES``: a hybrid ICI×DCN mesh on multi-host TPU
  topologies (``mesh_utils.create_hybrid_device_mesh``, so the
  data-parallel axis stripes across hosts without tripping over
  non-contiguous device order), a flat reshape of ``jax.devices()``
  everywhere else (CPU, single-host TPU, and the CI
  ``xla_force_host_platform_device_count`` virtual mesh).

* **the Partitioner** — wraps any grid kernel for the mesh two ways:
  ``partition()`` jits with NamedSharding in/out constraints (the pjit
  path; right for XLA-tier kernels, which partition transparently), and
  ``shard_build()`` wraps a per-shard kernel *builder* in shard_map (the
  Pallas path, where each device must trace a kernel of the local batch
  size).  Both resolve dim specs through the logical-axis rules in
  ``parallel/axes.py`` so no kernel ever names a mesh axis.  Padding
  math (``pad_rows``/``pad_packed``) and the ``will_shard`` gate live
  here too so every caller pads identically — the round-DOWN remainder
  spill the old ``divisible_batch`` forced on the consensus driver is
  replaced by round-UP padding accounted in stats.

* **demotion state** — a sharded compile failure or device loss calls
  ``demote(cause)``; the partitioner then answers ``will_shard() ->
  False`` for the rest of the process and every caller falls back to
  its existing single-device build (the ``sharded -> single-device``
  lattice edge; see resilience/lattice.record_shard_demotion).  Output
  stays byte-identical because sharding only ever changes *where* rows
  compute, never what is computed.

``get_partitioner()`` is memoized through the topology-keyed
``ops/kernel_cache.device_keyed_cache`` with the mesh shape and rule
set as explicit key components, so reconfiguring devices, the mesh
knob, or the rules never serves a stale mesh wrap.
"""

from __future__ import annotations

import sys
from typing import Optional, Sequence, Tuple

import numpy as np

from .. import config
from ..device import named_like
from ..ops.kernel_cache import Program, body_and_key, device_keyed_cache
from . import axes


def _warn(msg: str) -> None:
    print(f"[racon-tpu] {msg}", file=sys.stderr)


# --------------------------------------------------------------------------
# mesh discovery
# --------------------------------------------------------------------------

def mesh_shape(n_devices: Optional[int] = None) -> Tuple[int, int]:
    """(data, model) mesh shape from ``RACON_TPU_MESH_SHAPE``.

    Accepted spellings: ``"8"`` -> (8, 1); ``"4,2"`` / ``"4x2"`` ->
    (4, 2).  Unset defaults to (n_devices, 1) — every device on the
    data-parallel axis.  A shape asking for more devices than exist (or
    unparseable text) falls back to the default with a warning rather
    than failing: mis-set knobs degrade, they don't kill a polish."""
    if n_devices is None:
        import jax
        n_devices = len(jax.devices())
    raw = config.get_str("RACON_TPU_MESH_SHAPE").strip()
    if not raw:
        return (n_devices, 1)
    try:
        parts = [int(p) for p in raw.replace("x", ",").split(",")
                 if p.strip()]
    except ValueError:
        parts = []
    if len(parts) == 1:
        parts.append(1)
    if (len(parts) != 2 or any(p < 1 for p in parts)
            or parts[0] * parts[1] > n_devices):
        _warn(f"RACON_TPU_MESH_SHAPE={raw!r} invalid for {n_devices} "
              f"device(s); using ({n_devices}, 1)")
        return (n_devices, 1)
    return (parts[0], parts[1])


def build_mesh(shape: Optional[Tuple[int, int]] = None):
    """A 2-D Mesh over ``axes.MESH_AXES`` for the current device set.

    Multi-host TPU topologies get ``create_hybrid_device_mesh`` (ICI
    within a host, DCN across hosts); anything else
    gets a flat reshape of ``jax.devices()`` in enumeration order, which
    is exactly what the CI forced-host CPU mesh and single-host silicon
    want.  Uses the first data*model devices when the shape deliberately
    under-subscribes the machine."""
    import jax
    from jax.sharding import Mesh

    devs = jax.devices()
    if shape is None:
        shape = mesh_shape(len(devs))
    data, model = shape
    if (jax.process_count() > 1 and devs[0].platform == "tpu"
            and data % jax.process_count() == 0):
        from jax.experimental import mesh_utils

        try:
            arr = mesh_utils.create_hybrid_device_mesh(
                (data // jax.process_count(), model),
                (jax.process_count(), 1))
            return Mesh(arr, axes.MESH_AXES)
        except Exception as exc:  # noqa: BLE001 — hybrid mesh construction is best-effort; any topology error falls back to the flat mesh
            _warn(f"hybrid mesh ({data},{model}) failed ({exc!r}); "
                  f"using flat device order")
    arr = np.asarray(devs[:data * model], dtype=object).reshape(
        (data, model))
    return Mesh(arr, axes.MESH_AXES)


# --------------------------------------------------------------------------
# the Partitioner
# --------------------------------------------------------------------------

class Partitioner:
    """Shards grid kernels over a concrete mesh via logical-axis rules.

    Not callable on purpose: instances pass through
    ``analysis.sanitize.wrap_kernel`` unchanged when memoized through
    the kernel cache."""

    def __init__(self, mesh, rules: axes.Rules):
        axes.validate_rules(rules, tuple(mesh.shape))
        self.mesh = mesh
        self.rules = rules
        self._disabled: Optional[str] = None  # demotion cause, sticky

    # -- topology ----------------------------------------------------------

    @property
    def n_devices(self) -> int:
        return int(np.prod(list(self.mesh.shape.values())))

    @property
    def batch_axis_size(self) -> int:
        """Shard count along the batch (``windows``) logical axis — the
        device-count multiple every sharded batch must pad to."""
        mesh_axis = dict(self.rules).get("windows")
        if mesh_axis is None:
            return 1
        return int(self.mesh.shape[mesh_axis])

    @property
    def disabled(self) -> Optional[str]:
        return self._disabled

    def demote(self, cause: str) -> bool:
        """Permanently drop to single-device dispatch.  Returns True the
        first time (callers log/record the lattice edge exactly once)."""
        first = self._disabled is None
        self._disabled = str(cause)
        return first

    # -- spec resolution ---------------------------------------------------

    def spec(self, *logical: Optional[str]):
        """PartitionSpec for an array whose dims carry these logical
        axis names (None entries = replicated dims)."""
        return axes.resolve_spec(logical, self.rules, tuple(self.mesh.shape))

    def sharding(self, *logical: Optional[str]):
        from jax.sharding import NamedSharding

        return NamedSharding(self.mesh, self.spec(*logical))

    # -- kernel wrapping ---------------------------------------------------

    def partition(self, fn, in_axes: Sequence, out_axes):
        """``fn`` (a bare function or a single-device Program, taken by
        its body and key) as a Program with sharding constraints
        resolved from logical axes — the pjit path for XLA-tier kernels.

        ``in_axes`` is one logical-axis tuple per input; ``out_axes`` is
        a single tuple (one output) or a tuple of tuples."""
        in_sh = tuple(self.sharding(*a) for a in in_axes)
        if (isinstance(out_axes, (list, tuple)) and out_axes
                and isinstance(out_axes[0], (list, tuple))):
            out_sh = tuple(self.sharding(*a) for a in out_axes)
        else:
            out_sh = self.sharding(*out_axes)
        body, key = body_and_key(fn)
        return Program(
            body, shardings=(in_sh, out_sh),
            key=key and ("partition", key, self._mesh_key(),
                         tuple(map(tuple, in_axes)), repr(out_axes)))

    def _mesh_key(self):
        """The mesh as a program's key carries it: axis names and sizes
        (which devices fill them is the call's business, not the
        program's)."""
        return tuple(self.mesh.shape.items())

    def shard_build(self, build_local, batch: int, n_in: int, n_out: int):
        """shard_map wrap of a per-shard kernel *builder* — the Pallas
        path, where each device traces a kernel of the local batch size.
        Every input/output is sharded on the leading ``windows`` dim.
        ``build_local`` returns the per-shard Program (taken by its body
        and key) or a bare function.  Returns None when this batch
        shouldn't shard (caller keeps its single-device build)."""
        import jax

        m = self.batch_axis_size
        if self._disabled is not None or m <= 1 or batch % m or batch < m:
            return None
        body, key = body_and_key(build_local(batch // m))
        spec = self.spec("windows")
        rows = self.sharding("windows")
        out_specs = (spec,) * n_out if n_out > 1 else spec
        return Program(
            jax.shard_map(named_like(body), mesh=self.mesh, in_specs=(spec,) * n_in,
                          out_specs=out_specs, check_vma=False),
            shardings=((rows,) * n_in,
                       (rows,) * n_out if n_out > 1 else rows),
            key=key and ("shard_map", key, self._mesh_key(), n_in, n_out))

    # -- batch padding (satellite: the one place pad math lives) -----------

    def pad_rows(self, n: int) -> int:
        """Smallest batch >= n that divides over the batch axis — the
        round-UP replacement for mesh.divisible_batch's round-DOWN."""
        m = self.batch_axis_size
        return max(1, (max(n, 1) + m - 1) // m) * m

    def pad_packed(self, packed, pad_to: Optional[int] = None):
        """Pad every array's leading dim to a batch-axis multiple (or to
        ``pad_to``) by repeating the final row — always a valid, already
        computed-for row, so padded lanes do real-but-discarded work and
        can never poison the kernel.  Returns (padded tuple, n_pad)."""
        rows = int(np.asarray(packed[0]).shape[0])
        target = self.pad_rows(rows) if pad_to is None else int(pad_to)
        pad = target - rows
        if pad <= 0:
            return tuple(packed), 0
        out = []
        for a in packed:
            a = np.asarray(a)
            out.append(np.concatenate(
                [a, np.repeat(a[-1:], pad, axis=0)], axis=0))
        return tuple(out), pad

    # -- dispatch gate -----------------------------------------------------

    def will_shard(self, batch: int) -> bool:
        """Whether a batch of this many rows should dispatch over the
        mesh: sharding enabled (``RACON_TPU_SHARD`` != 0), not demoted,
        >1 shard on the batch axis, and batch at least
        ``RACON_TPU_SHARD_MIN_BATCH`` (default: one row per shard) so
        tiny tails aren't padded up just to ship one window per chip."""
        if self._disabled is not None:
            return False
        if config.get_raw("RACON_TPU_SHARD") == "0":
            return False
        m = self.batch_axis_size
        if m <= 1:
            return False
        min_batch = config.get_int("RACON_TPU_SHARD_MIN_BATCH")
        return batch >= (min_batch if min_batch > 0 else m)


# --------------------------------------------------------------------------
# topology-keyed singleton
# --------------------------------------------------------------------------

@device_keyed_cache(maxsize=8)
def _build_partitioner(shape: Tuple[int, int], rules: axes.Rules):
    return Partitioner(build_mesh(shape), rules)


def get_partitioner() -> Partitioner:
    """The process-wide Partitioner for the current topology, mesh-shape
    knob, and rule set.  Demotion state rides on the memoized instance,
    so one sharded compile failure disables sharding for every
    subsequent caller on the same topology (tests reset via
    ``reset_partitioner``)."""
    return _build_partitioner(mesh_shape(), axes.rules_key())


def reset_partitioner() -> None:
    """Drop memoized partitioners (and their demotion state)."""
    _build_partitioner.cache_clear()
