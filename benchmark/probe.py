"""A one-second probe of the chip's int32 elementwise rate.

The DP kernels are integer VPU work; the published peaks of a v5e are
for the MXU (bf16, int8) and there is no published VPU peak.  The
compute term of the kernels' roofline is therefore measured on the chip
the run holds: a chain of dependent int32 add / max / compare-select ops
over an array that is read and written once per ``OPS_PER_ELEMENT``
operations, so the pass is bound by the vector units and not by HBM.
The rate is what XLA's own elementwise code reaches, a practical
ceiling for hand-written kernels of the same operations, not a
datasheet figure.  Run after the measured window, so that it changes
neither the window nor the order in which set-up lowers its programs.
"""

from __future__ import annotations

import time

ELEMENTS = 1 << 22
ROUNDS = 64            # each round: add, sub, max, compare, add, select
OPS_PER_ELEMENT = 6 * ROUNDS


def int32_ops_per_s(budget_s: float = 1.0) -> float:
    import jax
    import jax.numpy as jnp

    @jax.jit
    def chain(x, y):
        for r in range(ROUNDS):
            x = jnp.maximum(x + y, y - r)
            y = jnp.where(x > y, y + 1, x)
        return x, y

    x = jnp.arange(ELEMENTS, dtype=jnp.int32)
    y = jnp.ones(ELEMENTS, dtype=jnp.int32)
    x, y = chain(x, y)
    jax.block_until_ready((x, y))          # compile + first run
    passes, t0 = 0, time.monotonic()
    while True:
        x, y = chain(x, y)
        jax.block_until_ready((x, y))
        passes += 1
        dt = time.monotonic() - t0
        if dt >= budget_s:
            return passes * ELEMENTS * OPS_PER_ELEMENT / dt
