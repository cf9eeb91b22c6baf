"""Device (JAX/XLA/Pallas) kernels: batched POA consensus and batched banded
global alignment, plus their drivers that claim work from the native pipeline
and fall back to the host for anything outside device limits."""
