"""Pallas TPU kernels for the decode-scoring and attention hot spots.

``confidence``      — fused streaming (argmax / max-prob / margin / entropy)
``flash_attention`` — bidirectional flash attention + sliding-window band
``ref``             — pure-jnp oracles (the allclose ground truth)

Decode callers choose the kernel through ``core.confidence.pallas_enabled``.
"""
