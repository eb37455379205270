"""Pallas TPU kernels for the perf-critical hot spots (CPU-validated via
interpret=True against the pure-jnp oracles in ref.py):

  intersect          sorted-set membership (the CA-intersection inner loop)
  searchsorted       count-based blocked binary search
  elca_segsum        ELCA child-NDesc aggregation as a masked mat-sum

ops.py hosts the jit-ready wrappers (window bookkeeping, padding) and the
kernel-backed query path used by engine backend="pallas".
"""
