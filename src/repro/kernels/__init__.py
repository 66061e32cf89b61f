"""Pallas TPU kernels for AQPIM's compute hot-spots.

- pq_decode       PQ decode attention on compressed KV (VMEM table = the paper's
                  intra-row indirection analogue); dense + block-table-native
                  (paged pool) variants
- paged_flash_decode  exact-policy flash decode, dense + block-table-native
                  (a grid step reads many pool pages of all kv heads of a
                  row, only those below its length)
- kmeans_assign   distance-calculation + cluster-assignment step of online k-means
- flash_attention exact blockwise attention (prefill / baseline)

Each kernel has a pure-jnp oracle in ref.py; ops.py holds the jit'd wrappers.
Kernels are validated with interpret=True on CPU and target Mosaic on TPU.
"""
from repro.kernels import ops, ref

__all__ = ["ops", "ref"]
