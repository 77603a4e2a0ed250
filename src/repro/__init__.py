"""repro — UMap-style user-space page management for JAX/TPU at pod scale.

Reproduction + TPU adaptation of:
  Peng et al., "UMap: Enabling Application-driven Optimizations for Page
  Management", LLNL, 2019 (cs.DC).

Layers (bottom-up):
  core/        the paper's contribution: user-space paging (page table, slot
               buffer, fillers/evictors, watermark flushing, backing stores,
               hints) — host-side, real threads + real I/O.
  kvcache/     on-device analogue: the serve engine's page allocator (the
               user page tables of its paged KV pools).
  kernels/     Pallas TPU kernels (flash attention, page gather/scatter,
               grouped matmul) with jnp oracles.
  models/      the 10 assigned architectures.
  distributed/ mesh, sharding rules, sequence-sharded decode, compression.
  train/ serve/ data/ ckpt/ launch/   the framework runtime.
"""

__version__ = "1.0.0"
