"""Pallas TPU kernels for the paper's compute hot-spots.

Each kernel ships as <name>.py (pl.pallas_call + explicit BlockSpec VMEM
tiling), with ops.py as the jit'd batched wrapper and ref.py as the
pure-jnp oracle.  The kernels are compiled for the TPU (every
``interpret`` flag defaults to False; tests/test_chip_compile.py compiles
them for a described v5e) and tested in interpret mode on the CPU against
the oracles (tests/test_kernels.py).

- gather_kv:   scalar-prefetch sparse KV gather (the SAC read path)
- scatter_kv:  coalesced write-back (the SAC write path)
- indexer:     lightning-indexer scoring (MXU matmul + weighted ReLU)
- sparse_attn: top-k sparse attention, online softmax (MLA + MQA/GQA)
"""
