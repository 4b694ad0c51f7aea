"""Pallas TPU kernels for the compute hot-spots.  Each entry dispatches
itself (``kernels.backend``): native Mosaic kernels on TPU, the jnp oracle
elsewhere, and Pallas interpret mode when a test passes ``interpret=True``:

* lora_matmul     — fused y = xW + scale·(xAᵀ)Bᵀ (the paper's adapter math)
* flash_attention — ``flash_attention_train``: online-softmax causal GQA
                    attention, VMEM-resident tiles, forward and fused
                    backward behind one custom VJP
* flash_decode    — one-token decode over per-slot KV caches, split-K over
                    the cache length with per-slot live-length masking
* ssd_scan        — Mamba2 chunked state-space duality forward
"""
from .flash_attention import (flash_attention_ref, flash_attention_train,
                              flash_decode, flash_decode_ref)
from .lora_matmul import lora_matmul, lora_matmul_ref
from .ssd_scan import ssd_scan, ssd_sequential_ref

__all__ = [
    "flash_attention_ref", "flash_attention_train", "flash_decode",
    "flash_decode_ref", "lora_matmul", "lora_matmul_ref", "ssd_scan",
    "ssd_sequential_ref",
]
