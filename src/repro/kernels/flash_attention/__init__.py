from .decode import flash_decode_kernel, flash_decode_q8_kernel
from .kernel import flash_attention_bwd_kernels, flash_attention_kernel
from .ops import flash_attention_train, flash_decode, paged_decode
from .paged_decode import paged_decode_kernel, paged_decode_q8_kernel
from .ref import (flash_attention_ref, flash_decode_q8_ref, flash_decode_ref,
                  paged_decode_q8_ref, paged_decode_ref)
from .tune import best_decode_block, best_paged_block, best_train_blocks

__all__ = ["flash_attention_bwd_kernels",
           "flash_attention_kernel", "flash_attention_ref",
           "flash_attention_train",
           "flash_decode", "flash_decode_kernel", "flash_decode_q8_kernel",
           "flash_decode_q8_ref", "flash_decode_ref",
           "paged_decode", "paged_decode_kernel", "paged_decode_q8_kernel",
           "paged_decode_q8_ref", "paged_decode_ref",
           "best_decode_block", "best_paged_block", "best_train_blocks"]
