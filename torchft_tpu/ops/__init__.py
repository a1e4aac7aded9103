from torchft_tpu.ops.flash_attention import (
    flash_attention,
    flash_attention_block,
    sharded_flash_attention,
)

__all__ = ["flash_attention", "flash_attention_block",
           "sharded_flash_attention"]
