from torchft_tpu.ops.flash_attention import (
    flash_attention,
    flash_attention_block,
    sharded_flash_attention,
    sparse_flash_attention,
)
from torchft_tpu.ops.gated_delta import (
    causal_conv1d,
    gated_delta_recurrent,
    gated_delta_rule,
)
from torchft_tpu.ops.ssd import ssd_recurrent, ssd_scan

__all__ = ["causal_conv1d", "flash_attention", "flash_attention_block",
           "gated_delta_recurrent", "gated_delta_rule",
           "sharded_flash_attention", "sparse_flash_attention",
           "ssd_recurrent", "ssd_scan"]
