from .attention import dot_product_attention, make_padding_mask, segment_mask
from .flash_attention import (
    flash_attention,
    flash_kernel_mode,
    paged_attention_decode,
    paged_attention_prefill,
)
