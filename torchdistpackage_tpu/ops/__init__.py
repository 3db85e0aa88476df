from .flash_attention import flash_attention, flash_attention_with_lse, mha_reference
from .paged_attention import (
    default_paged_params,
    modeled_attend_temp_bytes,
    paged_decode_attention,
    resolve_attn_impl,
)
from .ring_attention import ring_attention, ulysses_attention
