from .gpt import (
    GPTConfig,
    deinterleave_stage_params,
    gpt_forward,
    gpt_interleaved_param_specs,
    gpt_loss,
    gpt_param_specs,
    gpt_pipeline_1f1b,
    gpt_pipeline_loss,
    gpt_pipeline_zb,
    init_gpt_params,
    interleave_stage_params,
    llama_config,
    vocab_parallel_embed,
    vocab_parallel_xent,
)
from .convert import (
    from_hf_gpt2,
    from_hf_llama,
    gpt2_config_from_hf,
    llama_config_from_hf,
    to_hf_llama,
)
from .generate import (
    forward_cached,
    forward_cached_moe,
    beam_generate,
    generate,
    speculative_generate,
    init_kv_cache,
)
from .gpt_moe import (
    gpt_moe_forward,
    gpt_moe_loss,
    gpt_moe_param_specs,
    gpt_moe_pipeline_1f1b,
    gpt_moe_pipeline_param_specs,
    init_gpt_moe_params,
    is_moe_block,
    moe_block_forward,
    moe_layer_config,
    moe_stage_pattern,
    stack_moe_stage_params,
)
from .hybrid import (
    HybridConfig,
    hybrid_paged_forward,
    init_hybrid_params,
    init_state,
    mamba2_mixer,
)
from .vit import (
    ViTConfig,
    init_vit_params,
    patchify,
    vit_forward,
    vit_loss,
    vit_param_specs,
    vit_pipeline_1f1b,
)
from .vit_moe import (
    init_vit_moe_params,
    vit_moe_forward,
    vit_moe_loss,
    vit_moe_param_specs,
)
