"""Model zoo (TPU-first implementations; replaces the reference's per-arch
injection policies in module_inject/ and inference/v2/model_implementations/:
llama_v2, mistral, mixtral, falcon, opt, phi, qwen_v2 + gpt2/bloom/neox
policies in module_inject/replace_policy.py)."""
from .transformer import (
    Transformer,
    TransformerConfig,
    gpt2_config,
    llama_config,
    mistral_config,
    mixtral_config,
    qwen2_config,
    qwen2_moe_config,
    phi_config,
    phi3_config,
    falcon_config,
    opt_config,
    bloom_config,
    gptneox_config,
    longcat_flash_config,
    deepseek_v3_config,
    smallthinker_config,
    falcon_h1_config,
    granite_moe_hybrid_config,
)

from .hf_loader import load_hf_model, hf_to_config, convert_state_dict

MODEL_FAMILIES = {
    "gpt2": gpt2_config,
    "llama": llama_config,
    "mistral": mistral_config,
    "mixtral": mixtral_config,
    "qwen2": qwen2_config,
    "qwen2_moe": qwen2_moe_config,
    "phi": phi_config,
    "phi3": phi3_config,
    "falcon": falcon_config,
    "opt": opt_config,
    "bloom": bloom_config,
    "gptneox": gptneox_config,
    "longcat_flash": longcat_flash_config,
    "deepseek_v3": deepseek_v3_config,
    "smallthinker": smallthinker_config,
    "falcon_h1": falcon_h1_config,
    "granite_moe_hybrid": granite_moe_hybrid_config,
}


def get_model_config(family: str, size: str = None, **kw) -> TransformerConfig:
    """Registry lookup (the analog of the reference's policy matching in
    module_inject/replace_policy.py / v2 engine_factory)."""
    if family not in MODEL_FAMILIES:
        raise ValueError(f"unknown model family {family!r}; "
                         f"available: {sorted(MODEL_FAMILIES)}")
    fn = MODEL_FAMILIES[family]
    return fn(size, **kw) if size is not None else fn(**kw)


__all__ = [
    "Transformer", "TransformerConfig", "MODEL_FAMILIES", "get_model_config",
    "load_hf_model", "hf_to_config", "convert_state_dict",
    "gpt2_config", "llama_config", "mistral_config", "mixtral_config",
    "qwen2_config", "qwen2_moe_config", "phi_config", "phi3_config",
    "falcon_config", "opt_config",
    "bloom_config", "gptneox_config", "longcat_flash_config",
    "deepseek_v3_config", "smallthinker_config", "falcon_h1_config",
    "granite_moe_hybrid_config",
]
