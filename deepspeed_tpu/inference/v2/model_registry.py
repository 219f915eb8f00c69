"""Per-architecture engine factory.

Reference: `inference/v2/engine_factory.py` `build_hf_engine` +
`model_implementations/` (llama_v2, mistral, mixtral, falcon, opt, phi,
qwen_v2, qwen_v2_moe...) — policy-matches an architecture name to a model
implementation and builds the ragged engine.

TPU-first: all architectures share one paged-KV transformer program
(ragged_ops.py) parameterized by TransformerConfig; the registry maps arch
names to the config presets in models/ (the analog of per-arch containers).
"""
from __future__ import annotations

from typing import Optional

from ...models import MODEL_FAMILIES, get_model_config
from .engine_v2 import InferenceEngineV2, RaggedInferenceEngineConfig
from .families import family_of

__all__ = ["ARCH_REGISTRY", "arch_config", "apply_serving_tp",
           "build_engine", "build_hf_engine", "check_serving_moe"]

# arch name (HF-style, lowercased) -> models/ family key
ARCH_REGISTRY = {
    "gpt2": "gpt2",
    "llama": "llama",
    "llama_v2": "llama",
    "mistral": "mistral",
    "mixtral": "mixtral",
    "qwen2": "qwen2",
    "qwen_v2": "qwen2",
    "qwen_v2_moe": "qwen2_moe",
    "qwen2_moe": "qwen2_moe",
    "phi": "phi",
    "phi3": "phi3",
    "falcon": "falcon",
    "opt": "opt",
    "bloom": "bloom",
    "gptneox": "gptneox",
    "longcat_flash": "longcat_flash",
    "deepseek_v3": "deepseek_v3",
    "smallthinker": "smallthinker",
    "falcon_h1": "falcon_h1",
    "granite_moe_hybrid": "granite_moe_hybrid",
}


def arch_config(arch: str, size: Optional[str] = None, **kw):
    """Architecture name -> TransformerConfig (policy match; reference:
    engine_factory's model_implementations dispatch)."""
    key = arch.lower()
    if key not in ARCH_REGISTRY:
        raise ValueError(f"unsupported architecture {arch!r}; supported: "
                         f"{sorted(ARCH_REGISTRY)}")
    fam = ARCH_REGISTRY[key]
    return get_model_config(fam, size, **kw) if size else get_model_config(fam, **kw)


def apply_serving_tp(engine_config: Optional[RaggedInferenceEngineConfig],
                     serving_config) -> RaggedInferenceEngineConfig:
    """Fold a ServingConfig's validated TP fields onto an engine config
    (a fresh default config when None) — the seam that lets a
    ThreadedServer / FleetRouter engine factory build TP engines
    straight from the JSON-wired serving knobs.  Explicit engine-config
    values win only when the serving side keeps its defaults (ServeLoop
    accepts that direction — an engine configured stronger than the
    serving defaults still serves the contract); a CONFLICT (both sides
    set, different values) is refused loudly here, where the config was
    made."""
    import dataclasses
    engine_config = engine_config or RaggedInferenceEngineConfig()
    tp = serving_config.tensor_parallel_size
    coll = serving_config.tp_collectives
    if (tp > 1 and engine_config.tensor_parallel_size > 1
            and engine_config.tensor_parallel_size != tp):
        raise ValueError(
            f"serving.tensor_parallel_size={tp} conflicts with the "
            f"engine config's tensor_parallel_size="
            f"{engine_config.tensor_parallel_size}")
    out = dataclasses.replace(
        engine_config,
        tensor_parallel_size=(tp if tp > 1
                              else engine_config.tensor_parallel_size))
    if coll != "xla":
        if (engine_config.tp_collectives != "xla"
                and engine_config.tp_collectives != coll):
            raise ValueError(
                f"serving.tp_collectives={coll!r} conflicts with the "
                f"engine config's {engine_config.tp_collectives!r}")
        out = dataclasses.replace(out, tp_collectives=coll)
    return out


def check_serving_moe(model_config, serving_config) -> None:
    """Refuse a ServingConfig.moe that the model's layout cannot serve —
    at the factory, where the arch was chosen, not as an engine probe
    failure mid-construction.  Expert paging needs an MoE
    parameterization (moe_experts > 1: the registry's MoE layouts are
    mixtral / qwen2_moe) and slot counts inside [top_k, E]: fewer slots
    than top_k would reroute on EVERY token, more than E is a config
    typo."""
    moe = getattr(serving_config, "moe", None)
    if moe is None or not moe.enabled:
        return
    E = model_config.moe_experts
    family = family_of(model_config)
    if E > 1 and not family.shards:
        family.refuse(
            "serving.moe (expert paging: it swaps whole experts of a model "
            "that holds them all in the slot stacks of `params['layers']`, "
            "counted by the arena's census rider; drop serving.moe)",
            ValueError)
    if E <= 1:
        raise ValueError(
            f"serving.moe needs an MoE model layout (moe_experts > 1); "
            f"this config has moe_experts={E} — pick an MoE arch "
            f"(mixtral / qwen2_moe) or drop serving.moe")
    slots = moe.slots_per_layer
    if slots and not (model_config.moe_top_k <= slots <= E):
        raise ValueError(
            f"serving.moe.slots_per_layer={slots} is outside "
            f"[top_k={model_config.moe_top_k}, E={E}] for this model "
            f"layout (0 = full residency)")


def build_engine(arch: str, size: Optional[str] = None, params=None,
                 engine_config: Optional[RaggedInferenceEngineConfig] = None,
                 serving_config=None, **cfg_kw) -> InferenceEngineV2:
    """Reference: build_hf_engine — arch string in, serving engine out.
    `serving_config`: a ServingConfig whose JSON-wired TP fields
    (tensor_parallel_size / tp_collectives) are folded onto the engine
    config via `apply_serving_tp`."""
    from ...models import Transformer
    cfg = arch_config(arch, size, **cfg_kw)
    model = Transformer(cfg)
    if serving_config is not None:
        engine_config = apply_serving_tp(engine_config, serving_config)
        check_serving_moe(cfg, serving_config)
    return InferenceEngineV2(model, params=params, config=engine_config)


def build_hf_engine(model, engine_config: Optional[
        RaggedInferenceEngineConfig] = None, dtype=None,
        serving_config=None, **cfg_kw) -> InferenceEngineV2:
    """HF torch model (or name/path) -> ragged serving engine with converted
    weights (reference: engine_factory.build_hf_engine — the checkpoint-path
    entry; weight map in models/hf_loader.py).  `serving_config` as in
    `build_engine`."""
    from ...models.hf_loader import load_hf_model
    bundle, params = load_hf_model(model, dtype=dtype, **cfg_kw)
    if serving_config is not None:
        engine_config = apply_serving_tp(engine_config, serving_config)
        check_serving_moe(bundle.cfg, serving_config)
    return InferenceEngineV2(bundle, params=params, config=engine_config)
