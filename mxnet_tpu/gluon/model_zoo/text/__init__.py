"""Text models (beyond the reference's zoo, which is vision only).

    net = text.get_model("deepseek_v3", **published_config)
    net = text.get_model("phi4flash", layers_kept=[0, 1, 16, 17, 18, 19],
                         **published_config)
    net = text.get_model("lfm2_moe", layers_kept=[0, 2, 3, 4, 5],
                         experts_held=(0, 8), **published_config)
"""
from .deepseek_v3 import DeepseekV3Block, DeepseekV3ForCausalLM, deepseek_v3
from .lfm2_moe import Lfm2MoeBlock, Lfm2MoeForCausalLM, lfm2_moe
from .phi4flash import Phi4FlashBlock, Phi4FlashForCausalLM, phi4flash

_models = {"deepseek_v3": deepseek_v3, "phi4flash": phi4flash,
           "lfm2_moe": lfm2_moe}


def get_model(name, /, **kwargs):
    """Create a text model by ``model_type`` (the published config's):
    ``deepseek_v3``, ``phi4flash`` or ``lfm2_moe``."""
    name = name.lower()
    if name not in _models:
        raise ValueError(
            f"Model {name!r} is not supported. Available: {sorted(_models)}")
    return _models[name](**kwargs)


__all__ = ["get_model", "deepseek_v3", "DeepseekV3Block",
           "DeepseekV3ForCausalLM", "phi4flash", "Phi4FlashBlock",
           "Phi4FlashForCausalLM", "lfm2_moe", "Lfm2MoeBlock",
           "Lfm2MoeForCausalLM"]
