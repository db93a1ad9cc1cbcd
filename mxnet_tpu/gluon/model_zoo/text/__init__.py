"""Text models (beyond the reference's zoo, which is vision only).

    net = text.get_model("deepseek_v3", **published_config)
    net = text.get_model("phi4flash", layers_kept=[0, 1, 16, 17, 18, 19],
                         **published_config)
"""
from .deepseek_v3 import DeepseekV3Block, DeepseekV3ForCausalLM, deepseek_v3
from .phi4flash import Phi4FlashBlock, Phi4FlashForCausalLM, phi4flash

_models = {"deepseek_v3": deepseek_v3, "phi4flash": phi4flash}


def get_model(name, /, **kwargs):
    """Create a text model by ``model_type`` (the published config's)."""
    name = name.lower()
    if name not in _models:
        raise ValueError(
            f"Model {name!r} is not supported. Available: {sorted(_models)}")
    return _models[name](**kwargs)


__all__ = ["get_model", "deepseek_v3", "DeepseekV3Block",
           "DeepseekV3ForCausalLM", "phi4flash", "Phi4FlashBlock",
           "Phi4FlashForCausalLM"]
