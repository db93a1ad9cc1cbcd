"""Text models (beyond the reference's zoo, which is vision only).

    net = text.get_model("deepseek_v3", **published_config)
"""
from .deepseek_v3 import DeepseekV3Block, DeepseekV3ForCausalLM, deepseek_v3

_models = {"deepseek_v3": deepseek_v3}


def get_model(name, /, **kwargs):
    """Create a text model by ``model_type`` (the published config's)."""
    name = name.lower()
    if name not in _models:
        raise ValueError(
            f"Model {name!r} is not supported. Available: {sorted(_models)}")
    return _models[name](**kwargs)


__all__ = ["get_model", "deepseek_v3", "DeepseekV3Block",
           "DeepseekV3ForCausalLM"]
