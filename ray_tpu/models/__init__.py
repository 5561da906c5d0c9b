"""Model zoo: TPU-first functional implementations (pure param pytrees +
jit-able apply functions; no framework lock-in, shardings are declared as
logical-axes pytrees consumed by ray_tpu.parallel)."""
import importlib

from ray_tpu.models.llama import (LlamaConfig, llama_configs, init_params,
                                  forward, loss_fn, param_logical_axes)
from ray_tpu.models.resnet import ResNetConfig, resnet_configs
from ray_tpu.models.serving import ServingSpec
from ray_tpu.models.vit import ViTConfig, vit_configs

# The serving seam: which module serves a config, keyed on the config's
# type (by name, so nothing is imported until asked for).  serve/llm.py
# asks here and names no model itself; what a serving module gives is
# models/serving.py's to say.  A new family: a module, a line here.
_SERVING = {"LlamaConfig": "ray_tpu.models.llama",
            "Lfm2MoeConfig": "ray_tpu.models.lfm2",
            "MlaMoeConfig": "ray_tpu.models.mla_moe",
            "SsmHybridConfig": "ray_tpu.models.ssm_hybrid",
            "Glm5NextConfig": "ray_tpu.models.glm5_next",
            "Dots3NoteConfig": "ray_tpu.models.dots3_note",
            "NemotronHConfig": "ray_tpu.models.nemotron_h",
            "MimoV2Config": "ray_tpu.models.mimo_v2",
            "Cohere2MoeConfig": "ray_tpu.models.cohere2_moe",
            "SolarOpen2Config": "ray_tpu.models.solar_open2",
            "MiniCpmSalaConfig": "ray_tpu.models.minicpm_sala"}


def serving_model(cfg):
    """The module that serves `cfg` through serve/llm.LLMEngine (a
    subclass of a served config type is served by its base's module)."""
    for klass in type(cfg).__mro__:
        if klass.__name__ in _SERVING:
            return importlib.import_module(_SERVING[klass.__name__])
    raise TypeError(f"no serving model for a {type(cfg).__name__}; the "
                    f"engine serves {sorted(_SERVING)}")


def named_config(name: str):
    """A preset config by name (the `LLMServer(model="debug")` form)."""
    for mod in _SERVING.values():
        presets = importlib.import_module(mod).serving_configs()
        if name in presets:
            return presets[name]
    raise KeyError(f"no preset model config {name!r}")


__all__ = ["LlamaConfig", "llama_configs", "init_params", "forward",
           "loss_fn", "param_logical_axes", "serving_model", "named_config",
           "ServingSpec",
           "ResNetConfig", "resnet_configs",
           "ViTConfig", "vit_configs"]
