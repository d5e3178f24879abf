"""Text models of the Gluon model zoo, built by name as the vision nets
are: ``get_model("glm4_moe_lite", hidden_size=..., ...)``,
``get_model("nemotron_h", ...)``, ``get_model("olmo_hybrid", ...)``,
``get_model("mimo_v2", ...)``.
``config_keys(name)`` names the sizes a configuration file may hand that
model's builder; ``CONFIG_KEYS`` is ``glm4_moe_lite``'s, under the name it
had while that was the only model."""
from .glm_moe_lite import (CONFIG_KEYS, GLM4MoELite, LMLoss,  # noqa: F401
                           glm4_moe_lite)
from .nemotron_h import CONFIG_KEYS as _NEMOTRON_KEYS
from .nemotron_h import NemotronH, nemotron_h  # noqa: F401
from .olmo_hybrid import CONFIG_KEYS as _OLMO_KEYS
from .olmo_hybrid import OlmoHybrid, olmo_hybrid  # noqa: F401
from .mimo_v2 import CONFIG_KEYS as _MIMO_KEYS
from .mimo_v2 import MiMoV2, mimo_v2  # noqa: F401

_models = {"glm4_moe_lite": glm4_moe_lite, "nemotron_h": nemotron_h,
           "olmo_hybrid": olmo_hybrid, "mimo_v2": mimo_v2}
_config_keys = {"glm4_moe_lite": CONFIG_KEYS, "nemotron_h": _NEMOTRON_KEYS,
                "olmo_hybrid": _OLMO_KEYS, "mimo_v2": _MIMO_KEYS}


def config_keys(name):
    """The keyword arguments ``get_model(name, ...)`` takes from a
    configuration file."""
    return _config_keys[name]
