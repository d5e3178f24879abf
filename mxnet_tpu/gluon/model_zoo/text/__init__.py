"""Text models of the Gluon model zoo, built by name as the vision nets
are: ``get_model("glm4_moe_lite", hidden_size=..., ...)``."""
from .glm_moe_lite import (CONFIG_KEYS, GLM4MoELite, LMLoss,  # noqa: F401
                           glm4_moe_lite)

_models = {"glm4_moe_lite": glm4_moe_lite}
