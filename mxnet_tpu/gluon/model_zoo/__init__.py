"""Model zoo (ref: python/mxnet/gluon/model_zoo/__init__.py)."""
from . import text  # noqa: F401
from . import vision  # noqa: F401


def get_model(name, **kwargs):
    """A zoo model by name: the text models, else the vision nets."""
    builder = text._models.get(name.lower())
    return builder(**kwargs) if builder else vision.get_model(name, **kwargs)
