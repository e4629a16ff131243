"""Model zoo (reference: python/mxnet/gluon/model_zoo/)."""
from . import language, vision  # noqa: F401
