"""Operator library: every module registers its ops on import.

Layout mirrors the reference's src/operator/ families (SURVEY.md §2.1):
elemwise/broadcast/reduce = tensor ops, nn = neural net ops, random_ops =
samplers, ordering = sort/topk, optimizer_ops = fused updates.
"""
from . import registry  # noqa: F401
from . import elemwise  # noqa: F401
from . import broadcast  # noqa: F401
from . import reduce  # noqa: F401
from . import matrix  # noqa: F401
from . import nn  # noqa: F401
from . import random_ops  # noqa: F401
from . import ordering  # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import rnn_op  # noqa: F401
from . import linalg  # noqa: F401
from . import pallas_kernels  # noqa: F401
from . import lm_ops  # noqa: F401
from . import quantization  # noqa: F401
from . import ctc  # noqa: F401
from . import contrib_ops  # noqa: F401
from . import vision_ops  # noqa: F401
from . import image_ops  # noqa: F401
from . import misc_ops  # noqa: F401
from . import rcnn_ops  # noqa: F401
from . import sparse_ops  # noqa: F401
from . import parity_ops  # noqa: F401
from .. import operator as _custom_host  # noqa: F401  (registers Custom)

from .registry import get_op, list_ops, register  # noqa: F401
