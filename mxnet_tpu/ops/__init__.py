"""Operator corpus: importing this package populates the registry."""
from .registry import Op, register, get_op, list_ops, OP_REGISTRY  # noqa: F401
from . import elemwise  # noqa: F401
from . import reduce  # noqa: F401
from . import matrix  # noqa: F401
from . import nn  # noqa: F401
from . import random_ops  # noqa: F401
from . import optim_ops  # noqa: F401
from . import contrib  # noqa: F401
from . import lm  # noqa: F401
from . import moe  # noqa: F401
from . import custom  # noqa: F401
from . import ssd  # noqa: F401
from . import rcnn  # noqa: F401
