"""Operator registry and all built-in operator definitions.

Importing this package registers every op (mirrors the reference's static
registration at library load; SURVEY.md Appendix A is the catalog).
"""
from . import registry
from .registry import OpDef, register, get, list_ops, invoke, FrozenAttrs

# register all built-in op families
from . import attention     # noqa: F401  (kernel library, no op names)
from . import math_ops      # noqa: F401
from . import matrix_ops    # noqa: F401
from . import nn_ops        # noqa: F401
from . import random_ops    # noqa: F401
from . import optimizer_ops # noqa: F401
from . import rnn_ops       # noqa: F401
from . import contrib_ops   # noqa: F401

# attach the dmlc::Parameter-style per-op parameter declarations
from . import op_params     # noqa: E402
op_params.attach_specs(get)

__all__ = ["OpDef", "register", "get", "list_ops", "invoke", "FrozenAttrs",
           "registry"]
