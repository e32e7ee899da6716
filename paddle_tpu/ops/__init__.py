"""XLA lowering registry for all operator families.

Importing this package registers every op lowering (the analog of the
reference's static REGISTER_OPERATOR blocks linking into one binary).
"""

from .registry import (register_lowering, register_grad_lowering,
                       get_lowering, has_lowering, LoweringContext, run_op)

from . import math_ops  # noqa: F401
from . import activation_ops  # noqa: F401
from . import tensor_ops  # noqa: F401
from . import loss_ops  # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import nn_ops  # noqa: F401
from . import metric_ops  # noqa: F401
from . import host_ops  # noqa: F401
from . import sequence_ops  # noqa: F401
from . import control_flow_ops  # noqa: F401
from . import beam_search_ops  # noqa: F401
from . import crf_ops  # noqa: F401
from . import attention_ops  # noqa: F401
from . import moe_ops  # noqa: F401
from . import ssm_ops  # noqa: F401
from . import detection_ops  # noqa: F401
from . import ctc_ops  # noqa: F401
from . import quantize_ops  # noqa: F401
from . import concurrency_ops  # noqa: F401
from . import misc_ops  # noqa: F401
from . import sparse  # noqa: F401

# wrap every optimizer lowering with SelectedRows (SparseRows) handling —
# the analog of the reference's separate SelectedRows optimizer kernels
for _opt in ('sgd', 'momentum', 'adam', 'adamax', 'adagrad',
             'decayed_adagrad', 'rmsprop', 'adadelta', 'ftrl'):
    sparse.sparsify_optimizer(_opt)
del _opt
