"""paddle_tpu.fluid — the TPU-native Fluid-compatible frontend.

Re-designed from the reference python/paddle/fluid/__init__.py: the same
program-building API, but every program block compiles to XLA and runs on
TPU (fluid.TPUPlace()) instead of per-op CPU/CUDA kernels.
"""

from . import flags
from .flags import FLAGS
# env bootstrap first, so flags govern everything imported below
# (reference __init__.py:121-141 init_gflags tryfromenv)
flags.try_from_env(flags.TRYFROMENV)
from . import core
from .core import (CPUPlace, TPUPlace, CUDAPlace, CUDAPinnedPlace, LoDTensor,
                   LoDTensorArray, Scope, is_compiled_with_tpu,
                   is_compiled_with_cuda, default_place)
from . import framework
from .framework import (Program, Operator, Variable, Parameter,
                        default_main_program, default_startup_program,
                        program_guard, name_scope, get_var)
from . import executor
from .executor import Executor, global_scope, scope_guard, fetch_var
from . import parallel_executor
from .parallel_executor import ParallelExecutor, ExecutionStrategy, \
    BuildStrategy
from . import dataflow
from .dataflow import FeedPipeline
from . import trace
from . import initializer
from . import layers
from . import nets
from . import contrib
from . import optimizer
from . import backward
from .backward import append_backward, calc_gradient, gradients
from . import regularizer
from . import clip
from .clip import (ErrorClipByValue, GradientClipByValue, GradientClipByNorm,
                   GradientClipByGlobalNorm)
from .param_attr import ParamAttr, WeightNormParamAttr
from . import unique_name
from .data_feeder import DataFeeder
from . import io
from .io import (save_vars, save_params, save_persistables, load_vars,
                 load_params, load_persistables, save_inference_model,
                 load_inference_model, get_inference_program)
from . import metrics
from . import profiler
from . import lod_tensor
from .lod_tensor import create_lod_tensor, create_random_int_lodtensor
from . import recordio_writer
from . import transpiler
from .transpiler import DistributeTranspiler, DistributeTranspilerConfig, \
    memory_optimize, release_memory, InferenceTranspiler, \
    Float16Transpiler
from . import evaluator
from . import concurrency
from . import amp
from .amp import amp_guard, enable_amp
from .concurrency import (Go, make_channel, channel_send, channel_recv,
                          channel_close, Select)
from . import debugger
from .trainer import (Trainer, BeginEpochEvent, EndEpochEvent,
                      BeginStepEvent, EndStepEvent, CheckpointConfig)
from .inferencer import Inferencer

Tensor = LoDTensor

__all__ = framework.__all__ + executor.__all__ + [
    'io', 'initializer', 'layers', 'nets', 'optimizer', 'backward',
    'regularizer', 'LoDTensor', 'CPUPlace', 'TPUPlace', 'CUDAPlace',
    'CUDAPinnedPlace', 'default_place', 'Tensor', 'ParamAttr', 'WeightNormParamAttr',
    'DataFeeder', 'clip', 'profiler', 'unique_name', 'flags', 'FLAGS',
    'dataflow', 'FeedPipeline', 'trace',
]
