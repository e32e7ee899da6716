"""Python side of the C inference API (csrc/capi.cc).

Reference: paddle/legacy/capi — a pure-C ABI (paddle_matrix,
paddle_gradient_machine_*) for embedding inference into C/C++ apps.  The
TPU build's engine lives in Python/JAX, so the C shim embeds CPython and
drives this bridge: byte buffers + shapes cross the ABI, numpy/JAX stays
on this side."""

import numpy as np

from . import inference as _inference
from . import fluid

_DTYPES = {0: np.float32, 1: np.int64, 2: np.int32, 3: np.float64}
_DTYPE_CODES = {np.dtype(v): k for k, v in _DTYPES.items()}


class CApiPredictor(object):
    def __init__(self, model_dir):
        config = _inference.NativeConfig(model_dir=model_dir)
        self._predictor = _inference.create_paddle_predictor(config)
        self._inputs = {}
        self._outputs = []

    def set_input(self, name, data, shape, dtype_code):
        arr = np.frombuffer(data, dtype=_DTYPES[int(dtype_code)]).reshape(
            [int(s) for s in shape])
        self._inputs[name] = arr

    def run(self):
        outs = self._predictor.run(self._inputs)
        self._outputs = [
            np.ascontiguousarray(np.asarray(t.data)) for t in outs
        ]
        self._inputs = {}
        return len(self._outputs)

    def output_count(self):
        return len(self._outputs)

    def get_output(self, i):
        arr = self._outputs[int(i)]
        code = _DTYPE_CODES.get(arr.dtype)
        if code is None:
            arr = arr.astype(np.float32)
            code = 0
        return (arr.tobytes(), list(arr.shape), code)


def create(model_dir):
    return CApiPredictor(model_dir)


class CApiTrainer(object):
    """C-side TRAINING loop (reference train/demo/demo_trainer.cc: load
    serialized startup/main ProgramDesc files, find the mean op's output
    as the loss, run the startup program, then step the train program).
    The program files are the framework.proto bytes the reference demo
    reads — full contract parity."""

    def __init__(self, model_dir):
        import os
        with open(os.path.join(model_dir, 'main_program'), 'rb') as f:
            self._main = fluid.Program.parse_from_string(f.read())
        with open(os.path.join(model_dir, 'startup_program'), 'rb') as f:
            startup = fluid.Program.parse_from_string(f.read())
        self._loss_name = None
        for op in self._main.global_block().ops:
            if op.type == 'mean':
                self._loss_name = op.output('Out')[0]
                break
        if self._loss_name is None:
            raise RuntimeError('loss (mean op) not found in main program')
        place = fluid.default_place()
        self._scope = fluid.core.Scope()
        self._exe = fluid.Executor(place)
        with fluid.scope_guard(self._scope):
            self._exe.run(startup)
        self._inputs = {}

    def set_input(self, name, data, shape, dtype_code):
        arr = np.frombuffer(data, dtype=_DTYPES[int(dtype_code)]).reshape(
            [int(s) for s in shape])
        self._inputs[name] = arr

    def step(self):
        """One training step; returns the scalar loss."""
        with fluid.scope_guard(self._scope):
            v, = self._exe.run(self._main, feed=dict(self._inputs),
                               fetch_list=[self._loss_name])
        return float(np.asarray(v).flatten()[0])


def create_trainer(model_dir):
    return CApiTrainer(model_dir)
