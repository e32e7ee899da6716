"""Deployment predictor API
(reference: paddle/fluid/inference/api/paddle_inference_api.h:67-177 —
PaddleTensor / PaddlePredictor / CreatePaddlePredictor).

The engine-agnostic ABI maps to Python: a Predictor owns a compiled
inference program + scope; ``run`` takes named inputs and returns outputs;
``clone`` shares weights with an independent compile cache (the reference's
Clone shares the scope, api_impl.cc:89).  The analysis/TensorRT engines'
role (graph fusion) is played by XLA itself.
"""

import numpy as np

from . import fluid
from .fluid import core

__all__ = ['PaddleTensor', 'NativeConfig', 'PaddlePredictor',
           'create_paddle_predictor']


class PaddleTensor(object):
    """(reference paddle_inference_api.h:67)"""

    def __init__(self, name=None, data=None, lod=None):
        self.name = name
        self.data = np.asarray(data) if data is not None else None
        self.lod = lod or []

    @property
    def shape(self):
        return list(self.data.shape) if self.data is not None else []


class NativeConfig(object):
    """(reference paddle_inference_api.h NativeConfig)"""

    def __init__(self,
                 model_dir=None,
                 prog_file=None,
                 param_file=None,
                 use_tpu=True,
                 device=0,
                 half_precision=None):
        self.model_dir = model_dir
        self.prog_file = prog_file
        self.param_file = param_file
        self.use_tpu = use_tpu
        self.device = device
        # 'bfloat16' (TPU-native) or 'float16': run the loaded program
        # through InferenceTranspiler (BN fold) + Float16Transpiler so
        # the graph computes in half precision while feeds/fetches stay
        # f32 (reference contrib/float16 flow)
        self.half_precision = half_precision


class PaddlePredictor(object):
    """(reference paddle_inference_api.h:90 / NativePaddlePredictor)"""

    def __init__(self, config, _shared_scope=None, _shared_model=None):
        self._config = config
        place = core.default_place(config.device) if config.use_tpu \
            else fluid.CPUPlace()
        self._exe = fluid.Executor(place)
        self._scope = _shared_scope or core.Scope()
        with fluid.scope_guard(self._scope):
            if _shared_model is not None:
                # clone: share the (possibly transpiled) program — the
                # BN-fold scope rewrite is not idempotent, so a clone
                # must never reload + re-transpile against the shared
                # scope
                (self._program, self._feed_names,
                 self._fetch_targets) = _shared_model
                return
            (self._program, self._feed_names,
             self._fetch_targets) = fluid.io.load_inference_model(
                 config.model_dir,
                 self._exe,
                 model_filename=config.prog_file,
                 params_filename=config.param_file)
            if getattr(config, 'half_precision', None):
                fluid.InferenceTranspiler().transpile(
                    self._program, scope=self._scope)
                fluid.Float16Transpiler().transpile(
                    self._program, scope=self._scope,
                    dtype=config.half_precision,
                    feeded_var_names=self._feed_names,
                    fetch_var_names=self._fetch_targets)

    @property
    def feed_names(self):
        return list(self._feed_names)

    @property
    def fetch_names(self):
        return [v.name for v in self._fetch_targets]

    def run(self, inputs, batch_size=-1):
        """inputs: list of PaddleTensor (positional per feed_names) or a
        {name: array} dict.  Returns a list of PaddleTensor."""
        if isinstance(inputs, dict):
            feed = dict(inputs)
        else:
            feed = {}
            for i, t in enumerate(inputs):
                name = t.name or self._feed_names[i]
                value = t.data
                if t.lod:
                    lt = core.LoDTensor(np.asarray(value))
                    lt.set_lod(t.lod)
                    value = lt
                feed[name] = value
        with fluid.scope_guard(self._scope):
            outs = self._exe.run(
                self._program, feed=feed, fetch_list=self._fetch_targets)
        return [
            PaddleTensor(name=v.name, data=o)
            for v, o in zip(self._fetch_targets, outs)
        ]

    def clone(self):
        """New predictor sharing weights (reference Run/Clone contract)."""
        return PaddlePredictor(
            self._config, _shared_scope=self._scope,
            _shared_model=(self._program, self._feed_names,
                           self._fetch_targets))


def create_paddle_predictor(config):
    """(reference CreatePaddlePredictor<ConfigT>, :177)"""
    return PaddlePredictor(config)
