"""Plain reference of ``paddle_tpu.models.nemotron_h``.  ONE source: the
file the benchmark keeps, ``chipbench/reference/nemotron_h_ref.py`` (float32
``jax.numpy``; it imports nothing of the program), loaded here under this
module's name so that the library's tests and the benchmark's comparison
read the same equations."""

import importlib.util
import os
import sys

_SOURCE = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, os.pardir,
    os.pardir, 'chipbench', 'reference', 'nemotron_h_ref.py'))
_spec = importlib.util.spec_from_file_location(__name__, _SOURCE)
_module = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_module)
sys.modules[__name__] = _module
