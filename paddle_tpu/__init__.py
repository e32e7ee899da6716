"""paddle_tpu — a TPU-native deep learning framework with the capability
surface of PaddlePaddle Fluid (reference: /root/reference, Fluid 0.14).

Programs are built with the fluid API (``paddle_tpu.fluid``), compiled
whole-block to XLA, and executed on TPU.  See SURVEY.md for the layer map.
"""

__version__ = '0.1.0'

from . import fluid  # noqa: F401
from . import reader  # noqa: F401
from . import dataset  # noqa: F401
from . import parallel  # noqa: F401
from . import inference  # noqa: F401
from . import serving  # noqa: F401  (after fluid: it builds on it)


def batch(reader_creator, batch_size, drop_last=False):
    """Group a sample reader into a batched reader
    (reference: python/paddle/batch.py)."""

    def batch_reader():
        r = reader_creator()
        b = []
        for instance in r:
            b.append(instance)
            if len(b) == batch_size:
                yield b
                b = []
        if b and not drop_last:
            yield b

    return batch_reader


# imported after `batch` exists: v2 re-exports it
from . import v2  # noqa: F401,E402
from . import distributed  # noqa: F401,E402

__all__ = ['fluid', 'reader', 'dataset', 'parallel', 'inference',
           'serving', 'batch', 'v2', 'distributed']
