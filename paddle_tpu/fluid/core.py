"""Runtime core for the TPU-native fluid framework.

This module plays the role of the reference's pybind ``core`` extension
(``paddle/fluid/pybind/pybind.cc``): places, dtype enums, Scope/Variable,
LoDTensor, and the bridge to the device runtime.  Here the device runtime is
JAX/XLA rather than CUDA: a ``Place`` resolves to a ``jax.Device``, and tensors
are ``jax.Array``s (host side: numpy).

Reference parity notes:
  - Place variant: paddle/fluid/platform/place.h:78
  - LoDTensor:     paddle/fluid/framework/lod_tensor.h:110
  - Scope:         paddle/fluid/framework/scope.h:39
"""

import threading

import numpy as np

__all__ = [
    'CPUPlace', 'TPUPlace', 'CUDAPlace', 'Place', 'VarDesc', 'LoDTensor',
    'Scope', 'is_compiled_with_tpu', 'is_compiled_with_cuda',
    'get_tpu_device_count', 'EOFException', 'NoAcceleratorError',
    'default_place', 'device_info',
]


class EOFException(Exception):
    """Raised by Executor.run when a program's reader is exhausted
    (reference: the C++ EOFException thrown by reader ops)."""
    pass


class NoAcceleratorError(RuntimeError):
    """A TPUPlace was asked for its device and JAX has no accelerator."""


_jax = None
_jax_lock = threading.Lock()


def lazy_jax():
    """Import jax lazily so that pure graph construction needs no device."""
    global _jax
    if _jax is None:
        with _jax_lock:
            if _jax is None:
                import jax
                _jax = jax
    return _jax


# ----------------------------------------------------------------------------
# Places (paddle/fluid/platform/place.h)
# ----------------------------------------------------------------------------
class Place(object):
    """Base class of device placements."""

    def __eq__(self, other):
        return type(self) is type(other) and self.__dict__ == other.__dict__

    def __hash__(self):
        return hash((type(self).__name__, tuple(sorted(self.__dict__.items()))))


class CPUPlace(Place):
    def __repr__(self):
        return 'CPUPlace'

    def jax_device(self):
        jax = lazy_jax()
        return jax.devices('cpu')[0]


class TPUPlace(Place):
    """First-class TPU placement — the north-star addition vs the reference
    (which only has CPUPlace/CUDAPlace, place.h:36)."""

    def __init__(self, device_id=0):
        self.device_id = int(device_id)

    def __repr__(self):
        return 'TPUPlace(%d)' % self.device_id

    def jax_device(self):
        devs = accelerator_devices()
        if not devs:
            raise NoAcceleratorError(
                '%r: JAX found no accelerator, only platform(s) %s — '
                'use CPUPlace() (or fluid.default_place()) to run on '
                'the host' % (self, sorted(
                    {d.platform for d in lazy_jax().devices()})))
        return devs[self.device_id % len(devs)]


class CUDAPlace(TPUPlace):
    """Compatibility alias: reference models built for CUDAPlace run on the
    default accelerator unchanged."""

    def __repr__(self):
        return 'CUDAPlace(%d)' % self.device_id


class CUDAPinnedPlace(CPUPlace):
    def __repr__(self):
        return 'CUDAPinnedPlace'


def accelerator_devices():
    """JAX's non-CPU devices (empty on a CPU-only backend).  A backend
    that fails to initialize raises here: a held chip or a broken
    libtpu must never read as "no TPU"."""
    return [d for d in lazy_jax().devices() if d.platform != 'cpu']


def is_compiled_with_tpu():
    return bool(accelerator_devices())


def is_compiled_with_cuda():
    # No CUDA in this build, ever (BASELINE.json north star).
    return False


def get_tpu_device_count():
    return len(accelerator_devices())


def default_place(device_id=0):
    """THE place-choosing function: TPUPlace(device_id) where JAX has
    an accelerator, CPUPlace() where it has none.  Every default
    (engines, registries, readers, tools) resolves through here, and
    what it chose is reported by ``device_info`` in
    ``engine.metrics()['device']`` / ``registry.status()['device']`` —
    a server on the wrong device is visible, never silent."""
    return TPUPlace(device_id) if is_compiled_with_tpu() else CPUPlace()


def place_of(device):
    """The Place naming a jax.Device (mesh executors derive theirs from
    the mesh's own devices rather than assuming an accelerator)."""
    if device.platform == 'cpu':
        return CPUPlace()
    return TPUPlace(accelerator_devices().index(device))


def device_info(devices):
    """{'platform', 'device_kind', 'count'} of a device list, as JAX
    reports it — the block every metrics/bench record carries."""
    devices = list(devices)
    return {'platform': devices[0].platform,
            'device_kind': devices[0].device_kind,
            'count': len(devices)}


# ----------------------------------------------------------------------------
# Dtype enum (paddle/fluid/framework/framework.proto:97-131 VarType)
# ----------------------------------------------------------------------------
class VarDesc(object):
    class VarType(object):
        # data types
        BOOL = 0
        INT16 = 1
        INT32 = 2
        INT64 = 3
        FP16 = 4
        FP32 = 5
        FP64 = 6
        UINT8 = 20
        INT8 = 21
        BF16 = 22
        # var kinds
        LOD_TENSOR = 7
        SELECTED_ROWS = 8
        FEED_MINIBATCH = 9
        FETCH_LIST = 10
        STEP_SCOPES = 11
        LOD_RANK_TABLE = 12
        LOD_TENSOR_ARRAY = 13
        PLACE_LIST = 14
        READER = 15
        CHANNEL = 16
        RAW = 17
        TUPLE = 18


_DTYPE_TO_NP = {
    VarDesc.VarType.BOOL: np.bool_,
    VarDesc.VarType.INT16: np.int16,
    VarDesc.VarType.INT32: np.int32,
    VarDesc.VarType.INT64: np.int64,
    VarDesc.VarType.FP16: np.float16,
    VarDesc.VarType.FP32: np.float32,
    VarDesc.VarType.FP64: np.float64,
    VarDesc.VarType.UINT8: np.uint8,
    VarDesc.VarType.INT8: np.int8,
}
_NP_TO_DTYPE = {np.dtype(v): k for k, v in _DTYPE_TO_NP.items()}


def convert_np_dtype_to_dtype_(np_dtype):
    """numpy dtype (or string) -> VarType enum.  bfloat16 handled via ml_dtypes."""
    if isinstance(np_dtype, int):
        return np_dtype
    if np_dtype in ('bfloat16', 'bf16'):
        return VarDesc.VarType.BF16
    dtype = np.dtype(np_dtype)
    if dtype in _NP_TO_DTYPE:
        return _NP_TO_DTYPE[dtype]
    try:
        import ml_dtypes
        if dtype == np.dtype(ml_dtypes.bfloat16):
            return VarDesc.VarType.BF16
    except ImportError:
        pass
    raise ValueError('unsupported numpy dtype %s' % np_dtype)


def convert_dtype_to_np(dtype):
    """VarType enum (or string/np dtype) -> numpy dtype."""
    if dtype == VarDesc.VarType.BF16:
        import ml_dtypes
        return np.dtype(ml_dtypes.bfloat16)
    if isinstance(dtype, int):
        return np.dtype(_DTYPE_TO_NP[dtype])
    if dtype in ('bfloat16', 'bf16'):
        import ml_dtypes
        return np.dtype(ml_dtypes.bfloat16)
    return np.dtype(dtype)


class PaddedSequence(object):
    """A LoD feed already lowered to device: padded [B, T, ...] data plus
    per-row lengths.  Produced by the double-buffer reader's prefetch
    thread (reference create_double_buffer_reader_op.cc moved batches to
    device ahead of the compute stream); consumed by
    executor.prepare_feed_arrays.  ``rows`` carries the OUTER level of a
    nested (2-level LoD) batch — sub-sequences per sequence — or None."""

    __slots__ = ('data', 'lengths', 'rows')

    def __init__(self, data, lengths, rows=None):
        self.data = data
        self.lengths = lengths
        self.rows = rows


# ----------------------------------------------------------------------------
# LoDTensor (paddle/fluid/framework/lod_tensor.h)
# ----------------------------------------------------------------------------
class LoDTensor(object):
    """A tensor with optional level-of-detail (nested variable-length
    sequence) offset metadata.

    Mirrors the reference's recursive-sequence-length semantics
    (framework/lod_tensor.h:58-110): ``lod`` is a list of offset vectors, one
    per nesting level, each starting at 0 and monotonically increasing; the
    last level's final offset equals dim 0 of the data.
    """

    def __init__(self, array=None, lod=None):
        self._array = None if array is None else np.asarray(array)
        self._lod = [list(l) for l in (lod or [])]

    def set(self, array, place=None):
        self._array = np.asarray(array)

    def set_lod(self, lod):
        self._lod = [list(l) for l in lod]

    def lod(self):
        return [list(l) for l in self._lod]

    def set_recursive_sequence_lengths(self, lengths):
        self._lod = []
        for level in lengths:
            offsets = [0]
            for n in level:
                offsets.append(offsets[-1] + n)
            self._lod.append(offsets)

    def recursive_sequence_lengths(self):
        return [[l[i + 1] - l[i] for i in range(len(l) - 1)]
                for l in self._lod]

    def has_valid_recursive_sequence_lengths(self):
        if not self._lod:
            return True
        for i, level in enumerate(self._lod):
            if not level or level[0] != 0:
                return False
            if any(level[j] > level[j + 1] for j in range(len(level) - 1)):
                return False
        if self._array is not None and self._lod:
            return self._lod[-1][-1] == self._array.shape[0]
        return True

    def shape(self):
        return list(self._array.shape) if self._array is not None else []

    def numpy(self):
        return np.asarray(self._array)

    def __array__(self, dtype=None):
        a = self.numpy()
        return a.astype(dtype) if dtype is not None else a

    def __repr__(self):
        return 'LoDTensor(shape=%s, lod=%s)' % (self.shape(), self._lod)


class LoDTensorArray(list):
    """Ordered list of LoDTensors — the host-side mirror of the
    LOD_TENSOR_ARRAY var type (reference pybind LoDTensorArray surface:
    append + indexing; produced/consumed by the tensor-array ops)."""

    def append(self, tensor):
        if not isinstance(tensor, LoDTensor):
            tensor = LoDTensor(np.asarray(tensor))
        list.append(self, tensor)


# ----------------------------------------------------------------------------
# SelectedRows (paddle/fluid/framework/selected_rows.h:32)
# ----------------------------------------------------------------------------
class SelectedRows(object):
    """Row-subset tensor {rows, value, height} — the host-side mirror of a
    sparse gradient (pybind.cc:233 surface: rows/set_rows/height/
    set_height/get_tensor)."""

    def __init__(self, rows=None, height=0):
        self._rows = list(rows) if rows is not None else []
        self._height = int(height)
        self._tensor = LoDTensor()

    def rows(self):
        return self._rows

    def set_rows(self, rows):
        self._rows = list(rows)

    def height(self):
        return self._height

    def set_height(self, height):
        self._height = int(height)

    def get_tensor(self):
        return self._tensor

    def to_dense(self):
        vals = self._tensor.numpy()
        out = np.zeros((self._height, ) + vals.shape[1:], vals.dtype)
        np.add.at(out, np.asarray(self._rows, np.int64), vals)
        return out

    def __repr__(self):
        return 'SelectedRows(n=%d, height=%d)' % (len(self._rows),
                                                  self._height)


# ----------------------------------------------------------------------------
# Scope (paddle/fluid/framework/scope.h:39)
# ----------------------------------------------------------------------------
class _ScopeVariable(object):
    """Runtime variable slot (framework/variable.h:26)."""

    __slots__ = ['_value']

    def __init__(self):
        self._value = None

    def get_tensor(self):
        if self._value is None:
            self._value = LoDTensor()
        return self._value

    def set_value(self, value):
        self._value = value

    def value(self):
        return self._value


class Scope(object):
    """Hierarchical name->Variable map with parent-chain lookup."""

    def __init__(self, parent=None):
        self._vars = {}
        self._parent = parent
        self._kids = []

    def var(self, name):
        v = self.find_var(name)
        if v is None:
            v = _ScopeVariable()
            self._vars[name] = v
        return v

    def find_var(self, name):
        if name in self._vars:
            return self._vars[name]
        if self._parent is not None:
            return self._parent.find_var(name)
        return None

    def new_scope(self):
        kid = Scope(self)
        self._kids.append(kid)
        return kid

    def drop_kids(self):
        self._kids = []

    def erase(self, names):
        for n in names:
            self._vars.pop(n, None)

    def local_var_names(self):
        return list(self._vars.keys())


_global_scope = Scope()


def global_scope():
    return _global_scope


# ----------------------------------------------------------------------------
# feed/fetch helpers (framework/feed_fetch_method.h parity)
# ----------------------------------------------------------------------------
def set_feed_variable(scope, value, name, idx=0):
    var = scope.var(name)
    if isinstance(value, LoDTensor):
        var.set_value(value)
    else:
        var.set_value(LoDTensor(np.asarray(value)))


def get_fetch_variable(scope, name, idx=0):
    var = scope.find_var(name)
    return None if var is None else var.value()
