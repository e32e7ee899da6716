"""ctypes bindings over libpaddle_tpu_rt.so (csrc/)."""

import ctypes
import os
import subprocess
import threading

_HERE = os.path.dirname(os.path.abspath(__file__))
_SO_PATH = os.path.join(_HERE, 'libpaddle_tpu_rt.so')
_CSRC = os.path.normpath(os.path.join(_HERE, '..', '..', 'csrc'))

_lib = None
_lib_lock = threading.Lock()
_build_tried = False


def _try_build():
    """Build the library from csrc/ (a clean checkout has no .so: it is
    git-ignored).  Tried once per process.  Every class below has a
    pure-Python mirror, so no caller needs the library and a failed
    build is not fatal — but it is never SILENT: the compiler's own
    words go out as a warning."""
    global _build_tried
    if _build_tried or not os.path.isdir(_CSRC):
        return False
    _build_tried = True
    try:
        subprocess.run(['make'], cwd=_CSRC, check=True,
                       capture_output=True, timeout=120)
    except (OSError, subprocess.SubprocessError) as e:
        import warnings
        warnings.warn(
            'paddle_tpu.runtime: building libpaddle_tpu_rt.so from csrc/ '
            'failed, using the pure-Python fallbacks (%s: %s)' % (
                e, (getattr(e, 'stderr', None) or b'').decode(
                    'utf-8', 'replace')[-800:]))
        return False
    return os.path.exists(_SO_PATH)


def _load():
    global _lib
    if _lib is not None:
        return _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        if not os.path.exists(_SO_PATH):
            if not _try_build():
                return None
        # an on-disk .so from an older source tree may predate newly added
        # symbols.  Probe BEFORE dlopening the stale image into the
        # process (a dlopen'd inode cannot be reloaded, and relinking it
        # in place would corrupt the live mapping): rebuild to a temp
        # path and atomically replace, then load once
        probe = ctypes.CDLL(_SO_PATH)
        if not hasattr(probe, 'ms_create'):
            del probe  # note: the stale image stays mapped (no dlclose)
            import tempfile
            import subprocess as sp
            tmp = None
            try:
                tmp = tempfile.NamedTemporaryFile(
                    dir=os.path.dirname(_SO_PATH), suffix='.so',
                    delete=False)
                tmp.close()
                sp.run(['make', '-B', 'OUT=%s' % tmp.name], cwd=_CSRC,
                       check=True, capture_output=True, timeout=120)
                os.chmod(tmp.name, 0o755)
                os.replace(tmp.name, _SO_PATH)
            except Exception:
                if tmp is not None:
                    try:
                        os.unlink(tmp.name)
                    except OSError:
                        pass
                return None
            lib = ctypes.CDLL(_SO_PATH)
            if not hasattr(lib, 'ms_create'):
                return None
        else:
            lib = probe
        # recordio
        lib.recordio_writer_create.restype = ctypes.c_void_p
        lib.recordio_writer_create.argtypes = [ctypes.c_char_p,
                                               ctypes.c_int,
                                               ctypes.c_uint64]
        lib.recordio_writer_write.restype = ctypes.c_int
        lib.recordio_writer_write.argtypes = [ctypes.c_void_p,
                                              ctypes.c_char_p,
                                              ctypes.c_uint64]
        lib.recordio_writer_close.restype = ctypes.c_int
        lib.recordio_writer_close.argtypes = [ctypes.c_void_p]
        lib.recordio_scanner_create.restype = ctypes.c_void_p
        lib.recordio_scanner_create.argtypes = [ctypes.c_char_p]
        lib.recordio_scanner_next.restype = ctypes.c_int
        lib.recordio_scanner_next.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_uint64)
        ]
        lib.recordio_scanner_destroy.argtypes = [ctypes.c_void_p]
        # blocking queue
        lib.bq_create.restype = ctypes.c_void_p
        lib.bq_create.argtypes = [ctypes.c_uint64]
        lib.bq_push.restype = ctypes.c_int
        lib.bq_push.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                ctypes.c_uint64]
        lib.bq_pop.restype = ctypes.c_int64
        lib.bq_pop.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                               ctypes.c_uint64]
        lib.bq_size.restype = ctypes.c_uint64
        lib.bq_size.argtypes = [ctypes.c_void_p]
        lib.bq_close.argtypes = [ctypes.c_void_p]
        lib.bq_reopen.argtypes = [ctypes.c_void_p]
        lib.bq_destroy.argtypes = [ctypes.c_void_p]
        # host pool
        lib.hp_in_use.restype = ctypes.c_uint64
        lib.hp_cached.restype = ctypes.c_uint64
        lib.hp_peak.restype = ctypes.c_uint64
        # CSP channels
        lib.ch_create.restype = ctypes.c_void_p
        lib.ch_create.argtypes = [ctypes.c_uint64]
        lib.ch_destroy.argtypes = [ctypes.c_void_p]
        lib.ch_size.restype = ctypes.c_uint64
        lib.ch_size.argtypes = [ctypes.c_void_p]
        lib.ch_is_closed.restype = ctypes.c_int
        lib.ch_is_closed.argtypes = [ctypes.c_void_p]
        lib.ch_close.argtypes = [ctypes.c_void_p]
        lib.ch_send.restype = ctypes.c_int
        lib.ch_send.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                ctypes.c_uint64]
        lib.ch_try_send.restype = ctypes.c_int
        lib.ch_try_send.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                    ctypes.c_uint64]
        lib.ch_recv.restype = ctypes.c_int
        lib.ch_recv.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                ctypes.c_uint64]
        lib.ch_try_recv.restype = ctypes.c_int
        lib.ch_try_recv.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                    ctypes.c_uint64]
        # EDL master task queue
        lib.ms_create.restype = ctypes.c_void_p
        lib.ms_create.argtypes = [ctypes.c_double, ctypes.c_int]
        lib.ms_destroy.argtypes = [ctypes.c_void_p]
        lib.ms_add_task.restype = ctypes.c_int64
        lib.ms_add_task.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                    ctypes.c_uint64]
        lib.ms_get_task.restype = ctypes.c_int
        lib.ms_get_task.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                    ctypes.c_uint64,
                                    ctypes.POINTER(ctypes.c_int64)]
        lib.ms_task_finished.restype = ctypes.c_int
        lib.ms_task_finished.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.ms_task_failed.restype = ctypes.c_int
        lib.ms_task_failed.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.ms_new_pass.argtypes = [ctypes.c_void_p]
        lib.ms_counts.argtypes = [ctypes.c_void_p,
                                  ctypes.POINTER(ctypes.c_int64)]
        lib.ms_snapshot.restype = ctypes.c_int64
        lib.ms_snapshot.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                    ctypes.c_uint64]
        lib.ms_restore.restype = ctypes.c_int
        lib.ms_restore.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                   ctypes.c_uint64]
        _lib = lib
        return _lib


def lib_available():
    return _load() is not None


class RecordIOWriter(object):
    """(reference recordio/writer.h)"""

    def __init__(self, path, compressor='zlib', max_chunk_bytes=1 << 20):
        lib = _load()
        self._lib = lib
        self._py_records = None
        self._path = path
        if lib is None:
            self._py_records = []
            self._compressor = compressor
            return
        self._h = lib.recordio_writer_create(
            path.encode(), 1 if compressor == 'zlib' else 0,
            max_chunk_bytes)
        if not self._h:
            raise IOError('cannot open %s for writing' % path)

    def write(self, data):
        if isinstance(data, str):
            data = data.encode()
        if self._py_records is not None:
            self._py_records.append(bytes(data))
            return
        if self._lib.recordio_writer_write(self._h, data, len(data)) != 0:
            raise IOError('recordio write failed')

    def close(self):
        if self._py_records is not None:
            _py_write_recordio(self._path, self._py_records,
                               self._compressor)
            return
        if self._lib.recordio_writer_close(self._h) != 0:
            raise IOError('recordio close/flush failed')
        self._h = None

    def __enter__(self):
        return self

    def __exit__(self, *a):
        self.close()


class RecordIOScanner(object):
    """(reference recordio/scanner.h)"""

    def __init__(self, path):
        lib = _load()
        self._lib = lib
        if lib is None:
            self._records = iter(_py_read_recordio(path))
            self._h = None
            return
        self._h = lib.recordio_scanner_create(path.encode())
        if not self._h:
            raise IOError('cannot open %s' % path)

    def __iter__(self):
        return self

    def __next__(self):
        if self._h is None:
            return next(self._records)
        buf = ctypes.c_char_p()
        length = ctypes.c_uint64()
        status = self._lib.recordio_scanner_next(self._h, ctypes.byref(buf),
                                                 ctypes.byref(length))
        if status == 0:
            raise StopIteration
        if status < 0:
            raise IOError('corrupt recordio chunk (crc/format)')
        return ctypes.string_at(buf, length.value)

    def close(self):
        if self._h is not None:
            self._lib.recordio_scanner_destroy(self._h)
            self._h = None


# --- pure-python fallback implementing the same on-disk format ---
def _py_write_recordio(path, records, compressor='zlib'):
    import struct
    import zlib as _z
    with open(path, 'wb') as f:
        raw = b''.join(
            struct.pack('<I', len(r)) + r for r in records)
        stored = _z.compress(raw, 1) if compressor == 'zlib' else raw
        comp = 1 if compressor == 'zlib' else 0
        f.write(
            struct.pack('<6I', 0x0c010cec, comp, len(records), len(raw),
                        len(stored), _z.crc32(stored) & 0xffffffff))
        f.write(stored)


def _py_read_recordio(path):
    import struct
    import zlib as _z
    out = []
    with open(path, 'rb') as f:
        while True:
            hdr = f.read(24)
            if len(hdr) < 24:
                break
            magic, comp, n, raw_len, stored_len, crc = struct.unpack(
                '<6I', hdr)
            if magic != 0x0c010cec:
                raise IOError('bad recordio magic')
            stored = f.read(stored_len)
            if _z.crc32(stored) & 0xffffffff != crc:
                raise IOError('recordio crc mismatch')
            raw = _z.decompress(stored) if comp else stored
            off = 0
            for _ in range(n):
                (l, ) = struct.unpack_from('<I', raw, off)
                off += 4
                out.append(raw[off:off + l])
                off += l
    return out


class NativeBlockingQueue(object):
    """Bounded producer/consumer byte queue
    (reference operators/reader/lod_tensor_blocking_queue.h)."""

    def __init__(self, capacity):
        lib = _load()
        self._lib = lib
        if lib is None:
            import queue as _q
            self._q = _q.Queue(maxsize=capacity)
            self._closed = False
            return
        self._q = None
        self._h = lib.bq_create(capacity)
        self._pop_cap = 1 << 16  # size hint only; buffers are per-call

    def push(self, data):
        if self._q is not None:
            import queue as _q
            # bounded wait so close() interrupts a blocked producer like
            # the native bq_push does
            while not self._closed:
                try:
                    self._q.put(bytes(data), timeout=0.05)
                    return True
                except _q.Full:
                    continue
            return False
        return self._lib.bq_push(self._h, bytes(data), len(data)) == 0

    def pop(self):
        """bytes, or None when closed + drained."""
        if self._q is not None:
            import queue as _q
            while True:
                try:
                    return self._q.get(timeout=0.05)
                except _q.Empty:
                    if self._closed:
                        return None
        cap = self._pop_cap
        while True:
            # per-call buffer: concurrent consumers never share bytes
            buf = ctypes.create_string_buffer(cap)
            n = self._lib.bq_pop(self._h, buf, cap)
            if n == -1:
                return None
            if n <= -2:  # buffer too small: grow and retry
                cap = -(n + 2)
                self._pop_cap = max(self._pop_cap, cap)
                continue
            return buf.raw[:n]

    def size(self):
        if self._q is not None:
            return self._q.qsize()
        return self._lib.bq_size(self._h)

    def close(self):
        if self._q is not None:
            self._closed = True
            return
        self._lib.bq_close(self._h)

    def reopen(self):
        if self._q is not None:
            import queue as _q
            self._q = _q.Queue(maxsize=self._q.maxsize)
            self._closed = False
            return
        self._lib.bq_reopen(self._h)

    def __del__(self):
        try:
            if self._q is None and self._lib is not None:
                self._lib.bq_destroy(self._h)
        except Exception:
            pass


def host_pool_stats():
    lib = _load()
    if lib is None:
        return {'in_use': 0, 'cached': 0, 'peak': 0, 'native': False}
    return {
        'in_use': int(lib.hp_in_use()),
        'cached': int(lib.hp_cached()),
        'peak': int(lib.hp_peak()),
        'native': True,
    }


class _PyChan(object):
    """Pure-Python mirror of csrc/channel.cc — same rendezvous, try and
    close-drain semantics, used when the native lib is unavailable."""

    def __init__(self, capacity):
        self.capacity = capacity
        self._cond = threading.Condition()
        self._items = []
        self._recv_waiters = 0
        self._sent_seq = 0
        self._taken_seq = 0
        self._closed = False

    def send(self, data):
        with self._cond:
            eff = self.capacity or 1
            self._cond.wait_for(
                lambda: self._closed or len(self._items) < eff)
            if self._closed:
                return False
            self._items.append(bytes(data))
            self._sent_seq += 1
            my_seq = self._sent_seq
            self._cond.notify_all()
            if self.capacity == 0:
                self._cond.wait_for(
                    lambda: self._closed or self._taken_seq >= my_seq)
                if self._taken_seq < my_seq:
                    # closed before pickup: withdraw the payload so a
                    # close-drain recv can't deliver a message already
                    # reported as failed (mirrors csrc/channel.cc)
                    if self._items and self._sent_seq == my_seq:
                        self._items.pop()
                        self._sent_seq -= 1
                    return False
            return True

    def try_send(self, data):
        with self._cond:
            if self._closed:
                return NativeChannel.CLOSED
            if self.capacity == 0:
                if self._recv_waiters <= 0 or self._items:
                    return NativeChannel.WOULD_BLOCK
            elif len(self._items) >= self.capacity:
                return NativeChannel.WOULD_BLOCK
            self._items.append(bytes(data))
            self._sent_seq += 1
            self._cond.notify_all()
            return True

    def _pop_locked(self):
        item = self._items.pop(0)
        self._taken_seq += 1
        self._cond.notify_all()
        return item

    def recv(self):
        with self._cond:
            self._recv_waiters += 1
            self._cond.notify_all()
            self._cond.wait_for(lambda: self._closed or self._items)
            self._recv_waiters -= 1
            if not self._items:
                return NativeChannel.CLOSED
            return self._pop_locked()

    def try_recv(self):
        with self._cond:
            if not self._items:
                return (NativeChannel.CLOSED
                        if self._closed else NativeChannel.WOULD_BLOCK)
            return self._pop_locked()

    def close(self):
        with self._cond:
            self._closed = True
            self._cond.notify_all()

    def size(self):
        with self._cond:
            return len(self._items)


class NativeChannel(object):
    """CSP channel over the native runtime (csrc/channel.cc), with a pure
    Python fallback (_PyChan) implementing the same semantics.
    capacity=0 means unbuffered rendezvous (reference framework/channel.h
    MakeChannel semantics)."""

    WOULD_BLOCK = object()
    CLOSED = object()

    def __init__(self, capacity=0):
        self.capacity = capacity
        lib = _load()
        self._lib = lib
        if lib is None:
            self._q = _PyChan(capacity)
            self._cap = 1 << 12
            return
        self._q = None
        self._h = lib.ch_create(capacity)
        self._cap = 1 << 12

    # payloads are opaque bytes; serialization lives in fluid.concurrency
    def send(self, data):
        """True on delivery, False if the channel is/was closed."""
        if self._q is not None:
            return self._q.send(data)
        return self._lib.ch_send(self._h, bytes(data), len(data)) == 0

    def try_send(self, data):
        if self._q is not None:
            return self._q.try_send(data)
        r = self._lib.ch_try_send(self._h, bytes(data), len(data))
        if r == 0:
            return True
        return self.CLOSED if r == -1 else self.WOULD_BLOCK

    def _recv_native(self, fn):
        cap = self._cap
        while True:
            buf = ctypes.create_string_buffer(cap)
            n = fn(self._h, buf, cap)
            if n == -1:
                return self.CLOSED
            if n == -2:
                return self.WOULD_BLOCK
            if n <= -3:
                cap = -(n + 3)
                self._cap = max(self._cap, cap)
                continue
            return buf.raw[:n]

    def recv(self):
        """bytes, or CLOSED after close+drain."""
        if self._q is not None:
            return self._q.recv()
        return self._recv_native(self._lib.ch_recv)

    def try_recv(self):
        if self._q is not None:
            return self._q.try_recv()
        return self._recv_native(self._lib.ch_try_recv)

    def close(self):
        if self._q is not None:
            self._q.close()
            return
        self._lib.ch_close(self._h)

    def size(self):
        if self._q is not None:
            return self._q.size()
        return int(self._lib.ch_size(self._h))

    def __del__(self):
        try:
            if self._q is None and self._lib is not None:
                self._lib.ch_destroy(self._h)
        except Exception:
            pass
