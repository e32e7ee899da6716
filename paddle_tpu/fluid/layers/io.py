"""Input layers + reader pipeline (reference: python/paddle/fluid/layers/io.py).

py_reader (reference io.py:474) feeds minibatches through the native
blocking queue (csrc/blocking_queue.cc) from a background thread; the
executor pops each batch on the host and feeds the compiled XLA step.
double_buffer() adds a device-prefetch thread that pads and stages the
next batch on device while the current step runs (the reference's
create_double_buffer_reader_op.cc behavior).
"""

import contextlib
import pickle
import threading

import numpy as np

from .. import core
from .. import unique_name
from ..framework import default_main_program, default_startup_program, \
    Variable
from ..layer_helper import LayerHelper

__all__ = ['data', 'py_reader', 'read_file', 'batch', 'double_buffer',
           'open_recordio_file', 'open_files', 'shuffle', 'Preprocessor',
           'random_data_generator']

# reader var name -> _PyReaderFeeder.  Weak values: the strong reference
# lives on the reader Variable (program lifetime), so discarding a program
# frees its feeder/queue instead of leaking per py_reader() call.
import weakref

_READER_REGISTRY = weakref.WeakValueDictionary()


def get_reader_feeder(name):
    return _READER_REGISTRY.get(name)


def data(name,
         shape,
         append_batch_size=True,
         dtype='float32',
         lod_level=0,
         type=core.VarDesc.VarType.LOD_TENSOR,
         stop_gradient=True):
    """Declare a feed variable (reference layers/io.py:38).

    With ``append_batch_size`` the leading dim becomes -1 (batch)."""
    helper = LayerHelper('data', name=name)
    shape = list(shape)
    if append_batch_size:
        shape = [-1] + shape

    data_var = helper.create_global_variable(
        name=name,
        shape=shape,
        dtype=dtype,
        type=type,
        stop_gradient=stop_gradient,
        lod_level=lod_level,
        is_data=True,
        persistable=False)
    return data_var


class _PyReaderFeeder(object):
    """Producer side of a py_reader: background thread -> native queue."""

    def __init__(self, capacity, shapes, dtypes, lod_levels):
        from ...runtime import NativeBlockingQueue
        self.queue = NativeBlockingQueue(capacity)
        self.capacity = capacity
        self._closed = False
        self.shapes = shapes
        self.dtypes = dtypes
        self.lod_levels = lod_levels or [0] * len(shapes)
        self._provider = None
        self._thread = None
        self._exhausted = False
        self._error = None
        self._shuffle_buffer = 0
        # one batch handed back by a consumer that drained past a
        # shape-bucket boundary (reader-fed run_multi): delivered again
        # by the next pop of the SAME pass
        self._pushback = None
        # serializes pass-boundary state (generation, _exhausted,
        # _error) against a pop() racing reset()+start()
        self._gen_lock = threading.RLock()
        # set by double_buffer(): batches are padded + device_put on a
        # prefetch thread so transfer of batch N+1 overlaps step N
        self._double_buffer_place = None
        self._double_buffer_requested = False
        self._executor_place = None  # bound by the consuming Executor
        self._dev_queue = None
        self._convert_thread = None

    def _effective_db_place(self):
        """Prefetch target: explicit double_buffer place, else the place
        of the executor consuming THIS reader (bound per-feeder at pop
        time), else the place of whichever executor last ran (covers the
        batches converted before the first pop), else the build
        default."""
        if self._double_buffer_place is not None:
            return self._double_buffer_place
        if self._executor_place is not None:
            return self._executor_place
        if _last_executor_place is not None:
            return _last_executor_place
        return core.default_place()

    def decorate_paddle_reader(self, reader, places=None):
        """reader yields per-sample tuples; batches are assembled with
        DataFeeder semantics by the caller via paddle.batch-style readers
        that already yield lists of samples."""
        from ..data_feeder import DataToLoDTensorConverter

        def provider():
            for batch_rows in reader():
                converters = [
                    DataToLoDTensorConverter(None, lod, shape, dtype)
                    for lod, shape, dtype in zip(
                        self.lod_levels, self.shapes, self.dtypes)
                ]
                for row in batch_rows:
                    for conv, slot in zip(converters, row):
                        conv.feed(slot)
                yield tuple(c.done() for c in converters)

        self._provider = provider

    def decorate_tensor_provider(self, provider):
        """provider yields tuples of numpy arrays / LoDTensors directly."""

        def gen():
            for item in provider():
                yield tuple(item)

        self._provider = gen

    def start(self):
        if self._provider is None:
            raise RuntimeError('decorate a data source before start()')
        with self._gen_lock:
            self.queue.reopen()
            self._exhausted = False
            self._error = None
            # every pass is one generation: pop()/push_back() compare
            # against it so an aborted pass can neither hang on a dead
            # queue nor leak state into a restarted one
            self._generation = getattr(self, '_generation', 0) + 1

        provider = self._provider
        if self._shuffle_buffer > 1:
            provider = _shuffled_provider(provider, self._shuffle_buffer)

        if self._double_buffer_requested:
            self._start_zero_copy_pipeline(provider)
            return

        def work():
            try:
                for batch in provider():
                    # in-process framing only (never persisted to disk)
                    if not self.queue.push(pickle.dumps(batch, protocol=4)):
                        return
            except BaseException as e:  # surface to the consumer, not EOF
                self._error = e
            finally:
                self.queue.close()

        self._thread = threading.Thread(target=work, daemon=True)
        self._thread.start()

    # ---- double-buffer device prefetch (reference
    # operators/reader/create_double_buffer_reader_op.cc: a prefetch
    # thread keeps the next batch resident on device).  Batches move
    # producer -> converter as PYTHON REFERENCES, not serialized bytes:
    # at ResNet batch sizes the pickle+queue+unpickle round trip costs
    # more than the training step itself. ----
    def _convert_batch(self, item):
        import jax
        from ..executor import _lod_to_padded
        dev = self._effective_db_place().jax_device()
        out = []
        for slot in item:
            if isinstance(slot, core.LoDTensor) and slot.lod():
                padded, lengths = _lod_to_padded(slot)
                lod = slot.lod()
                rows = None
                if len(lod) >= 2:  # nested: keep the outer level too
                    outer = np.asarray(lod[0], np.int64)
                    rows = jax.device_put(
                        (outer[1:] - outer[:-1]).astype(np.int32), dev)
                out.append(
                    core.PaddedSequence(
                        jax.device_put(padded, dev),
                        jax.device_put(lengths, dev), rows))
            else:
                arr = slot.numpy() if isinstance(slot, core.LoDTensor) \
                    else np.asarray(slot)
                out.append(jax.device_put(arr, dev))
        return tuple(out)

    def _start_zero_copy_pipeline(self, provider):
        import queue as _queue
        end = object()
        # locals captured by the closures: a thread from a PREVIOUS
        # generation that outlives reset() keeps touching ITS queues and
        # can never corrupt the next epoch's state
        ref_q = _queue.Queue(maxsize=max(2, min(int(self.capacity), 8)))
        dev_q = _queue.Queue(maxsize=2)
        with self._gen_lock:
            # the pass state flips atomically w.r.t. a pop() snapshot:
            # a consumer never sees the new generation with the OLD (or
            # a missing) device queue and route/poll the wrong stream
            self._closed = False
            gen = self._generation  # bumped by start(), the only caller
            self._dev_queue = dev_q

        def _live():
            return not self._closed and self._generation == gen

        def _put(q, item):
            while _live():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except _queue.Full:
                    continue
            return False

        def _record_error(e):
            if _live():
                self._error = e

        def produce():
            try:
                for batch in provider():
                    if not _put(ref_q, tuple(batch)):
                        return
            except BaseException as e:
                _record_error(e)
            finally:
                _put(ref_q, end)

        def convert():
            try:
                while _live():
                    try:
                        item = ref_q.get(timeout=0.1)
                    except _queue.Empty:
                        continue
                    if item is end:
                        _put(dev_q, None)
                        return
                    _put(dev_q, self._convert_batch(item))
            except BaseException as e:
                _record_error(e)
                _put(dev_q, None)

        with self._gen_lock:
            self._thread = threading.Thread(target=produce, daemon=True)
            self._convert_thread = threading.Thread(target=convert,
                                                    daemon=True)
        self._thread.start()
        self._convert_thread.start()

    def _eof_or_raise(self):
        """End of stream: surface a provider error once, then signal EOF
        on this and every later pop until reset()."""
        self._exhausted = True
        if self._error is not None:
            err, self._error = self._error, None
            raise RuntimeError(
                'py_reader data provider failed: %r' % (err, )) from err
        return None

    def push_back(self, batch):
        """Hand ONE popped batch back to the stream: the next pop of
        this pass delivers it again (reader-fed run_multi drains up to
        a shape-bucket boundary and returns the first differing batch
        here instead of dropping it).  Generation-stamped: a batch
        whose pass was reset() between the pop and the push-back is
        DROPPED, never leaked into a restarted pass's stream."""
        with self._gen_lock:
            if getattr(self, '_generation', 0) == \
                    getattr(self, '_last_pop_gen', 0):
                self._pushback = batch

    def pop(self):
        with self._gen_lock:
            # one consistent pass snapshot: reset()/start() mutate the
            # pushback, queue, flags and generation under this lock, so
            # the held batch we deliver, the queue we poll below and
            # the generation we compare against can never straddle a
            # pass boundary.  Routing keys on the device queue ALONE
            # (its presence is the zero-copy pass marker) — no second
            # field to read consistently.
            if self._pushback is not None:
                batch, self._pushback = self._pushback, None
                return batch
            dev_q = self._dev_queue
            gen = self._last_pop_gen = getattr(self, '_generation', 0)
        if dev_q is not None:
            if self._exhausted:  # the sentinel is delivered only once
                return None
            import queue as _queue_mod
            while True:
                try:
                    batch = dev_q.get(timeout=0.1)
                    break
                except _queue_mod.Empty:
                    if self._closed or self._generation != gen:
                        # reset() raced this pop: the generation's
                        # workers exit WITHOUT delivering the sentinel,
                        # so a bare get() would hang forever.  Under
                        # the gen lock, signal EOF (or the provider's
                        # error) for THIS pass — if reset()+start()
                        # already began the next generation, report
                        # plain EOF without poisoning its state.
                        with self._gen_lock:
                            if getattr(self, '_generation', 0) != gen:
                                return None
                            return self._eof_or_raise()
            if batch is None:
                return self._eof_or_raise()
            return batch
        data = self.queue.pop()
        if data is None:
            return self._eof_or_raise()
        return pickle.loads(data)

    def reset(self):
        with self._gen_lock:
            self._pushback = None  # a held batch dies with its pass
            self.queue.close()
            self._closed = True
        if self._convert_thread is not None:
            self._convert_thread.join(timeout=5)
            self._convert_thread = None
            self._dev_queue = None
        if self._thread is not None:
            self._thread.join(timeout=5)
        self._thread = None


def py_reader(capacity,
              shapes,
              dtypes,
              lod_levels=None,
              name=None,
              use_double_buffer=True):
    """Create a feedable reader (reference layers/io.py:474).

    Returns a reader Variable with ``decorate_paddle_reader`` /
    ``decorate_tensor_provider`` / ``start`` / ``reset`` methods; pair with
    :func:`read_file` to get the data variables."""
    helper = LayerHelper('py_reader', name=name)
    reader = helper.create_global_variable(
        name=unique_name.generate('create_py_reader'),
        type=core.VarDesc.VarType.READER,
        persistable=True)
    feeder = _PyReaderFeeder(capacity, list(shapes), list(dtypes),
                             lod_levels)
    reader._feeder = feeder  # strong ref: feeder lives as long as the var
    _READER_REGISTRY[reader.name] = feeder
    reader._shapes = list(shapes)
    reader._dtypes = list(dtypes)
    reader._lod_levels = lod_levels or [0] * len(shapes)
    reader.decorate_paddle_reader = feeder.decorate_paddle_reader
    reader.decorate_tensor_provider = feeder.decorate_tensor_provider
    reader.start = feeder.start
    reader.reset = feeder.reset
    return reader


def read_file(reader):
    """Emit the read op producing this reader's data vars
    (reference layers/io.py read_file)."""
    helper = LayerHelper('read_file')
    out = []
    for shape, dtype, lod in zip(reader._shapes, reader._dtypes,
                                 reader._lod_levels):
        v = helper.create_variable_for_type_inference(
            dtype, stop_gradient=True)
        v.shape = tuple(shape)
        v.lod_level = lod
        v.is_data = True
        out.append(v)
    helper.append_op(
        type='read',
        inputs={'Reader': [reader]},
        outputs={'Out': out})
    if len(out) == 1:
        return out[0]
    return out


def batch(reader, batch_size):
    """Kept for reader-pipeline API parity; batching happens host-side."""
    return reader


def note_executor_place(place):
    """Called by Executor.run: remembers the live execution place so
    double_buffer(place=None) prefetches to the device actually running
    the program (a CPU-place Executor on a TPU build must NOT get its
    batches staged to the TPU)."""
    global _last_executor_place
    _last_executor_place = place


_last_executor_place = None


def double_buffer(reader, place=None, name=None):
    """Stage batches on device one step ahead (reference layers/io.py:891,
    create_double_buffer_reader_op.cc): a prefetch thread pads LoD slots
    and ``device_put``s every slot, so the host->device transfer of batch
    N+1 overlaps device execution of step N.  Takes effect at the
    reader's next ``start()``.  With ``place=None`` the target device is
    resolved lazily per batch from the executor that last ran (falling
    back to the build default before any run); a mis-staged early batch
    is re-put by the executor's feed conversion, so this is a perf
    default, never a correctness choice."""
    feeder = get_reader_feeder(reader.name)
    if feeder is not None:
        feeder._double_buffer_place = place
        feeder._double_buffer_requested = True
    return reader


def _shuffled_provider(provider, buffer_size):
    import random

    def gen():
        buf = []
        for item in provider():
            buf.append(item)
            if len(buf) >= buffer_size:
                random.shuffle(buf)
                for b in buf:
                    yield b
                buf = []
        random.shuffle(buf)
        for b in buf:
            yield b

    return gen


def shuffle(reader, buffer_size):
    """Shuffle a py_reader's batches through a host-side reservoir
    (reference layers/io.py shuffle created a shuffle-reader op)."""
    feeder = get_reader_feeder(reader.name)
    if feeder is not None:
        feeder._shuffle_buffer = int(buffer_size)
    return reader


def _decode_npz_record(rec):
    """recordio records are npz-framed numpy tuples (data-only, no code
    execution) — shared by every recordio reader layer."""
    import io as _io
    with np.load(_io.BytesIO(rec), allow_pickle=False) as z:
        return tuple(z['arr_%d' % i] for i in range(len(z.files)))


def _scan_file(filename):
    from ...runtime import RecordIOScanner
    scanner = RecordIOScanner(filename)
    try:
        for rec in scanner:
            yield _decode_npz_record(rec)
    finally:
        scanner.close()


def open_recordio_file(filename,
                       shapes,
                       dtypes,
                       lod_levels=None,
                       pass_num=1,
                       for_parallel=True):
    """Reader over a recordio file written by
    paddle_tpu.recordio / fluid.recordio_writer (reference
    operators/reader/create_recordio_file_reader_op.cc)."""
    rd = py_reader(64, shapes, dtypes, lod_levels)

    def provider():
        for _ in range(pass_num):
            for item in _scan_file(filename):
                yield item

    rd.decorate_tensor_provider(provider)
    return rd


def open_files(filenames,
               shapes,
               lod_levels,
               dtypes,
               thread_num=None,
               buffer_size=None,
               pass_num=1,
               is_test=None):
    """Multi-file multi-thread recordio reader (reference layers/io.py:724;
    operators/reader/open_files_op.cc).  is_test (or thread_num == 1)
    preserves file order; otherwise reader threads interleave files."""
    import queue as _queue

    thread_num = (1 if is_test else
                  min(thread_num or len(filenames), len(filenames)))
    buffer_size = buffer_size or 3 * thread_num
    rd = py_reader(buffer_size, shapes, dtypes, lod_levels)

    def provider():
        for _ in range(pass_num):
            if thread_num == 1:
                for fname in filenames:
                    for item in _scan_file(fname):
                        yield item
                continue
            q = _queue.Queue(maxsize=buffer_size)
            done = object()
            errors = []

            def work(my_files):
                try:
                    for fname in my_files:
                        for item in _scan_file(fname):
                            q.put(item)
                except BaseException as e:
                    # surface reader failures to the consumer: silently
                    # truncating the dataset would look like a clean EOF
                    errors.append(e)
                finally:
                    q.put(done)

            shards = [filenames[i::thread_num] for i in range(thread_num)]
            workers = [
                threading.Thread(target=work, args=(shard, ), daemon=True)
                for shard in shards
            ]
            for w in workers:
                w.start()
            finished = 0
            while finished < thread_num:
                item = q.get()
                if item is done:
                    finished += 1
                else:
                    yield item
            for w in workers:
                w.join()
            if errors:
                raise RuntimeError(
                    'open_files reader thread failed: %r' %
                    (errors[0], )) from errors[0]

    rd.decorate_tensor_provider(provider)
    return rd


def random_data_generator(low, high, shapes, lod_levels, for_parallel=True):
    """Uniform-random dummy reader (reference layers/io.py:410,
    operators/reader/create_random_data_generator_op.cc): a reader
    Variable that synthesizes float32 batches itself — no file, no
    start() needed.  Pair with read_file to get the data vars."""
    shapes = [list(s) for s in shapes]
    reader = py_reader(
        capacity=4,
        shapes=shapes,
        dtypes=['float32'] * len(shapes),
        lod_levels=list(lod_levels))
    rng = np.random.RandomState(0)

    def provider():
        while True:
            yield tuple(
                rng.uniform(low, high, size=s).astype('float32')
                for s in shapes)

    feeder = get_reader_feeder(reader.name)
    feeder.decorate_tensor_provider(provider)
    feeder.start()
    return reader


class Preprocessor(object):
    """Custom reader-transform block (reference layers/io.py Preprocessor /
    operators/reader/create_custom_reader_op.cc): a sub-block of ops is
    defined between ``inputs()`` and ``outputs()`` and applied to every
    batch the underlying reader yields.

    TPU-native mechanism: the block's ops run through the same XLA
    lowering registry as any program — per batch, on the host-visible
    feed path — by executing a tiny derived Program over the popped
    batch, then pushing the transformed slots onward.  The returned
    reader var swaps its feeder for the transforming one at ``start``.
    """

    BEFORE_SUB_BLOCK = 0
    IN_SUB_BLOCK = 1
    AFTER_SUB_BLOCK = 2

    def __init__(self, reader, name=None):
        self.underlying = reader
        self.helper = LayerHelper('create_custom_reader', name=name)
        self.status = Preprocessor.BEFORE_SUB_BLOCK
        self.main_prog = self.helper.main_program
        self.sub_block = None
        self.source_vars = None
        self.sink_vars = None

    def _is_completed(self):
        return self.sub_block and self.source_vars and self.sink_vars

    @contextlib.contextmanager
    def block(self):
        self.status = Preprocessor.IN_SUB_BLOCK
        self.sub_block = self.main_prog.create_block()
        try:
            yield
        finally:
            self.main_prog.rollback()
            self.status = Preprocessor.AFTER_SUB_BLOCK
            if not self._is_completed():
                raise RuntimeError(
                    'Preprocessor block needs inputs() and outputs()')
            self._install()

    def inputs(self):
        if self.status != Preprocessor.IN_SUB_BLOCK:
            raise RuntimeError(
                'Preprocessor.inputs() must be called inside block()')
        feeder = get_reader_feeder(self.underlying.name)
        self.source_vars = []
        for i, (shape, dtype) in enumerate(
                zip(feeder.shapes, feeder.dtypes)):
            v = self.sub_block.create_var(
                name=unique_name.generate('preprocessor_src_%d' % i),
                dtype=dtype)
            v.shape = tuple(shape)
            self.source_vars.append(v)
        return self.source_vars

    def outputs(self, *outs):
        if self.status != Preprocessor.IN_SUB_BLOCK:
            raise RuntimeError(
                'Preprocessor.outputs() must be called inside block()')
        self.sink_vars = list(outs)

    def _install(self):
        from ..executor import Executor
        src_names = [v.name for v in self.source_vars]
        sink_names = [v.name for v in self.sink_vars]
        # derived per-batch program: the sub-block's ops over feed vars
        from ..framework import Program
        prog = Program()
        blk = prog.global_block()
        for v in self.source_vars:
            nv = blk.create_var(name=v.name, dtype=v.dtype)
            nv.shape = getattr(v, 'shape', None)
            nv.is_data = True
        for op in self.sub_block.ops:
            blk.append_op(type=op.type, inputs=dict(op.inputs),
                          outputs=dict(op.outputs), attrs=dict(op.attrs))
        for name, v in self.sub_block.vars.items():
            if name not in blk.vars:
                blk.vars[name] = v
        underlying_feeder = get_reader_feeder(self.underlying.name)
        exe = Executor(core.CPUPlace())

        original_pop = underlying_feeder.pop

        def transforming_pop():
            batch = original_pop()
            if batch is None:
                return None
            feed = dict(zip(src_names, batch))
            outs = exe.run(prog, feed=feed, fetch_list=sink_names)
            return tuple(np.asarray(o) for o in outs)

        underlying_feeder.pop = transforming_pop

    def __call__(self):
        return self.underlying
