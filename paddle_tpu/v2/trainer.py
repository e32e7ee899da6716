"""v2 SGD trainer (reference: python/paddle/v2/trainer.py — there driving
the C++ GradientMachine via swig; here compiling the topology's fluid
Program once and stepping it on TPU/CPU)."""

import numpy as np

from . import event as v2_event
from . import data_type as _dt
from .topology import Topology
from .. import fluid

__all__ = ['SGD']


def _build_feed(data_layers, data_batch, feeding=None):
    """Convert a v2 minibatch (list of per-sample tuples) into a fluid
    feed dict according to each data layer's InputType (reference
    py_paddle DataProviderConverter)."""
    if feeding is None:
        order = {i: i for i in range(len(data_layers))}
    else:
        order = {i: feeding[l.name] for i, l in enumerate(data_layers)}
    feed = {}
    for i, layer in enumerate(data_layers):
        col = [sample[order[i]] for sample in data_batch]
        t = layer.data_type
        if t.seq_type == 2:  # nested: sample = list of sub-sequences
            width, dt = ((1, np.int64) if t.type == _dt.DataType.Index
                         else (t.dim, np.float32))
            chunks, inner, outer = [], [], []
            for sample_rows in col:
                outer.append(len(sample_rows))
                for sub in sample_rows:
                    arr = np.asarray(sub, dt).reshape(-1, width)
                    chunks.append(arr)
                    inner.append(len(arr))
            flat = (np.concatenate(chunks) if chunks
                    else np.zeros((0, width), dt))
            lt = fluid.core.LoDTensor(flat)
            lt.set_recursive_sequence_lengths([outer, inner])
            feed[layer.name] = lt
        elif t.seq_type:  # variable-length rows -> LoDTensor
            if t.type == _dt.DataType.Index:
                flat = np.concatenate(
                    [np.asarray(r, np.int64).reshape(-1, 1) for r in col])
            else:
                flat = np.concatenate(
                    [np.asarray(r, np.float32).reshape(-1, t.dim)
                     for r in col])
            lt = fluid.core.LoDTensor(flat)
            lt.set_recursive_sequence_lengths([[len(r) for r in col]])
            feed[layer.name] = lt
        elif t.type == _dt.DataType.Index:
            feed[layer.name] = np.asarray(col, np.int64).reshape(-1, 1)
        else:
            feed[layer.name] = np.asarray(
                col, np.float32).reshape(len(col), t.dim)
    return feed


class SGD(object):
    """(reference v2/trainer.py:37 SGD)"""

    def __init__(self, cost, parameters, update_equation,
                 extra_layers=None, is_local=True, **kwargs):
        self.topology = (cost if isinstance(cost, Topology)
                         else parameters.topology)
        self.parameters = parameters
        self._train_program = self.topology.main_program.clone()
        self._test_program = self.topology.main_program.clone(for_test=True)
        # optimizer accumulators initialize via their own startup program:
        # the topology startup already ran when Parameters was created, and
        # re-running it would re-randomize the weights
        opt_startup = fluid.Program()
        with fluid.program_guard(self._train_program, opt_startup):
            cost_var = self._train_program.global_block().var(
                self.topology.cost_var.name)
            update_equation.to_fluid().minimize(cost_var)
        with fluid.scope_guard(parameters.scope):
            fluid.Executor(fluid.CPUPlace()).run(opt_startup)
        self._place = fluid.default_place()
        self._exe = fluid.Executor(self._place)

    def train(self, reader, num_passes=1, event_handler=None, feeding=None):
        if event_handler is None:
            event_handler = lambda e: None
        cost_name = self.topology.cost_var.name
        data_layers = self.topology.data_layers
        with fluid.scope_guard(self.parameters.scope):
            for pass_id in range(num_passes):
                event_handler(v2_event.BeginPass(pass_id))
                for batch_id, data_batch in enumerate(reader()):
                    event_handler(
                        v2_event.BeginIteration(pass_id, batch_id))
                    feed = _build_feed(data_layers, data_batch, feeding)
                    cost, = self._exe.run(self._train_program, feed=feed,
                                          fetch_list=[cost_name])
                    event_handler(v2_event.EndIteration(
                        pass_id, batch_id,
                        float(np.asarray(cost).flatten()[0])))
                event_handler(v2_event.EndPass(pass_id))

    def test(self, reader, feeding=None):
        cost_name = self.topology.cost_var.name
        data_layers = self.topology.data_layers
        costs, n = 0.0, 0
        with fluid.scope_guard(self.parameters.scope):
            for data_batch in reader():
                feed = _build_feed(data_layers, data_batch, feeding)
                cost, = self._exe.run(self._test_program, feed=feed,
                                      fetch_list=[cost_name])
                costs += float(np.asarray(cost).flatten()[0])
                n += 1
        return v2_event.TestResult(cost=costs / max(n, 1))
