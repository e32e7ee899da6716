"""v2 inference (reference: python/paddle/v2/inference.py)."""

import numpy as np

from .trainer import _build_feed
from .. import fluid

__all__ = ['infer', 'Inference']


class Inference(object):
    def __init__(self, output_layer, parameters):
        from .layer import parse_network
        outputs = (output_layer if isinstance(output_layer, (list, tuple))
                   else [output_layer])
        self.parameters = parameters
        self.topology = parameters.topology
        # input columns = the data layers the OUTPUTS depend on, in
        # declaration order (reference v2 infer feeding semantics) — NOT a
        # positional prefix of the cost DAG's inputs
        self.data_layers = parse_network(*outputs)
        program = self.topology.main_program
        ctx = self.topology._ctx
        if any(self.topology.var_of(out) is None for out in outputs):
            # outputs outside the cost DAG build into a CLONE so the
            # shared training topology is never mutated
            program = self.topology.main_program.clone()
            ctx = dict(ctx)
            with fluid.program_guard(program,
                                     self.topology.startup_program):
                for out in outputs:
                    out.to_fluid(ctx)
        self.output_names = [ctx[out.name].name for out in outputs]
        # prune away the cost branch so label inputs are not required
        # (reference inference.py builds from the pruned inference proto)
        pruned = program.prune(self.output_names)
        self._program = pruned.clone(for_test=True)
        self._exe = fluid.Executor(fluid.default_place())

    def infer(self, input, feeding=None, field='value'):
        # with an explicit feeding map, wider rows are fine — _build_feed
        # selects the mapped columns; only the positional default needs
        # the column count to match exactly
        if feeding is None and len(input[0]) != len(self.data_layers):
            raise ValueError(
                'infer input has %d columns but the output layer depends '
                'on %d data layers (%s); pass feeding={name: column} for '
                'wider rows' %
                (len(input[0]), len(self.data_layers),
                 [l.name for l in self.data_layers]))
        feed = _build_feed(self.data_layers, input, feeding)
        outs = self._exe.run(self._program, feed=feed,
                             fetch_list=self.output_names,
                             scope=self.parameters.scope)
        outs = [np.asarray(o) for o in outs]
        return outs[0] if len(outs) == 1 else outs


def infer(output_layer, parameters, input, feeding=None, field='value'):
    return Inference(output_layer, parameters).infer(input, feeding=feeding,
                                                     field=field)
