"""Graph program representation: Program / Block / Operator / Variable.

This is the TPU-native re-design of the reference's "program = data" layer
(python/paddle/fluid/framework.py:207,496,923,1407 and
paddle/fluid/framework/framework.proto).  The Python API surface matches the
reference; the representation is pure Python descs.  Instead of being
interpreted op-by-op by a C++ Executor (executor.cc:321-339), whole blocks are
compiled to XLA by :mod:`paddle_tpu.fluid.executor`.
"""

import collections
import contextlib
import copy
import itertools

import numpy as np

from . import core
from . import unique_name

__all__ = [
    'Program', 'Block', 'Operator', 'Variable', 'Parameter', 'program_guard',
    'default_main_program', 'default_startup_program', 'switch_main_program',
    'switch_startup_program', 'name_scope', 'grad_var_name', 'in_dygraph_mode',
]

GRAD_VAR_SUFFIX = '@GRAD'
ZERO_VAR_SUFFIX = '@ZERO'
TEMP_VAR_NAME = '@TEMP@'


def grad_var_name(name):
    return name + GRAD_VAR_SUFFIX


def in_dygraph_mode():
    return False


class Variable(object):
    """A typed symbolic value in a Block (reference framework.py:207).

    Holds shape/dtype/lod_level metadata; runtime values live in a Scope.
    """

    def __init__(self,
                 block,
                 type=core.VarDesc.VarType.LOD_TENSOR,
                 name=None,
                 shape=None,
                 dtype=None,
                 lod_level=None,
                 capacity=None,
                 persistable=None,
                 error_clip=None,
                 stop_gradient=False,
                 is_data=False,
                 initializer=None,
                 **kwargs):
        self.block = block
        if name is None:
            name = unique_name.generate('_generated_var')
        self.name = name
        self.type = type
        self.shape = tuple(shape) if shape is not None else ()
        if dtype is None:
            dtype = core.VarDesc.VarType.FP32
        if not isinstance(dtype, int):
            dtype = core.convert_np_dtype_to_dtype_(dtype)
        self.dtype = dtype
        self.lod_level = lod_level if lod_level is not None else 0
        self.persistable = bool(persistable)
        self.stop_gradient = stop_gradient
        self.is_data = is_data
        self.error_clip = error_clip
        self.capacity = capacity
        # op that produced this var (filled by Block.append_op)
        self.op = None

    @property
    def np_dtype(self):
        return core.convert_dtype_to_np(self.dtype)

    def to_string(self, throw_on_error=False, with_details=False):
        return 'var %s : shape=%s dtype=%s persistable=%s' % (
            self.name, self.shape, np.dtype(self.np_dtype).name,
            self.persistable)

    __repr__ = __str__ = lambda self: self.to_string()

    # ---- math operator sugar is patched in by layers.math_op_patch ----

    def clone_to(self, block):
        v = Variable(
            block,
            type=self.type,
            name=self.name,
            shape=self.shape,
            dtype=self.dtype,
            lod_level=self.lod_level,
            persistable=self.persistable,
            stop_gradient=self.stop_gradient,
            is_data=self.is_data)
        return v


class Parameter(Variable):
    """A persistable, trainable Variable (reference framework.py:1995)."""

    def __init__(self, block, shape, dtype, **kwargs):
        if shape is None or dtype is None:
            raise ValueError('Parameter needs shape and dtype')
        kwargs.setdefault('persistable', True)
        super(Parameter, self).__init__(
            block, shape=shape, dtype=dtype, **kwargs)
        self.trainable = kwargs.get('trainable', True)
        self.optimize_attr = kwargs.get('optimize_attr', {'learning_rate': 1.0})
        self.regularizer = kwargs.get('regularizer', None)
        self.gradient_clip_attr = kwargs.get('gradient_clip_attr', None)
        self.do_model_average = kwargs.get('do_model_average', None)

    def astype(self, dtype):
        """Graph-side cast (reference Parameter.astype via math_op_patch):
        returns a new Variable carrying this parameter cast to dtype."""
        from .layers import tensor as tensor_layers
        return tensor_layers.cast(self, dtype)


class Operator(object):
    """One operation: type + named input/output var lists + attrs
    (reference framework.py:496, framework.proto OpDesc)."""

    OP_WITHOUT_KERNEL_SET = {
        'feed', 'fetch', 'save', 'load', 'save_combine', 'load_combine',
        'recurrent', 'go', 'print', 'while', 'conditional_block',
    }

    def __init__(self, block, type, inputs=None, outputs=None, attrs=None):
        self.block = block
        self.type = type
        # slot name -> list of var names
        self.inputs = {}
        self.outputs = {}
        if inputs:
            for slot, arg in inputs.items():
                self.inputs[slot] = self._to_name_list(arg)
        if outputs:
            for slot, arg in outputs.items():
                self.outputs[slot] = self._to_name_list(arg)
        self.attrs = dict(attrs) if attrs else {}

    @staticmethod
    def _to_name_list(arg):
        if arg is None:
            return []
        if isinstance(arg, (list, tuple)):
            return [a.name if isinstance(a, Variable) else a for a in arg]
        return [arg.name if isinstance(arg, Variable) else arg]

    def input(self, slot):
        return self.inputs.get(slot, [])

    def output(self, slot):
        return self.outputs.get(slot, [])

    @property
    def input_names(self):
        return list(self.inputs.keys())

    @property
    def output_names(self):
        return list(self.outputs.keys())

    @property
    def input_arg_names(self):
        return [n for ns in self.inputs.values() for n in ns]

    @property
    def output_arg_names(self):
        return [n for ns in self.outputs.values() for n in ns]

    def attr(self, name):
        return self.attrs.get(name)

    def has_attr(self, name):
        return name in self.attrs

    def set_attr(self, name, val):
        self.attrs[name] = val
        self.block.program._bump_version()

    _set_attr = set_attr

    def all_attrs(self):
        return dict(self.attrs)

    def attr_type(self, name):
        """Python-type stand-in for the reference's proto AttrType enum.
        Raises on unknown names like the reference pybind surface."""
        if name not in self.attrs:
            raise ValueError('op %r has no attr %r' % (self.type, name))
        return type(self.attrs[name])

    def has_kernel(self, op_type=None):
        return (op_type or self.type) not in self.OP_WITHOUT_KERNEL_SET

    def block_attr_id(self, name):
        """Index of a sub-block attr (reference block_attr_id)."""
        v = self.attrs.get(name)
        return v.idx if isinstance(v, Block) else int(v)

    def block_attr(self, name):
        return self.block_attr_id(name)

    def blocks_attr_ids(self, name):
        v = self.attrs.get(name) or []
        return [b.idx if isinstance(b, Block) else int(b) for b in v]

    def blocks_attr(self, name):
        return self.blocks_attr_ids(name)

    def rename_input(self, old_name, new_name):
        for slot, names in self.inputs.items():
            self.inputs[slot] = [new_name if n == old_name else n
                                 for n in names]
        self.block.program._bump_version()

    def rename_output(self, old_name, new_name):
        for slot, names in self.outputs.items():
            self.outputs[slot] = [new_name if n == old_name else n
                                  for n in names]
        self.block.program._bump_version()

    def to_string(self, throw_on_error=False):
        ins = {k: v for k, v in self.inputs.items()}
        outs = {k: v for k, v in self.outputs.items()}
        return '{%s} = %s(%s) attrs=%s' % (outs, self.type, ins, {
            k: v
            for k, v in self.attrs.items() if not k.startswith('_')
        })

    __repr__ = __str__ = lambda self: self.to_string()


class Block(object):
    """An ordered op list plus a var symbol table (reference framework.py:923,
    framework.proto BlockDesc:170)."""

    def __init__(self, program, idx, parent_idx=-1):
        self.program = program
        self.idx = idx
        self.parent_idx = parent_idx
        self.vars = collections.OrderedDict()  # name -> Variable
        self.ops = []
        # sub-block ops (while/cond) keep attrs pointing at Block objects

    @property
    def parent_block(self):
        if self.parent_idx < 0:
            return None
        return self.program.block(self.parent_idx)

    def create_var(self, *args, **kwargs):
        var = Variable(self, *args, **kwargs)
        self.vars[var.name] = var
        self.program._bump_version()
        return var

    def create_parameter(self, *args, **kwargs):
        global_block = self.program.global_block()
        param = Parameter(global_block, *args, **kwargs)
        global_block.vars[param.name] = param
        self.program._bump_version()
        return param

    def var(self, name):
        v = self.vars.get(name)
        if v is None:
            raise ValueError('var %r not in block %d' % (name, self.idx))
        return v

    def has_var(self, name):
        return name in self.vars

    def _find_var_recursive(self, name):
        blk = self
        while blk is not None:
            if name in blk.vars:
                return blk.vars[name]
            blk = blk.parent_block
        return None

    def var_recursive(self, name):
        v = self._find_var_recursive(name)
        if v is None:
            raise ValueError('var %r not found (block %d)' % (name, self.idx))
        return v

    def all_parameters(self):
        return [v for v in self.vars.values() if isinstance(v, Parameter)]

    def append_op(self, type=None, inputs=None, outputs=None, attrs=None):
        op = Operator(self, type, inputs=inputs, outputs=outputs, attrs=attrs)
        self.ops.append(op)
        for names in op.outputs.values():
            for n in names:
                v = self._find_var_recursive(n)
                if v is not None and v.op is None:
                    v.op = op
        self.program._bump_version()
        return op

    def _prepend_op(self, type=None, inputs=None, outputs=None, attrs=None):
        op = Operator(self, type, inputs=inputs, outputs=outputs, attrs=attrs)
        self.ops.insert(0, op)
        self.program._bump_version()
        return op

    prepend_op = _prepend_op

    def _insert_op(self, index, type=None, inputs=None, outputs=None,
                   attrs=None):
        op = Operator(self, type, inputs=inputs, outputs=outputs, attrs=attrs)
        self.ops.insert(index, op)
        self.program._bump_version()
        return op

    def _remove_op(self, index):
        del self.ops[index]
        self.program._bump_version()

    def to_string(self, throw_on_error=False, with_details=False):
        lines = ['block %d (parent %d):' % (self.idx, self.parent_idx)]
        for v in self.vars.values():
            lines.append('  ' + v.to_string())
        for op in self.ops:
            lines.append('  ' + op.to_string())
        return '\n'.join(lines)

    __repr__ = __str__ = lambda self: self.to_string()


class Program(object):
    """A list of Blocks; block 0 is the global block
    (reference framework.py:1407, framework.proto ProgramDesc:183)."""

    _serials = itertools.count()

    def __init__(self):
        # this process's name for the Program in records that outlive it
        # (fluid.trace.lowering_choices)
        self._serial = next(Program._serials)
        self.blocks = [Block(self, 0)]
        self.current_block_idx = 0
        self.random_seed = 0
        self._version = 0
        self._op_role_var = []
        self._is_distributed = False

    # executor compile-cache invalidation
    def _bump_version(self):
        self._version += 1

    @property
    def num_blocks(self):
        return len(self.blocks)

    def global_block(self):
        return self.blocks[0]

    def block(self, idx):
        return self.blocks[idx]

    def current_block(self):
        return self.blocks[self.current_block_idx]

    def create_block(self, parent_idx=None):
        new_idx = len(self.blocks)
        parent = self.current_block_idx if parent_idx is None else parent_idx
        self.blocks.append(Block(self, new_idx, parent))
        self.current_block_idx = new_idx
        self._bump_version()
        return self.current_block()

    def rollback(self):
        self.current_block_idx = self.current_block().parent_idx

    def list_vars(self):
        for blk in self.blocks:
            for v in blk.vars.values():
                yield v

    def all_parameters(self):
        return self.global_block().all_parameters()

    def clone(self, for_test=False):
        """Deep-copy the program.  With ``for_test=True``, ops behave in
        inference mode (is_test attr set; dropout/batch_norm switched)."""
        p = copy.deepcopy(self)
        if for_test:
            for blk in p.blocks:
                for op in blk.ops:
                    # batch_norm note: is_test only stops the running-
                    # statistics update; WHICH statistics normalize is
                    # the lowering's use_global_stats decision, so an
                    # explicit use_global_stats=False still gets batch
                    # statistics at test time without eval batches
                    # polluting the moving averages (ops/nn_ops.py)
                    if 'is_test' in _IS_TEST_OPS.get(op.type, ()):
                        op.attrs['is_test'] = True
        p._bump_version()
        return p

    def __deepcopy__(self, memo):
        cls = self.__class__
        p = cls.__new__(cls)
        memo[id(self)] = p
        for k, v in self.__dict__.items():
            setattr(p, k, copy.deepcopy(v, memo))
        p._serial = next(Program._serials)   # a copy is another Program
        return p

    def prune(self, targets):
        """Keep only ops needed to compute ``targets`` (framework/prune.h)."""
        if not isinstance(targets, (list, tuple)):
            targets = [targets]
        target_names = set(
            t.name if isinstance(t, Variable) else t for t in targets)
        p = copy.deepcopy(self)
        blk = p.global_block()
        needed = set(target_names)
        kept = []
        for op in reversed(blk.ops):
            if op.type == 'fetch' or set(op.output_arg_names) & needed or (
                    op.type == 'feed' and set(op.output_arg_names) & needed):
                kept.append(op)
                needed.update(op.input_arg_names)
        blk.ops = list(reversed(kept))
        p._bump_version()
        return p

    def inference_optimize(self, prune_read_op=True):
        p = self.clone(for_test=True)
        if prune_read_op:
            blk = p.global_block()
            blk.ops = [op for op in blk.ops if op.type not in ('read', )]
        return p

    def to_string(self, throw_on_error=False, with_details=False):
        return '\n'.join(b.to_string() for b in self.blocks)

    __repr__ = __str__ = lambda self: self.to_string()

    def copy_data_info_from(self, other):
        """Copy is_data/stop_gradient marks from ``other``'s global block
        onto same-named vars here (reference Program.copy_data_info_from —
        used after clone/prune so feed vars keep their data semantics)."""
        for name, src in other.global_block().vars.items():
            dst = self.global_block().vars.get(name)
            if dst is not None:
                dst.is_data = getattr(src, 'is_data', False)
                dst.stop_gradient = src.stop_gradient

    def get_desc(self):
        """The program's wire-level description (the reference returns the
        C++ ProgramDesc; here the structural dict the serde round-trips)."""
        return self.desc_dict()

    @contextlib.contextmanager
    def optimized_guard(self, param_and_grads):
        """Scope marking appended ops as optimizer ops (reference
        Program.optimized_guard sets OpRole.Optimize + the param/grad
        pair on every op built inside)."""
        prior = self._op_role_var
        self._op_role_var = [
            v.name if isinstance(v, Variable) else v
            for v in (param_and_grads or [])
        ]
        try:
            yield
        finally:
            self._op_role_var = prior

    # ---- serialization (program-is-data contract) ----
    def desc_dict(self):
        from . import program_serde
        return program_serde.program_to_dict(self)

    def serialize_to_string(self):
        """framework.proto ProgramDesc bytes — the reference's public
        model contract (framework.proto:183)."""
        from . import proto_serde
        return proto_serde.serialize_program(self)

    @staticmethod
    def parse_from_string(data):
        if isinstance(data, str):
            data = data.encode('utf-8')
        if data[:1] == b'{':
            # legacy structural-JSON artifact (pre-protobuf rounds)
            from . import program_serde
            return program_serde.deserialize_program(data)
        from . import proto_serde
        return proto_serde.deserialize_program(data)


# ops whose clone(for_test) should set is_test
_IS_TEST_OPS = {
    'dropout': ('is_test', ),
    'batch_norm': ('is_test', ),
    'layer_norm': (),
}

# ----------------------------------------------------------------------------
# default programs + guards (reference framework.py:2100-2230)
# ----------------------------------------------------------------------------
_main_program_ = Program()
_startup_program_ = Program()


def default_startup_program():
    return _startup_program_


def default_main_program():
    return _main_program_


def switch_main_program(program):
    global _main_program_
    prev = _main_program_
    _main_program_ = program
    return prev


def switch_startup_program(program):
    global _startup_program_
    prev = _startup_program_
    _startup_program_ = program
    return prev


@contextlib.contextmanager
def program_guard(main_program, startup_program=None):
    prev_main = switch_main_program(main_program)
    prev_start = None
    if startup_program is not None:
        prev_start = switch_startup_program(startup_program)
    try:
        yield
    finally:
        switch_main_program(prev_main)
        if prev_start is not None:
            switch_startup_program(prev_start)


_name_scope_stack = []


@contextlib.contextmanager
def name_scope(prefix=None):
    _name_scope_stack.append(prefix or '')
    try:
        yield
    finally:
        _name_scope_stack.pop()


def get_var(name, program=None):
    """Look up a Variable by name in ``program``'s global block
    (reference framework.get_var)."""
    program = program if program is not None else default_main_program()
    v = program.global_block().vars.get(name)
    if v is None:
        raise ValueError('var %r not found in program' % name)
    return v
