"""A one-chip benchmark cell's K-step program compiled for the TPU v5e here,
without the chip: ``python3 tools/compile_for_v5e.py <cell> [--min-mb N]
[--op NAME ...]``.

Builds the cell as ``chipbench/run.py`` does (``chipbench/workloads/<cell>
.json`` through ``chipbench/models/``), runs startup on the CPU and hands the
executor's own train scan its staged arguments as shapes on a described v5e.
Writes the optimized HLO to ``chiprun_out/hlo/<cell>.hlo.txt``: its operation
names (``fusion.937``, ``copy.365``) are a ``--trace 1`` run's and the
ledger's.  Prints what 'auto' lowered ``flash_attention`` to (and, for
``ssd_scan``, ``causal_conv1d``, ``gated_rms_norm``, ``moe_experts``,
``kda`` and ``rotary``, the ops by choice and one op's record), XLA's memory
analysis, each result over N MB that an operation outside the fused
computations writes, with its ``op_name``, which gradient ops tied their
parameters' updates (``param_update_order``) and the whole copies of state
(``state_copies``).  ``--op NAME`` (a line of the ledger's
``breakdown.device_ops``) prints that operation's row of
``fluid.hlo_text.op_rows`` instead of the two lists: its Fluid op
(``scope``), the scopes fused ``inside`` it, its ``owner``, what a prefetch
``moves``, its MB and its computation.  The parser and the ahead-of-time
compile are the program's (``paddle_tpu/fluid/hlo_text.py``,
``fluid.trace.aot_compile``).  One process at a time can hold
the TPU's library (``/tmp/libtpu_lockfile``): not beside the tier-1 tests.
NMT compiles in 20 s, the transformer in 75, granite in 95.
"""
import argparse
import json
import os
import re
import sys

os.environ.setdefault('TPU_LOG_DIR', 'disabled')
os.environ.setdefault('JAX_PLATFORMS', 'cpu')
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from paddle_tpu.fluid.hlo_text import (   # noqa: E402
    MOVES, NO_BUFFER, mb as _mb, op_name_of, op_rows, operands, operations)


def compile_train_scan(device, main, startup, loss, per_step, amp):
    """The executor's own K-step train scan (K = len(per_step) feed dicts,
    ``loss`` fetched) compiled for ``device`` of a described topology."""
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import executor

    class DescribedTPU(fluid.TPUPlace):
        # no CPU place, so 'auto' and the Pallas kernels lower as on the
        # chip; what it stages stays on the host: the compiler gets shapes
        def jax_device(self):
            return jax.devices('cpu')[0]

    exe = fluid.Executor(DescribedTPU())
    per_step = [executor.prepare_feed_arrays(dict(f)) for f in per_step]
    scanned = {n: executor.stack_steps([f[n] for f in per_step])
               for n in per_step[0]}
    chip = jax.sharding.SingleDeviceSharding(device)
    with fluid.scope_guard(fluid.core.Scope()), fluid.amp_guard(bool(amp)):
        exe.run(startup)
        program, scope, _, block = exe._resolve_and_compile(
            main, per_step[0], [loss], None, pop_readers=False)
        state_rw, state_ro, _ = block._materialize_args(scope, {})
        args = jax.tree.map(
            lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=chip),
            (state_rw, state_ro, {}, scanned, exe._next_rng(program)))
        return fluid.trace.aot_compile(
            block._lane_jit('train', {}, scanned), args + (len(per_step), ))


def large_results(hlo, min_mb):
    """(MB, computation, operation, shape, op_name) of each array that an
    operation outside the fused computations produces, largest first."""
    rows = []
    for where, _, name, shape, opcode, rest in operations(hlo):
        if opcode in NO_BUFFER:
            continue
        rows += [(_mb(array), where, name, array, op_name_of(rest) or '-')
                 for array in re.findall(r'\b[a-z]+?\d*\[[\d,]*\]', shape)
                 if _mb(array) > min_mb]
    return sorted(rows, reverse=True)


def state_copies(hlo):
    """(MB, 'loop body' or 'fetched step', computation, copy, shape, what
    it copies, its first reader) of each whole copy of state: a ``copy``
    outside the fused computations of an element of a loop's argument
    tuple (a value the loop carries) or of an ENTRY parameter named
    ``state_rw__*`` (a donated argument), taken from it directly or
    through the operations that only move it (``MOVES``: memory-space
    assignment prefetches a weight in slices and copies what it joined).
    An in-place update whose order after the other readers of the old
    value is not in the data flow forces such a copy
    (``ops/registry.py:order_param_updates``)."""
    bodies = set(re.findall(r' while\(.*?body=%?([\w.\-]+)', hlo))
    rows, seen = [], None
    for where, entry, name, shape, opcode, rest in operations(hlo):
        if where != seen:   # a computation's names are its own
            seen, carried, state, unread = where, set(), {}, {}
        args = operands(rest)
        for read in unread.keys() & set(args):
            rows[unread.pop(read)][-1] = name
        sources = {state.get(a) for a in args}
        if opcode == 'parameter' and where in bodies:
            carried.add(name)
        elif (opcode == 'parameter' and entry
              and name.startswith('state_rw__')) or (
                  opcode == 'get-tuple-element' and args[0] in carried):
            state[name] = (name, _mb(shape))
        elif (opcode in MOVES or 'custom_call_target="ConcatBitcast"' in rest) \
                and len(sources) == 1 and None not in sources:
            state[name] = sources.pop()
        elif opcode == 'copy' and _mb(shape) == state.get(args[0], (0, 0))[1]:
            unread[name] = len(rows)
            rows.append([_mb(shape), 'fetched step' if entry else 'loop body',
                         where, name, shape.split('{')[0],
                         state[args[0]][0], '-'])
    return sorted(map(tuple, rows), reverse=True)


def neighbours(hlo, name):
    """Lines of text for ``--op``: the operation's own shape (its layout
    is there), each operand's, and its first reader's."""
    ops = {}
    for where, _, op, shape, opcode, rest in operations(hlo):
        ops.setdefault(where, []).append(
            (op, shape, opcode, operands(rest), op_name_of(rest)))
    out = []
    for where, rows in ops.items():
        shapes = {op: (shape, opcode) for op, shape, opcode, _, _ in rows}
        for i, (op, shape, opcode, args, _) in enumerate(rows):
            if op != name:
                continue
            out.append('  %s = %s %s in %s' % (op, shape, opcode, where))
            out += ['    reads %s = %s %s' % ((a, ) + shapes[a])
                    for a in args if a in shapes]
            out += ['    first read by %s = %s %s  %s' % (
                r[0], r[1], r[2], r[4] or '-')
                for r in rows[i + 1:] if name in r[3]][:1]
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('cell')
    ap.add_argument('--min-mb', type=float, default=256.0)
    ap.add_argument('--op', action='append', default=[],
                    help="an operation's name, as a trace prints it")
    args = ap.parse_args(argv)
    import jax
    from jax.experimental import topologies
    from chipbench import run as bench
    from paddle_tpu.fluid import trace
    jax.config.update('jax_enable_compilation_cache', False)
    cell = bench.load_json('workloads', args.cell + '.json')
    if int(cell['chips']) != 1:
        sys.exit('compile_for_v5e: %s is no one-chip cell' % args.cell)
    cfg = bench.load_json('configs', cell['config'] + '.json')
    traffic = bench.load_json('traffic', cell['traffic'] + '.json')
    lib = bench.load_module('models', cfg['builder'] + '.py')
    model = lib.build(cfg, traffic)
    batches = bench.load_module('traffic.py').token_batches(
        traffic, lib.vocab(cfg), 0)
    feeds = [lib.feed(cfg, next(batches))
             for _ in range(int(cell['steps_per_dispatch']))]
    device = topologies.get_topology_desc(
        platform='tpu', topology_name='v5e:2x2').devices[0]
    compiled = compile_train_scan(device, model['main'], model['startup'],
                                  model['loss'], feeds, cfg['amp'])
    hlo, mem = compiled.as_text(), compiled.memory_analysis()
    path = os.path.join(ROOT, 'chiprun_out', 'hlo', args.cell + '.hlo.txt')
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, 'w') as f:
        f.write(hlo)
    print('compile_for_v5e: %s K=%d -> %s\n  flash_attention lowered to %s\n'
          '  GB arguments=%.3f temporaries=%.3f' % (
              args.cell, len(feeds), path,
              trace.lowering_choices('flash_attention'),
              mem.argument_size_in_bytes / 1e9, mem.temp_size_in_bytes / 1e9))
    for chooser in ('ssd_scan', 'causal_conv1d', 'gated_rms_norm',
                    'moe_experts', 'kda', 'rotary'):
        for seen, counted in list(zip(
                trace.lowering_choices(chooser, seen=True),
                trace.lowering_choices(chooser)))[-1:]:
            print('  %s lowered to %s %s' % (chooser, counted, sorted(
                seen.values(), key=repr)[:1]))
    if args.op:
        rows = op_rows(hlo)
        for name in args.op:
            print('%s  %s' % (name, json.dumps(rows.get(name), indent=1)))
            for line in neighbours(hlo, name):
                print(line)
        return 0
    for row in large_results(hlo, args.min_mb):
        print('%8.1f MB  %s  %s  %s  %s' % row)
    print('  param_update_order %s\n  whole copies of state:' %
          trace.lowering_choices('param_update_order'))
    copies = state_copies(hlo)
    for row in copies:
        if row[0] > args.min_mb:
            print('%8.1f MB  %s %s  %s  %s of %s, first read by %s' % row)
    for kind in ('loop body', 'fetched step'):
        mbs = [row[0] for row in copies if row[1] == kind]
        print('%8.1f MB a %s in %d copies, %.1f MB in the %d over %g MB' % (
            sum(mbs), kind, len(mbs), sum(m for m in mbs if m > args.min_mb),
            sum(m > args.min_mb for m in mbs), args.min_mb))


if __name__ == '__main__':
    sys.exit(main())
