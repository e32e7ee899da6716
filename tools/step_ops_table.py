"""A ``--trace 1`` run of a benchmark cell in this process, then its device
trace joined with the program's own record of the step executable
(``fluid.trace.executable_record``), as tables for a perf engineer:

    chiprun -- python3 tools/step_ops_table.py --workload <cell> --seed N

``chipbench/run.py`` runs as it always does (its output and result line come
first, unchanged); the tables follow on standard output and go to
``chiprun_out/ops/<cell>.<seed>.txt``.  The join and the rules of which
operation is a wait and which carries an update are the readers' own
(``chipbench/executable_ops.py``); here they are only grouped and printed:

  * what the record cost to make after the window, and XLA's memory analysis;
  * the share of the traced stretch's device self time under operation names
    the record holds (the names are one compile's: all of it);
  * the ``unscoped`` bucket by opcode and by the class of the owner the
    record gives each operation, and the operations no rule reaches;
  * the prefetch waits (``copy-done``, ``slice-done``) by owner class and by
    what they carry;
  * the fusions that hold an optimizer's update beside other work, by the
    class of the fusion's own scope.
"""
import argparse
import importlib.util
import json
import os
import runpy
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _executable_ops():
    """The benchmark's file, as its readers load it: by path, once."""
    key = 'chipbench_executable_ops'
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            key, os.path.join(ROOT, 'chipbench', 'executable_ops.py'))
        sys.modules[key] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[key])
    return sys.modules[key]


def tables(joined, made, top=12):
    """The tables' lines, from ``executable_ops.joined``'s pair and the
    program's record."""
    executable_ops = _executable_ops()
    scopes = executable_ops.scopes()
    classes = scopes.load_classes()
    ops, steps = joined

    def ms(secs):
        return 1e3 * secs / steps

    def klass(scope):
        if not scope:
            return '-'
        if scope.startswith(('state_', 'scanned_', 'feeds_')):
            return 'argument'
        return scopes.class_of(scope, classes)

    def by(pairs):
        out = {}
        for key, secs in pairs:
            out[key] = out.get(key, 0.0) + secs
        return sorted(out.items(), key=lambda kv: -kv[1])

    def longest(chosen, show):
        return '  longest: %s' % [
            (o[0], round(ms(o[1]), 4)) + show(o[3])
            for o in sorted(chosen, key=lambda o: -o[1])[:top]]

    total = sum(o[1] for o in ops)
    known = sum(o[1] for o in ops if o[3])
    out = ['', 'step_ops_table: %d steps in the stretch, step %.3f ms'
           % (steps, ms(total)),
           'record: live=%s seconds=%s ops=%d memory GB=%s' % (
               made['live'],
               json.dumps({k: round(v, 3)
                           for k, v in made['seconds'].items()}),
               len(made['ops']), json.dumps({
                   k: round(v / 1e9, 3)
                   for k, v in (made['memory'] or {}).items()})),
           'device self time under names the record holds: %.4f%% '
           '(%.4f of %.4f ms a step); unknown: %s' % (
               100.0 * known / total, ms(known), ms(total),
               [(o[0], round(ms(o[1]), 4)) for o in sorted(
                   ops, key=lambda o: -o[1]) if not o[3]][:top])]
    unscoped = [o for o in ops if o[2] == scopes.BUCKET_UNSCOPED]
    out += ['', 'unscoped %.4f ms a step: by opcode and owner class'
            % ms(sum(o[1] for o in unscoped))]
    out += ['  %-16s %-12s %9.4f' % (key + (ms(secs), )) for key, secs in by(
        ((row['opcode'] if row else '?', klass(row and row['owner'])), s)
        for _, s, _, row in unscoped)]
    out += [longest(unscoped, lambda row: (
        row and row['opcode'], row and row['owner']))]
    out += ['unscoped and no owner (ms a step): %s' % [
        (o[0], round(ms(o[1]), 4)) for o in sorted(
            unscoped, key=lambda o: -o[1])
        if not (o[3] and o[3]['owner'])][:top]]
    waits = [o for o in ops if o[3] and executable_ops.is_wait(o[2], o[3])]
    out += ['', 'prefetch waits %.4f ms a step: by opcode, owner class, what '
            'it carries' % ms(sum(o[1] for o in waits))]
    out += ['  %-12s %-12s %-12s %9.4f' % (key + (ms(secs), ))
            for key, secs in by(((row['opcode'], klass(row['owner']),
                                  klass(row['moves'])), s)
                                for _, s, _, row in waits)]
    out += [longest(waits, lambda row: (row['owner'], row['moves']))]
    update = executable_ops.carries_update()
    fused = [o for o in ops if o[3] and update(o[2], o[3])]
    out += ['', 'fusions with an update inside, outside the optimizer bucket '
            '%.4f ms a step: by bucket' % ms(sum(o[1] for o in fused))]
    out += ['  %-12s %9.4f' % (key, ms(secs))
            for key, secs in by((o[2], o[1]) for o in fused)]
    out += [longest(fused, lambda row: (row['scope'], [
        scope for scope in row['inside']
        if klass(scope) == executable_ops.UPDATES]))]
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, default=0)
    args, rest = ap.parse_known_args(argv)   # the rest is run.py's
    run = os.path.join(ROOT, 'chipbench', 'run.py')
    sys.argv = [run, '--workload', args.workload, '--seed', str(args.seed),
                '--seconds', '10', '--trace', '1'] + rest
    try:
        # as the running program: its readers look for __main__.T_PROCESS
        runpy.run_path(run, run_name='__main__')
    except SystemExit as e:
        if e.code:
            raise
    executable_ops = _executable_ops()
    with open(os.path.join(ROOT, 'chipbench', 'workloads',
                           args.workload + '.json')) as f:
        cell = json.load(f)
    joined = executable_ops.joined({
        'trace': True, 'cell': cell,
        'steps_per_dispatch': int(cell['steps_per_dispatch'])})
    lines = tables(joined, executable_ops.program_record()) if joined else [
        'step_ops_table: no trace, or no record from the program']
    path = os.path.join(ROOT, 'chiprun_out', 'ops',
                        '%s.%d.txt' % (args.workload, args.seed))
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, 'w') as f:
        f.write('\n'.join(lines) + '\n')
    print('\n'.join(lines), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
