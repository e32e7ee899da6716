"""A traced run's device operations (``scopes.py``'s ``worst['ops']``: name
-> self time, bucket) joined by operation name with the program's record of
its own step executable (``fluid.trace.executable_record``: a row for every
operation of the optimized module, under the name the trace prints, with the
Fluid op that owns it, the scopes fused into it and what a prefetch carries).
What ``unscoped_owned_share.train``, ``prefetch_wait_device_ms.train``,
``update_fused_device_ms.train`` and ``step_temp_gb.train`` read, and
``tools/step_ops_table.py`` prints; the rules of which operation is a wait and
which carries an update live here alone."""
import importlib.util
import os
import sys

FUN_NAME = 'paddle_tpu_train_scan'
WAITS = ('copy-done', 'slice-done')
UPDATES = 'optimizer'


def scopes():
    if 'chipbench_scopes' not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            'chipbench_scopes', os.path.join(
                os.path.dirname(os.path.abspath(__file__)), 'scopes.py'))
        sys.modules['chipbench_scopes'] = \
            importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules['chipbench_scopes'])
    return sys.modules['chipbench_scopes']


def program_record():
    """The program's record of its K-step training executable; None where
    the program has no such leg, no train lane has run, or the making
    raises."""
    try:
        from paddle_tpu.fluid import trace
        return trace.executable_record(FUN_NAME)
    except Exception:
        return None


def joined(record):
    """[(operation name, self seconds in the traced stretch, bucket, the
    program's row or None)] on the device that idles most, and the steps
    the stretch holds; None without a trace or without the program's
    record."""
    reduced = scopes().of_record(record) if record.get('trace') else None
    worst = reduced and reduced['worst']
    if not worst or not worst['scoped'] or not worst['step_runs']:
        return None
    made = program_record()
    if not made or not made.get('ops'):
        return None
    rows = made['ops']
    return ([(name, op['self_s'], op['bucket'], rows.get(name))
             for name, op in worst['ops'].items()],
            worst['step_runs'] * record['steps_per_dispatch'])


def ms_per_step(record, wanted):
    """Self time a training step of the operations ``wanted(bucket, row)``
    accepts; 0.0 where it accepts none, None where ``joined`` is."""
    both = joined(record)
    if both is None:
        return None
    ops, steps = both
    return 1e3 * sum(secs for _, secs, bucket, row in ops
                     if row and wanted(bucket, row)) / steps


def is_wait(bucket, row):
    """The done half of an asynchronous copy or slice: where the device
    waits for a prefetch of memory-space assignment."""
    return row['opcode'] in WAITS


def carries_update():
    """The rule ``(bucket, row)`` of an operation outside the ``optimizer``
    bucket whose fused computation holds instructions of an
    ``optimizer``-class Fluid op (``scope_classes.json``)."""
    classes, seen = scopes().load_classes(), {}

    def updates(scope):
        if scope not in seen:
            seen[scope] = scopes().class_of(scope, classes) == UPDATES
        return seen[scope]

    return lambda bucket, row: bucket != UPDATES and any(
        updates(scope) for scope in row.get('inside') or ())
