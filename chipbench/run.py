"""chipbench: one cell of the benchmark, in this process, on the chip.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is found by name and nothing is listed
in code: ``workloads/<cell>.json`` names the configuration
(``configs/<config>.json``), the traffic (``traffic/<traffic>.json``), the
chips and the driver (``drivers/<driver>.py``); the configuration names its
builder (``models/<builder>.py``); ``BENCHMARK.json`` says which metrics the
cell reports, and each per-layer metric is read by
``layer_metrics/<metric>.py``.  See ``chipbench/README.md``.

The last line of standard output is the result, one JSON object.  Without
a TPU, or with fewer chips than the cell asks for, the process exits
non-zero and prints none.  ``--cpu-tiny`` runs the same code at the toy
sizes the files give under ``cpu_tiny`` on CPU devices, for rehearsals: its
``device`` block says ``cpu`` and its numbers are never speeds.
"""

import time

T_PROCESS = time.perf_counter()   # set-up counts from here

import argparse   # noqa: E402
import importlib.util   # noqa: E402
import json   # noqa: E402
import os   # noqa: E402
import sys   # noqa: E402
import types   # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts):
    path = os.path.join(HERE, *parts)
    if not os.path.exists(path):
        sys.exit('chipbench: no file %s' % os.path.relpath(path, ROOT))
    with open(path) as f:
        return json.load(f)


def load_module(*parts):
    """A benchmark file as a module, by path (metric names have dots)."""
    path = os.path.join(HERE, *parts)
    if not os.path.exists(path):
        sys.exit('chipbench: no file %s' % os.path.relpath(path, ROOT))
    name = 'chipbench_' + '_'.join(parts).replace('.', '_')
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def tiny(params):
    """The file's parameters with its ``cpu_tiny`` sizes laid over them."""
    out = dict(params)
    out.update(out.pop('cpu_tiny', {}))
    return out


def metrics_of(benchmark, group, cell):
    """The metrics of ``group`` this cell reports: those that list it, and
    those that list no cell."""
    return [m for m in benchmark[group]
            if cell in m.get('workloads', [cell])]


def device_peaks(kind):
    peaks = load_json('peaks.json')['peaks']
    if kind not in peaks:
        sys.exit('chipbench: no peak rates for device_kind %r in '
                 'chipbench/peaks.json (known: %s): add it with its source'
                 % (kind, sorted(peaks)))
    return peaks[kind]


def memory_peak(device):
    """Peak bytes the device held: its arrays (``peak_bytes_in_use``) and
    what the runtime reserved for the running programs' temporaries
    (``peak_bytes_reserved``; on the v5e a program's temporary memory is
    counted there and not among the bytes in use).  0 where the backend
    reports no statistics (CPU)."""
    stats = device.memory_stats() or {}
    return stats.get('peak_bytes_in_use', 0) + stats.get(
        'peak_bytes_reserved', 0)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, default=0)
    ap.add_argument('--seconds', type=float, default=10.0)
    ap.add_argument('--trace', type=int, choices=(0, 1), default=0)
    ap.add_argument('--cpu-tiny', action='store_true',
                    help='toy sizes on CPU devices, for rehearsals; '
                         'without it the platform must be tpu')
    args = ap.parse_args(argv)

    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        benchmark = json.load(f)
    cell = load_json('workloads', args.workload + '.json')
    config = load_json('configs', cell['config'] + '.json')
    traffic = load_json('traffic', cell['traffic'] + '.json')
    chips = int(cell['chips'])
    if args.cpu_tiny:
        config, traffic = tiny(config), tiny(traffic)
        os.environ['JAX_PLATFORMS'] = 'cpu'
        flags = os.environ.get('XLA_FLAGS', '')
        if '--xla_force_host_platform_device_count' not in flags:
            os.environ['XLA_FLAGS'] = (
                '%s --xla_force_host_platform_device_count=%d'
                % (flags, chips)).strip()

    sys.path.insert(0, ROOT)
    import jax
    devices = jax.devices()
    device = {'platform': devices[0].platform,
              'kind': devices[0].device_kind, 'count': len(devices)}
    print('chipbench: cell=%s platform=%s device_kind=%s count=%d'
          % (args.workload, device['platform'], device['kind'],
             device['count']), flush=True)
    if not args.cpu_tiny and device['platform'] != 'tpu':
        sys.exit('chipbench: needs a TPU, JAX found platform %r: run on '
                 'the chip, or pass --cpu-tiny for a rehearsal'
                 % device['platform'])
    if len(devices) < chips:
        sys.exit('chipbench: cell %s needs %d chip(s), JAX found %d'
                 % (args.workload, chips, len(devices)))
    peaks = None if args.cpu_tiny else device_peaks(device['kind'])

    from paddle_tpu.fluid import flags
    cache_dir = flags.enable_compile_cache()

    marks = {}   # set-up's phases, seconds since the process started

    def mark(name):
        now = time.perf_counter()
        marks[name] = now - T_PROCESS
        return now

    mark('jax_ready')
    ctx = types.SimpleNamespace(
        cell=cell, config=config, traffic=traffic,
        devices=devices[:chips], seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace),
        trace_dir=os.path.join(ROOT, '.chipbench_out', 'trace',
                               args.workload),
        model_lib=load_module('models', config['builder'] + '.py'),
        traffic_lib=load_module('traffic.py'), mark=mark)
    record = load_module('drivers', cell['driver'] + '.py').run(ctx)
    record.update(cell=cell, chips=chips, peaks=peaks,
                  memory_peak_bytes=max(map(memory_peak, ctx.devices)))
    record['end_to_end']['setup_s'] = marks['window_opens']
    print('chipbench: setup %s window=%s counted=%s compile_cache=%s'
          % (' '.join('%s=%.3f' % kv for kv in marks.items()),
             json.dumps(record['window']), json.dumps(record['counted']),
             cache_dir), flush=True)

    result = {'correct': bool(record['correct']),
              'attempted': int(record['attempted']),
              'failed': int(record['failed']), 'metrics': {},
              'device': dict(device,
                             memory_peak_bytes=record['memory_peak_bytes'])}
    if args.trace:
        xplane = load_module('xplane.py')
        path = xplane.find_trace(ctx.trace_dir)
        record['trace'] = xplane.reduce(path) if path else None
        if record['trace']:
            worst = record['trace']['worst']
            result['device'].update(busy_s=record['trace']['busy_s'],
                                    window_s=record['trace']['window_s'])
            result['breakdown'] = {'device_ops': worst['ops'],
                                   'idle_gaps': worst['gaps']}
        for m in metrics_of(benchmark, 'per_layer', args.workload):
            value = load_module(
                'layer_metrics', m['name'] + '.py').read(record)
            if value is not None:
                result['metrics'][m['name']] = {'value': value,
                                                'unit': m['unit']}
    else:
        for m in metrics_of(benchmark, 'end_to_end', args.workload):
            result['metrics'][m['name']] = {
                'value': record['end_to_end'][m['name']], 'unit': m['unit']}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
