"""Device time by the Fluid op that emitted it, and the program's host spans,
from the same ``*.xplane.pb``.

    python3 chipbench/scopes.py <trace dir or .xplane.pb> [--top 40]

The program traces every Fluid op under ``jax.named_scope('<op type>.<first
output>')`` inside ``paddle_tpu.step`` (``paddle_tpu/ops/registry.py``), and
XLA carries the scope into each executed operation's *metadata* in the
trace: ``tf_op`` (``jit(paddle_tpu_train_scan)/while/body/paddle_tpu.step/
mul.fc_3.tmp_0/dot_general:``), ``hlo_category``, and XLA's own ``flops``
and ``bytes_accessed`` for the operation.  ``jax.profiler.ProfileData``
exposes an event's stats but not its metadata's, so this file reads the
protobuf's wire format itself (``XSpace`` of tsl's ``xplane.proto``; no
schema, no dependency).  Times are cut to whole nanoseconds as
``ProfileData`` cuts them, and the traced stretch, the self times and the
busy union are ``xplane.py``'s (loaded by path), so busy time here is
``xplane.reduce``'s.  They are kept as whole nanoseconds until the sums are
made: in float seconds an operation that starts when its neighbour ends can
compare as starting just before, ``self_times`` then takes it for a child
of the neighbour, and the loop that encloses both counts its time twice
(2.5-2.9% too much in the cells of PR 24).

Every operation's self time in the stretch falls into exactly one bucket:
a class of ``scope_classes.json`` (by the innermost Fluid-op scope of its
``tf_op``), ``scan_lane`` (a ``tf_op`` outside ``paddle_tpu.step``: the
K-step scan's own loop, carries and feed slicing) or ``unscoped`` (inside
``paddle_tpu.step`` with no Fluid-op scope, or no ``tf_op`` at all).  The
buckets sum to the busy time.

The persistent compile cache leaves metadata out of its key: after a change
that only renames scopes, the cache still serves executables with the old
names until the HLO changes.  ``unscoped_device_share.train`` jumping is
the alarm.

Host spans are the program's ``paddle_tpu/<layer>/<what>``
``TraceAnnotation``s (``paddle_tpu/fluid/trace.py:span``) on the host
plane's thread lines.
"""

import fnmatch
import importlib.util
import json
import os
import re
import statistics
import struct
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
SPAN_PREFIX = 'paddle_tpu/'
BUCKET_LANE, BUCKET_UNSCOPED, CLASS_OTHER = 'scan_lane', 'unscoped', 'other'
_NS = 1e-9
_WRAPPED = re.compile(r'^[A-Za-z_][A-Za-z0-9_]*\((.*)\)$')   # jvp(...), ...


def _by_path(name):
    """A sibling file as a module, once per process (the benchmark's files
    are loaded by path: ``chipbench`` is no package)."""
    key = 'chipbench_' + name
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            key, os.path.join(HERE, name + '.py'))
        sys.modules[key] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[key])
    return sys.modules[key]


xplane = _by_path('xplane')


# ---- the wire format ----------------------------------------------------

def _fields(buf):
    """(field number, wire type, value) of one message; a length-delimited
    value is a memoryview, a fixed one its raw bytes."""
    i, n = 0, len(buf)
    while i < n:
        key = shift = 0
        while True:
            c = buf[i]
            i += 1
            key |= (c & 0x7f) << shift
            if c < 0x80:
                break
            shift += 7
        wire = key & 7
        if wire in (0, 2):   # a varint: the value, or a length
            val = shift = 0
            while True:
                c = buf[i]
                i += 1
                val |= (c & 0x7f) << shift
                if c < 0x80:
                    break
                shift += 7
            if wire == 2:
                i += val
                val = buf[i - val:i]
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            val = bytes(buf[i:i + size])
            i += size
        else:
            raise ValueError('xplane: wire type %d' % wire)
        yield key >> 3, wire, val


def _text(view):
    return bytes(view).decode('utf-8', 'replace')


def _signed(v):
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf, stat_names):
    """One XStat -> (name, value); a ref_value names another stat's
    metadata, whose name is the string."""
    name = value = None
    for f, wire, v in _fields(buf):
        if f == 1:
            name = stat_names.get(v, str(v))
        elif f == 2:
            value = struct.unpack('<d', v)[0]
        elif f == 3:
            value = v
        elif f == 4:
            value = _signed(v)
        elif f == 5:
            value = _text(v)
        elif f == 7:
            value = stat_names.get(v, '')
    return name, value


def _map_entry(buf):
    """A proto map's entry: (int64 key, message value)."""
    key = value = None
    for f, _, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _event_metadata(buf, stat_names):
    meta = {'name': '', 'stats': {}}
    for f, _, v in _fields(buf):
        if f == 2:
            meta['name'] = _text(v)
        elif f == 5:
            name, value = _stat(v, stat_names)
            meta['stats'][name] = value
    return meta


def _line(buf, wanted):
    """One XLine -> (name, id, [(start_ns, duration_ns, metadata id)]).
    Only events whose metadata id ``wanted`` accepts are kept (None: all)."""
    name, line_id, stamp_ns, raw = '', 0, 0, []
    for f, _, v in _fields(buf):
        if f == 1:
            line_id = v
        elif f == 2:
            name = _text(v)
        elif f == 3:
            stamp_ns = _signed(v)
        elif f == 4:
            raw.append(v)
    events = []
    for ev in raw:
        mid = offset_ps = duration_ps = 0
        for f, _, v in _fields(ev):
            if f == 1:
                mid = v
            elif f == 2:
                offset_ps = v
            elif f == 3:
                duration_ps = v
            else:
                break   # stats follow; nothing here reads an event's own
        if wanted is None or mid in wanted:
            events.append((stamp_ns + offset_ps // 1000,
                           duration_ps // 1000, mid))
    return name, line_id, events


def read_planes(path):
    """[{'name', 'metadata': {id: {'name', 'stats'}}, 'lines': [(name, id,
    events)]}] for the device planes (lines ``XLA Ops``, ``XLA Modules``)
    and the host planes (only ``paddle_tpu/`` events)."""
    with open(path, 'rb') as f:
        space = memoryview(f.read())
    planes = []
    for f, _, plane in _fields(space):
        if f != 1:
            continue
        name, lines, meta_raw, stat_names = '', [], [], {}
        for g, _, v in _fields(plane):
            if g == 2:
                name = _text(v)
            elif g == 3:
                lines.append(v)
            elif g == 4:
                meta_raw.append(v)
            elif g == 5:
                key, value = _map_entry(v)
                for h, _, w in _fields(value):
                    if h == 2:
                        stat_names[key] = _text(w)
        device = xplane.DEVICE_PLANE.match(name)
        if not device and not name.startswith('/host:'):
            continue
        metadata = {}
        for raw in meta_raw:
            key, value = _map_entry(raw)
            metadata[key] = _event_metadata(value, stat_names)
        if device:
            wanted, keep = None, (xplane.OPS_LINE, xplane.MODULES_LINE)
        else:
            wanted = {k for k, m in metadata.items()
                      if m['name'].startswith(SPAN_PREFIX)}
            keep = None
        parsed = []
        for raw in lines:
            if keep is not None:
                head = next((_text(v) for g, _, v in _fields(raw) if g == 2),
                            '')
                if head not in keep:
                    continue
            elif not wanted:
                continue
            parsed.append(_line(raw, wanted))
        planes.append({'name': name, 'metadata': metadata, 'lines': parsed})
    return planes


def load(path):
    """{'devices': {index: {'ops', 'modules', 'metadata'}}, 'spans'}: ops
    and modules as ``xplane.load`` gives them, (start, end, key) sorted by
    start, but in whole nanoseconds, and an op's key is its metadata id
    (``metadata[id]`` holds its name and stats); spans are (start, end,
    name, host line)."""
    devices, spans = {}, []
    for plane in read_planes(path):
        m = xplane.DEVICE_PLANE.match(plane['name'])
        meta = plane['metadata']
        if m:
            dev = {'ops': [], 'modules': [], 'metadata': meta}
            for name, _, events in plane['lines']:
                for start, dur, mid in events:
                    if name == xplane.OPS_LINE:
                        dev['ops'].append((start, start + dur, mid))
                    else:
                        dev['modules'].append((start, start + dur,
                                               meta[mid]['name']))
            for key in ('ops', 'modules'):
                dev[key].sort(key=lambda e: (e[0], -e[1]))
            devices[int(m.group(1))] = dev
        else:
            for name, line_id, events in plane['lines']:
                line = '%s#%d' % (name, line_id)   # threads may share a name
                for start, dur, mid in events:
                    # TraceMe encodes arguments as name#k=v,...#
                    spans.append((start, start + dur,
                                  meta[mid]['name'].split('#', 1)[0], line))
    spans.sort()
    return {'devices': devices, 'spans': spans}


# ---- scopes and classes -------------------------------------------------

def load_classes(path=None):
    """``scope_classes.json``: the step's scope, the form of a Fluid-op
    scope (a pattern with the groups op type and variable), and the op
    types of each class."""
    with open(path or os.path.join(HERE, 'scope_classes.json')) as f:
        return json.load(f)


def fluid_scope(tf_op, classes):
    """(inside the step's scope?, the innermost path element of a Fluid
    op's form under it, or None).  JAX wraps the elements of a transformed
    trace (``transpose(jvp(mul.fc_0.tmp_0))``, ``jit(relu)``); the
    wrappers are taken off first."""
    inside, scope = False, None
    for element in (tf_op or '').rstrip(':').split('/'):
        while True:
            m = _WRAPPED.match(element)
            if not m:
                break
            element = m.group(1)
        if element == classes['step_scope']:
            inside = True
        elif inside and re.match(classes['scope_pattern'], element):
            scope = element
    return inside, scope


def class_of(scope, classes):
    op_type, var = re.match(classes['scope_pattern'], scope).groups()
    if op_type.endswith('_grad'):
        op_type = op_type[:-len('_grad')]
    for rule in classes.get('by_variable', []):
        if rule['op'] == op_type and fnmatch.fnmatchcase(var,
                                                         rule['variable']):
            return rule['class']
    for name, types in classes['classes'].items():
        if op_type in types:
            return name
    return CLASS_OTHER


def bucket_of(stats, classes):
    """(bucket, Fluid scope or None, inside the step's scope?) of one
    operation's metadata stats."""
    tf_op = stats.get('tf_op')
    if not tf_op:
        return BUCKET_UNSCOPED, None, False
    inside, scope = fluid_scope(tf_op, classes)
    if not inside:
        return BUCKET_LANE, None, False
    if scope is None:
        return BUCKET_UNSCOPED, None, True
    return class_of(scope, classes), scope, True


def label_gap(gap, spans):
    """The innermost (shortest) ``paddle_tpu/`` span that covers most of
    the gap, else 'unattributed'."""
    best, best_key = 'unattributed', (0, 0)
    for start, end, name, _ in spans:
        if start >= gap[1]:
            break
        over = min(end, gap[1]) - max(start, gap[0])
        if over > 0 and (over, start - end) > best_key:
            best, best_key = name, (over, start - end)
    return best


def reduce_device(dev, spans, classes):
    """One device plane over ``xplane.reduce_device``'s stretch: busy time,
    self time by bucket, by Fluid scope and by operation."""
    modules, ops, meta = dev['modules'], dev['ops'], dev['metadata']
    if not ops:
        return None
    step = xplane.step_module(modules)
    runs = [m for m in modules if m[2] == step][1:-1]
    lo = runs[0][0] if runs else ops[0][0]
    hi = runs[-1][1] if runs else max(e[1] for e in ops)
    inside = xplane.clip(ops, lo, hi)
    busy = xplane.union(inside)
    busy_s, window_s = xplane.total(busy) * _NS, (hi - lo) * _NS
    leaf = {(e[0], e[2]) for e in xplane.leaves(inside)}
    buckets, scopes, by_op, placed = {}, {}, {}, {}
    for mid, ns in xplane.self_times(inside):
        secs = ns * _NS
        if mid not in placed:
            placed[mid] = bucket_of(meta[mid]['stats'], classes)
        bucket, scope, _ = placed[mid]
        buckets[bucket] = buckets.get(bucket, 0.0) + secs
        name = xplane.op_name(meta[mid]['name'])
        row = by_op.setdefault(name, {
            'self_s': 0.0, 'bucket': bucket, 'scope': scope,
            'tf_op': meta[mid]['stats'].get('tf_op')})
        row['self_s'] += secs
        if scope is not None:
            row = scopes.setdefault(scope, {
                'self_s': 0.0, 'class': bucket, 'flops': 0, 'bytes': 0,
                'categories': {}})
            row['self_s'] += secs
            cat = meta[mid]['stats'].get('hlo_category') or '?'
            row['categories'][cat] = row['categories'].get(cat, 0.0) + secs
    # XLA's operations and bytes once per executed leaf: an enclosing
    # ``while`` reports its body's again
    for start, _, mid in inside:
        scope = placed[mid][1]
        if scope is not None and (start, mid) in leaf:
            stats = meta[mid]['stats']
            scopes[scope]['flops'] += stats.get('flops') or 0
            scopes[scope]['bytes'] += stats.get('bytes_accessed') or 0
    gaps = xplane.subtract([(lo, hi)], busy)
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        'window_s': window_s, 'busy_s': busy_s,
        'idle_share': 1.0 - busy_s / window_s if window_s > 0 else None,
        'step_module': step, 'step_runs': len(runs), 'lo': lo, 'hi': hi,
        # False for a program that traces under no step scope: its time
        # has no partition to report
        'scoped': any(inside for _, _, inside in placed.values()),
        'buckets': buckets, 'scopes': scopes, 'ops': by_op,
        'gaps': [[label_gap(g, spans), (g[1] - g[0]) * _NS]
                 for g in gaps[:xplane.LONGEST_GAPS]],
    }


def reduce(path, classes=None):
    """The whole trace: per device ``reduce_device``; ``worst`` is the
    device that idles most, as in ``xplane.reduce``; ``spans`` the
    ``paddle_tpu/`` spans that lie inside the worst device's stretch,
    name -> {'durations_s', 'lines'}."""
    classes = classes or load_classes()
    loaded = load(path)
    devs = {i: r for i, r in (
        (i, reduce_device(d, loaded['spans'], classes))
        for i, d in sorted(loaded['devices'].items())) if r}
    if not devs:
        return None
    worst = max(devs.values(), key=lambda r: r['idle_share'] or 0.0)
    spans = {}
    for start, end, name, line in loaded['spans']:
        if start >= worst['lo'] and end <= worst['hi']:
            row = spans.setdefault(name, {'durations_s': [], 'lines': set()})
            row['durations_s'].append((end - start) * _NS)
            row['lines'].add(line)
    return {'devices': devs, 'worst': worst, 'spans': spans}


# ---- what the layer metrics read ----------------------------------------

def of_record(record):
    """``reduce`` of the traced run's file, parsed once per run and kept on
    the record; None where the run has no trace."""
    if not record.get('trace'):
        return None
    if 'scopes' not in record:
        trace_dir = os.path.join(os.path.dirname(HERE), '.chipbench_out',
                                 'trace', record['cell']['name'])
        path = xplane.find_trace(trace_dir)
        record['scopes'] = reduce(path) if path else None
    return record['scopes']


def _scoped_worst(record):
    reduced = of_record(record)
    return reduced['worst'] if reduced and reduced['worst']['scoped'] \
        else None


def _steps(record, worst):
    return worst['step_runs'] * record['steps_per_dispatch']


def bucket_ms_per_step(record, bucket):
    """Self time per training step, on the worst device, of the operations
    in ``bucket``; 0.0 where it owns none, None without a trace."""
    worst = _scoped_worst(record)
    if not worst or not worst['step_runs']:
        return None
    return 1e3 * worst['buckets'].get(bucket, 0.0) / _steps(record, worst)


def bucket_share(record, bucket):
    worst = _scoped_worst(record)
    if not worst or not worst['busy_s']:
        return None
    return 100.0 * worst['buckets'].get(bucket, 0.0) / worst['busy_s']


def span_median_ms(record, name):
    """Median duration of the program's span ``name`` inside the traced
    stretch; None where the trace holds none (a program without it)."""
    reduced = of_record(record)
    if not reduced or name not in reduced['spans']:
        return None
    return 1e3 * statistics.median(reduced['spans'][name]['durations_s'])


def compile_seconds(record, kinds, window):
    """Counts and seconds of the program's ``fluid.trace.compile_log``
    kinds before the window (``window=False``) or inside it, on
    ``time.perf_counter()``: the window opens ``setup_s`` after ``run.py``'s
    first statement.  None where ``run.py`` is not the running program, or
    the program has no compile log."""
    main = sys.modules.get('__main__')
    t_process = getattr(main, 'T_PROCESS', None)
    try:
        from paddle_tpu.fluid import trace
        summary = trace.compile_summary
    except (ImportError, AttributeError):
        return None
    if t_process is None or 'setup_s' not in record.get('end_to_end', {}):
        return None
    opens = t_process + record['end_to_end']['setup_s']
    if window:
        got = summary(since=opens, until=opens + record['window']['seconds'])
    else:
        got = summary(until=opens)
    return {'count': sum(got[k]['count'] for k in kinds),
            'seconds': sum(got[k]['seconds'] for k in kinds)}


# ---- the table, for a perf engineer -------------------------------------

def _peaks():
    with open(os.path.join(HERE, 'peaks.json')) as f:
        return json.load(f)['peaks']['TPU v5 lite']


def table(reduced, steps_per_run=None, top=40):
    """Lines of text: buckets, the ``top`` Fluid scopes by self time, the
    operations outside every scope, the host spans, the longest gaps."""
    w, peaks = reduced['worst'], _peaks()
    per = max(w['step_runs'], 1) * (steps_per_run or 1)
    unit = 'ms/step' if steps_per_run else 'ms/run'
    out = ['%s: %d whole runs, busy %.3f ms of %.3f ms (idle %.3f%%)' % (
        w['step_module'], w['step_runs'], 1e3 * w['busy_s'],
        1e3 * w['window_s'], 100 * (w['idle_share'] or 0.0)), '',
        '%-12s %10s %7s' % ('bucket', unit, 'busy%')]
    for name, secs in sorted(w['buckets'].items(), key=lambda kv: -kv[1]):
        out.append('%-12s %10.4f %7.2f' % (
            name, 1e3 * secs / per, 100 * secs / w['busy_s']))
    out += ['', '%-44s %-10s %9s %6s %8s %8s %6s %6s  %s' % (
        'fluid scope', 'class', unit, 'busy%', 'GFLOP', 'MB', 'flop%',
        'hbm%', 'hlo categories')]
    rows = sorted(w['scopes'].items(), key=lambda kv: -kv[1]['self_s'])
    for scope, r in rows[:top]:
        secs = r['self_s'] or 1e-30
        cats = ' '.join('%s:%.0f%%' % (c, 100 * s / secs) for c, s in sorted(
            r['categories'].items(), key=lambda kv: -kv[1])[:3])
        out.append('%-44s %-10s %9.4f %6.2f %8.2f %8.1f %6.1f %6.1f  %s' % (
            scope[:44], r['class'], 1e3 * r['self_s'] / per,
            100 * r['self_s'] / w['busy_s'], r['flops'] / 1e9 / per,
            r['bytes'] / 1e6 / per,
            100 * r['flops'] / secs / peaks['bf16_flops_per_s'],
            100 * r['bytes'] / secs / peaks['hbm_bytes_per_s'], cats))
    for bucket in (BUCKET_UNSCOPED, BUCKET_LANE):
        out += ['', 'operations in %s (%s, busy%%, tf_op)' % (bucket, unit)]
        ops = sorted(((n, r) for n, r in w['ops'].items()
                      if r['bucket'] == bucket),
                     key=lambda kv: -kv[1]['self_s'])
        for name, r in ops[:max(top // 4, 5)]:
            out.append('%-40s %9.4f %6.2f  %s' % (
                name[:40], 1e3 * r['self_s'] / per,
                100 * r['self_s'] / w['busy_s'], r['tf_op'] or '-'))
    out += ['', 'host spans inside the stretch (count, median ms, lines)']
    for name, r in sorted(reduced['spans'].items()):
        out.append('%-36s %5d %10.4f  %s' % (
            name, len(r['durations_s']),
            1e3 * statistics.median(r['durations_s']),
            ','.join(sorted(r['lines']))))
    out += ['', 'longest idle gaps (ms, innermost paddle_tpu/ span over it)']
    out += ['%10.4f  %s' % (1e3 * secs, label) for label, secs in w['gaps']]
    return out


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('trace', help='a trace directory or an .xplane.pb')
    ap.add_argument('--top', type=int, default=40)
    ap.add_argument('--steps-per-run', type=int, default=None,
                    help="the cell's steps_per_dispatch: per-step columns")
    args = ap.parse_args(argv)
    path = args.trace if os.path.isfile(args.trace) else \
        xplane.find_trace(args.trace)
    reduced = path and reduce(path)
    if not reduced:
        sys.exit('scopes: no device operations in %s' % args.trace)
    print('\n'.join(table(reduced, args.steps_per_run, args.top)))
    return 0


if __name__ == '__main__':
    sys.exit(main())
