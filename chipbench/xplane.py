"""Reduction of a JAX profiler trace (``*.xplane.pb``) to device numbers.

Reads the trace with ``jax.profiler.ProfileData`` and nothing else.  What a
TPU trace holds (looked at by hand on the v5e, PR 23): one plane per chip,
``/device:TPU:<n>``, whose line ``XLA Ops`` has one event per executed HLO
operation (the event's name is the operation's HLO text, ``%name = ...``),
whose line ``XLA Modules`` has one event per executed program, and whose
line ``Async XLA Ops`` has one event per asynchronous operation from its
start to its done.  ``TraceAnnotation`` spans of the benchmark are events
named ``chipbench/...`` on the host plane's thread lines.  The device's
clock and the host's differ by about a millisecond in these traces, so an
idle gap is attributed to a host span only by its overlap, and only gaps
much longer than that mean anything.

All times are seconds.  Nothing here touches a device: the functions work
on a recorded file (``chipbench/testdata/probe.xplane.pb`` in the tests).
"""

import glob
import os
import re
import statistics

DEVICE_PLANE = re.compile(r'^/device:TPU:(\d+)$')
OPS_LINE, MODULES_LINE, ASYNC_LINE = 'XLA Ops', 'XLA Modules', 'Async XLA Ops'
SPAN_PREFIX = 'chipbench/'
COLLECTIVE = re.compile(
    r'^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute'
    r'|collective-broadcast)(-start|-done)?(\.\d+)?$')
_NS = 1e-9
TOP_OPS, LONGEST_GAPS = 10, 5   # what a breakdown may carry


def find_trace(trace_dir):
    """The one ``*.xplane.pb`` a ``jax.profiler.start_trace(trace_dir)`` /
    ``stop_trace()`` pair wrote, or None."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, 'plugins', 'profile', '*', '*.xplane.pb')))
    return found[-1] if found else None


def op_name(event_name):
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``."""
    head = event_name.split(' = ', 1)[0].strip()
    return head.lstrip('%')[:80]


def load(path):
    """{'devices': {index: {'ops', 'modules', 'async'}}, 'spans': [...]};
    every entry a list of (start_s, end_s, name) sorted by start."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = {'ops': [], 'modules': [], 'async': []}
            keys = {OPS_LINE: 'ops', MODULES_LINE: 'modules',
                    ASYNC_LINE: 'async'}
            for line in plane.lines:
                key = keys.get(line.name)
                if key is None:
                    continue
                for e in line.events:
                    start = e.start_ns * _NS
                    name = e.name if key == 'modules' else op_name(e.name)
                    dev[key].append((start, start + e.duration_ns * _NS,
                                     name))
            for key in dev:
                # an enclosing event before what it encloses
                dev[key].sort(key=lambda e: (e[0], -e[1]))
            devices[int(m.group(1))] = dev
        elif plane.name.startswith('/host:'):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        start = e.start_ns * _NS
                        spans.append((start, start + e.duration_ns * _NS,
                                      e.name))
    spans.sort()
    return {'devices': devices, 'spans': spans}


def union(intervals):
    """Merged, sorted, non-overlapping [(start, end)] of the intervals."""
    out = []
    for start, end in sorted((iv[0], iv[1]) for iv in intervals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def total(merged):
    return sum(end - start for start, end in merged)


def clip(intervals, lo, hi):
    """The parts of (start, end, ...) intervals inside [lo, hi]."""
    return [(max(iv[0], lo), min(iv[1], hi)) + tuple(iv[2:])
            for iv in intervals if iv[1] > lo and iv[0] < hi]


def subtract(merged, cover):
    """The parts of the merged intervals that no ``cover`` interval (also
    merged) overlaps."""
    out, j = [], 0
    for start, end in merged:
        at = start
        while j < len(cover) and cover[j][1] <= at:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < end:
            if cover[k][0] > at:
                out.append((at, cover[k][0]))
            at = max(at, cover[k][1])
            k += 1
        if at < end:
            out.append((at, end))
    return out


def self_times(events):
    """[(name, self_seconds)] for events sorted by start.  An
    event that encloses others (a ``while`` round its body's operations)
    keeps only the time its children do not cover, so a loop does not
    count its body twice."""
    out, stack = [], []   # stack entries: [start, end, name, child_s]

    def close(entry):
        out.append((entry[2], max(entry[1] - entry[0] - entry[3], 0.0)))

    for start, end, name in events:
        while stack and stack[-1][1] <= start:
            close(stack.pop())
        if stack:
            stack[-1][3] += min(end, stack[-1][1]) - start
        stack.append([start, end, name, 0.0])
    while stack:
        close(stack.pop())
    return out


def leaves(events):
    """The events that enclose no other event."""
    out = []
    for i, (start, end, name) in enumerate(events):
        nxt = events[i + 1] if i + 1 < len(events) else None
        if nxt is None or nxt[0] >= end:
            out.append((start, end, name))
    return out


def step_module(modules):
    """Name of the program that took most device time: the step."""
    by_name = {}
    for start, end, name in modules:
        by_name[name] = by_name.get(name, 0.0) + end - start
    return max(by_name, key=by_name.get) if by_name else None


def label_gap(gap, spans):
    """The benchmark span that overlaps the gap most, else 'unattributed'."""
    best, best_s = 'unattributed', 0.0
    for start, end, name in spans:
        if start >= gap[1]:
            break
        over = min(end, gap[1]) - max(start, gap[0])
        if over > best_s:
            best, best_s = name, over
    return best


def reduce_device(dev, spans):
    """One device plane's numbers over the stretch from its first to its
    last whole run of the step program (all it recorded, if it ran none).
    The trace cuts the runs under way when it starts and stops and records
    the pieces as runs, so the first and the last are never counted."""
    modules, ops = dev['modules'], dev['ops']
    if not ops:
        return None
    step = step_module(modules)
    runs = [m for m in modules if m[2] == step][1:-1]
    lo = runs[0][0] if runs else ops[0][0]
    hi = runs[-1][1] if runs else max(e[1] for e in ops)
    inside = clip(ops, lo, hi)
    busy = union(inside)
    busy_s, window_s = total(busy), hi - lo
    by_op = {}
    for name, secs in self_times(inside):
        by_op[name] = by_op.get(name, 0.0) + secs
    gaps = subtract([(lo, hi)], busy)
    gaps.sort(key=lambda g: g[0] - g[1])
    between = [b[0] - a[1] for a, b in zip(runs, runs[1:])]
    # collectives: synchronous ones are operations, asynchronous ones run
    # from their start to their done; exposed is the part of that time in
    # which no other operation runs
    leaf = leaves(inside)
    coll = [e for e in leaf if COLLECTIVE.match(e[2])]
    coll += [e for e in clip(dev['async'], lo, hi) if COLLECTIVE.match(e[2])]
    other = union(e for e in leaf if not COLLECTIVE.match(e[2]))
    coll_u = union(coll)
    return {
        'window_s': window_s, 'busy_s': busy_s,
        'idle_share': 1.0 - busy_s / window_s if window_s > 0 else None,
        'step_module': step, 'step_runs': len(runs),
        'step_run_s': [m[1] - m[0] for m in runs],
        'step_gap_median_s': statistics.median(between) if between else None,
        'ops': sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP_OPS],
        'gaps': [[label_gap(g, spans), g[1] - g[0]]
                 for g in gaps[:LONGEST_GAPS]],
        'collective_s': total(coll_u),
        'collective_exposed_s': total(subtract(coll_u, other)),
    }


def reduce(path):
    """The whole trace: per-device numbers, over the devices the mean busy
    time and the longest window, and ``worst``, the device that idles
    most."""
    loaded = load(path)
    devs = {i: r for i, r in (
        (i, reduce_device(d, loaded['spans']))
        for i, d in sorted(loaded['devices'].items())) if r}
    if not devs:
        return None
    return {
        'devices': devs,
        'busy_s': statistics.mean(r['busy_s'] for r in devs.values()),
        'window_s': max(r['window_s'] for r in devs.values()),
        'worst': max(devs.values(), key=lambda r: r['idle_share'] or 0.0),
    }
