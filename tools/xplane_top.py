"""Top device-time ops from a JAX profiler capture (xplane).

Wall clock includes the host; xplane device slices are what the chip
itself spent — this is the instrument that found the round-4
CE-backward convert (13% of step).  Usage:

    import tools.xplane_top as xt
    with xt.capture('/tmp/tracedir'):
        ... run steps ...
    rows = xt.top_ops('/tmp/tracedir')      # [(name, total_us, count)]
    xt.print_top('/tmp/tracedir', n=30)

or from the CLI:  python tools/xplane_top.py /tmp/tracedir [N]
"""

import contextlib
import glob
import os
import re
from collections import defaultdict


@contextlib.contextmanager
def capture(trace_dir):
    import jax
    jax.profiler.start_trace(trace_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _find_xplanes(trace_dir):
    return glob.glob(os.path.join(trace_dir, 'plugins', 'profile', '*',
                                  '*.xplane.pb'))


def device_planes(trace_dir):
    """Yield (plane_name, plane) for accelerator planes in the capture."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    for path in sorted(_find_xplanes(trace_dir), key=os.path.getmtime):
        space = xplane_pb2.XSpace()
        with open(path, 'rb') as f:
            space.ParseFromString(f.read())
        for plane in space.planes:
            if ('TPU' in plane.name or 'device' in plane.name.lower()) \
                    and 'host' not in plane.name.lower():
                yield plane.name, plane


def top_ops(trace_dir, merge_fusion_params=True):
    """Aggregate device event durations by event name across all device
    planes.  Returns [(name, total_us, count)] sorted by total desc."""
    totals = defaultdict(lambda: [0.0, 0])
    for _, plane in device_planes(trace_dir):
        for line in plane.lines:
            # 'XLA Ops' carries the per-op device slices; 'Steps'/'XLA
            # Modules' duplicate whole-step spans and 'Async XLA Ops'
            # overlap real compute — both would double-count
            if line.name != 'XLA Ops':
                continue
            for ev in line.events:
                name = plane.event_metadata[ev.metadata_id].name
                if merge_fusion_params:
                    name = re.sub(r'^%', '', name)
                    name = re.sub(r'[.\-][0-9]+( = .*)?$', '', name)
                totals[name][0] += ev.duration_ps / 1e6
                totals[name][1] += 1
    rows = [(k, v[0], v[1]) for k, v in totals.items()]
    rows.sort(key=lambda r: -r[1])
    return rows


def total_device_us(trace_dir):
    return sum(r[1] for r in top_ops(trace_dir))


def print_top(trace_dir, n=30):
    rows = top_ops(trace_dir)
    total = sum(r[1] for r in rows) or 1.0
    print('%-72s %12s %8s %6s' % ('op', 'total_us', 'count', '%'))
    for name, us, cnt in rows[:n]:
        print('%-72s %12.1f %8d %5.1f%%' %
              (name[:72], us, cnt, 100.0 * us / total))
    print('TOTAL device us: %.1f' % total)


if __name__ == '__main__':
    import sys
    print_top(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 30)
