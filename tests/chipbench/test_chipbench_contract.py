"""BENCHMARK.json against the benchmark's contract and against the files
under chipbench/: every entry has its file and every file its entry."""

import importlib.util
import json
import os
import re
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from chipbench_helpers import BENCH, ROOT, benchmark, names_in  # noqa: E402

NAME = re.compile(r'^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$')
UNIT = re.compile(r'^[A-Za-z0-9_/%.\-]{1,16}$')
SOURCES = {'device_trace', 'program_span', 'program_counter', 'host_clock'}
B = benchmark()
METRICS = B['end_to_end'] + B['per_layer']
CELLS = [w['name'] for w in B['workloads']]


def cell_file(name):
    with open(os.path.join(BENCH, 'workloads', name + '.json')) as f:
        return json.load(f)


def layer_module(name):
    spec = importlib.util.spec_from_file_location(
        'lm', os.path.join(BENCH, 'layer_metrics', name + '.py'))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_top_level_keys_and_limits():
    assert set(B) == {'command', 'paths', 'run_seconds', 'configs',
                      'workloads', 'end_to_end', 'per_layer'}
    assert B['command'] == ['python3', 'chipbench/run.py']
    assert B['paths'] == ['chipbench', 'tests/chipbench']
    assert isinstance(B['run_seconds'], int) and 1 <= B['run_seconds'] <= 51
    assert os.path.getsize(os.path.join(ROOT, 'BENCHMARK.json')) < 65536
    four = sum(w['chips'] == 4 for w in B['workloads'])
    assert four <= max(1, len(B['workloads']) // 4)


@pytest.mark.parametrize('entry', METRICS + B['workloads'] + B['configs'],
                         ids=lambda e: e['name'])
def test_names_units_and_keys(entry):
    assert NAME.match(entry['name'])
    for key in ('config', 'traffic'):
        assert key not in entry or NAME.match(entry[key])
    texts = [entry[k] for k in ('why', 'layer') if k in entry]
    if entry in B['configs']:
        texts.append(entry['source'])
    for text in texts:
        assert 1 <= len(text) <= 200 and '\n' not in text \
            and '\t' not in text
    if entry in METRICS:
        assert UNIT.match(entry['unit'])
        assert entry['better'] in ('lower', 'higher')
        assert entry['source'] in SOURCES
        assert set(entry.get('workloads', CELLS)) <= set(CELLS)
    if entry in B['end_to_end']:
        assert set(entry) - {'workloads'} == {
            'name', 'unit', 'better', 'bound', 'source'}
        assert entry['source'] in ('host_clock', 'device_trace')
        assert 0.01 <= entry['bound'] <= 0.1
    if entry in B['per_layer']:
        assert set(entry) - {'workloads'} == {
            'name', 'unit', 'better', 'source', 'layer', 'moves'}
    if entry in B['workloads']:
        assert set(entry) == {'name', 'config', 'traffic', 'chips', 'why'}
        assert entry['chips'] in (1, 4)
    if entry in B['configs']:
        assert set(entry) == {'name', 'source', 'file', 'reduced', 'why'}
        assert all(NAME.match(k) for k in entry['reduced'])


def test_no_name_twice():
    for group in (METRICS, B['workloads'], B['configs']):
        names = [e['name'] for e in group]
        assert len(names) == len(set(names))
    pairs = [(w['config'], w['traffic']) for w in B['workloads']]
    assert len(pairs) == len(set(pairs))
    assert 'setup_s' in [m['name'] for m in B['end_to_end']]


def test_every_entry_has_its_file_and_every_file_its_entry():
    assert names_in('workloads') == sorted(CELLS)
    assert names_in('configs') == sorted(c['name'] for c in B['configs'])
    assert names_in('layer_metrics') == sorted(
        m['name'] for m in B['per_layer'])
    cells = [cell_file(c) for c in CELLS]
    assert names_in('traffic') == sorted({c['traffic'] for c in cells})
    assert names_in('drivers') == sorted({c['driver'] for c in cells})
    builders = set()
    for c in B['configs']:
        assert c['file'] == 'chipbench/configs/%s.json' % c['name']
        with open(os.path.join(ROOT, c['file'])) as f:
            cfg = json.load(f)
        assert cfg['source'] == c['source']
        assert cfg['reduced'] == c['reduced'] and 'assumed' in cfg
        builders.add(cfg['builder'])
    assert names_in('models') == sorted(builders)
    assert {c['name'] for c in B['configs']} == {
        w['config'] for w in B['workloads']}


@pytest.mark.parametrize('cell', CELLS)
def test_cell_file_agrees_with_its_entry(cell):
    entry = next(w for w in B['workloads'] if w['name'] == cell)
    on_file = cell_file(cell)
    for key in ('name', 'config', 'traffic', 'chips', 'why'):
        assert on_file[key] == entry[key], key
    assert on_file['who']
    reported = [m['name'] for m in B['end_to_end']
                if cell in m.get('workloads', CELLS)]
    assert 'setup_s' in reported and len(reported) >= 2
    assert any(cell in m.get('workloads', CELLS) for m in B['per_layer'])


@pytest.mark.parametrize('metric', B['per_layer'], ids=lambda m: m['name'])
def test_layer_metric_reader_and_moves(metric):
    module = layer_module(metric['name'])
    assert (module.LAYER, module.UNIT, module.BETTER, module.SOURCE,
            module.MOVES) == (metric['layer'], metric['unit'],
                              metric['better'], metric['source'],
                              metric['moves'])
    moved = next(m for m in B['end_to_end'] if m['name'] == metric['moves'])
    # the moved metric is reported in every cell where this one is
    assert set(metric.get('workloads', CELLS)) <= set(
        moved.get('workloads', CELLS))
    # a reader that finds nothing to read returns nothing
    if metric['source'] == 'device_trace':
        assert module.read({'trace': None}) is None
