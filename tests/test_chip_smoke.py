"""chip_smoke.py off the chip: the same two phases at the tiny width on the
CPU mesh (selected by the explicit argument's config, never by
detection), its refusal to run without a TPU, and the device-selection
contract it asserts on (typed TPUPlace error, the one place-choosing
function, the device block in engine/registry snapshots)."""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import serving
from paddle_tpu.fluid import core

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402


def _run_smoke(args, tmp_path, **env):
    env = dict(os.environ, JAX_PLATFORMS='cpu',
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / 'cache'), **env)
    env.pop('XLA_FLAGS', None)
    return subprocess.run(
        [sys.executable, os.path.join(REPO, 'chip_smoke.py')] + args,
        env=env, cwd=str(tmp_path), capture_output=True, text=True,
        timeout=300)


def test_smoke_cpu_tiny_end_to_end(tmp_path):
    """``python chip_smoke.py --cpu-tiny``: both phases (Executor leg,
    generation serving from two client threads) in one process, and the
    last stdout line is the object the driver parses — EXACTLY the keys
    ``ok`` and ``device`` {platform, kind, count}, nothing else (the
    first chip check was refused for extra keys on that line)."""
    proc = _run_smoke(['--cpu-tiny'], tmp_path)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.splitlines()
    assert lines[0].startswith('chip_smoke: platform=cpu device_kind=cpu')
    result = json.loads(lines[-1])
    assert result == {'ok': True, 'device': {
        'platform': 'cpu', 'kind': 'cpu', 'count': 1}}
    assert type(result['device']['count']) is int
    tag = 'chip_smoke: summary '
    assert lines[-2].startswith(tag)
    summary = json.loads(lines[-2][len(tag):])
    assert summary['width'] == 'tiny'
    assert summary['compile_cache']['dir'] == str(tmp_path / 'cache')
    train, serve = summary['trainer'], summary['server']
    assert train['executor'] == 'Executor'
    assert train['devices'] == train['feed_devices'] == 1
    assert train['dispatches'] == chip_smoke.DISPATCHES
    assert np.isfinite(train['losses']).all()
    assert train['losses'][-1] < train['losses'][0]
    assert serve['requests'] == 3 * len(chip_smoke.PROMPT_LENS)
    assert serve['decode_dispatches'] > 0 and serve['tokens'] > 0
    assert serve['engine_device']['platform'] == 'cpu'


def test_trainer_phase_tiny_mesh_leg():
    """ParallelExecutor leg on a dp=4 mesh of the suite's virtual CPU
    devices: loss falls, every state array and the scanned feed block are
    laid out over exactly the four devices (the phase raises
    otherwise)."""
    rec = chip_smoke.train_phase(chip_smoke.TINY['train'],
                                 jax.devices('cpu')[:4])
    assert rec['executor'] == 'ParallelExecutor'
    assert rec['devices'] == rec['feed_devices'] == 4
    assert rec['global_batch'] == \
        chip_smoke.TINY['train']['batch_per_chip'] * 4
    assert rec['dispatches'] == chip_smoke.DISPATCHES
    assert np.isfinite(rec['losses']).all()
    assert rec['losses'][-1] < rec['losses'][0]


def test_smoke_refuses_to_run_without_a_tpu(tmp_path):
    """``JAX_PLATFORMS=cpu python chip_smoke.py``: states the platform it
    found first, exits non-zero, prints no result line, starts no
    child."""
    proc = _run_smoke([], tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.splitlines()[0].startswith(
        'chip_smoke: platform=cpu device_kind=cpu count=')
    assert "JAX found platform 'cpu'" in proc.stderr
    for line in proc.stdout.splitlines():
        assert not line.startswith('{'), line
    import inspect
    assert 'subprocess' not in inspect.getsource(chip_smoke)


def test_tpu_place_raises_typed_error_without_accelerator():
    with pytest.raises(core.NoAcceleratorError, match="'cpu'"):
        fluid.TPUPlace().jax_device()
    with pytest.raises(core.NoAcceleratorError):
        fluid.CUDAPlace(0).jax_device()
    assert core.is_compiled_with_tpu() is False
    assert core.get_tpu_device_count() == 0


def test_default_place_is_cpu_here_and_says_so():
    """THE place-choosing function returns CPUPlace on a CPU-only
    backend, and what it chose is visible wherever a server reports
    itself."""
    place = fluid.default_place()
    assert place == fluid.CPUPlace()
    assert core.device_info([place.jax_device()]) == {
        'platform': 'cpu', 'device_kind': 'cpu', 'count': 1}
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        x = fluid.layers.data('x', shape=[4], dtype='float32')
        pred = fluid.layers.fc(x, 3, act='softmax')
    scope = core.Scope()
    with fluid.scope_guard(scope):
        fluid.Executor(place).run(startup)
    reg = serving.ModelRegistry()
    reg.load('m', program=prog, feed_names=['x'], fetch_list=[pred],
             scope=scope)
    try:
        assert reg.status()['device']['platform'] == 'cpu'
        assert reg.metrics()['models']['m']['device'] == {
            'platform': 'cpu', 'device_kind': 'cpu', 'count': 1}
    finally:
        reg.stop()


def test_mesh_executor_place_comes_from_the_mesh():
    """_SpmdCompiledBlock derives its place from the mesh's devices (it
    used to build TPUPlace() and lean on the CPU fallback)."""
    from paddle_tpu import parallel
    from paddle_tpu.fluid.parallel_executor import _SpmdCompiledBlock
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        x = fluid.layers.data('x', shape=[4], dtype='float32')
        out = fluid.layers.fc(x, 3)
    mesh = parallel.make_mesh({'dp': 2}, jax.devices('cpu')[:2])
    block = _SpmdCompiledBlock(prog, 0, ['x'], [out.name], mesh,
                               core.Scope())
    assert block.place == fluid.CPUPlace()
    assert core.place_of(jax.devices('cpu')[3]) == fluid.CPUPlace()
