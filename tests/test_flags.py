"""Flag registry, env bootstrap, and debug-mode tests (reference parity:
FLAGS_* gflags surfaced via __init__.py:121-141 tryfromenv;
FLAGS_check_nan_inf post-op scan in framework/operator.cc;
FLAGS_cpu_deterministic pinned by dist tests test_dist_base.py:233)."""

import os

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import flags


@pytest.fixture(autouse=True)
def _restore_flags():
    saved = {n: flags.get_flag(n) for n in flags.TRYFROMENV}
    yield
    for n, v in saved.items():
        flags.set_flag(n, v)


def test_define_get_set_roundtrip():
    assert flags.get_flag('check_nan_inf') is False
    flags.set_flag('check_nan_inf', True)
    assert flags.FLAGS.check_nan_inf is True
    flags.FLAGS.check_nan_inf = False
    assert flags.get_flag('check_nan_inf') is False
    flags.set_flag('paddle_num_threads', '4')
    assert flags.FLAGS.paddle_num_threads == 4
    flags.set_flag('fraction_of_gpu_memory_to_use', '0.5')
    assert flags.FLAGS.fraction_of_gpu_memory_to_use == 0.5
    with pytest.raises(KeyError):
        flags.set_flag('no_such_flag', 1)
    with pytest.raises(ValueError):
        flags.set_flag('check_nan_inf', 'not-a-bool')


def test_env_bootstrap_tryfromenv(monkeypatch):
    monkeypatch.setenv('FLAGS_benchmark', '1')
    monkeypatch.setenv('FLAGS_paddle_num_threads', '8')
    monkeypatch.setenv('FLAGS_rpc_deadline', '5000')
    flags.try_from_env(flags.TRYFROMENV)
    assert flags.FLAGS.benchmark is True
    assert flags.FLAGS.paddle_num_threads == 8
    assert flags.FLAGS.rpc_deadline == 5000
    # absent vars keep their values
    monkeypatch.delenv('FLAGS_benchmark')
    flags.try_from_env(['benchmark'])
    assert flags.FLAGS.benchmark is True


def _nan_program():
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        x = fluid.layers.data(name='x', shape=[4], dtype='float32')
        y = fluid.layers.log(x)  # log(-1) -> NaN
        out = fluid.layers.mean(y)
    return prog, startup, out


def test_check_nan_inf_raises_on_jit_path():
    flags.FLAGS.check_nan_inf = True
    prog, startup, out = _nan_program()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.core.Scope()):
        exe.run(startup)
        with pytest.raises(Exception) as ei:
            exe.run(prog, feed={'x': -np.ones((2, 4), np.float32)},
                    fetch_list=[out])
    assert 'nan' in str(ei.value).lower()


def test_check_nan_inf_off_lets_nan_through():
    flags.FLAGS.check_nan_inf = False
    prog, startup, out = _nan_program()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.core.Scope()):
        exe.run(startup)
        r, = exe.run(prog, feed={'x': -np.ones((2, 4), np.float32)},
                     fetch_list=[out])
    assert np.isnan(np.asarray(r)).all()


def test_check_nan_inf_eager_path_names_op():
    """Host op in the block forces the eager path, which attributes the
    failure to the producing op like the reference post-op scan."""
    flags.FLAGS.check_nan_inf = True
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        x = fluid.layers.data(name='x', shape=[4], dtype='float32')
        y = fluid.layers.log(x)
        # host 'print' op forces eager execution of the block
        prog.current_block().append_op(
            type='print', inputs={'In': [y]}, outputs={},
            attrs={'message': ''})
        out = fluid.layers.mean(y)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.core.Scope()):
        exe.run(startup)
        # either our per-op scan (RuntimeError naming the op) or
        # jax_debug_nans (FloatingPointError naming the primitive) fires,
        # whichever sees the NaN first
        with pytest.raises((RuntimeError, FloatingPointError)) as ei:
            exe.run(prog, feed={'x': -np.ones((2, 4), np.float32)},
                    fetch_list=[out])
    msg = str(ei.value).lower()
    assert 'log' in msg or 'nan' in msg


def test_cpu_deterministic_pins_rng_stream():
    """Two executors that ran different things beforehand still produce an
    identical dropout mask stream for the same program under
    FLAGS_cpu_deterministic."""
    flags.FLAGS.cpu_deterministic = True
    prog, startup = fluid.Program(), fluid.Program()
    prog.random_seed = 7
    with fluid.program_guard(prog, startup):
        x = fluid.layers.data(name='x', shape=[64], dtype='float32')
        out = fluid.layers.dropout(x, dropout_prob=0.5)
    xv = np.ones((8, 64), np.float32)

    def run_fresh(warmup):
        exe = fluid.Executor(fluid.CPUPlace())
        with fluid.scope_guard(fluid.core.Scope()):
            exe.run(startup)
            if warmup:  # perturb the executor's would-be shared stream
                wp, ws = fluid.Program(), fluid.Program()
                with fluid.program_guard(wp, ws):
                    z = fluid.layers.data(name='z', shape=[4],
                                          dtype='float32')
                    zo = fluid.layers.dropout(z, dropout_prob=0.5)
                exe.run(wp, feed={'z': np.ones((2, 4), np.float32)},
                        fetch_list=[zo])
            r, = exe.run(prog, feed={'x': xv}, fetch_list=[out])
        return np.asarray(r)

    a = run_fresh(warmup=False)
    b = run_fresh(warmup=True)
    np.testing.assert_array_equal(a, b)


def test_compile_cache_flag_places_cache_when_env_unset(tmp_path,
                                                        monkeypatch):
    """JAX_COMPILATION_CACHE_DIR unset: FLAGS_xla_compile_cache_dir
    points jax at a persistent on-disk compilation cache, clearing it
    detaches the cache, and enable_compile_cache() with no flag set
    resolves to the ONE fixed in-checkout directory (never a temp dir,
    a pid or a timestamp — the path is part of the cache key)."""
    import jax
    monkeypatch.delenv(flags.COMPILE_CACHE_ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    threshold = jax.config.jax_persistent_cache_min_compile_time_secs
    cache = str(tmp_path / 'xla_cache')
    try:
        flags.FLAGS.xla_compile_cache_dir = cache
        assert jax.config.jax_compilation_cache_dir == cache
        assert flags.compile_cache_dir() == cache
        assert os.path.isdir(cache)  # the setter creates it
        # an explicit flag wins over the in-checkout default
        assert flags.enable_compile_cache() == cache
        flags.FLAGS.xla_compile_cache_dir = ''
        assert jax.config.jax_compilation_cache_dir is None
        assert flags.compile_cache_dir() is None
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert flags.DEFAULT_COMPILE_CACHE_DIR == os.path.join(
            repo, '.jax_cache')
        assert flags.enable_compile_cache() == \
            flags.DEFAULT_COMPILE_CACHE_DIR
        with open(os.path.join(repo, '.gitignore')) as f:
            assert '.jax_cache/' in f.read().split()
    finally:
        flags.FLAGS.xla_compile_cache_dir = ''
        jax.config.update('jax_compilation_cache_dir', before)
        jax.config.update('jax_persistent_cache_min_compile_time_secs',
                          threshold)


def test_compile_cache_env_wins_and_nothing_is_written(tmp_path,
                                                       monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: the cache was placed from outside
    (JAX reads the variable itself, at import), so OUR code performs no
    jax_compilation_cache_dir update — not from the flag, not from the
    flag's '' reset (which used to write None over it), not from
    enable_compile_cache()."""
    import jax
    monkeypatch.setenv(flags.COMPILE_CACHE_ENV, str(tmp_path / 'outside'))
    threshold = jax.config.jax_persistent_cache_min_compile_time_secs
    writes = []
    real_update = jax.config.update

    def spy(name, value):
        writes.append(name)
        return real_update(name, value)

    monkeypatch.setattr(jax.config, 'update', spy)
    held = jax.config.jax_compilation_cache_dir
    try:
        flags.FLAGS.xla_compile_cache_dir = str(tmp_path / 'ours')
        flags.FLAGS.xla_compile_cache_dir = ''
        assert flags.enable_compile_cache() == held
    finally:
        flags.FLAGS.xla_compile_cache_dir = ''
        real_update('jax_persistent_cache_min_compile_time_secs',
                    threshold)
    assert 'jax_compilation_cache_dir' not in writes, writes
    assert jax.config.jax_compilation_cache_dir == held
    assert not os.path.exists(str(tmp_path / 'ours'))
