"""Typed module-level flags with environment-variable bootstrap.

TPU-native analog of the reference's gflags machinery: C++ modules
DEFINE_* flags (e.g. FLAGS_check_nan_inf in framework/operator.cc,
FLAGS_cpu_deterministic in details/build_strategy.h:41), and the Python
package bootstraps a whitelist of them from environment variables at
import via core.init_gflags(["--tryfromenv=..."])
(python/paddle/fluid/__init__.py:121-141, platform/init.cc:36).

Here flags are plain typed Python descriptors in one registry; the env
bootstrap reads the same ``FLAGS_<name>`` variable names the reference
honors, so launcher scripts keep working.  Flags with side effects (the
NaN debugger) apply them in their setter.
"""

import os

__all__ = ['DEFINE_bool', 'DEFINE_int32', 'DEFINE_double', 'DEFINE_string',
           'get_flag', 'set_flag', 'try_from_env', 'FLAGS',
           'enable_compile_cache', 'compile_cache_dir']

_TRUE = ('1', 'true', 'yes', 'on')
_FALSE = ('0', 'false', 'no', 'off', '')


class _Flag(object):
    __slots__ = ('name', 'type', 'value', 'default', 'help', 'on_set')

    def __init__(self, name, type_, default, help_, on_set=None):
        self.name = name
        self.type = type_
        self.value = default
        self.default = default
        self.help = help_
        self.on_set = on_set


_registry = {}


def _define(name, type_, default, help_, on_set=None):
    if name in _registry:
        raise ValueError('flag %r already defined' % name)
    _registry[name] = _Flag(name, type_, default, help_, on_set)


def DEFINE_bool(name, default, help_=''):
    _define(name, bool, default, help_)


def DEFINE_int32(name, default, help_=''):
    _define(name, int, default, help_)


def DEFINE_double(name, default, help_=''):
    _define(name, float, default, help_)


def DEFINE_string(name, default, help_=''):
    _define(name, str, default, help_)


def _coerce(flag, value):
    if flag.type is bool:
        if isinstance(value, str):
            v = value.strip().lower()
            if v in _TRUE:
                return True
            if v in _FALSE:
                return False
            raise ValueError('invalid bool for flag %r: %r'
                             % (flag.name, value))
        return bool(value)
    return flag.type(value)


def get_flag(name):
    return _registry[name].value


def set_flag(name, value):
    flag = _registry[name]
    new = _coerce(flag, value)
    # on_set doubles as validator: a raise must leave the old value
    if flag.on_set is not None:
        flag.on_set(new)
    flag.value = new


def on_set(name, fn):
    """Attach a side-effect callback invoked on every set (and once now if
    the flag already differs from its default)."""
    flag = _registry[name]
    flag.on_set = fn
    if flag.value != flag.default:
        fn(flag.value)


def try_from_env(names):
    """Read ``FLAGS_<name>`` env vars for each whitelisted name — the
    reference's --tryfromenv contract: absent vars keep defaults, present
    ones are parsed per the flag's type."""
    for name in names:
        env = os.environ.get('FLAGS_' + name)
        if env is not None:
            set_flag(name, env)


class _FlagsView(object):
    """Attribute-style access mirroring gflags' FLAGS object."""

    def __getattr__(self, name):
        try:
            return _registry[name].value
        except KeyError:
            raise AttributeError('no flag named %r' % name)

    def __setattr__(self, name, value):
        set_flag(name, value)


FLAGS = _FlagsView()


def _toggle_jax_debug_nans(enabled):
    # the in-jit half of check_nan_inf: XLA inserts checks after every
    # primitive so failures name the op, like the reference's post-op scan
    # in operator.cc
    import jax
    jax.config.update('jax_debug_nans', bool(enabled))


# ---------------------------------------------------------------------------
# The flag set.  Names follow the reference's FLAGS_* spelling so existing
# launcher environments keep working; GPU-memory flags are accepted but
# inert (device memory belongs to PJRT on TPU) and documented as such.
# ---------------------------------------------------------------------------

DEFINE_bool('check_nan_inf', False,
            'Scan outputs for NaN/Inf after execution (reference '
            'operator.cc post-op scan); inside jit uses jax_debug_nans '
            'for per-op attribution.')
DEFINE_bool('cpu_deterministic', False,
            'Force deterministic execution: pins the program RNG stream '
            'and is asserted by distributed tests '
            '(reference build_strategy.h:41, test_dist_base.py:233).')
DEFINE_bool('cudnn_deterministic', False,
            'Accepted for reference launcher parity; XLA:TPU kernels are '
            'deterministic by construction so this is an alias of '
            'cpu_deterministic for the compiled path.')
DEFINE_bool('benchmark', False,
            'Log per-run wall time and fetch sizes (reference '
            'executor.cc:335 per-op sync + memory log).')
DEFINE_double('fraction_of_gpu_memory_to_use', 0.92,
              'Inert on TPU: device memory is managed by PJRT.')
DEFINE_bool('use_pinned_memory', True,
            'Use the pooled host staging allocator (csrc/host_pool.cc) '
            'for feed buffers.')
DEFINE_bool('init_allocated_mem', False,
            'Fill host-pool allocations with a debug pattern.')
DEFINE_bool('free_idle_memory', False,
            'Aggressively trim the host staging pool.')
DEFINE_int32('paddle_num_threads', 1,
             'Host-side worker threads for readers and host ops.')
DEFINE_int32('rpc_deadline', 180000,
             'Distributed control-plane timeout in ms '
             '(jax.distributed initialize timeout).')
DEFINE_bool('eager_delete_scope', True,
            'Drop executor kid scopes eagerly (scope lifetimes are '
            'Python-managed here; kept for launcher parity).')
DEFINE_string('xla_compile_cache_dir', '',
              'Persistent XLA compilation cache directory for a process '
              'whose environment does not place one.  '
              'JAX_COMPILATION_CACHE_DIR, where set, wins: JAX reads it '
              'itself and this flag then writes nothing '
              '(enable_compile_cache has the contract).  Empty: no '
              'in-code cache.')
DEFINE_bool('cost_accounting', False,
            'Capture XLA cost_analysis FLOPs + memory_analysis bytes '
            'for every executable the executors dispatch '
            '(fluid.trace.analyze_cost -> Executor.cost_report()): the '
            'per-executable ground truth behind achieved-MFU serving '
            'metrics and bench.py MFU.  Off by default.  The analysis '
            'goes through fluid.trace.aot_compile, whose abstract '
            'arguments carry the real ones\' shardings: JAX\'s own '
            'caches then serve the dispatch the executable the analysis '
            'compiled (or the other way round), and capture costs no '
            'second XLA compile.')

on_set('check_nan_inf', _toggle_jax_debug_nans)


COMPILE_CACHE_ENV = 'JAX_COMPILATION_CACHE_DIR'
# the one in-code default: fixed (the path is part of the cache key, so
# a directory that moves never hits), inside the checkout, git-ignored
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), '.jax_cache')


def _apply_xla_compile_cache(path):
    if os.environ.get(COMPILE_CACHE_ENV):
        return  # placed from outside: JAX already holds that directory
    import jax
    if path:
        os.makedirs(path, exist_ok=True)
    jax.config.update('jax_compilation_cache_dir', path or None)


def enable_compile_cache():
    """Entry scripts (chip_smoke.py, bench.py children, tools) call this
    once.  Where JAX_COMPILATION_CACHE_DIR is set the cache lives there
    and nothing here touches ``jax_compilation_cache_dir``; where it is
    unset the cache is DEFAULT_COMPILE_CACHE_DIR.  Every compile is
    cached, not only the slow ones: short-lived processes re-pay each
    small serving executable otherwise.  Returns the resolved
    directory."""
    import jax
    if not os.environ.get(COMPILE_CACHE_ENV) \
            and not get_flag('xla_compile_cache_dir'):
        set_flag('xla_compile_cache_dir', DEFAULT_COMPILE_CACHE_DIR)
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
    return compile_cache_dir()


def compile_cache_dir():
    """The directory JAX's persistent compile cache really uses (None:
    no cache) — whoever placed it, the environment or the flag."""
    import jax
    return jax.config.jax_compilation_cache_dir


on_set('xla_compile_cache_dir', _apply_xla_compile_cache)


# the reference whitelists which flags may come from the environment
# (__init__.py:121-141); everything defined above is eligible here
TRYFROMENV = tuple(sorted(_registry))
