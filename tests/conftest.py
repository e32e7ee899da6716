"""Test configuration: force an 8-device virtual CPU mesh so SPMD tests run
without TPU hardware (the driver separately dry-runs multi-chip compile)."""

import os

# force (not setdefault), BEFORE jax is imported — JAX reads
# JAX_PLATFORMS once, at import: the suite must run on the deterministic
# 8-device virtual CPU mesh whatever the ambient environment names, and
# it must never take the chip from a process that needs it
os.environ['JAX_PLATFORMS'] = 'cpu'
flags = os.environ.get('XLA_FLAGS', '')
if '--xla_force_host_platform_device_count' not in flags:
    os.environ['XLA_FLAGS'] = (
        flags + ' --xla_force_host_platform_device_count=8').strip()


def pytest_configure(config):
    # the tier-1 run is `-m 'not slow'` (ROADMAP): sustained load
    # harnesses and other long soaks carry @pytest.mark.slow so the
    # suite stays inside its wall-clock budget
    config.addinivalue_line(
        'markers', "slow: excluded from the tier-1 -m 'not slow' run")
