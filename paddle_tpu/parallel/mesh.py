"""Device-mesh construction (the analog of the reference's
NCCLContextMap world setup, platform/nccl_helper.h:81-123 — but rendezvous
and topology are owned by the TPU runtime, not an id-exchange op)."""

import numpy as np

__all__ = ['make_mesh', 'mesh_axes', 'DeviceMesh']


def make_mesh(axes=None, devices=None):
    """Build a jax.sharding.Mesh.

    axes: dict axis_name -> size (sizes must multiply to len(devices));
          an axis size of -1 is inferred.  Default: {'dp': n_devices}.
    devices: default ``jax.devices()`` — JAX's default backend, so the
          mesh is the chips where there are chips and the (virtual)
          host devices under JAX_PLATFORMS=cpu, by JAX's own rule.
    """
    import jax
    from jax.sharding import Mesh
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if axes is None:
        axes = {'dp': n}
    names = list(axes.keys())
    sizes = list(axes.values())
    if -1 in sizes:
        known = int(np.prod([s for s in sizes if s != -1]))
        sizes[sizes.index(-1)] = n // known
    if int(np.prod(sizes)) != n:
        raise ValueError('mesh axes %s do not cover %d devices' %
                         (dict(zip(names, sizes)), n))
    arr = np.asarray(devices).reshape(sizes)
    return Mesh(arr, tuple(names))


def mesh_axes(mesh):
    return dict(zip(mesh.axis_names, mesh.devices.shape))


class DeviceMesh(object):
    """Thin named wrapper kept for API symmetry with places."""

    def __init__(self, axes=None, devices=None):
        self.mesh = make_mesh(axes, devices)

    def __enter__(self):
        self._ctx = self.mesh
        self._ctx.__enter__()
        return self

    def __exit__(self, *a):
        return self._ctx.__exit__(*a)
