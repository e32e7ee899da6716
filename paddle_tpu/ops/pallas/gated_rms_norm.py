"""Mamba-2's grouped, gated output norm (``ops/ssm_ops.py``:
``gated_rms_norm`` with ``groups`` G) as two Pallas TPU kernels, the
forward and its gradient.  Each reads its inputs once in the dtype they
are held in and writes each output once: the [rows, G, C / G] view XLA
lays out anew round every float32 intermediate is never formed.

Layout: X, Gate, Y, dY [N, C], the op's leading axes as rows (a free
reshape of [B, L, C]); the weight comes in as [1, C] float32.  A group is
a static slice of C / G lanes, a whole number of 128-lane tiles, so
nothing is reshaped.

Grid (N / ``block``,): a program takes ``block`` rows by all C channels
and walks them in tiles of 16 rows (a loop inside it; whole bf16 sublane
tiles), each group of a tile on its own.

* forward: ``xs = x silu(z)``, ``r = rsqrt(mean_g(xs^2) + eps)`` a row
  and group, ``Y = xs r w``.
* gradient: ``xs`` and ``r`` are made again from X and Gate (the forward
  keeps nothing); ``dxs = r (w dY) - xs r^3 mean_g(xs w dY)``, ``dX =
  dxs silu(z)``, ``dGate = dxs x silu'(z)``.  The weight's sums over the
  rows, ``dY xs r``, gather in a [16, C] float32 scratch and leave as one
  [1, C] row a program: the caller sums them.

Precision is the XLA form's: X and Gate are widened inside the kernels
(a float32 copy made outside may be computed by XLA from the value
before its bf16 rounding), and every statistic, factor and product is
float32.  The sigmoid is ``(tanh(z / 2) + 1) / 2``: one transcendental
and no divide.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ['gated_rms_norm', 'gated_rms_norm_grad', 'block', 'BLOCK_ELEMS',
           'LANES']

LANES = 128
BLOCK_ELEMS = 1 << 19   # a block: rows x channels
_TILE_ROWS = 16         # rows a step of a program's loop
_F32 = jnp.float32


def block(rows, channels):
    """Rows a program takes: the most whole 16-row tiles that divide
    ``rows`` and keep a block within ``BLOCK_ELEMS``; None where no such
    number exists."""
    fits = [r for r in range(_TILE_ROWS,
                             min(rows, BLOCK_ELEMS // channels) + 1,
                             _TILE_ROWS) if rows % r == 0]
    return max(fits) if fits else None


def _vmem_limit(rows, channels, itemsizes):
    """Double-buffered blocks of arrays of ``itemsizes`` bytes an element
    and 8 MiB for the scratch and the compiler's own."""
    need = 2 * rows * channels * sum(itemsizes) + (8 << 20)
    return max(16 << 20, -(-need // (1 << 20)) << 20)


def _sigmoid(z):
    return 0.5 * jnp.tanh(0.5 * z) + 0.5


def _groups(channels, groups):
    width = channels // groups
    return [pl.ds(g * width, width) for g in range(groups)]


def _normed(x_ref, z_ref, at, lanes, eps):
    """A tile's group: x, z, sigmoid(z), xs and its row factor r, f32."""
    x = x_ref[at, lanes].astype(_F32)
    z = z_ref[at, lanes].astype(_F32)
    sig = _sigmoid(z)
    xs = x * (z * sig)
    r = jax.lax.rsqrt(jnp.mean(xs * xs, axis=1, keepdims=True) + eps)
    return x, z, sig, xs, r


def _tiles(rows, body):
    def tile(i, carry):
        body(pl.ds(pl.multiple_of(i * _TILE_ROWS, _TILE_ROWS), _TILE_ROWS))
        return carry

    jax.lax.fori_loop(0, rows // _TILE_ROWS, tile, 0)


def _fwd_kernel(x_ref, z_ref, w_ref, y_ref, *, groups, eps):
    def body(at):
        for lanes in _groups(x_ref.shape[1], groups):
            _, _, _, xs, r = _normed(x_ref, z_ref, at, lanes, eps)
            y_ref[at, lanes] = (xs * r * w_ref[:, lanes]).astype(y_ref.dtype)

    _tiles(x_ref.shape[0], body)


def _bwd_kernel(x_ref, z_ref, w_ref, dy_ref, dx_ref, dz_ref, dw_ref,
                sums_scr, *, groups, eps):
    sums_scr[...] = jnp.zeros(sums_scr.shape, _F32)

    def body(at):
        for lanes in _groups(x_ref.shape[1], groups):
            x, z, sig, xs, r = _normed(x_ref, z_ref, at, lanes, eps)
            dy = dy_ref[at, lanes].astype(_F32)
            wdy = w_ref[:, lanes] * dy
            normed = xs * r
            sums_scr[:, lanes] += dy * normed
            dxs = r * wdy - normed * (r * r) * jnp.mean(
                xs * wdy, axis=1, keepdims=True)
            dx_ref[at, lanes] = (dxs * (z * sig)).astype(dx_ref.dtype)
            dz_ref[at, lanes] = (dxs * x * (sig * (1.0 + z * (1.0 - sig)))
                                 ).astype(dz_ref.dtype)

    _tiles(x_ref.shape[0], body)
    dw_ref[0] = jnp.sum(sums_scr[...], axis=0, keepdims=True)


# ---- the calls ---------------------------------------------------------

def _params(interpret, vmem):
    if interpret:
        return {'interpret': True}
    return {'compiler_params': pltpu.CompilerParams(
        dimension_semantics=('parallel', ), vmem_limit_bytes=vmem)}


def _rows_spec(rows, channels):
    return pl.BlockSpec((rows, channels), lambda i: (i, 0))


@functools.partial(jax.jit, static_argnames=('eps', 'groups', 'out_dtype',
                                             'interpret'))
def _fwd_call(x, z, w, eps, groups, out_dtype, interpret):
    n, c = x.shape
    rows = block(n, c)
    spec = _rows_spec(rows, c)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, groups=groups, eps=eps),
        grid=(n // rows, ),
        in_specs=[spec, spec, pl.BlockSpec((1, c), lambda i: (0, 0))],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, out_dtype),
        name='paddle_tpu_gated_rms_norm_fwd',
        **_params(interpret, _vmem_limit(rows, c, (
            x.dtype.itemsize, z.dtype.itemsize,
            jnp.dtype(out_dtype).itemsize))))(x, z, w)


@functools.partial(jax.jit, static_argnames=('eps', 'groups', 'interpret'))
def _bwd_call(x, z, w, dy, eps, groups, interpret):
    n, c = x.shape
    rows = block(n, c)
    spec = _rows_spec(rows, c)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, groups=groups, eps=eps),
        grid=(n // rows, ),
        in_specs=[spec, spec, pl.BlockSpec((1, c), lambda i: (0, 0)), spec],
        out_specs=[spec, spec, pl.BlockSpec((1, 1, c), lambda i: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(z.shape, z.dtype),
                   jax.ShapeDtypeStruct((n // rows, 1, c), _F32)],
        scratch_shapes=[pltpu.VMEM((_TILE_ROWS, c), _F32)],
        name='paddle_tpu_gated_rms_norm_bwd',
        **_params(interpret, _vmem_limit(rows, c, (
            x.dtype.itemsize, z.dtype.itemsize, dy.dtype.itemsize,
            x.dtype.itemsize, z.dtype.itemsize))))(x, z, w, dy)


def _rows(t):
    return t.reshape(-1, t.shape[-1])


def gated_rms_norm(x, gate, w, eps, groups, out_dtype, interpret=False):
    """Y in ``out_dtype``, x's shape: ``x silu(gate)`` normalised by its
    root-mean-square over each of ``groups`` equal runs of the last axis,
    times ``w`` [C]; float32 arithmetic.  interpret: Pallas interpret
    mode, for a CPU place only (the caller decides from the place it
    lowers for)."""
    y = _fwd_call(_rows(x), _rows(gate), w.astype(_F32).reshape(1, -1),
                  eps=float(eps), groups=int(groups),
                  out_dtype=jnp.dtype(out_dtype), interpret=bool(interpret))
    return y.reshape(x.shape)


def gated_rms_norm_grad(x, gate, w, dy, eps, groups, interpret=False):
    """(dX in x's dtype, dGate in gate's, dW [C] float32) of
    ``gated_rms_norm`` from its inputs and dY; the forward's factors are
    made again, nothing of it is kept."""
    dx, dz, dw = _bwd_call(_rows(x), _rows(gate),
                           w.astype(_F32).reshape(1, -1), _rows(dy),
                           eps=float(eps), groups=int(groups),
                           interpret=bool(interpret))
    return dx.reshape(x.shape), dz.reshape(gate.shape), jnp.sum(dw, (0, 1))
