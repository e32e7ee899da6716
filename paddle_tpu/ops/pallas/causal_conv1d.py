"""Mamba-2's depthwise convolution over time (``ops/ssm_ops.py``:
``causal_conv1d``) as two Pallas TPU kernels, the forward and its
gradient.  Each reads its inputs once in the layout they have and writes
each output once: no padded copy of the input and no shifted copy a tap
reaches HBM.

Layout: the op's own, X, Y, dY, dX [B, L, C] row-major; the filter comes
in as [K, C] f32 (a tap a row of lanes), the bias as [1, C] f32.

Grid (B, C / tc): a program takes the whole length of ``tc`` channels of
one sequence (``block``) and walks it in tiles of ``rows`` positions by
128 channels (a loop inside it; 16 f32 vregs a value).  A tile of X in
f32 goes below the 16 positions before it (zeros before the start), and
the tap that reads ``j`` positions back is that column rolled down ``j``
sublanes, the tile's rows of it: no scratch, no unaligned read.

* forward: ``pre = bias + sum_k w[k] x[t - K + 1 + k]``, then SiLU if
  asked; f32 arithmetic, Y in X's dtype.
* gradient: the tiles in reverse.  ``pre`` is made again from X (the
  forward keeps nothing), ``dpre = dY silu'(pre)``, and
  ``dX[t] = sum_k dpre[t + K - 1 - k] w[k]`` reads the tile's ``dpre``
  over the first 16 of the tile after it, which a VMEM scratch keeps from
  the step before in the loop (zeros after the end), rolled up.  The
  filter's and the bias's sums over the tile's positions gather in a
  second scratch and leave as one [K + 1, tc] f32 block a program: the
  caller sums the batch rows.

The sigmoid is ``(tanh(x / 2) + 1) / 2``: one transcendental and no
divide (``jax.nn.sigmoid``'s divide cost the forward a third of its time
on the v5e).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ['causal_conv1d', 'causal_conv1d_grad', 'block', 'BLOCK_ELEMS',
           'LANES', 'MAX_TAPS']

LANES = 128
MAX_TAPS = 8
_BEFORE = 16          # positions kept before a tile: K - 1 at most, whole
#                       bf16 sublane tiles
BLOCK_ELEMS = 1 << 20   # X's block: positions x channels
_TILE_ROWS = 128        # positions a tile: [128, 128] f32 is 16 vregs
_F32 = jnp.float32


def block(length, channels):
    """[positions, channels] of X a program takes: the whole length and
    the widest multiple of 128 channels that divides ``channels`` and
    keeps the block within ``BLOCK_ELEMS``."""
    return [length, max(w for w in range(LANES, channels + 1, LANES)
                        if channels % w == 0 and w * length <= BLOCK_ELEMS)]


def _rows(length):
    """Positions a tile of the loop takes: whole bf16 sublane tiles that
    divide the length, up to ``_TILE_ROWS``."""
    return max(r for r in range(_BEFORE, min(_TILE_ROWS, length) + 1,
                                _BEFORE) if length % r == 0)


def _vmem_limit(length, width, arrays, itemsize):
    """Double-buffered blocks of ``arrays`` [length, width] arrays and
    8 MiB for the scratch and the compiler's own."""
    need = 2 * arrays * length * width * itemsize + (8 << 20)
    return max(16 << 20, -(-need // (1 << 20)) << 20)


def _sigmoid(pre):
    return 0.5 * jnp.tanh(0.5 * pre) + 0.5


def _column(x_ref, start, i, rows, lanes):
    """Tile ``i`` of X's column ``lanes`` in f32 below the 16 positions
    before it (zeros before the start): [16 + rows, 128]."""
    at = pl.multiple_of(jnp.maximum(start - _BEFORE, 0), _BEFORE)
    before = x_ref[0, pl.ds(at, _BEFORE), lanes].astype(_F32)
    tile = x_ref[0, pl.ds(start, rows), lanes].astype(_F32)
    return jnp.concatenate([jnp.where(i > 0, before, 0.0), tile], axis=0)


def _back(column, j):
    """The tile's positions of ``column`` moved down ``j`` sublanes: the
    value ``j`` positions back."""
    return pltpu.roll(column, j, 0)[_BEFORE:] if j else column[_BEFORE:]


def _pre(column, w_ref, b_ref, lanes):
    taps = w_ref.shape[0]
    pre = b_ref[:, lanes]
    for k in range(taps):
        pre = pre + w_ref[k:k + 1, lanes] * _back(column, taps - 1 - k)
    return pre


def _columns(width):
    return [pl.ds(c, LANES) for c in range(0, width, LANES)]


# ---- forward -----------------------------------------------------------

def _fwd_kernel(x_ref, w_ref, b_ref, y_ref, *, act, rows):
    def tile(i, carry):
        start = pl.multiple_of(i * rows, _BEFORE)
        for lanes in _columns(x_ref.shape[2]):
            y = _pre(_column(x_ref, start, i, rows, lanes), w_ref, b_ref,
                     lanes)
            if act:
                y = y * _sigmoid(y)
            y_ref[0, pl.ds(start, rows), lanes] = y.astype(y_ref.dtype)
        return carry

    jax.lax.fori_loop(0, x_ref.shape[1] // rows, tile, 0)


# ---- gradient ----------------------------------------------------------

def _bwd_kernel(x_ref, w_ref, b_ref, dy_ref, dx_ref, dwb_ref, after_scr,
                sums_scr, *, act, rows):
    taps = w_ref.shape[0]
    n, size = x_ref.shape[1] // rows, rows + _BEFORE
    # the first 16 positions' dpre of the tile after this one: none after
    # the end
    after_scr[...] = jnp.zeros(after_scr.shape, _F32)
    sums_scr[...] = jnp.zeros(sums_scr.shape, _F32)

    def tile(j, carry):
        i = n - 1 - j
        start = pl.multiple_of(i * rows, _BEFORE)
        for lanes in _columns(x_ref.shape[2]):
            column = _column(x_ref, start, i, rows, lanes)
            dpre = dy_ref[0, pl.ds(start, rows), lanes].astype(_F32)
            if act:
                pre = _pre(column, w_ref, b_ref, lanes)
                sig = _sigmoid(pre)
                dpre = dpre * (sig * (1.0 + pre * (1.0 - sig)))
            ahead = jnp.concatenate([dpre, after_scr[:, lanes]], axis=0)
            dx = w_ref[taps - 1:taps, lanes] * dpre
            for k in range(taps - 1):
                # dpre K - 1 - k positions ahead: rolled up that far
                dx = dx + w_ref[k:k + 1, lanes] * pltpu.roll(
                    ahead, size - (taps - 1 - k), 0)[:rows]
            dx_ref[0, pl.ds(start, rows), lanes] = dx.astype(dx_ref.dtype)
            after_scr[:, lanes] = dpre[:_BEFORE]
            for k in range(taps):
                sums_scr[k:k + 1, lanes] += jnp.sum(
                    dpre * _back(column, taps - 1 - k), axis=0,
                    keepdims=True)
            sums_scr[taps:taps + 1, lanes] += jnp.sum(dpre, axis=0,
                                                      keepdims=True)
        return carry

    jax.lax.fori_loop(0, n, tile, 0)
    dwb_ref[0] = sums_scr[:taps + 1]


# ---- the calls ---------------------------------------------------------

def _params(interpret, vmem):
    if interpret:
        return {'interpret': True}
    return {'compiler_params': pltpu.CompilerParams(
        dimension_semantics=('parallel', 'parallel'),
        vmem_limit_bytes=vmem)}


def _specs(x, taps):
    length, width = block(*x.shape[1:])
    return width, dict(
        x=pl.BlockSpec((1, length, width), lambda b, c: (b, 0, c)),
        w=pl.BlockSpec((taps, width), lambda b, c: (0, c)),
        bias=pl.BlockSpec((1, width), lambda b, c: (0, c)),
        sums=pl.BlockSpec((1, taps + 1, width), lambda b, c: (b, 0, c)))


@functools.partial(jax.jit, static_argnames=('act', 'interpret'))
def _fwd_call(x, w, bias, act, interpret):
    b, length, channels = x.shape
    width, sp = _specs(x, w.shape[0])
    return pl.pallas_call(
        functools.partial(_fwd_kernel, act=act, rows=_rows(length)),
        grid=(b, channels // width),
        in_specs=[sp['x'], sp['w'], sp['bias']], out_specs=sp['x'],
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        name='paddle_tpu_causal_conv1d_fwd',
        **_params(interpret, _vmem_limit(length, width, 2,
                                         x.dtype.itemsize)))(x, w, bias)


@functools.partial(jax.jit, static_argnames=('act', 'interpret'))
def _bwd_call(x, w, bias, dy, act, interpret):
    b, length, channels = x.shape
    taps = w.shape[0]
    width, sp = _specs(x, taps)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, act=act, rows=_rows(length)),
        grid=(b, channels // width),
        in_specs=[sp['x'], sp['w'], sp['bias'], sp['x']],
        out_specs=[sp['x'], sp['sums']],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((b, taps + 1, channels), _F32)],
        scratch_shapes=[pltpu.VMEM((_BEFORE, width), _F32),
                        pltpu.VMEM((-(-(taps + 1) // 8) * 8, width), _F32)],
        name='paddle_tpu_causal_conv1d_bwd',
        **_params(interpret, _vmem_limit(length, width, 3,
                                         x.dtype.itemsize)))(
            x, w, bias, dy)


def _taps_rows(w, bias):
    """The filter [C, K] as the kernels take it, a tap a row [K, C], and
    the bias as a row [1, C], both f32."""
    return jnp.transpose(w.astype(_F32)), bias.astype(_F32).reshape(1, -1)


def causal_conv1d(x, w, bias, act='', interpret=False):
    """Y [B, L, C] in x's dtype: the depthwise convolution of x over its
    last K positions (zeros before the start), f32, plus the bias, SiLU'd
    if ``act == 'silu'``.  x [B, L, C], w [C, K] (tap K - 1 weighs the
    current position), bias [C].  interpret: Pallas interpret mode, for a
    CPU place only (the caller decides from the place it lowers for)."""
    return _fwd_call(x, *_taps_rows(w, bias), act=act == 'silu',
                     interpret=bool(interpret))


def causal_conv1d_grad(x, w, bias, dy, act='', interpret=False):
    """(dX in x's dtype, dW [C, K] f32, dBias [C] f32) of
    ``causal_conv1d`` from its inputs and dY; the forward is made again
    from x, nothing of it is kept."""
    taps = w.shape[1]
    dx, sums = _bwd_call(x, *_taps_rows(w, bias), dy.astype(x.dtype),
                         act=act == 'silu', interpret=bool(interpret))
    sums = jnp.sum(sums, axis=0)
    return dx, jnp.transpose(sums[:taps]), sums[taps]
