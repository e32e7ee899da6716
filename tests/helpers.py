"""Shared test helpers."""

import numpy as np

import paddle_tpu.fluid as fluid


def lod_feed(rows, dtype, dim=1):
    """rows: list of per-sequence lists -> LoDTensor."""
    flat = np.concatenate([np.asarray(r, dtype).reshape(-1, dim)
                           for r in rows])
    lt = fluid.core.LoDTensor(flat)
    lt.set_recursive_sequence_lengths([[len(r) for r in rows]])
    return lt


def assert_close_across_executables(got, want, ulps=8, err_msg=''):
    """``got`` and ``want`` come from two different XLA executables of
    the same program (a batched or chunked lane against a per-request
    run).  XLA gives no bitwise contract across executables: another
    batch shape may vectorize or associate a reduction differently, and
    the last bit of a float32 sum moves (0.19931597 against 0.19931595
    in a softmax).  Floats agree within ``ulps`` units in the last place
    of the array's largest magnitude — a reduction's rounding scales
    with its terms, not its result; shapes, and anything that is not a
    float, agree exactly.  Where both sides run ONE executable, tests
    keep exact equality."""
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape, (err_msg, got.shape, want.shape)
    if want.dtype.kind != 'f':
        np.testing.assert_array_equal(got, want, err_msg=str(err_msg))
        return
    scale = float(np.max(np.abs(want))) if want.size else 0.0
    np.testing.assert_allclose(
        got, want, rtol=0,
        atol=ulps * float(np.finfo(want.dtype).eps) * scale,
        err_msg=str(err_msg))
