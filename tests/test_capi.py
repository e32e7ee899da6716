"""C inference API tests (reference parity: legacy/capi — pure-C inference
embedding; paddle/legacy/capi/tests).  Exercises the C ABI both in-process
(ctypes over the already-running interpreter) and as a standalone C
program embedding CPython."""

import ctypes
import os
import subprocess

import numpy as np
import pytest

import paddle_tpu.fluid as fluid

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CAPI_SO = os.path.join(REPO, 'paddle_tpu', 'runtime',
                       'libpaddle_tpu_capi.so')


def _build_capi():
    if not os.path.exists(CAPI_SO):
        subprocess.run(['make', 'capi'], cwd=os.path.join(REPO, 'csrc'),
                       check=True, capture_output=True, timeout=180)
    return os.path.exists(CAPI_SO)


def _run_demo(argv, timeout=120):
    """Run an embedded-CPython demo binary on the CPU backend
    (JAX_PLATFORMS=cpu in the child's environment is JAX's own
    contract; the demo process embeds the interpreter, so it is the
    one process that would otherwise take the chip)."""
    env = dict(os.environ)
    env['PYTHONPATH'] = REPO + os.pathsep + env.get('PYTHONPATH', '')
    env['LD_LIBRARY_PATH'] = (os.path.dirname(CAPI_SO) + os.pathsep +
                              env.get('LD_LIBRARY_PATH', ''))
    env['JAX_PLATFORMS'] = 'cpu'
    env.pop('XLA_FLAGS', None)
    return subprocess.run(argv, capture_output=True, text=True, env=env,
                          timeout=timeout)


def _save_toy_model(model_dir):
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        x = fluid.layers.data(name='x', shape=[4], dtype='float32')
        y = fluid.layers.fc(x, size=3, act='softmax')
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        fluid.io.save_inference_model(model_dir, ['x'], [y], exe,
                                      main_program=prog)
        ones = np.ones((2, 4), np.float32)
        want, = exe.run(prog, feed={'x': ones}, fetch_list=[y])
    return np.asarray(want)


def test_capi_inprocess_roundtrip(tmp_path):
    if not _build_capi():
        pytest.skip('capi library not buildable here')
    model_dir = os.path.join(str(tmp_path), 'model')
    want = _save_toy_model(model_dir)

    lib = ctypes.CDLL(CAPI_SO)
    lib.ptc_init.argtypes = [ctypes.c_char_p]
    lib.ptc_predictor_create.restype = ctypes.c_void_p
    lib.ptc_predictor_create.argtypes = [ctypes.c_char_p]
    lib.ptc_set_input.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_uint64,
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int, ctypes.c_int
    ]
    lib.ptc_run.argtypes = [ctypes.c_void_p]
    lib.ptc_get_output_shape.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int, ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_int)
    ]
    lib.ptc_get_output_data.restype = ctypes.c_int64
    lib.ptc_get_output_data.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                        ctypes.c_char_p, ctypes.c_uint64]
    lib.ptc_predictor_destroy.argtypes = [ctypes.c_void_p]

    assert lib.ptc_init(REPO.encode()) == 0  # interpreter already up
    pred = lib.ptc_predictor_create(model_dir.encode())
    assert pred

    data = np.ones((2, 4), np.float32).tobytes()
    shape = (ctypes.c_int64 * 2)(2, 4)
    assert lib.ptc_set_input(pred, b'x', data, len(data), shape, 2, 0) == 0
    assert lib.ptc_run(pred) == 1

    oshape = (ctypes.c_int64 * 8)()
    ondim = ctypes.c_int()
    odtype = ctypes.c_int()
    assert lib.ptc_get_output_shape(pred, 0, oshape, 8,
                                    ctypes.byref(ondim),
                                    ctypes.byref(odtype)) == 0
    dims = [oshape[i] for i in range(ondim.value)]
    assert dims == [2, 3] and odtype.value == 0
    buf = ctypes.create_string_buffer(2 * 3 * 4)
    n = lib.ptc_get_output_data(pred, 0, buf, len(buf))
    assert n == 2 * 3 * 4
    got = np.frombuffer(buf.raw[:n], np.float32).reshape(2, 3)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    lib.ptc_predictor_destroy(pred)


def test_capi_standalone_c_program(tmp_path):
    """Compile and run the pure-C demo: a C program embedding CPython and
    driving inference with no Python code of its own."""
    if not _build_capi():
        pytest.skip('capi library not buildable here')
    model_dir = os.path.join(str(tmp_path), 'model')
    want = _save_toy_model(model_dir)

    demo_bin = os.path.join(str(tmp_path), 'capi_demo')
    ldflags = subprocess.run(
        'python3-config --ldflags --embed || python3-config --ldflags',
        shell=True, capture_output=True, text=True).stdout.split()
    cc = subprocess.run(
        ['gcc', os.path.join(REPO, 'csrc', 'capi_demo.c'),
         '-o', demo_bin, CAPI_SO] + ldflags,
        capture_output=True, text=True)
    if cc.returncode != 0:
        pytest.skip('cannot link embedded-python demo: %s' % cc.stderr[:200])

    run = _run_demo([demo_bin, model_dir, REPO, '4'])
    assert run.returncode == 0, run.stderr[-800:]
    assert 'output shape: 2 3' in run.stdout
    row0 = [float(v) for v in
            run.stdout.split('row0:')[1].strip().split()]
    # the child runs on the CPU backend like this process, so this is an
    # exact-backend comparison
    np.testing.assert_allclose(row0, want[0], rtol=1e-5)
    np.testing.assert_allclose(sum(row0), 1.0, rtol=1e-5)


def _save_train_programs(model_dir):
    """fit-a-line training programs serialized as ProgramDesc bytes (what
    the reference train/demo/demo_trainer.cc loads)."""
    os.makedirs(model_dir, exist_ok=True)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name='x', shape=[13], dtype='float32')
        y = fluid.layers.data(name='y', shape=[1], dtype='float32')
        pred = fluid.layers.fc(input=x, size=1, act=None)
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(input=pred, label=y))
        fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)
    with open(os.path.join(model_dir, 'main_program'), 'wb') as f:
        f.write(main.serialize_to_string())
    with open(os.path.join(model_dir, 'startup_program'), 'wb') as f:
        f.write(startup.serialize_to_string())


def test_capi_trainer_bridge(tmp_path):
    """The trainer bridge drives a full training loop from serialized
    programs (reference train/demo/demo_trainer.cc flow)."""
    from paddle_tpu import capi_bridge
    model_dir = os.path.join(str(tmp_path), 'train_model')
    _save_train_programs(model_dir)
    tr = capi_bridge.create_trainer(model_dir)
    x = (np.arange(26, dtype='float32') / 26.0).reshape(2, 13)
    y = np.asarray([[0.0], [1.0]], 'float32')
    tr.set_input('x', x.tobytes(), [2, 13], 0)
    tr.set_input('y', y.tobytes(), [2, 1], 0)
    losses = [tr.step() for _ in range(10)]
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0], losses


def test_capi_standalone_c_trainer(tmp_path):
    """Compile and run the pure-C TRAINING demo: a C program that loads
    ProgramDesc files, initializes params, and steps the optimizer —
    no Python code of its own (reference train/demo/demo_trainer.cc)."""
    if not _build_capi():
        pytest.skip('capi library not buildable here')
    model_dir = os.path.join(str(tmp_path), 'train_model')
    _save_train_programs(model_dir)

    demo_bin = os.path.join(str(tmp_path), 'train_demo')
    ldflags = subprocess.run(
        'python3-config --ldflags --embed || python3-config --ldflags',
        shell=True, capture_output=True, text=True).stdout.split()
    cc = subprocess.run(
        ['gcc', os.path.join(REPO, 'csrc', 'train_demo.c'),
         '-o', demo_bin, CAPI_SO] + ldflags,
        capture_output=True, text=True)
    if cc.returncode != 0:
        pytest.skip('cannot link embedded-python demo: %s' % cc.stderr[:200])

    run = _run_demo([demo_bin, model_dir, REPO, '10'])
    assert run.returncode == 0, (run.stdout[-400:], run.stderr[-800:])
    assert 'TRAIN_OK' in run.stdout, run.stdout[-400:]
