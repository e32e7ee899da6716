"""Public API surface regression gate (reference: tools/diff_api.py +
paddle/fluid/API.spec — any public signature change must update the
spec deliberately)."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_api_spec_matches():
    sys.path.insert(0, os.path.join(REPO, 'tools'))
    try:
        import gen_api_spec
        current = gen_api_spec.generate()
    finally:
        sys.path.pop(0)
    spec_path = os.path.join(REPO, 'paddle_tpu', 'API.spec')
    with open(spec_path) as f:
        pinned = [l.rstrip('\n') for l in f if l.strip()]
    cur_set, pin_set = set(current), set(pinned)
    removed = sorted(pin_set - cur_set)
    added = sorted(cur_set - pin_set)
    assert not removed and not added, (
        'public API surface changed.\nRemoved/changed:\n  %s\n'
        'Added/changed:\n  %s\n'
        'If intentional, regenerate: python tools/gen_api_spec.py > '
        'paddle_tpu/API.spec' %
        ('\n  '.join(removed) or '-', '\n  '.join(added) or '-'))


def test_serving_module_is_covered():
    """The serving engine (ISSUE 2) is public surface: every
    serving.__all__ name — and the executors' run_eval_multi — must be
    pinned in API.spec so signature drift is deliberate."""
    import paddle_tpu.serving as serving
    spec_path = os.path.join(REPO, 'paddle_tpu', 'API.spec')
    with open(spec_path) as f:
        spec = f.read()
    for name in serving.__all__:
        assert ('paddle_tpu.serving.%s' % name) in spec, name
    assert 'paddle_tpu.fluid.Executor.run_eval_multi' in spec
    assert 'paddle_tpu.fluid.ParallelExecutor.run_eval_multi' in spec


def test_api_diff_zero_unexplained():
    """Every one of the reference's 428 pinned public names must resolve
    here or carry a replacement rationale (tools/api_diff.py; VERDICT r2
    next-#4: zero unexplained rows)."""
    import importlib
    sys.path.insert(0, os.path.join(REPO, 'tools'))
    try:
        api_diff = importlib.import_module('api_diff')
        if not os.path.exists(api_diff.REF_SPEC):
            pytest.skip('the reference checkout %s is not on this '
                        'machine' % api_diff.REF_SPEC)
        import paddle_tpu.fluid as fluid
        missing = []
        n_present = n_replaced = 0
        for name in api_diff.ref_names():
            if api_diff.resolves(fluid, name):
                n_present += 1
            elif api_diff.replaced_reason(name) is not None:
                n_replaced += 1
            else:
                missing.append(name)
    finally:
        sys.path.pop(0)
    assert not missing, 'unexplained reference API names: %s' % missing
    assert n_present >= 420  # 422 at round 3; never regress below this
