"""Collection hook of the benchmark's tests, for one case.

``test_benchmark_cells.py::test_config_entry_and_its_file`` was written
when every configuration ran uncut: it asserts ``reduced == []`` and
GPT-2's keys for each entry of ``configs``. ``glm-5.2-ep16`` is one
chip's share of a deployment and lists its cuts there, as the
benchmark's contract asks, and no file the benchmark already has may be
edited by the PR that adds a configuration. So that one case, and no
other, is marked as an expected failure here, and
``test_glm_dsa_cell.py::test_config_entry_and_its_file_with_cuts`` makes
the same checks with ``reduced`` compared as it stands. This hook takes
no second name: the next cut configuration needs the ``benchmark`` PR
that relaxes the assertion and deletes this file (PERF.md, Open
questions 0i).
"""

import pytest

THE_CASE = "test_config_entry_and_its_file[glm-5.2-ep16]"


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.name == THE_CASE:
            item.add_marker(pytest.mark.xfail(
                strict=True,
                reason="asserts reduced == []; this configuration is cut "
                       "(see test_glm_dsa_cell.py for the same checks)"))
