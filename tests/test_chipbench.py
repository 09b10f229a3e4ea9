"""The benchmark harness's own tests (``chipbench/tests/``), collected
under the tier-1 command so that the harness, every file a cell brings
and the A/A data are guarded by the run the driver makes (PERF.md Open
question 9). Thin on purpose: it holds no test of its own.

Every test module of that directory is imported here under its own
names, so each case counts as one. Their fixtures come with them; the
``copy`` fixture is completed as ``chipbench/conftest.py`` completes it
for a run of that directory itself. A harness run sets a deployment's
environment in this process (``harness.Run.boot``): it is put back when
the module is done, so the worker's next file boots what it asks for.
"""

import os

import pytest

from chipbench.conftest import add_sources
from chipbench.tests.conftest import copy as _bare_copy
from chipbench.tests.test_bounds import *  # noqa: F401,F403
from chipbench.tests.test_manifest import *  # noqa: F401,F403
from chipbench.tests.test_reference import *  # noqa: F401,F403
from chipbench.tests.test_seam import *  # noqa: F401,F403
from chipbench.tests.test_seam import sourced as _seam_sourced
from chipbench.tests.test_span_metrics import *  # noqa: F401,F403
from chipbench.tests.test_trace_reduce import *  # noqa: F401,F403
from chipbench.tests.test_traffic import *  # noqa: F401,F403


@pytest.fixture
def copy(_bare_copy, request):
    if "sourced" not in request.fixturenames:
        add_sources(_bare_copy)
    return _bare_copy


@pytest.fixture
def sourced(_seam_sourced):  # test_seam's own, then the files it lacks
    add_sources(_seam_sourced)
    return _seam_sourced


@pytest.fixture(autouse=True, scope="module")
def _environment_put_back():
    saved = dict(os.environ)
    yield
    os.environ.clear()
    os.environ.update(saved)
