"""Completes the ``copy`` fixture of ``chipbench/tests/conftest.py``.

That fixture copies the directories whose files ``BENCHMARK.json`` names
into a temporary root, and predates ``chipbench/sources/``: since a
configuration with ``source_keys`` is in the benchmark, a copy without
its source's file no longer validates. A PR that adds a configuration may
add benchmark files and edit none, so the missing directory is copied
from here, once the fixture has run; a ``benchmark`` PR can put
``"sources"`` into the fixture's own list and delete this file.
"""

import os
import shutil

import pytest

from chipbench import validate


@pytest.hookimpl(hookwrapper=True)
def pytest_fixture_setup(fixturedef, request):
    outcome = yield
    if outcome.excinfo is not None:
        return
    # ``sourced`` (test_seam.py) makes the copy's ``sources/`` itself, so
    # for its tests the files go in once it has; for every other user of
    # ``copy`` as soon as the copy is made
    if fixturedef.argname == "copy" and "sourced" not in request.fixturenames:
        add_sources(outcome.get_result())
    elif fixturedef.argname == "sourced":
        add_sources(outcome.get_result())


def add_sources(root) -> None:
    """The files of ``chipbench/sources`` that a copy lacks."""
    have = os.path.join(validate.ROOT, "chipbench", "sources")
    want = os.path.join(str(root), "chipbench", "sources")
    if not os.path.isdir(have):
        return
    os.makedirs(want, exist_ok=True)
    for name in os.listdir(have):
        if name.endswith(".json") and not os.path.exists(os.path.join(want, name)):
            shutil.copy(os.path.join(have, name), want)
