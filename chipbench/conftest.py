"""What is left of PR 34's completion of the ``copy`` fixture.

Since PR 56 ``chipbench/tests/conftest.py``'s ``copy`` copies
``chipbench/sources/`` itself and ``test_seam.py``'s ``sourced`` takes the
directory as it finds it, so nothing is completed from outside any more.
``add_sources`` stays because ``tests/test_chipbench.py`` imports it and
calls it on every copy: it finds no file missing and copies none. The PR
that may edit that collector deletes the import and this file (PERF.md
Open question 9).
"""

import os
import shutil

from chipbench import validate


def add_sources(root) -> None:
    """The files of ``chipbench/sources`` that a copy lacks: none, since
    the fixture copies the directory."""
    have = os.path.join(validate.ROOT, "chipbench", "sources")
    want = os.path.join(str(root), "chipbench", "sources")
    os.makedirs(want, exist_ok=True)
    for name in os.listdir(have):
        if name.endswith(".json") and not os.path.exists(os.path.join(want, name)):
            shutil.copy(os.path.join(have, name), want)
