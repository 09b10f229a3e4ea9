import os
import shutil

import pytest

from chipbench import validate


@pytest.fixture
def copy(tmp_path):
    """A copy of BENCHMARK.json and of every directory whose files it
    names, to break or to add to."""
    shutil.copy(os.path.join(validate.ROOT, "BENCHMARK.json"), tmp_path)
    for kind in ("configs", "sources", "traffic", "layer_metrics", "heads",
                 "costs"):
        shutil.copytree(os.path.join(validate.ROOT, "chipbench", kind),
                        tmp_path / "chipbench" / kind,
                        ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path
