"""Test bootstrap: repo-root imports + 8 virtual CPU devices.

Tests run on CPU with --xla_force_host_platform_device_count=8 so every
multi-chip sharding path (DP/TP/SP/EP meshes, collectives, ring attention)
executes on a virtual 8-device mesh without TPU hardware — the
multi-node-without-a-cluster mechanism described in SURVEY.md §4.
"""

import os
import sys

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)
