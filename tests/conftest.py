"""Test bootstrap: repo-root imports + 8 virtual CPU devices.

Tests run on CPU with --xla_force_host_platform_device_count=8 so every
multi-chip sharding path (DP/TP/SP/EP meshes, collectives, ring attention)
executes on a virtual 8-device mesh without TPU hardware — the
multi-node-without-a-cluster mechanism described in SURVEY.md §4.

One persistent compile cache serves the whole run. A RiskServer boot
compiles ~150 programs, and the suite boots dozens of servers across
xdist workers and subprocesses; without a cache each compiles them anew
(most of the suite's core-seconds, PERF.md PR 25). The directory is set
in the environment before jax is imported, so every worker and every
child process inherits the same one. It lives under the system temp dir,
keyed by the checkout path (the path is part of the cache key), and is
left in place between runs: an entry is keyed by its program, so a stale
one is never read. An operator's own setting wins.
"""

import faulthandler
import hashlib
import os
import random
import signal
import socket
import sys
import tempfile

import pytest

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(tempfile.gettempdir(), "igaming-tier1-jax-cache-"
                 + hashlib.sha1(REPO_ROOT.encode()).hexdigest()[:12]))
# JAX's own thresholds (1 s, and a size floor) would keep nearly every
# CPU program out of the cache.
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
# Most of a cold run is XLA optimizing ~2,200 small CPU programs that then
# run for milliseconds. JAX's own setting for "the cost of optimization is
# greater than that of running a less-optimized program": the suite's CPU
# time falls from 810 s to 653 s (PERF.md PR 25). What the tests compare is
# compiled under one setting on both sides. tests/test_eval.py, which
# trains for hundreds of steps, turns the optimizer back on for itself.
os.environ.setdefault("JAX_DISABLE_MOST_OPTIMIZATIONS", "1")

# The 50 ms objective belongs to a chip deployment; a starved test machine
# misses it all the time. Two mechanisms act on it, and both have a
# documented setting that holds for the suite (subprocess servers inherit
# it): the burn->shed gate sheds bulk RPCs while the objective is being
# missed (BURN_SHED=0 opts out), and a request that carries no deadline is
# given the objective as its budget and shed once it has queued that long
# (DEADLINE_DEFAULT_MS). The tests of the gate build it with
# ``enabled=True``; the tests of deadlines send explicit ones.
os.environ.setdefault("BURN_SHED", "0")
os.environ.setdefault("DEADLINE_DEFAULT_MS", "600000")
# The stall watch (obs/hostprof.Heartbeat) samples every thread while an
# RPC stays open past STALL_DUMP_MS, which a starved test machine's first
# compiles do all the time, and writes a file and a WARNING for each. Off
# for the suite (subprocess servers inherit it; the heartbeat itself still
# runs and counts); tests/test_stall_watch.py sets it.
os.environ.setdefault("STALL_DUMP_MS", "0")

# A hang costs one test, not the run (the driver's clock is 1470 s).
TEST_TIMEOUT_S = 300


@pytest.fixture(autouse=True)
def _per_test_timeout(request):
    def _expired(signum, frame):
        pytest.fail(f"{request.node.nodeid} exceeded {TEST_TIMEOUT_S} s "
                    "(tests/conftest.py TEST_TIMEOUT_S)", pytrace=True)

    # Every thread's stack goes to stderr just before the alarm fails the
    # test, so a hang in a helper thread or a lock is visible too.
    faulthandler.dump_traceback_later(TEST_TIMEOUT_S - 1, exit=False)
    previous = signal.signal(signal.SIGALRM, _expired)
    signal.alarm(TEST_TIMEOUT_S)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, previous)
        faulthandler.cancel_dump_traceback_later()


@pytest.fixture
def free_port():
    """Picker of a port for a child process to listen on. It picks below
    the kernel's ephemeral range (32768 up): a port the kernel chose for
    ``bind(0)`` is, from the moment the picker closes it, free again for
    any outbound connection on the machine, and a busy suite makes
    thousands before the child has bound it ("Address already in use")."""

    def pick() -> int:
        while True:
            port = random.randrange(20000, 32000)
            with socket.socket() as s:
                try:
                    s.bind(("localhost", port))
                except OSError:
                    continue
                return port

    return pick
