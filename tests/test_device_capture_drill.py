"""End-to-end drill of the on-device capture script: its paths, env
plumbing and redirections must produce a whole artifact set.

Runs `benchmarks/device_capture.sh` with CAPTURE_QUICK=1 in CPU mode
into a scratch dir and asserts every artifact of all six stages appears,
non-empty and JSON-parseable. Gated behind CAPTURE_DRILL=1 (it takes
minutes — CI runs it as its own step; `make drill` locally).
"""

import json
import os
import subprocess

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ARTIFACTS = [
    "BENCH_device.json",
    "SOAK_int8.json",
    "SOAK_f32.json",
    "SOAK_paced110k.json",
    "BENCH_MATRIX.json",
    "EVAL_device.json",
    "DEVICE_PARITY.json",
]


@pytest.mark.skipif(
    os.environ.get("CAPTURE_DRILL") != "1",
    reason="minutes-long end-to-end drill; set CAPTURE_DRILL=1 (CI runs it as its own step)",
)
def test_capture_script_produces_all_artifacts(tmp_path):
    out_dir = tmp_path / "drill"
    env = dict(
        os.environ,
        CAPTURE_QUICK="1",
        JAX_PLATFORMS="cpu",
    )
    proc = subprocess.run(
        ["sh", os.path.join(REPO, "benchmarks", "device_capture.sh"), str(out_dir)],
        capture_output=True, text=True, timeout=3000, env=env, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "done" in proc.stdout

    problems = []
    for name in ARTIFACTS:
        path = out_dir / name
        if not path.exists():
            problems.append(f"{name}: MISSING")
            continue
        text = path.read_text().strip()
        if not text:
            problems.append(f"{name}: EMPTY (log tail: "
                            f"{(out_dir / name.replace('.json', '.log')).read_text()[-300:]!r})")
            continue
        try:
            # One (possibly indented, multi-line) JSON document — or, for
            # the matrix, one JSON object per line.
            json.loads(text)
        except json.JSONDecodeError:
            try:
                for line in text.splitlines():
                    if line.strip():
                        json.loads(line)
            except json.JSONDecodeError as exc:
                problems.append(f"{name}: UNPARSEABLE ({exc})")
    assert not problems, "\n".join(problems)
