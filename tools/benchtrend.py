"""Perf-trajectory table over the committed bench artifacts.

Every PR since r01 has committed a measured JSON artifact
(``DEADLINE_r12.json``, ``SESSION_r13.json``, ``MESH_r15.json``, ...).
Each records its own gates, but nothing reads them TOGETHER — a slow
regression that stays inside each PR's noise bar is invisible until
someone diffs artifacts by hand. This tool is that diff: it parses every
committed ``*_r*.json`` artifact (plain JSON or JSONL — the soak /
matrix artifacts are line-delimited), normalizes each to a trajectory
row (revision, family, flat-out txns/s, paced p99, e2e p99 — with the
JSON path each number came from), and flags within-series regressions
beyond a noise band.

Comparability discipline: artifacts measure DIFFERENT things (device
stream vs e2e wire vs session-on index mode vs open-loop paced), so
regression flags only compare rows whose metric came from the SAME
source path (e.g. all ``e2e_txns_per_sec`` artifacts form one series;
``flat_out.txns_per_sec`` another). Cross-family deltas are displayed,
never flagged.

Usage:
    python tools/benchtrend.py [--root DIR] [--noise 0.15] [--json]

Exit status is 0 even when regressions are flagged (``--gate`` makes
flags fatal — the trend gate CI mode). Accepted historical regressions
live in ``TREND_WAIVERS.json`` next to the artifacts: waived flags are
still reported, but only NEW (unwaived) flags trip the gate — the gate
exists to catch this PR's regression, not to re-litigate r05.
"""

from __future__ import annotations

import json
import os
import re
import sys

# Artifact filename -> (family, revision): SESSION_r13.json -> ("SESSION", 13).
# The optional suffix keeps BENCH_MATRIX_r04_cpu_control in the MATRIX family
# with its variant visible.
_ARTIFACT_RE = re.compile(
    r"^(?P<family>[A-Z][A-Z0-9_]*?)_r(?P<rev>\d+)(?P<variant>[A-Za-z0-9_]*)\.json$")

# Ordered extraction paths per trajectory column. A dotted path is
# followed exactly from the artifact root; a bare key is searched
# recursively (first depth-first hit). Order encodes preference: the
# headline e2e figure beats a nested arm figure.
FLAT_OUT_PATHS = (
    "e2e_txns_per_sec",                  # bench.py wire headline
    "flat_out.txns_per_sec",             # DEADLINE_r12
    "session_ab.rows_per_s_session_on",  # SESSION_r13 stateful flat-out
    "hostprof_on_txns_per_sec",          # HOSTPROF_r16 profiled arm
    "saturation.txns_per_sec",           # WALLET_REPLICAS curve knee
)
PACED_P99_PATHS = (
    "paced.rpc_p99_ms",              # DEADLINE_r12 open-loop paced
    "fused_arm.paced_rpc_p99_ms",    # bench.py --fused
    "sharded_arm.paced_rpc_p99_ms",  # MESH_r15
)
E2E_P99_PATHS = (
    "e2e_rpc_p99_ms",        # bench.py
    "flat_out.rpc_p99_ms",   # DEADLINE_r12 closed-loop arm
    "rpc_p99_ms",            # soak / matrix lines
)
# Generic fallback for the earliest artifacts: the headline {metric,
# value} pair when the metric is a throughput.
_THROUGHPUT_METRIC_RE = re.compile(r"txns?_per_sec")


def load_artifact(path: str):
    """Parse one artifact file: plain JSON, or JSONL (the soak and
    bench-matrix artifacts are line-delimited — ``json.load`` raises
    'Extra data' on them). Returns a dict, or a list of dicts for
    JSONL."""
    with open(path) as fh:
        text = fh.read()
    try:
        return json.loads(text)
    except json.JSONDecodeError:
        rows = []
        for line in text.splitlines():
            line = line.strip()
            if not line:
                continue
            rows.append(json.loads(line))
        if not rows:
            raise
        return rows


def _get_path(obj, dotted: str):
    """Follow a dotted path from the root; None when any hop is missing."""
    cur = obj
    for part in dotted.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    return cur


def _search_key(obj, key: str, _depth: int = 0):
    """Depth-first recursive search for ``key``; first hit wins."""
    if _depth > 8:
        return None
    if isinstance(obj, dict):
        if key in obj and isinstance(obj[key], (int, float)):
            return obj[key]
        for v in obj.values():
            hit = _search_key(v, key, _depth + 1)
            if hit is not None:
                return hit
    elif isinstance(obj, list):
        for v in obj:
            hit = _search_key(v, key, _depth + 1)
            if hit is not None:
                return hit
    return None


def _extract(doc, paths) -> tuple[float | None, str | None]:
    """First (value, source_path) along the ordered candidates: dotted
    paths are followed exactly, bare keys searched recursively."""
    for p in paths:
        if "." in p:
            v = _get_path(doc, p)
        else:
            v = _search_key(doc, p)
        if isinstance(v, (int, float)):
            return float(v), p
    return None, None


def _headline_throughput(doc) -> tuple[float | None, str | None]:
    """The earliest artifacts' {metric, value} headline when it is a
    throughput (the matrix lines' shape)."""
    if not isinstance(doc, dict):
        return None, None
    metric = doc.get("metric")
    value = doc.get("value")
    if (isinstance(metric, str) and _THROUGHPUT_METRIC_RE.search(metric)
            and isinstance(value, (int, float))):
        return float(value), f"value[{metric}]"
    return None, None


def normalize(path: str, doc) -> dict | None:
    """One artifact -> one trajectory row (or None for non-artifact
    JSON). JSONL artifacts extract from each line in order, first hit
    per column; wrapper artifacts ({cmd, parsed, rc, tail} — the r01–r05
    driver shape) unwrap ``parsed``."""
    name = os.path.basename(path)
    m = _ARTIFACT_RE.match(name)
    if m is None:
        return None
    docs = doc if isinstance(doc, list) else [doc]
    docs = [d.get("parsed", d) if isinstance(d, dict) else d for d in docs]

    def first(extractor, *args):
        for d in docs:
            v, src = extractor(d, *args) if args else extractor(d)
            if v is not None:
                return v, src
        return None, None

    flat, flat_src = first(_extract, FLAT_OUT_PATHS)
    if flat is None:
        flat, flat_src = first(_headline_throughput)
    paced, paced_src = first(_extract, PACED_P99_PATHS)
    e2e_p99, e2e_src = first(_extract, E2E_P99_PATHS)
    return {
        "file": name,
        "family": m.group("family") + (m.group("variant") or ""),
        "revision": int(m.group("rev")),
        "flat_out_txns_per_sec": flat,
        "flat_out_source": flat_src,
        "paced_p99_ms": paced,
        "paced_p99_source": paced_src,
        "e2e_p99_ms": e2e_p99,
        "e2e_p99_source": e2e_src,
    }


def build_trajectory(root: str = ".") -> list[dict]:
    """Scan ``root`` for committed artifacts and normalize each into a
    trajectory row, sorted by (revision, file)."""
    rows = []
    for name in sorted(os.listdir(root)):
        if not _ARTIFACT_RE.match(name):
            continue
        full = os.path.join(root, name)
        try:
            doc = load_artifact(full)
        except (json.JSONDecodeError, OSError) as exc:
            rows.append({"file": name, "error": f"{type(exc).__name__}: {exc}"})
            continue
        row = normalize(full, doc)
        if row is not None:
            rows.append(row)
    rows.sort(key=lambda r: (r.get("revision", -1), r.get("file", "")))
    return rows


# Which direction is "worse" per column: throughput regresses DOWN,
# latency regresses UP.
_COLUMNS = (
    ("flat_out_txns_per_sec", "flat_out_source", "down"),
    ("paced_p99_ms", "paced_p99_source", "up"),
    ("e2e_p99_ms", "e2e_p99_source", "up"),
)


def flag_regressions(rows: list[dict], noise: float = 0.15) -> list[dict]:
    """Within-series regression flags: rows sharing a (family, column,
    source path) form one comparable series; sorted by revision, each value is
    compared to the best-so-far in its series and flagged when worse by
    more than the ``noise`` fraction. Cross-source comparisons (device
    figure vs wire figure vs session arm) are never made — that is the
    comparability rule that keeps the table honest."""
    flags: list[dict] = []
    for col, src_col, worse in _COLUMNS:
        series: dict[tuple[str, str], list[dict]] = {}
        for r in rows:
            if r.get(col) is None or r.get(src_col) is None:
                continue
            # Series key includes the FAMILY: a soak artifact and a
            # bench artifact both report rpc_p99_ms, but under different
            # workloads — they never compare.
            series.setdefault((r["family"], r[src_col]), []).append(r)
        for (_family, src), members in series.items():
            members = sorted(members, key=lambda r: r["revision"])
            best = None
            best_row = None
            for r in members:
                v = r[col]
                if best is not None:
                    regressed = (v < best * (1.0 - noise) if worse == "down"
                                 else v > best * (1.0 + noise))
                    if regressed:
                        flags.append({
                            "file": r["file"],
                            "revision": r["revision"],
                            "metric": col,
                            "source": src,
                            "value": v,
                            "best_so_far": best,
                            "best_file": best_row["file"],
                            "delta_pct": round(
                                (v / best - 1.0) * 100.0, 1),
                            "noise_band_pct": round(noise * 100.0, 1),
                        })
                if (best is None
                        or (worse == "down" and v > best)
                        or (worse == "up" and v < best)):
                    best, best_row = v, r
    flags.sort(key=lambda f: (f["revision"], f["file"], f["metric"]))
    return flags


WAIVERS_FILE = "TREND_WAIVERS.json"


def load_waivers(root: str) -> dict[tuple[str, str], str]:
    """Accepted historical regressions: {(artifact file, metric): reason}.
    Each entry must name the exact flag it absorbs — a waiver for one
    metric of one artifact never quiets a different series."""
    path = os.path.join(root, WAIVERS_FILE)
    if not os.path.exists(path):
        return {}
    with open(path) as fh:
        entries = json.load(fh)
    return {(e["file"], e["metric"]): e.get("reason", "") for e in entries}


def render_table(rows: list[dict]) -> str:
    """Fixed-width text table of the trajectory (the human face; --json
    is the machine one)."""
    header = (f"{'rev':>4}  {'artifact':<34} {'flat-out txns/s':>16}  "
              f"{'paced p99 ms':>13}  {'e2e p99 ms':>11}")
    lines = [header, "-" * len(header)]
    for r in rows:
        if "error" in r:
            lines.append(f"{'?':>4}  {r['file']:<34} parse error: {r['error']}")
            continue
        def fmt(v, nd=1):
            return f"{v:,.{nd}f}" if isinstance(v, (int, float)) else "-"
        lines.append(
            f"{'r%02d' % r['revision']:>4}  {r['file']:<34} "
            f"{fmt(r['flat_out_txns_per_sec']):>16}  "
            f"{fmt(r['paced_p99_ms'], 3):>13}  "
            f"{fmt(r['e2e_p99_ms'], 3):>11}")
    return "\n".join(lines)


def main(argv: list[str] | None = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    root = "."
    noise = 0.15
    as_json = False
    gate = False
    for arg in argv:
        if arg.startswith("--root="):
            root = arg.split("=", 1)[1]
        elif arg.startswith("--noise="):
            noise = float(arg.split("=", 1)[1])
        elif arg == "--json":
            as_json = True
        elif arg == "--gate":
            gate = True
        else:
            raise SystemExit(
                "usage: benchtrend.py [--root=DIR] [--noise=F] [--json] [--gate]")
    rows = build_trajectory(root)
    flags = flag_regressions(rows, noise)
    waivers = load_waivers(root)
    for f in flags:
        if (f["file"], f["metric"]) in waivers:
            f["waived"] = waivers[(f["file"], f["metric"])] or True
    fatal = [f for f in flags if "waived" not in f]
    if as_json:
        print(json.dumps({"trajectory": rows, "regressions": flags,
                          "noise": noise}, indent=2))
    else:
        print(render_table(rows))
        if flags:
            print(f"\nREGRESSIONS (beyond {noise:.0%} of best-so-far, "
                  "same-source series only):")
            for f in flags:
                tag = " [waived]" if "waived" in f else ""
                print(f"  {f['file']} {f['metric']} [{f['source']}]: "
                      f"{f['value']:,.1f} vs best {f['best_so_far']:,.1f} "
                      f"({f['best_file']}) {f['delta_pct']:+.1f}%{tag}")
        else:
            print(f"\nno regressions beyond the {noise:.0%} noise band")
    if gate and fatal:
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
