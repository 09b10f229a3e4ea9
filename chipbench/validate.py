"""Manifest and data-file loading, and the rules both are held to.

``BENCHMARK.json`` names cells, configurations and metrics; everything
that belongs to one of them sits in a file of its own under
``chipbench/`` (``configs/<config>.json``, ``traffic/<mix>.json``,
``layer_metrics/<metric>.json``) which this module finds by name. So
does the code a configuration or a metric brings: the plain reference of
a session head (``heads/<name>.py``) and the operations and bytes of a
kernel (``costs/<name>.py``). A later PR adds a cell by adding files and
entries, never by editing one.

``check_manifest`` returns every breach as one line; ``python -m
chipbench.run --validate`` prints them and exits non-zero on any.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
END_TO_END_SOURCES = ("host_clock", "device_trace")

_MANIFEST_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
                  "end_to_end", "per_layer"}
_ENTRY_KEYS = {
    "configs": ({"name", "source", "file", "reduced", "why"}, set()),
    "workloads": ({"name", "config", "traffic", "chips", "why"}, set()),
    "end_to_end": ({"name", "unit", "better", "bound", "source"}, {"workloads"}),
    "per_layer": ({"name", "unit", "better", "source", "layer", "moves"},
                  {"workloads"}),
}
_CONFIG_KEYS = {"name", "source", "chips", "resident_accounts", "ml_backend",
                "trunk", "env", "fill_chunk", "store_accounts",
                "store_loaded_accounts", "session_events_preloaded",
                "precision", "guarantees", "reduced", "reduced_why", "assumed",
                "limits"}
# A configuration may bring its session head: ``{"reference": <name of a
# file under heads/>, ...}``. The other keys are that head's own (its
# sizes, what was published, how it was cut) and are not listed here.
_CONFIG_OPTIONAL = {"head"}
# What a code file found by name has to define (``load_code``).
CODE_DEFINES = {"heads": lambda name: ("make_params", "forward"),
                "costs": lambda name: (name,)}
_TRAFFIC_KEYS = {"name", "loop", "clients", "rpc", "rows", "pool_frames",
                 "accounts", "tx_types", "amounts", "check"}
_LAYER_KEYS = {"name", "layer", "unit", "better", "source", "moves", "reader"}
# Parameters each generic reader takes (chipbench/readers.py).
READER_PARAMS = {
    "hostprof_us_per_row": {"stages"},
    "counter_ratio": {"numerator", "denominator", "scale"},
    "counter_delta_per": {"counter", "per"},
    "trace_device_idle": set(),
    "trace_program_ms": {"pattern"},
    "trace_roofline_share": {"pattern", "cost"},
    "client_latency": {"percentile"},
}


def _load(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_manifest(root: str = ROOT) -> dict:
    return _load(os.path.join(root, "BENCHMARK.json"))


def data_path(kind: str, name: str, root: str = ROOT, ext: str = ".json") -> str:
    """``kind`` is ``configs``, ``traffic`` or ``layer_metrics``, or with
    ``ext=".py"`` ``heads`` or ``costs``."""
    if not NAME_RE.match(name):
        raise ValueError(f"{kind} name {name!r} breaks the character rules")
    return os.path.join(root, "chipbench", kind, name + ext)


def load_data(kind: str, name: str, root: str = ROOT) -> dict:
    return _load(data_path(kind, name, root))


_code: dict[str, object] = {}


def load_code(kind: str, name: str, root: str = ROOT):
    """The module ``chipbench/<kind>/<name>.py`` under ``root``, loaded by
    its path: a file a later PR adds is found with no import line
    anywhere. ``kind`` is ``heads`` (``make_params(seed, config)`` and
    ``forward(params, windows, lengths, rounder)``) or ``costs`` (one
    function named like the file)."""
    path = data_path(kind, name, root, ".py")
    if path not in _code:
        if not os.path.isfile(path):
            raise FileNotFoundError(f"no file chipbench/{kind}/{name}.py")
        spec = importlib.util.spec_from_file_location(
            f"chipbench_{kind}_{re.sub(r'[^A-Za-z0-9_]', '_', name)}", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        missing = [f for f in CODE_DEFINES[kind](name)
                   if not callable(getattr(module, f, None))]
        if missing:
            raise AttributeError(
                f"chipbench/{kind}/{name}.py does not define {missing}")
        _code[path] = module
    return _code[path]


def head_name(config: dict) -> str:
    """The file under ``heads/`` that holds the reference of this
    deployment's session head: the configuration's ``head.reference``,
    and without one the value of ``SESSION_HEAD`` (default ``pattern``)."""
    return (config.get("head", {}).get("reference")
            or config.get("env", {}).get("SESSION_HEAD", "pattern"))


def load_cell(workload: str, root: str = ROOT) -> dict:
    """Everything one run needs: the cell, its configuration, its traffic
    mix, and the per-layer metric files that list this cell."""
    manifest = load_manifest(root)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"BENCHMARK.json has {sorted(cells)}")
    cell = cells[workload]
    e2e = [m for m in manifest["end_to_end"]
           if workload in m.get("workloads", [workload])]
    layer = [dict(load_data("layer_metrics", m["name"], root))
             for m in manifest["per_layer"]
             if workload in m.get("workloads", [workload])]
    return {
        "cell": cell,
        "config": load_data("configs", cell["config"], root),
        "traffic": load_data("traffic", cell["traffic"], root),
        "end_to_end": e2e,
        "per_layer": layer,
        "run_seconds": manifest["run_seconds"],
        "root": root,
    }


def _check_name(errors: list, what: str, value) -> None:
    if not isinstance(value, str) or not NAME_RE.match(value):
        errors.append(
            f"{what}: {value!r} must be 1 to 64 characters from letters, "
            "digits, '_', '.' and '-', starting with a letter, digit or '_'")


def _check_unit(errors: list, what: str, value) -> None:
    if not isinstance(value, str) or not UNIT_RE.match(value):
        errors.append(f"{what}: unit {value!r} must be 1 to 16 characters "
                      "from letters, digits, '_', '/', '%', '.' and '-'")


def _check_line(errors: list, what: str, value, limit: int = 200) -> None:
    if (not isinstance(value, str) or not 1 <= len(value) <= limit
            or "\n" in value or "\t" in value):
        errors.append(f"{what}: must be 1 to {limit} characters on one line")


def _check_keys(errors: list, what: str, entry: dict, required: set,
                optional: set = frozenset()) -> None:
    missing = required - set(entry)
    unknown = set(entry) - required - set(optional)
    if missing:
        errors.append(f"{what}: missing keys {sorted(missing)}")
    if unknown:
        errors.append(f"{what}: unknown keys {sorted(unknown)}")


def check_manifest(root: str = ROOT) -> list[str]:
    """Every breach of the rules in ``BENCHMARK.json`` and the data files
    it names, one line each; empty when all hold."""
    errors: list[str] = []
    try:
        m = load_manifest(root)
    except (OSError, ValueError) as exc:
        return [f"BENCHMARK.json: {exc}"]
    _check_keys(errors, "BENCHMARK.json", m, _MANIFEST_KEYS)
    if errors:
        return errors
    if os.path.getsize(os.path.join(root, "BENCHMARK.json")) > 64 * 1024:
        errors.append("BENCHMARK.json: larger than 64 KiB")
    if not (isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51):
        errors.append("run_seconds: a whole number from 1 to 51")
    if not (isinstance(m["command"], list) and 1 <= len(m["command"]) <= 32):
        errors.append("command: a list of 1 to 32 strings")
    for word in m["command"]:
        _check_line(errors, f"command word {word!r}", word)
    paths = m["paths"]
    if not (isinstance(paths, list) and 1 <= len(paths) <= 16):
        errors.append("paths: 1 to 16 directories")
    for p in paths:
        if (not re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", p) or p.startswith("/")
                or ".." in p.split("/")):
            errors.append(f"paths: {p!r} is not a relative path inside the repo")

    for section, (required, optional) in _ENTRY_KEYS.items():
        entries = m[section]
        if not isinstance(entries, list) or not entries:
            errors.append(f"{section}: at least one entry")
            continue
        seen = set()
        for e in entries:
            what = f"{section} {e.get('name')!r}"
            _check_keys(errors, what, e, required, optional)
            _check_name(errors, f"{what} name", e.get("name"))
            if e.get("name") in seen:
                errors.append(f"{what}: name used twice")
            seen.add(e.get("name"))

    cells = {w.get("name"): w for w in m["workloads"]}
    configs = {c.get("name"): c for c in m["configs"]}
    metric_names = [x.get("name") for x in m["end_to_end"] + m["per_layer"]]
    for n in set(metric_names):
        if metric_names.count(n) > 1:
            errors.append(f"metric {n!r}: name used twice")

    # -- configurations ----------------------------------------------------
    files = set()
    for name, c in configs.items():
        what = f"config {name!r}"
        _check_line(errors, f"{what} source", c.get("source"))
        _check_line(errors, f"{what} why", c.get("why"))
        reduced = c.get("reduced", [])
        if not isinstance(reduced, list) or len(reduced) > 16:
            errors.append(f"{what}: reduced has at most 16 keys")
        for key in reduced if isinstance(reduced, list) else []:
            _check_name(errors, f"{what} reduced key", key)
        path = c.get("file", "")
        if not any(path.startswith(p.rstrip("/") + "/") for p in paths):
            errors.append(f"{what}: file {path!r} is not under paths")
        if path in files:
            errors.append(f"{what}: file {path!r} belongs to another config")
        files.add(path)
        if path != f"chipbench/configs/{name}.json":
            errors.append(f"{what}: file must be chipbench/configs/{name}.json")
        try:
            data = _load(os.path.join(root, path))
        except (OSError, ValueError) as exc:
            errors.append(f"{what}: {exc}")
            continue
        _check_keys(errors, f"{path}", data, _CONFIG_KEYS, _CONFIG_OPTIONAL)
        head = data.get("head")
        if head is not None and not (isinstance(head, dict) and isinstance(
                head.get("reference"), str)):
            errors.append(f"{path}: head is an object that names its "
                          "'reference', a file under chipbench/heads/")
        else:
            try:
                load_code("heads", head_name(data), root)
            except (OSError, ValueError, AttributeError, SyntaxError) as exc:
                errors.append(f"{path}: session head: {exc}")
        if data.get("name") != name:
            errors.append(f"{path}: name {data.get('name')!r} != {name!r}")
        if data.get("source") != c.get("source"):
            errors.append(f"{path}: source differs from BENCHMARK.json")
        if data.get("reduced") != reduced:
            errors.append(f"{path}: reduced differs from BENCHMARK.json")
        for key in reduced if isinstance(reduced, list) else []:
            if key not in data or key not in data.get("reduced_why", {}):
                errors.append(f"{path}: reduced key {key!r} needs its value "
                              "and a line in reduced_why")
        if not data.get("guarantees"):
            errors.append(f"{path}: states no guarantees")
        if not any(w.get("config") == name for w in m["workloads"]):
            errors.append(f"{what}: no cell uses it")

    # -- cells --------------------------------------------------------------
    pairs = set()
    for name, w in cells.items():
        what = f"workload {name!r}"
        _check_line(errors, f"{what} why", w.get("why"))
        _check_name(errors, f"{what} config", w.get("config"))
        _check_name(errors, f"{what} traffic", w.get("traffic"))
        if w.get("chips") not in (1, 4):
            errors.append(f"{what}: chips is 1 or 4")
        if w.get("config") not in configs:
            errors.append(f"{what}: config {w.get('config')!r} is not defined")
        pair = (w.get("config"), w.get("traffic"))
        if pair in pairs:
            errors.append(f"{what}: config and traffic pair appears twice")
        pairs.add(pair)
        try:
            t = load_data("traffic", w.get("traffic", ""), root)
        except (OSError, ValueError) as exc:
            errors.append(f"{what}: traffic file: {exc}")
            continue
        tpath = f"chipbench/traffic/{w.get('traffic')}.json"
        _check_keys(errors, tpath, t, set(), _TRAFFIC_KEYS)
        if t.get("name") != w.get("traffic"):
            errors.append(f"{tpath}: name {t.get('name')!r} != file name")
        if t.get("loop") != "closed" or not t.get("clients"):
            errors.append(f"{tpath}: loop is 'closed' and fixes clients")
        if t.get("rpc") not in ("index", "proto"):
            errors.append(f"{tpath}: rpc is 'index' or 'proto'")
    four = sum(1 for w in cells.values() if w.get("chips") == 4)
    if four > max(1, len(cells) // 4):
        errors.append(f"workloads: {four} cells ask for 4 chips; at most "
                      f"{max(1, len(cells) // 4)} may")

    # -- end-to-end metrics -------------------------------------------------
    e2e = {x.get("name"): x for x in m["end_to_end"]}
    if "setup_s" not in e2e:
        errors.append("end_to_end: setup_s is required")
    for name, x in e2e.items():
        what = f"end_to_end {name!r}"
        _check_unit(errors, what, x.get("unit"))
        if x.get("better") not in ("lower", "higher"):
            errors.append(f"{what}: better is 'lower' or 'higher'")
        if x.get("source") not in END_TO_END_SOURCES:
            errors.append(f"{what}: source is one of {END_TO_END_SOURCES}")
        b = x.get("bound")
        if not (isinstance(b, (int, float)) and 0.01 <= b <= 0.1):
            errors.append(f"{what}: bound {b!r} is outside 0.01 to 0.1")
        for wl in x.get("workloads", []):
            if wl not in cells:
                errors.append(f"{what}: workload {wl!r} is not defined")

    def reporting(metric: dict) -> set:
        return set(metric.get("workloads", cells))

    for name, w in cells.items():
        mine = [n for n, x in e2e.items() if name in reporting(x)]
        if "setup_s" not in mine or len(mine) < 2:
            errors.append(f"workload {name!r}: reports setup_s and at least "
                          "one other end-to-end metric")

    # -- per-layer metrics --------------------------------------------------
    layered = set()
    for x in m["per_layer"]:
        name = x.get("name")
        what = f"per_layer {name!r}"
        _check_unit(errors, what, x.get("unit"))
        _check_name(errors, f"{what} layer", x.get("layer"))
        if x.get("better") not in ("lower", "higher"):
            errors.append(f"{what}: better is 'lower' or 'higher'")
        if x.get("source") not in SOURCES:
            errors.append(f"{what}: source is one of {SOURCES}")
        if name and name.endswith("_roofline") and x.get("unit") != "%":
            errors.append(f"{what}: a roofline share has the unit %")
        moves = e2e.get(x.get("moves"))
        if moves is None:
            errors.append(f"{what}: moves {x.get('moves')!r} is not an "
                          "end-to-end metric")
            continue
        for wl in reporting(x):
            if wl not in cells:
                errors.append(f"{what}: workload {wl!r} is not defined")
            elif wl not in reporting(moves):
                errors.append(f"{what}: cell {wl!r} does not report "
                              f"{x['moves']!r}, which this metric moves")
            layered.add(wl)
        try:
            d = load_data("layer_metrics", name or "", root)
        except (OSError, ValueError) as exc:
            errors.append(f"{what}: metric file: {exc}")
            continue
        lpath = f"chipbench/layer_metrics/{name}.json"
        reader = d.get("reader")
        if reader not in READER_PARAMS:
            errors.append(f"{lpath}: reader {reader!r} is not one of "
                          f"{sorted(READER_PARAMS)}")
        else:
            _check_keys(errors, lpath, d, _LAYER_KEYS, READER_PARAMS[reader])
        if "cost" in d:
            try:
                load_code("costs", str(d["cost"]), root)
            except (OSError, ValueError, AttributeError, SyntaxError) as exc:
                errors.append(f"{lpath}: cost: {exc}")
        for key in ("name", "layer", "unit", "better", "source", "moves"):
            if d.get(key) != x.get(key):
                errors.append(f"{lpath}: {key} {d.get(key)!r} differs from "
                              f"BENCHMARK.json's {x.get(key)!r}")
    for name in cells:
        if name not in layered:
            errors.append(f"workload {name!r}: reports no per-layer metric")
    return errors
