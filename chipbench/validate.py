"""Manifest and data-file loading, and the rules both are held to.

``BENCHMARK.json`` names cells, configurations and metrics; everything
that belongs to one of them sits in a file of its own under
``chipbench/`` (``configs/<config>.json``, ``traffic/<mix>.json``,
``layer_metrics/<metric>.json``) which this module finds by name. So
does the code a configuration or a metric brings: the plain reference of
a session head (``heads/<name>.py``) and the operations and bytes of a
kernel (``costs/<name>.py``). A configuration names the cost of its whole
step under ``step_cost``, so the step's roofline share is one metric that
every cell reads. A later PR adds a cell by adding files and entries,
never by editing one.

A configuration taken from a published source holds that source's keys
at its own top level, under the source's names (``source_keys`` lists
them), and a copy of the source sits beside it as
``sources/<config>.json``; ``check_source`` holds the one to the other by
the rule in ``source_rules.json``: a count may differ where ``reduced``
names it, a width never.

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
                "step_cost", "precision", "guarantees", "reduced",
                "reduced_why", "assumed", "limits"}
# A configuration may bring its session head: ``{"reference": <name of a
# file under heads/>, ...}``. The other keys are that head's own (the
# source's value of each reduced key under ``published``, the
# ``deployment``, what was ``assumed``, sizes of the program's own) and
# are not listed here. ``source_keys`` lists the top-level keys that are
# its published source's, under the source's names: they are admitted
# beside these, and ``sources/<config>.json`` is what they are held to.
_CONFIG_OPTIONAL = {"head", "source_keys"}
_SOURCE_KEYS = {"name", "source_url", "config"}
# What a code file found by name has to define (``load_code``).
CODE_DEFINES = {"heads": lambda name: ("make_params", "forward"),
                "costs": lambda name: (name,)}
_TRAFFIC_KEYS = {"name", "loop", "clients", "rpc", "rows", "pool_frames",
                 "accounts", "tx_types", "amounts", "check"}
_LAYER_KEYS = {"name", "layer", "unit", "better", "source", "moves", "reader"}
# A roofline metric's ``cost`` names its file under ``costs/``, or is this:
# the cell's configuration names the file, under ``step_cost``. The whole
# step's cost differs by configuration and by nothing else, so one metric
# (``device_step_roofline``) reads it in every cell, and a new
# configuration brings its step's roofline as its own file and a cost file.
STEP_COST = {"config": "step_cost"}
# Parameters each generic reader takes (chipbench/readers.py).
READER_PARAMS = {
    "hostprof_us_per_row": {"stages"},
    "counter_ratio": {"numerator", "denominator", "scale"},
    "counter_delta_per": {"counter", "per"},
    "trace_device_idle": set(),
    "trace_program_ms": {"pattern"},
    "trace_roofline_share": {"pattern", "cost"},
    "trace_op_ms": {"pattern", "program"},
    "trace_op_roofline_share": {"pattern", "cost", "program"},
    "client_latency": {"percentile"},
}


def _load(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_manifest(root: str = ROOT) -> dict:
    return _load(os.path.join(root, "BENCHMARK.json"))


def data_path(kind: str, name: str, root: str = ROOT, ext: str = ".json") -> str:
    """``kind`` is ``configs``, ``sources``, ``traffic`` or
    ``layer_metrics``, or with ``ext=".py"`` ``heads`` or ``costs``."""
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


def cost_name(metric: dict, config: dict) -> str:
    """The file under ``costs/`` that prices this metric in a cell of this
    configuration."""
    return (config["step_cost"] if metric["cost"] == STEP_COST
            else metric["cost"])


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


_rules: dict = {}


def source_rules() -> dict:
    """``source_rules.json``: which keys of a source count something (the
    rest are widths) and the floors a cut keeps to, with the guide's
    sentences they come from. The yardstick's own file, so it is read
    from beside this module whatever ``root`` is checked."""
    if not _rules:
        _rules.update(_load(os.path.join(HERE, "source_rules.json")))
    return _rules


def load_source(name: str, root: str = ROOT) -> dict | None:
    """``chipbench/sources/<config>.json``, the published entry a
    configuration was taken from (``{"name", "source_url", "config"}``),
    or ``None`` where the configuration has no such file."""
    path = data_path("sources", name, root)
    return _load(path) if os.path.isfile(path) else None


def _same(a, b) -> bool:
    """Equal as JSON: a boolean is not the number 1, ``null`` is only
    ``null``, lists and groups compare entry by entry."""
    if isinstance(a, bool) or isinstance(b, bool) or a is None or b is None:
        return a is b
    if isinstance(a, (int, float)) and isinstance(b, (int, float)):
        return a == b
    if isinstance(a, list) and isinstance(b, list):
        return len(a) == len(b) and all(map(_same, a, b))
    if isinstance(a, dict) and isinstance(b, dict):
        return a.keys() == b.keys() and all(_same(a[k], b[k]) for k in a)
    return type(a) is type(b) and a == b


def _show(value, limit: int = 60) -> str:
    text = json.dumps(value, separators=(",", ":"))
    return text if len(text) <= limit else text[:limit - 3] + "..."


def check_source(where: str, data: dict, source: dict, reduced) -> list[str]:
    """Hold a configuration file to the copy of its source: every key of
    the source's ``config`` is a top-level key of the file under the same
    name; a value equals the source's unless ``reduced`` names the key; a
    nested group equals the source's key for key unless ``reduced`` names
    the group or the key; and a width never differs, named or not. One
    line a breach, naming the key, the file's value and the source's."""
    rules, errors = source_rules(), []
    counts = {k for keys in rules["counts"].values() for k in keys}
    reduced = set(reduced if isinstance(reduced, list) else [])
    published = data.get("head", {})
    published = published.get("published", {}) if isinstance(published, dict) else {}

    def breach(path: str, mine, theirs, why: str) -> None:
        errors.append(f"{where}: {path} is {_show(mine)} and its source "
                      f"gives {_show(theirs)}: {why}")

    def compare(path: str, key: str, mine, theirs, named: bool) -> None:
        if isinstance(mine, dict) and isinstance(theirs, dict):
            for k in sorted(theirs.keys() - mine.keys()):
                errors.append(f"{where}: {path}.{k} is missing and its source "
                              f"gives {_show(theirs[k])}: a nested group is "
                              "copied whole")
            for k in sorted(mine.keys() - theirs.keys()):
                errors.append(f"{where}: {path}.{k} is {_show(mine[k])} and "
                              "its source has no such key: a nested group is "
                              "copied whole")
            for k in sorted(mine.keys() & theirs.keys()):
                compare(f"{path}.{k}", k, mine[k], theirs[k],
                        named or k in reduced)
        elif _same(mine, theirs):
            return
        elif key not in counts:
            breach(path, mine, theirs, "a width may not differ, whether or "
                   "not reduced names it or the group that holds it")
        elif not named:
            breach(path, mine, theirs, "reduced does not name it")

    theirs = source["config"]
    for key, value in theirs.items():
        if key not in data:
            hint = (" (it is under head.published, where nothing looks for it)"
                    if key in published else "")
            errors.append(f"{where}: {key} is missing at the top level and its "
                          f"source gives {_show(value)}{hint}")
        else:
            compare(key, key, data[key], value, key in reduced)

    # -- what a cut keeps to (the guide's floors), where the keys exist -----
    floors = rules["floors"]

    def first(group: str):
        return next(((k, data[k]) for k in rules["counts"][group]
                     if k in theirs and isinstance(data.get(k), int)
                     and isinstance(theirs[k], int)), None)

    layers, dense = first("layers"), first("leading_dense_layers")
    if layers and (layers[1] != theirs[layers[0]]
                   or (dense and dense[1] != theirs[dense[0]])):
        left = layers[1] - (dense[1] if dense else 0)
        if left < floors["layers_after_leading_dense"]:
            breach(layers[0], layers[1], theirs[layers[0]],
                   f"{left} layers follow the leading dense ones; a cut keeps "
                   f"at least {floors['layers_after_leading_dense']}")
    for key in rules["counts"]["one_entry_per_layer"]:
        if layers and isinstance(data.get(key), list) and key in theirs:
            if len(data[key]) != layers[1]:
                breach(key, data[key], theirs[key], f"{len(data[key])} "
                       f"entries for {layers[0]} {layers[1]}")
    for group, least, why in (
            ("experts_held", lambda n: floors["routed_experts"],
             f"at least {floors['routed_experts']} routed experts"),
            ("vocabulary_rows", lambda n: floors["vocabulary_share"] * n,
             "at least an eighth of the vocabulary")):
        for key in rules["counts"][group]:
            mine, src = data.get(key), theirs.get(key)
            if (isinstance(mine, int) and isinstance(src, int) and mine != src
                    and not least(src) <= mine <= src):
                breach(key, mine, src, f"a cut holds {why} and no more than "
                       "the source has")
    return errors


def _check_config_source(errors: list, path: str, name: str, data: dict,
                         root: str) -> set:
    """The configuration's ``source_keys`` and its file under
    ``sources/``; returns the top-level names that ``source_keys``
    admits."""
    listed = data.get("source_keys", [])
    if not (isinstance(listed, list)
            and all(isinstance(k, str) and k for k in listed)):
        errors.append(f"{path}: source_keys is a list of the top-level keys "
                      "that are the published source's")
        return set()
    for key in listed:
        if key in _CONFIG_KEYS | _CONFIG_OPTIONAL:
            errors.append(f"{path}: source_keys names {key!r}, which is a key "
                          "of the benchmark's own")
        elif key not in data:
            errors.append(f"{path}: source_keys names {key!r}, which is not a "
                          "top-level key of the file")
    spath = f"chipbench/sources/{name}.json"
    try:
        source = load_source(name, root)
    except (OSError, ValueError) as exc:
        errors.append(f"{spath}: {exc}")
        return set(listed)
    if source is None:
        if listed:
            errors.append(f"{path}: source_keys without {spath}, the copy of "
                          "the source that they are held to")
        return set(listed)
    if not (isinstance(source, dict) and _SOURCE_KEYS <= source.keys()
            and isinstance(source["config"], dict)):
        errors.append(f"{spath}: an object with {sorted(_SOURCE_KEYS)}, the "
                      "source's entry copied whole")
        return set(listed)
    if data.get("source") != source["source_url"]:
        errors.append(f"{path}: source {data.get('source')!r} is not its "
                      f"source's source_url {source['source_url']!r}")
    for key in sorted((set(listed) & data.keys()) - source["config"].keys()
                      - _CONFIG_KEYS - _CONFIG_OPTIONAL):
        errors.append(f"{path}: source_keys names {key!r}, which {spath} does "
                      "not have")
    errors.extend(check_source(path, data, source, data.get("reduced")))
    return set(listed)


def _check_preload(errors: list, path: str, data: dict, reduced) -> None:
    """``session_events_preloaded`` is 0 (every window starts empty) or
    ``{"events": "<low>-<high>", "rounds": R}`` (``traffic.history_spec``).
    It is a cut, and stands in ``reduced``, only where it leaves windows
    short of what the deployment would hold: at 0, or with ``high`` under
    ``SESSION_EVENTS``."""
    from chipbench import traffic

    try:
        spec = traffic.history_spec(data.get("session_events_preloaded"))
    except ValueError as exc:
        errors.append(f"{path}: {exc}")
        return
    try:
        n_events = int(data.get("env", {}).get("SESSION_EVENTS", 16))
    except (TypeError, ValueError):
        errors.append(f"{path}: env.SESSION_EVENTS is a whole number")
        return
    listed = isinstance(reduced, list) and "session_events_preloaded" in reduced
    if listed and spec is not None and spec["high"] >= n_events:
        errors.append(f"{path}: session_events_preloaded fills windows to "
                      f"SESSION_EVENTS {n_events} and is no cut: reduced "
                      "names it only at 0 or with its high under that")
    if spec is None and not listed:
        errors.append(f"{path}: session_events_preloaded 0 starts every "
                      "window empty, a cut that reduced has to name")


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
        admitted = _check_config_source(errors, path, name, data, root)
        _check_keys(errors, path, data, _CONFIG_KEYS, _CONFIG_OPTIONAL | admitted)
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
        if "step_cost" in data:
            try:
                load_code("costs", str(data["step_cost"]), root)
            except (OSError, ValueError, AttributeError, SyntaxError) as exc:
                errors.append(f"{path}: step_cost: {exc}")
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
        _check_preload(errors, path, data, reduced)
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
        if "cost" in d and d["cost"] != STEP_COST:
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
