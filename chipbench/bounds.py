"""The end-to-end bounds, derived from A/A runs: ``python -m chipbench.bounds``.

``chipbench/aa/<cell>.jsonl`` holds one line per untraced chip run of one
tree, a seed a run (``seed``, the end-to-end metrics, ``correct``,
``failed``, the ``run_seconds`` and ``warm_up_s`` it was taken with, and
``first`` on the first run of a call, whose set-up compiles;
``aa/proof/`` keeps the runs of the comparisons that proved the bounds,
which nothing here reads). The driver compares the medians of two sets of six runs, relative to the
parent's. So the question a bound answers is how far two such medians
of one unchanged program lie apart: from the runs of a cell this draws,
from a fixed seed, 1,000 pairs of disjoint sixes and takes
``|median(A) - median(B)| / median(A)`` of each. A bound is the 95th
percentile of that in the cell where it is widest, rounded up to the
next 0.005, and never outside 0.01 to 0.10. A cell with fewer than twelve
runs has no such draws (its ``aa_median`` and ``aa_p95`` read ``None``)
and is held by its spread alone.

That is the least a bound may be. Beside it stands the spread the driver
judges a bound's width by: the distance between the first and third
quartile of a cell's runs over their median (``statistics.quantiles``).
A bound is refused as too tight where the runs of a cell spread by more
than half of it (the mean over the two sixes, each without its run
farthest from the median: ``too_tight_share`` draws that too), and as
too loose where it is over eight times the widest spread. On a host-bound
cell that rule, not the A/A difference, sets the bound (PERF.md, PR 28).
"""

from __future__ import annotations

import glob
import json
import math
import os
import statistics
import sys

import numpy as np

from chipbench import validate

DRAWS, DRAW_SEED, SET = 1000, 28, 6
LOWEST, HIGHEST, STEP = 0.01, 0.10, 0.005


def load_runs(root: str = validate.ROOT) -> dict[str, list[dict]]:
    """Cell -> its A/A lines, in the order they were run."""
    runs = {}
    for path in sorted(glob.glob(os.path.join(root, "chipbench", "aa", "*.jsonl"))):
        with open(path, encoding="utf-8") as f:
            runs[os.path.basename(path)[:-6]] = [
                json.loads(line) for line in f if line.strip()]
    return runs


def aa_differences(values, draws: int = DRAWS, seed: int = DRAW_SEED) -> np.ndarray:
    """``|median(A) - median(B)| / median(A)`` over seeded draws of two
    disjoint sets of six of ``values``."""
    values = np.asarray(values, float)
    if len(values) < 2 * SET:
        raise ValueError(f"two disjoint sixes need 12 runs, not {len(values)}")
    rng = np.random.default_rng(seed)
    out = np.empty(draws)
    for i in range(draws):
        pick = rng.permutation(len(values))[:2 * SET]
        a, b = np.median(values[pick[:SET]]), np.median(values[pick[SET:]])
        out[i] = abs(a - b) / a
    return out


def spread(values) -> float:
    """Interquartile distance over the median, as the driver reads it."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def too_tight_share(values, bound: float, draws: int = DRAWS,
                    seed: int = DRAW_SEED) -> float:
    """The share of seeded draws of two disjoint sixes that the driver
    would read as too tight a ``bound``: the mean of the two sets' spreads,
    each set without its run farthest from its median, over half of it."""
    values = np.asarray(values, float)
    rng = np.random.default_rng(seed)

    def trimmed(six):
        far = np.argmax(np.abs(six - np.median(six)))
        return spread(np.delete(six, far).tolist())

    if len(values) < 2 * SET:  # one set: nothing to draw
        return float(trimmed(values) > bound / 2)
    over = 0
    for _ in range(draws):
        pick = rng.permutation(len(values))[:2 * SET]
        over += (trimmed(values[pick[:SET]])
                 + trimmed(values[pick[SET:]])) / 2 > bound / 2
    return over / draws


def round_up(share: float) -> float:
    """The next multiple of 0.005 at or above ``share``, within 0.01 to 0.10."""
    steps = math.ceil(share / STEP - 1e-9)
    return min(HIGHEST, max(LOWEST, round(steps * STEP, 3)))


def derive(root: str = validate.ROOT) -> dict:
    """Metric -> ``{"bound": ..., "cells": {cell: {"runs", "median",
    "aa_median", "aa_p95", "spread"}}}`` for every end-to-end metric of
    ``BENCHMARK.json`` that the A/A lines carry. ``setup_s`` leaves out
    the first run of a call."""
    names = [m["name"] for m in validate.load_manifest(root)["end_to_end"]]
    runs = load_runs(root)
    out = {}
    for name in names:
        cells = {}
        for cell, lines in runs.items():
            values = [line[name] for line in lines if name in line
                      and not (name == "setup_s" and line.get("first"))]
            if not values:
                continue
            # a cell with one set of runs (a four-chip cell's cost four
            # times a run's: PERF.md, PR 56) has a spread and no draws
            diffs = aa_differences(values) if len(values) >= 2 * SET else None
            cells[cell] = {"runs": len(values),
                           "median": statistics.median(values),
                           "aa_median": None if diffs is None else float(np.median(diffs)),
                           "aa_p95": None if diffs is None else float(np.percentile(diffs, 95)),
                           "spread": spread(values)}
        if cells:
            out[name] = {"bound": round_up(max(c["aa_p95"] or 0.0
                                               for c in cells.values())),
                         "cells": cells}
    return out


def main() -> int:
    stated = {m["name"]: m["bound"]
              for m in validate.load_manifest()["end_to_end"]}
    rc = 0
    for name, d in derive().items():
        for cell, c in d["cells"].items():
            draws = ("one set, no draws" if c["aa_p95"] is None else
                     f"A/A median {c['aa_median']:.4%}  95th {c['aa_p95']:.4%}")
            print(f"{name:12s} {cell:24s} runs {c['runs']:3d}  median "
                  f"{c['median']:.4f}  {draws}  spread {c['spread']:.4%}")
        short = stated[name] < d["bound"]
        rc |= short
        widest = max(c["spread"] for c in d["cells"].values())
        width = "judged by its median alone"
        if name != "setup_s":
            tight = max(too_tight_share([line[name] for line in lines],
                                        stated[name])
                        for lines in load_runs().values())
            width = (f"twice to eight times the widest spread {2 * widest:.3f} "
                     f"to {8 * widest:.3f}, too tight in {tight:.1%} of draws")
        print(f"{name:12s} least bound (A/A) {d['bound']:.3f}; BENCHMARK.json "
              f"states {stated[name]}: {width}"
              f"{'  UNDER THE A/A RUNS' if short else ''}")
    return rc


if __name__ == "__main__":
    sys.exit(main())
