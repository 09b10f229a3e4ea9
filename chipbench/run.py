"""``python -m chipbench.run --workload <name> --seed <n> --seconds <s>
--trace <0|1>``: one run of one cell of ``BENCHMARK.json``.

The last line of standard output is the result as one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and, in
a traced run, ``breakdown``); everything else worth keeping is on the
lines before it or under ``chipbench/out/``. ``--trace 0`` reports the
cell's end-to-end metrics, ``--trace 1`` its per-layer metrics.

``--validate`` checks the manifest and the data files and runs nothing.
``--rehearse`` runs every phase on the CPU at a tiny size: it proves
control flow and prints no device metric. ``--control`` repeats the
output check with the reference one precision step down, which has to
fail it. The parent of this process must not have touched JAX.
"""

from __future__ import annotations

import argparse
import json
import sys

from chipbench import validate


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(prog="chipbench.run")
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--validate", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)

    errors = validate.check_manifest()
    if args.validate or errors:
        for e in errors:
            print(e)
        print(f"manifest: {len(errors)} error(s)")
        return 1 if errors else 0
    if not args.workload:
        ap.error("--workload is required")
    spec = validate.load_cell(args.workload)
    seconds = args.seconds if args.seconds is not None else (
        2.0 if args.rehearse else float(spec["run_seconds"]))

    from chipbench import harness

    run = harness.Run(spec, seed=args.seed, seconds=seconds,
                      trace=bool(args.trace), rehearse=args.rehearse)
    run.boot()
    try:
        run.fill()
        ok, numbers = run.check()
        if args.control:
            dtype = run.config["precision"]["control_operand_dtype"]
            c_ok, c_numbers = run.judge(dtype, control=True)
            harness.log(f"control ({dtype}) correct={c_ok}: "
                        + json.dumps(c_numbers))
        result = run.window()
        stats = run.memory_line("after window")
    finally:
        run.shutdown()
    harness.log("set-up phases (s): " + json.dumps(
        {k: round(v, 2) for k, v in run.phase_s.items()}))
    on_chip = run.device.platform != "cpu"
    device = {"platform": run.device.platform, "kind": run.device.device_kind,
              "count": len(run.jax.devices()),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0)),
              **(result["device_extra"] if on_chip else {})}
    metrics = result["per_layer"] if args.trace else result["end_to_end"]
    if not on_chip:
        # a rehearsal proves control flow: no time, rate or share of a CPU
        # run goes out under a device metric's name
        metrics = {k: {"value": None, "unit": v["unit"]}
                   for k, v in metrics.items()}
    line = {"correct": bool(ok and result["correct"]),
            "attempted": result["attempted"], "failed": result["failed"],
            "metrics": metrics, "device": device}
    if args.trace and on_chip and result["breakdown"]:
        line["breakdown"] = result["breakdown"]
    if args.rehearse:
        line["rehearsal"] = True
    sys.stdout.flush()
    # every number compared beside its limit, as the last lines of
    # standard error too: the driver keeps the end of that
    print("\n".join(harness.COMPARED), file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
