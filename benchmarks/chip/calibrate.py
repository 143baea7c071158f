"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 benchmarks/chip/calibrate.py --workload <cell> \
        --seeds 11,12,... [--control-seeds 3] [--fault-seeds 3] [--out F]

For each seed, in this one process: the program's first steps at the
cell's own size, read as a run reads them (``harness.set_up``), then
the plain reference; the gaps between them are the program's readings
(the lower ones). On the first ``--control-seeds`` seeds also the
control, the reference in the precision below the configured one put in
the program's place, and on the first ``--fault-seeds`` the faults the
cell can have, planted in the reference put in the program's place:
half of the batch left out, and on a mesh the exchange between chips
left out. (A step that returns its state unchanged reads 1 on
``param_change_gap`` by the measure itself and needs no run.) One JSON
line per seed, then the worst program reading and the least control and
fault reading of each number. The benchmark's own runs do not run this.
"""
import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src"))

from chipbench import check, harness, spec  # noqa: E402


# What a step that returns its state unchanged reads, by the measure
# itself: no change and no RMSProp state, so nought against the
# reference's reading, a gap of 1 on the tensors at or above the median
# (about 1 for a sketch). No run needed.
UNCHANGED = dict.fromkeys(("grad_norm_gap", "param_change_gap",
                           "ema_change_gap", "grad_sketch_gap",
                           "param_sketch_gap", "ema_sketch_gap"), 1.0)


def limits(summary):
    """Each number's limit from its two readings. The lower is the worst
    sound run; the upper the least of the control's reading (where it is
    3x the lower or more), each planted fault's (where 10x or more) and
    the unchanged state's (where 3x or more). The limit sits two thirds
    of the way from the lower to the upper on a log scale, leaving more
    room above the lower; a number with no upper reading is not
    compared (None)."""
    out = {}
    for n, r in summary.items():
        lower = r["program_max"]
        ups = []
        if r["control_min"] is not None and r["control_min"] >= 3 * lower:
            ups.append(r["control_min"])
        ups += [v for v in r["faults_min"].values()
                if v is not None and v >= 10 * lower]
        if n in UNCHANGED and UNCHANGED[n] >= 3 * lower:
            ups.append(UNCHANGED[n])
        upper = min(ups) if ups else None
        out[n] = (float(f"{lower ** (1 / 3) * upper ** (2 / 3):.2g}")
                  if upper else None)
    return out


def calibrate(cell, seeds, control_seeds, fault_seeds, *, require_chip=True,
              out=None):
    mesh = int(cell.config["run"]["mesh_data"])
    faults = ["half"] + (["no_exchange"] if mesh > 1 else [])
    lines = []
    for i, seed in enumerate(seeds):
        t = time.perf_counter()
        s = harness.set_up(cell, seed, require_chip=require_chip)
        arrivals = list(s.rec.arrivals)
        s.tr = s.rec = None
        gc.collect()
        ref = harness.reference_readings(cell, s, seed, arrivals)
        line = {"seed": seed, "program": check.gaps(s.prog, ref)}
        if i < control_seeds:
            ctl = harness.reference_readings(cell, s, seed, arrivals,
                                             precision="low")
            line["control"] = check.gaps(ctl, ref)
        if i < fault_seeds:
            w_local = s.traffic.total_workers // mesh
            line["faults"] = {
                f: check.gaps(harness.reference_readings(
                    cell, s, seed, arrivals, fault=f, w_local=w_local), ref)
                for f in faults}
        line["seconds"] = time.perf_counter() - t
        lines.append(line)
        text = json.dumps(line)
        print(text, flush=True)
        if out:
            with open(out, "a") as f:
                f.write(text + "\n")
    summary = {}
    for n in check.NUMBERS:
        summary[n] = {
            "program_max": max(l["program"][n] for l in lines),
            "control_min": min((l["control"][n] for l in lines
                                if "control" in l), default=None),
            "faults_min": {f: min((l["faults"][f][n] for l in lines
                                   if "faults" in l), default=None)
                           for f in faults}}
    print(json.dumps({"summary": summary, "limits": limits(summary)}),
          flush=True)
    return lines, summary


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--out", default=None, help="append JSON lines here")
    args = ap.parse_args(argv)
    cell = spec.resolve(args.workload)
    try:
        harness.require_chips(cell.chips)
    except harness.NoChip as e:
        print(f"calibrate: {e}; nothing run", file=sys.stderr)
        return 2
    harness.use_compile_cache()
    calibrate(cell, [int(x) for x in args.seeds.split(",")],
              args.control_seeds, args.fault_seeds, out=args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
