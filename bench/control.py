"""The control of a cell's correctness check: the plain reference put in the
program's place, with its key comparisons made in a lower precision.

    python bench/control.py --workload osm200m.ycsb_a.sat --seeds 1,2,3

For each seed it makes the cell's keys and the operations a run would send
(a closed loop's warm-up and ``--steps`` steps; an open loop's arrivals over
``--seconds``, in steps of the per-step cap), answers them with the exact
reference and with the control (``config["control"]["key_dtype"]``), and
prints how many answers differ: the control's reading of the number that
decides ``correct``, whose limit is 0, and the ``correct`` that reading
gives, which a sound control reads false. It needs no accelerator.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def control_reading(cell, seed: int, steps: int, seconds: float,
                    overrides: dict | None = None) -> dict:
    """Answers of the control that differ from the exact reference's, on
    the operations a run of ``cell`` sends from ``seed``."""
    import dataclasses
    from bench import datasets, reference
    from bench.generator import Generator
    config, mix = cell.config, cell.mix
    n = int((overrides or {}).get("keys", config["dataset"]["keys"]))
    if overrides and "mix" in overrides:
        mix = dataclasses.replace(mix, **overrides["mix"])
    keys = datasets.make_keys(config["dataset"]["generator"], n, seed)
    gen = Generator(mix, keys, seed)
    if mix.loop == "closed":
        blocks = (gen.closed_steps(-mix.warmup_steps, mix.warmup_steps)
                  + gen.closed_steps(0, steps))
    else:
        _, ops = gen.open_arrivals(seconds)
        cap = int(mix.max_ops_per_step)
        blocks = [ops[i:i + cap] for i in range(0, len(ops), cap)]
    exact = reference.Reference(keys)
    low = reference.Reference(keys, config["control"]["key_dtype"])
    differ = total = 0
    for b in blocks:
        want = reference.answers(exact, b.op, b.key, b.arg)
        got = reference.answers(low, b.op, b.key, b.arg)
        differ += reference.compare(got, want)
        total += len(b)
    return {"seed": seed, "key_dtype": config["control"]["key_dtype"],
            "answers": total, "differ": differ, "correct": differ == 0}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--steps", type=int, default=8,
                    help="closed loop: measured steps a run makes")
    ap.add_argument("--seconds", type=float, default=30.0,
                    help="open loop: seconds of arrivals")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    cell = harness.load_cell(args.workload, ROOT)
    for s in args.seeds.split(","):
        t = time.perf_counter()
        out = control_reading(cell, int(s), args.steps, args.seconds)
        out["seconds"] = time.perf_counter() - t
        print(json.dumps({"workload": args.workload, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
