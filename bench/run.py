"""Run one benchmark cell on the accelerator and print its result line.

    python bench/run.py --workload osm200m.ycsb_a.sat --seed 7 \\
        --seconds 30 --trace 0

Earlier lines of standard output report each phase; the last line is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device``, with ``--trace 1`` ``breakdown``, and last
``compared``, each compared number beside its limit, which also ends
standard error. The run exits nonzero and prints no result when JAX finds
no TPU, fewer chips than the cell asks for, or no program next to the
benchmark (``src/repro``).
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"run.py: no program at {ROOT / 'src' / 'repro'}; nothing "
              f"was run", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    cell = harness.load_cell(args.workload, ROOT)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"run.py: no TPU found (JAX platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 1
    if len(devices) < cell.chips:
        print(f"run.py: {args.workload} needs {cell.chips} chips, JAX sees "
              f"{len(devices)}; nothing was run", file=sys.stderr)
        return 1
    result = harness.run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace), root=ROOT,
                              devices=devices[:cell.chips],
                              t_process=T_PROCESS)
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # the result is out: skip the interpreter's teardown, which would
    # first join the engine's compaction threads
    os._exit(code)
