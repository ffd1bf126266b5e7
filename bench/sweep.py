"""Find the highest rate an open-loop mix sustains: one process, one
set-up, a ladder of offered rates.

    python bench/sweep.py --config osm_200m --traffic ycsb_c_rate \\
        --seed 11 --rates 10000,20000,30000 --rung-seconds 15

Each rung offers the open-loop mix at one rate for ``--rung-seconds`` and
reports the gets served, p50 and p99 from each get's due time, and the
backlog: requests due but not yet admitted when the rung closed. A rung
holds when its backlog at the close is under 100 ms of arrivals and its
second half's p99 is under twice its first half's. The knee is the highest
rate below the first rung that does not hold; the last line is a JSON object
with the ladder, the knee and four fifths of it. The rungs' answers are not
checked here; ``run.py`` checks every answer of a run.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent


def rung(sess, mix, rate: float, seconds: float) -> dict:
    from bench.harness import open_latencies, percentile
    sess.mix = dataclasses.replace(mix, rate=rate)
    sess.gen.mix = sess.mix
    sess.steps = []
    sess.prepare_open(seconds)
    t0, t_end = sess.open_window(seconds)
    backlog = int(np.searchsorted(sess.due, seconds, side="right")
                  - sess.window_end_index)
    sess.drain(seconds)
    lat = open_latencies(sess, t0)
    due = sess.due[:lat.size]
    first = lat[due < seconds / 2]
    second = lat[due >= seconds / 2]
    out = {"rate": rate, "gets": int(lat.size),
           "served_per_s": sum(len(s.ops) for s in sess.steps
                               if s.phase == "window") / (t_end - t0),
           "steps": sum(1 for s in sess.steps if s.phase == "window"),
           "p50_ms": percentile(lat, 50) * 1e3,
           "p99_ms": percentile(lat, 99) * 1e3,
           "p99_first_half_ms": percentile(first, 99) * 1e3,
           "p99_second_half_ms": percentile(second, 99) * 1e3,
           "backlog_at_close": backlog}
    out["holds"] = bool(backlog < 0.1 * rate
                        and out["p99_second_half_ms"]
                        < 2 * out["p99_first_half_ms"])
    sess.steps = []
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True,
                    help="a configuration named in BENCHMARK.json")
    ap.add_argument("--traffic", required=True,
                    help="an open-loop mix under bench/traffic/")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, ascending")
    ap.add_argument("--rung-seconds", type=float, default=15.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    from bench.datasets import make_keys
    from bench.generator import Generator, Mix
    import jax
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = {c["name"]: c for c in spec["configs"]}[args.config]
    config = json.loads((ROOT / entry["file"]).read_text())
    mix = Mix.from_file(ROOT / "bench" / "traffic" / f"{args.traffic}.json")
    chips = int(config.get("chips", 1))
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print("sweep.py: no TPU with enough chips; nothing was run",
              file=sys.stderr)
        return 1
    if mix.loop != "open":
        print("sweep.py: the mix is not an open loop", file=sys.stderr)
        return 2
    harness.enable_compile_cache(ROOT)
    import repro.serving  # noqa: F401
    t = time.perf_counter()
    ds = config["dataset"]
    keys = make_keys(ds["generator"], int(ds["keys"]), args.seed)
    eng, _ = harness.build_engine(config, keys, devices[:chips])
    sess = harness.Session(eng, mix, Generator(mix, keys, args.seed), False)
    sess.warm_up()
    harness.log("setup", seconds=time.perf_counter() - t)
    ladder = []
    failed = 0
    for r in [float(x) for x in args.rates.split(",")]:
        ladder.append(rung(sess, mix, r, args.rung_seconds))
        harness.log("rung", **ladder[-1])
        failed = failed + 1 if not ladder[-1]["holds"] else 0
        if failed == 2:
            break
    held = [x["rate"] for x in ladder if x["holds"]]
    knee = None
    for x in ladder:
        if not x["holds"]:
            break
        knee = x["rate"]
    print(json.dumps({"config": args.config, "traffic": args.traffic,
                      "ladder": ladder,
                      "knee": knee, "four_fifths": knee and 0.8 * knee,
                      "held": held}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
