#!/usr/bin/env python3
"""The benchmark's command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

finds the cell in BENCHMARK.json, its configuration under
``benchmark/configs/``, its traffic mix under ``benchmark/traffic/``, the
driver of the mix's ``kind`` under ``benchmark/drivers/`` and one reader
per per-layer metric under ``benchmark/metrics/``.  The last line of
standard output is the result object; any refusal exits non-zero without
one.  It runs on the machine it is started on and needs a TPU.

``--rehearse`` is a separate mode for a CPU sandbox: the configuration's
and the mix's ``rehearsal`` sizes, any backend, and NO result line -- it
ends with ``{"rehearsal": true, "platform": ...}`` and the numbers
compared, so that no CPU number can stand under a device metric.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any backend; prints no result")
    ap.add_argument("--rates", default=None,
                    help="serving only: sweep these arrival rates "
                         "(comma separated) in one process; prints no "
                         "result")
    return ap.parse_args(argv)


def main(argv=None):
    from benchmark import harness
    from benchmark.peaks import peaks_for
    args = parse(argv)
    try:
        spec = harness.load_cell(args.workload, rehearse=args.rehearse)
        if args.seconds is None:
            args.seconds = float(spec["run_seconds"])
        driver = harness.load_driver(spec["mix"]["kind"])
        chips = int(spec["cell"]["chips"])
        try:
            import paddle_tpu  # noqa: F401  the system under test
        except ImportError as e:
            raise harness.BenchError(f"the program is not here: {e}")
        cache = harness.place_cache()
        device = harness.require_devices(chips, args.rehearse)
        harness.say(f"device {json.dumps(device)}; compile cache {cache}")
        if args.rates:
            return driver.sweep(spec, args, device)
        out = driver.run(spec, args, T_START, device)
    except harness.BenchError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    if args.rehearse:
        print(json.dumps({"rehearsal": True, "platform": device["platform"],
                          "attempted": out["attempted"],
                          "failed": out["failed"],
                          "compared": {n: [v, lim]
                                       for n, v, lim in out["checks"]}}))
        return 0
    device = dict(device, memory_peak_bytes=out["peak"],
                  **out.get("device_extra", {}))
    per_layer = {}
    if args.trace:
        per_layer = harness.read_per_layer(
            spec, dict(out["run"], config=spec["config"], mix=spec["mix"],
                       chips=chips, peaks=peaks_for(device["kind"])))
    print(harness.result_line(
        spec, bool(args.trace), device, out["e2e"], per_layer,
        out["attempted"], out["failed"], out["checks"],
        out.get("breakdown"), out.get("extra")), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
