"""Builder's and reviewer's tool, not run by the benchmark: reads, on the
chip and at a cell's own size, what the limits of ``correct`` are set
from -- the control (the reference in the program's place, one precision
below the configuration's) and the faults a cell can have -- and puts
each reading through the cell's own ``limits`` and the harness's own
verdict.  A control or a fault has to come out ``correct=False``, the
program ``correct=True``; anything else and the exit code is 1.

    python3 benchmark/prove.py --workload gpt3-1.3b.train --seeds 1,2,3
    python3 benchmark/prove.py --workload gpt3-1.3b.chat --seeds 1,2,3 \
        --seconds 8

Training: per seed, the float32 reference's first steps against (a) the
fp8 reference, (b) the float32 reference fed half of each batch, (c) its
own readings with no leaf changed (a step that returns its state
unchanged; arithmetic, no run).  Serving: per seed, a short window at the
cell's own load, then the served tokens' widest gap (the program's
reading) and the fp8 control's at the same prompts and positions.
"""
import argparse
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def judged(spec, seed, tag, numbers, want, note=""):
    """Put `numbers` [(name, value)] through the cell's limits; print the
    verdict; True when it is the one that `want`s."""
    from benchmark import harness
    limits = spec["mix"]["limits"]
    checks = [(n, v, limits[n]) for n, v in numbers if n in limits]
    correct = harness.verdict(checks)
    print(f"prove {spec['name']} seed {seed} {tag}: " + " ".join(
        f"{n}={v:.6g}{'<=' if harness.within(v, lim) else '>'}{lim:g}"
        for n, v, lim in checks)
        + f" correct={correct} "
        + ("as it has to be" if correct == want else "AND HAS TO BE "
           + str(want)) + note, flush=True)
    return correct == want


def train(spec, seeds):
    from benchmark import check, harness, traffic
    from benchmark.drivers.train import CHECK_STEPS
    from benchmark.references import training
    cfg, mix = spec["config"], spec["mix"]
    ref = harness.load_reference(cfg["reference"])
    lr = float(cfg["assumed"]["learning_rate"])
    positions = int(cfg["max_position_embeddings"])
    good = True
    for seed in seeds:
        rows = traffic.train_tokens(mix, seed, int(cfg["vocab_size"]))
        rows = rows[:CHECK_STEPS]
        base = training.first_steps(ref, cfg, positions, seed, rows, lr)
        unchanged = dict(base, change_norms=0 * base["change_norms"])
        numbers, _ = check.training_numbers(unchanged, base)
        good &= judged(spec, seed, "fault_state_unchanged", numbers, False)
        for tag, kw in (("control_fp8", {"precision": "fp8"}),
                        ("fault_half_batch", {"fault": "half_batch"})):
            t = time.perf_counter()
            other = training.first_steps(ref, cfg, positions, seed, rows,
                                         lr, **kw)
            numbers, _ = check.training_numbers(other, base)
            good &= judged(spec, seed, tag, numbers, False,
                           f" ({time.perf_counter() - t:.1f} s)")
    return good


def serve(spec, seeds, seconds):
    from benchmark import harness, traffic
    from benchmark.drivers import serve as drv
    cfg, mix = spec["config"], spec["mix"]
    ref = harness.load_reference(cfg["reference"])
    good = True
    for seed in seeds:
        cell = drv.ServeCell(spec, seed)
        cell.warm()
        schedule = traffic.serve_schedule(mix, seed, seconds,
                                          int(cfg["vocab_size"]))
        records, _, t_open, t_close = drv.drive(
            cell, schedule, seconds, bool(mix.get("follow_to_end")))
        positions = cell.positions
        cell.free()
        picked = drv.sample([r for r in records if t_open <= r.due < t_close],
                            seed,
                            int(mix.get("check_requests", 6)))
        gap, n = drv.compare(ref, cfg, positions, seed, picked)
        cgap, _ = drv.compare(ref, cfg, positions, seed, picked,
                              control="fp8")
        note = f" over {n} tokens of {len(picked)} requests"
        good &= judged(spec, seed, "program",
                       [("served_logit_gap", gap)], True, note)
        good &= judged(spec, seed, "control_fp8",
                       [("served_logit_gap", cgap)], False, note)
    return good


def main(argv=None):
    from benchmark import harness
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    spec = harness.load_cell(args.workload, rehearse=args.rehearse)
    harness.place_cache()
    device = harness.require_devices(int(spec["cell"]["chips"]),
                                     args.rehearse)
    harness.say(f"device {device}")
    seeds = [int(s) for s in args.seeds.split(",")]
    if spec["mix"]["kind"] == "train":
        good = train(spec, seeds)
    else:
        good = serve(spec, seeds, args.seconds)
    return 0 if good else 1


if __name__ == "__main__":
    sys.exit(main())
