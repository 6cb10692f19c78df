"""On-chip ResNet-50 throughput sweep (VERDICT r3 item 2: get the convnet
leg to >= 1.0x the A100 2,500 img/s bar).

Sweeps the levers that matter on TPU: data_format (NCHW vs channels-last
NHWC), the space-to-depth stem, and batch size; prints img/s + MFU per
config and names the winner so bench.py defaults (BENCH_RESNET_FORMAT /
s2d/batch) can be set from evidence.  Timing syncs by a host read.

Usage (on a TPU):
    python tools/resnet_tune.py [--quick]
"""
import argparse
import itertools
import json
import sys
import time

sys.path.insert(0, ".")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--steps", type=int, default=15)
    args = ap.parse_args()

    import bench

    fmts = ["NCHW", "NHWC"]
    s2ds = [True, False]
    batches = [256] if args.quick else [256, 512]
    # ResNet-50 fwd ~4.1 GMAC @224 = 8.2 GFLOP (2 flops/MAC, matching
    # bench.py's 6*N*tps convention); train ~3x fwd
    train_flops = 3 * 2 * 4.1e9
    peak = bench.PEAK_TFLOPS * 1e12

    results = []
    for fmt, s2d, b in itertools.product(fmts, s2ds, batches):
        t0 = time.time()
        try:
            r = bench.run_resnet(batch=b, steps=args.steps, warmup=3,
                                 s2d_stem=s2d, data_format=fmt)
        except Exception as e:
            print(f"{fmt} s2d={s2d} b{b}: FAILED "
                  f"{str(e).splitlines()[0][:140]}", flush=True)
            continue
        ips = r["ips"]
        mfu = ips * train_flops / peak
        results.append((ips, fmt, s2d, b))
        print(f"{fmt} s2d={s2d} b{b}: {ips:,.0f} img/s "
              f"(MFU {mfu*100:.1f}%, vs A100 {ips/2500.0:.2f}x, "
              f"wall {time.time()-t0:.0f}s)", flush=True)

    if results:
        best = max(results)
        print(json.dumps({
            "best_img_per_s": round(best[0], 1),
            "data_format": best[1], "s2d_stem": best[2], "batch": best[3],
            "vs_a100": round(best[0] / 2500.0, 3)}))


if __name__ == "__main__":
    main()
