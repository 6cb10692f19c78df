"""One-step MFU profile of the headline GPT config on the real chip.

Usage (chip-side, run the moment a claim window opens):

    python tools/mfu_profile.py [--preset gpt3-1.3B] [--seq 1024]
        [--batch 4] [--steps 6] [--trace]

Prints, per variant: measured step time, tokens/s, MFU vs the v5e's
197 TFLOP/s bf16 peak, and the device's live/peak HBM next to the
param footprint (donation audit: with donation working, peak ~= params
+ opt state + activations; a second param-sized plateau on top means
donate_argnums regressed).  --trace additionally captures a
jax.profiler trace into bench_results/trace_<preset>/ for op-level
attribution.

Variants swept (cheap, one compile each): pallas flash attention ON
(default) vs OFF — the override gate is decided at import time, so the
OFF leg runs in a subprocess with PADDLE_TPU_PALLAS=0.
"""
import argparse
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def run_variant(preset, seq, batch, steps, trace=False, cpu=False):
    import jax
    if cpu:
        jax.config.update("jax_platforms", "cpu")
    import paddle_tpu as pt
    from paddle_tpu.text import GPTConfig, GPTForCausalLM, gpt_loss_fn

    pt.seed(0)
    cfg = GPTConfig.from_preset(
        preset, vocab_size=50304, max_position_embeddings=seq,
        hidden_dropout=0.0, attention_dropout=0.0, tensor_parallel=False)
    t0 = time.time()
    with pt.LazyGuard():
        model = GPTForCausalLM(cfg)
    opt = pt.optimizer.Adafactor(learning_rate=1e-4,
                                 parameters=model.parameters())
    model, opt = pt.amp.decorate(models=model, optimizers=opt,
                                 dtype="bfloat16", master_weight=False)
    step = pt.jit.train_step(model, gpt_loss_fn, opt)
    ids = pt.randint(0, cfg.vocab_size, [batch, seq])
    labels = pt.randint(0, cfg.vocab_size, [batch, seq])
    build_s = time.time() - t0

    t0 = time.time()
    loss = step(ids, labels)
    float(loss._array)                   # host read = the only real sync
    compile_s = time.time() - t0
    float(step(ids, labels)._array)      # one cached-step warmup

    t0 = time.time()
    for _ in range(steps):
        loss = step(ids, labels)
    final = float(loss._array)
    dt = (time.time() - t0) / steps

    n_params = int(sum(p.size for p in model.parameters()))
    tps = batch * seq / dt
    from paddle_tpu.device import peak_bf16_tflops
    mfu = 6.0 * n_params * tps / (peak_bf16_tflops() * 1e12)

    # donation audit: live HBM peak vs the param+state footprint.  With
    # donation working, peak ~= params(bf16) + opt state + activations;
    # a second param-sized copy on top means donate_argnums regressed.
    audit = {}
    try:
        ms = jax.local_devices()[0].memory_stats() or {}
        audit = {"hbm_peak_gb": round(
                     ms.get("peak_bytes_in_use", 0) / 2 ** 30, 2),
                 "hbm_now_gb": round(
                     ms.get("bytes_in_use", 0) / 2 ** 30, 2),
                 "params_gb": round(2.0 * n_params / 2 ** 30, 2)}
    except Exception:
        pass

    out = {"preset": preset, "seq": seq, "batch": batch,
           "n_params": n_params, "loss": final,
           "build_s": round(build_s, 1), "compile_s": round(compile_s, 1),
           "step_ms": round(dt * 1e3, 2), "tps": round(tps, 1),
           "mfu": round(mfu, 4), **audit}

    if trace:
        d = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "..", "bench_results", f"trace_{preset}")
        os.makedirs(d, exist_ok=True)
        with jax.profiler.trace(d):
            for _ in range(3):
                loss = step(ids, labels)
            float(loss._array)
        out["trace_dir"] = d
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="gpt3-1.3B")
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--trace", action="store_true")
    ap.add_argument("--cpu", action="store_true",
                    help="CPU smoke (numbers are meaningless, wiring "
                         "check only)")
    ap.add_argument("--child", action="store_true",
                    help="internal: run one variant and print JSON")
    args = ap.parse_args()

    if args.child:
        res = run_variant(args.preset, args.seq, args.batch, args.steps,
                          trace=args.trace, cpu=args.cpu)
        print("MFU_RESULT " + json.dumps(res), flush=True)
        return

    # parent: sweep pallas on/off in subprocesses (the override gate is
    # decided at import time)
    for pallas in ("1", "0"):
        env = dict(os.environ, PADDLE_TPU_PALLAS=pallas)
        cmd = [sys.executable, os.path.abspath(__file__), "--child"] \
            + (["--cpu"] if args.cpu else []) + [
               "--preset", args.preset, "--seq", str(args.seq),
               "--batch", str(args.batch), "--steps", str(args.steps)]
        if args.trace and pallas == "1":
            cmd.append("--trace")
        try:
            r = subprocess.run(cmd, env=env, capture_output=True,
                               text=True, timeout=2400)
        except subprocess.TimeoutExpired:
            # fail open: the other variant still runs, the sweep still
            # prints one line per leg (a burned chip window must never
            # yield zero output)
            print(f"pallas={pallas}: FAILED :: timeout after 2400s")
            continue
        for line in r.stdout.splitlines():
            if line.startswith("MFU_RESULT "):
                res = json.loads(line[len("MFU_RESULT "):])
                print(f"pallas={pallas}: {json.dumps(res)}")
                break
        else:
            tail = (r.stderr.strip().splitlines() or ["?"])[-1]
            print(f"pallas={pallas}: FAILED :: {tail[:300]}")


if __name__ == "__main__":
    main()
