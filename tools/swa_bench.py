"""Sliding-window vs full-causal flash attention timings (chip-side).

    python tools/swa_bench.py [--chip | --cpu] [--seq 4096 8192 16384]
        [--window 4096] [--heads 16] [--dim 128]

Measures fwd and fwd+bwd wall time per call for the pallas kernel with
and without the window at each sequence length (host-read sync).  The
expected win is ~L/window once
L >> window, because banded KV blocks are skipped at the grid level.
CPU mode runs interpret-mode on tiny shapes (wiring check only).
"""
import argparse
import json
import sys
import os
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--chip", action="store_true")
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--seq", type=int, nargs="+",
                    default=[4096, 8192, 16384])
    ap.add_argument("--window", type=int, default=4096)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--kv-heads", type=int, default=8)
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--rounds", type=int, default=5)
    args = ap.parse_args()

    if not args.chip and not args.cpu:
        # a bare run is the CPU wiring smoke; --chip measures
        args.cpu = True
    import jax
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
        args.seq = [256]
        args.window = 64
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    interpret = bool(args.cpu)
    rng = np.random.RandomState(0)

    def bench(L, window):
        q = jnp.asarray(rng.randn(1, L, args.heads, args.dim),
                        jnp.bfloat16)
        k = jnp.asarray(rng.randn(1, L, args.kv_heads, args.dim),
                        jnp.bfloat16)
        v = jnp.asarray(rng.randn(1, L, args.kv_heads, args.dim),
                        jnp.bfloat16)

        fwd = jax.jit(lambda a, b, c: flash_attention(
            a, b, c, is_causal=True, window=window, interpret=interpret))

        def loss(a, b, c):
            o = flash_attention(a, b, c, is_causal=True, window=window,
                                interpret=interpret)
            return jnp.sum(o.astype(jnp.float32) ** 2)

        bwd = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

        float(jnp.sum(fwd(q, k, v).astype(jnp.float32)))   # compile+sync
        t0 = time.perf_counter()
        for _ in range(args.rounds):
            o = fwd(q, k, v)
        float(jnp.sum(o.astype(jnp.float32)))
        t_fwd = (time.perf_counter() - t0) / args.rounds

        g = bwd(q, k, v)
        float(jnp.sum(g[0].astype(jnp.float32)))
        t0 = time.perf_counter()
        for _ in range(args.rounds):
            g = bwd(q, k, v)
        float(jnp.sum(g[0].astype(jnp.float32)))
        t_bwd = (time.perf_counter() - t0) / args.rounds
        return t_fwd, t_bwd

    for L in args.seq:
        full_f, full_b = bench(L, None)
        win_f, win_b = bench(L, args.window)
        print(json.dumps({
            "seq": L, "window": args.window,
            "fwd_full_ms": round(full_f * 1e3, 2),
            "fwd_swa_ms": round(win_f * 1e3, 2),
            "fwd_speedup": round(full_f / win_f, 2),
            "bwd_full_ms": round(full_b * 1e3, 2),
            "bwd_swa_ms": round(win_b * 1e3, 2),
            "bwd_speedup": round(full_b / win_b, 2),
        }), flush=True)


if __name__ == "__main__":
    main()
