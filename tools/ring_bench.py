"""Ring-attention microbench: einsum streaming-softmax ring vs pallas
flash-kernel ring (VERDICT r3 item 5 evidence).

Reports, per implementation, the AOT compiled temp bytes (peak scratch —
the einsum path materializes [B, H, Lq, Lk_block] f32 score matrices per
step; the flash path is O(block)) and measured wall-clock per fwd+bwd
step.  Default: 8-device virtual CPU mesh, seq 16k (shape-level memory
evidence).  On the TPU claim run with --chip for real timings (sp=1
degenerates the ring there, so --chip benches the per-step kernel path
at full local length).

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python tools/ring_bench.py --seq 16384
"""
import argparse
import os
import sys
import time

sys.path.insert(0, ".")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=16384)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--kv-heads", type=int, default=2)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--chip", action="store_true",
                    help="run on the real TPU (timings); default CPU mesh")
    args = ap.parse_args()

    if not args.chip:
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
        import jax
        jax.config.update("jax_platforms", "cpu")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    from paddle_tpu.distributed.ring_attention import ring_attention

    devs = jax.devices()
    mesh = Mesh(np.array(devs), ("mp",))
    n = len(devs)
    B, L, H, Hkv, D = (args.batch, args.seq, args.heads, args.kv_heads,
                       args.head_dim)
    dtype = jnp.bfloat16 if args.chip else jnp.float32
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, L, H, D), dtype)
    k = jax.random.normal(ks[1], (B, L, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, L, Hkv, D), dtype)

    impls = ["einsum", "flash" if args.chip else "interpret"]
    print(f"# ring attention microbench  seq={L} B={B} H={H} Hkv={Hkv} "
          f"D={D} devices={n} dtype={dtype.__name__}\n")
    print("| impl | fwd+bwd temp bytes | s/step | tokens/s |")
    print("|---|---|---|---|")
    for impl in impls:
        def loss(q, k, v):
            o = ring_attention(q, k, v, mesh=mesh, causal=True, impl=impl)
            return jnp.sum(o.astype(jnp.float32) ** 2)

        g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        lowered = g.lower(q, k, v)
        ms = lowered.compile().memory_analysis()
        temp = ms.temp_size_in_bytes
        out = g(q, k, v)                    # warm, then time
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(args.steps):
            out = g(q, k, v)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / args.steps
        print(f"| {impl} | {temp:,} | {dt:.3f} | {B * L / dt:,.0f} |")


if __name__ == "__main__":
    main()
