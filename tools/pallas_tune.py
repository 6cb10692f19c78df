"""On-chip validation + block-size sweep for the Pallas flash attention.

Run on the real TPU.  For each GPT-shaped config, checks
numerics vs the XLA sdpa reference and times fwd and fwd+bwd for the
pallas kernel at several (block_q, block_k) choices vs plain XLA.

With --write, the best (bq, bk) per (head_dim, seq) is recorded into
paddle_tpu/ops/pallas/tuned_blocks.json — the table flash_attention
loads by default ({device_kind: {head_dim: {seq_bucket: [bq, bk]}}}).

Usage: python tools/pallas_tune.py [--quick] [--write]
"""
import argparse
import itertools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")
from paddle_tpu.ops.pallas import flash_attention as FA  # noqa: E402
from paddle_tpu.ops import dispatch  # noqa: E402

_xla_sdpa = dispatch.get("sdpa").fn
_TABLE_PATH = os.path.join(os.path.dirname(__file__), "..", "paddle_tpu",
                           "ops", "pallas", "tuned_blocks.json")


def _sync(x):
    np.asarray(jax.device_get(x))


def time_fn(fn, *args, iters=20, warmup=3):
    for _ in range(warmup):
        out = fn(*args)
    _sync(out if not isinstance(out, tuple) else out[0])
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    _sync(out if not isinstance(out, tuple) else out[0])
    return (time.perf_counter() - t0) / iters


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--write", action="store_true",
                    help="update paddle_tpu/ops/pallas/tuned_blocks.json "
                         "with the best (bq, bk) per (head_dim, seq)")
    args = ap.parse_args()

    print("devices:", jax.devices(), file=sys.stderr)
    shapes = [(4, 1024, 16, 64), (4, 2048, 16, 128)]
    if not args.quick:
        shapes.append((2, 4096, 16, 128))
    blocks = [(256, 256), (512, 512)] if args.quick else \
        [(128, 128), (256, 256), (512, 512), (512, 256), (256, 512),
         (1024, 512), (512, 1024)]

    best = {}   # (D, L) -> (t_fwd_bwd, (bq, bk))
    for (B, L, H, D), causal in itertools.product(shapes, (True, False)):
        key = jax.random.PRNGKey(0)
        kq, kk, kv, kg = jax.random.split(key, 4)
        q = jax.random.normal(kq, (B, L, H, D), jnp.bfloat16)
        k = jax.random.normal(kk, (B, L, H, D), jnp.bfloat16)
        v = jax.random.normal(kv, (B, L, H, D), jnp.bfloat16)
        do = jax.random.normal(kg, (B, L, H, D), jnp.bfloat16)

        def xla_fwd(q, k, v):
            return _xla_sdpa(q, k, v, mask=None, is_causal=causal)

        def xla_step(q, k, v, do):
            out, vjp = jax.vjp(xla_fwd, q, k, v)
            return vjp(do)

        jx_fwd = jax.jit(xla_fwd)
        jx_step = jax.jit(xla_step)
        t_x_f = time_fn(jx_fwd, q, k, v)
        t_x_b = time_fn(jx_step, q, k, v, do)
        ref = jx_fwd(q, k, v)

        # flops: 2*B*H*L*L*D (qk) + 2*B*H*L*L*D (pv); /2 if causal
        flops = 4 * B * H * L * L * D * (0.5 if causal else 1.0)
        print(f"\n== B{B} L{L} H{H} D{D} causal={causal} "
              f"XLA fwd {t_x_f*1e3:.2f}ms ({flops/t_x_f/1e12:.1f} TF/s) "
              f"fwd+bwd {t_x_b*1e3:.2f}ms", flush=True)

        for bq, bk in blocks:
            if bq > L or bk > L:
                continue
            if not FA.supports(q.shape, k.shape, None, q.dtype,
                               v_shape=v.shape, is_causal=causal):
                print(f"  pallas bq{bq} bk{bk}: unsupported shape")
                continue

            def pl_fwd(q, k, v, bq=bq, bk=bk):
                return FA.flash_attention(q, k, v, is_causal=causal,
                                          block_q=bq, block_k=bk)

            def pl_step(q, k, v, do, bq=bq, bk=bk):
                out, vjp = jax.vjp(lambda a, b, c: pl_fwd(a, b, c), q, k, v)
                return vjp(do)

            try:
                jp_fwd = jax.jit(pl_fwd)
                out = jp_fwd(q, k, v)
                err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                            - ref.astype(jnp.float32))))
                t_p_f = time_fn(jp_fwd, q, k, v)
                jp_step = jax.jit(pl_step)
                t_p_b = time_fn(jp_step, q, k, v, do)
                print(f"  pallas bq{bq} bk{bk}: fwd {t_p_f*1e3:.2f}ms "
                      f"({flops/t_p_f/1e12:.1f} TF/s, {t_x_f/t_p_f:.2f}x) "
                      f"fwd+bwd {t_p_b*1e3:.2f}ms ({t_x_b/t_p_b:.2f}x) "
                      f"maxerr {err:.4f}", flush=True)
                # tune on the causal train-shape step time (the bench path)
                if causal and err < 0.1:
                    cur = best.get((D, L))
                    if cur is None or t_p_b < cur[0]:
                        best[(D, L)] = (t_p_b, (bq, bk))
            except Exception as e:  # Mosaic compile errors surface here
                msg = str(e).splitlines()[0][:160]
                print(f"  pallas bq{bq} bk{bk}: FAILED {msg}", flush=True)

    if args.write and best:
        gen = jax.devices()[0].device_kind
        path = os.path.abspath(_TABLE_PATH)
        try:
            with open(path) as f:
                table = json.load(f)
        except (OSError, ValueError):
            table = {}
        for (D, L), (_, bqbk) in best.items():
            table.setdefault(gen, {}).setdefault(str(D), {})[str(L)] = \
                list(bqbk)
        with open(path, "w") as f:
            json.dump(table, f, indent=1, sort_keys=True)
        print(f"\nwrote {path}: "
              f"{ {k: v[1] for k, v in best.items()} }", flush=True)


if __name__ == "__main__":
    main()
