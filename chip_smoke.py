#!/usr/bin/env python
"""chip_smoke — the quickest proof that paddle_tpu still starts on the chip.

    python chip_smoke.py              # one TPU chip: train, then serve
    python chip_smoke.py --chips 4    # four chips: the sharded step only
    JAX_PLATFORMS=cpu PADDLE_TPU_PALLAS=interpret \
        python chip_smoke.py --rehearse   # tiny sizes, any backend

Default run (one chip), all phases in THIS one process — a chip belongs
to one process at a time, so nothing here starts a child:

1. device: ``jax.devices()[0].platform`` must be ``tpu``.
2. train: gpt3-1.3B (vocab 50304, seq 1024, batch 4, bf16 AMP-O2 without
   master weights, Adafactor, built under LazyGuard) through
   ``pt.amp.decorate`` + ``pt.jit.train_step``; 8 steps on a fixed batch,
   every loss finite, the last below the first, the flash kernel in the
   compiled step.
3. serve: the same preset through ``serving.LLMEngine`` (add_request /
   step until drained): 8 greedy requests, prompts of 16-512 tokens from
   the seed, 32 new tokens each; chunked prefill and continuous batching
   asserted from the engine's counters; two streams compared token for
   token with sequential ``generation.generate``; no pool leaks; the
   paged kernel in the decode program.  Weights are f32 and matmuls run
   at "highest" precision: greedy token identity between two attention
   paths that sum in different orders is not a property of bf16
   arithmetic (the tiny bf16 rehearsal already differs on the CPU), so
   the comparison is made where it is one.  The bf16 paged kernel is run
   against the gather fallback on its own.  The train state is released
   before the engine is built.
4. compile cache: the directory in use and its entry count.

``--chips 4`` runs ONLY the sharded path and what it is compared with:
the Llama-block config (hidden 2048, 16 layers) under dp2 x mp2 with
sharding stage 2 through ``fleet.build_train_step``, against the same
seed and batch on a 1-device mesh in the same process; then one
pp2 x mp2 leg of four 1F1B steps (GPT at gpt3-1.3B widths, 8 layers).

The numbers printed are smoke lines, not benchmark results.  The last
line of stdout is ``{"ok": true, "device": {...}}`` only when every phase
passed on a TPU; any failure exits non-zero without it.  ``--rehearse``
shrinks the sizes and lets any backend through (the first rehearsal of
the on-chip-measurement guide); it never prints the ok line.
"""
import argparse
import gc
import json
import sys
import time

FULL = dict(
    gpt=dict(preset="gpt3-1.3B", vocab=50304, seq=1024, batch=4, steps=8),
    serve=dict(n=8, prompt_lo=16, prompt_hi=512, new_tokens=32,
               prefill_chunk=64, max_running=8),
    llama=dict(hidden=2048, layers=16, heads=16, inter=5504, vocab=32000,
               seq=1024, batch=8, steps=4),
    # gpt3-1.3B widths, depth cut to 8 (Llama has no pipeline_decompose)
    pipe=dict(preset="gpt3-1.3B", vocab=50304, num_layers=8, seq=1024,
              batch=8, microbatches=2, steps=4),
)
# head_dim stays 128 so the paged kernel's gate still opens
TINY = dict(
    gpt=dict(preset=None, vocab=512, seq=64, batch=2, steps=8,
             hidden_size=256, num_layers=2, num_heads=2),
    serve=dict(n=8, prompt_lo=4, prompt_hi=40, new_tokens=8,
               prefill_chunk=16, max_running=8),
    llama=dict(hidden=256, layers=2, heads=4, inter=512, vocab=512,
               seq=64, batch=8, steps=4),
    pipe=dict(preset=None, vocab=512, hidden_size=256, num_heads=2,
              num_layers=4, seq=64, batch=8, microbatches=2, steps=4),
)
SEED = 0
KERNEL = "tpu_custom_call"     # how a Mosaic kernel shows in program text
# bf16 params + f32 loss; dp2 x mp2 reduces in another order than 1 device
LOSS_RTOL = 5e-3


def say(msg):
    print(f"# smoke: {msg}", flush=True)


def check(cond, what):
    if not cond:
        raise AssertionError(what)
    say(f"ok: {what}")


def peak_hbm_gb(dev):
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return None if peak is None else peak / 2 ** 30


def run_steps(step, batch, n, tag):
    """n timed steps (clock stopped after block_until_ready); prints and
    returns the loss series."""
    import jax
    losses, times = [], []
    for _ in range(n):
        t0 = time.perf_counter()
        loss = step(*batch)
        jax.block_until_ready(loss._array)
        times.append(time.perf_counter() - t0)
        losses.append(float(loss._array))
    say(f"{tag}: losses " + " ".join(f"{v:.4f}" for v in losses))
    say(f"{tag}: step s " + " ".join(f"{t:.3f}" for t in times)
        + " (the first includes the jit dispatch; smoke line)")
    return losses


def release():
    """Drop dead device buffers and compiled programs between phases."""
    import jax
    gc.collect()
    jax.clear_caches()
    gc.collect()


# ------------------------------------------------------------------- train
def gpt_config(size, max_len):
    from paddle_tpu.text import GPTConfig
    g = dict(size["gpt"])
    preset, vocab = g.pop("preset"), g.pop("vocab")
    for k in ("seq", "batch", "steps"):
        g.pop(k)
    kw = dict(vocab_size=vocab, max_position_embeddings=max_len,
              hidden_dropout=0.0, attention_dropout=0.0,
              tensor_parallel=False, **g)
    return GPTConfig.from_preset(preset, **kw) if preset else GPTConfig(**kw)


def phase_train(size, on_tpu):
    import jax
    import numpy as np
    import paddle_tpu as pt
    from paddle_tpu.text import GPTForCausalLM, gpt_loss_fn

    g = size["gpt"]
    pt.seed(SEED)
    cfg = gpt_config(size, g["seq"])
    with pt.LazyGuard():
        model = GPTForCausalLM(cfg)
    opt = pt.optimizer.Adafactor(learning_rate=1e-4,
                                 parameters=model.parameters())
    model, opt = pt.amp.decorate(models=model, optimizers=opt,
                                 dtype="bfloat16", master_weight=False)
    step = pt.jit.train_step(model, gpt_loss_fn, opt)
    ids = pt.randint(0, cfg.vocab_size, [g["batch"], g["seq"]])
    labels = pt.randint(0, cfg.vocab_size, [g["batch"], g["seq"]])
    n_params = sum(p.size for p in model.parameters())
    say(f"train: {g['preset'] or 'tiny gpt'} params={n_params / 1e9:.3f}B "
        f"batch={g['batch']} seq={g['seq']} bf16 Adafactor")

    t0 = time.perf_counter()
    compiled = step.lower(ids, labels).compile()
    say(f"train: compile {time.perf_counter() - t0:.1f} s (smoke line)")
    if on_tpu:
        check(KERNEL in compiled.as_text(),
              "flash kernel is in the compiled train step")
    del compiled

    losses = run_steps(step, (ids, labels), g["steps"], "train")
    peak = peak_hbm_gb(jax.devices()[0])
    say("train: peak HBM "
        + (f"{peak:.2f} GiB" if peak is not None else "not reported")
        + " (smoke line)")
    check(len(losses) == g["steps"] and bool(np.isfinite(losses).all()),
          f"{g['steps']} finite training losses")
    check(losses[-1] < losses[0], "last training loss below the first")


# ------------------------------------------------------------------- serve
def check_paged_kernel_bf16(size, on_tpu):
    """The decode kernel at the serving shape in bf16, as the chip runs
    it, against the gather fallback on the same random pool."""
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops.nn_kernels import paged_attention_k
    from paddle_tpu.ops.pallas import paged_attention as pa

    g = size["gpt"]
    heads = g.get("num_heads", 16)
    B, D, bs, M = 8, 128, 16, 8
    rs = np.random.RandomState(SEED)
    q = jnp.asarray(rs.randn(B, 1, heads, D), jnp.bfloat16)
    kp, vp = (jnp.asarray(rs.randn(B * M, bs, heads, D), jnp.bfloat16)
              for _ in range(2))
    tables = jnp.asarray(rs.permutation(B * M).reshape(B, M), jnp.int32)
    pos = jnp.asarray(rs.randint(0, M * bs, size=B), jnp.int32)
    check(pa.supports(q.shape, kp.shape, q.dtype),
          "paged kernel supports the bf16 serving shape")
    out = pa.paged_decode_attention(q, kp, vp, tables, pos + 1,
                                    interpret=not on_tpu)
    ref = paged_attention_k(q, kp, vp, tables, pos)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - ref.astype(jnp.float32))))
    check(err < 5e-2, f"bf16 paged kernel matches the gather fallback "
                      f"(max abs err {err:.4f})")


def phase_serve(size, on_tpu):
    import jax
    with jax.default_matmul_precision("highest"):
        _phase_serve(size, on_tpu)
    check_paged_kernel_bf16(size, on_tpu)


def _phase_serve(size, on_tpu):
    import jax
    import numpy as np
    import paddle_tpu as pt
    from paddle_tpu import serving
    from paddle_tpu.observability import metrics
    from paddle_tpu.text import GPTForCausalLM, generation

    s = size["serve"]
    max_len = s["prompt_hi"] + s["new_tokens"]
    pt.seed(SEED)
    cfg = gpt_config(size, max_len)
    with pt.LazyGuard():
        model = GPTForCausalLM(cfg)
    rs = np.random.RandomState(SEED)
    lens = [s["prompt_lo"], s["prompt_hi"]] + [
        int(rs.randint(s["prompt_lo"], s["prompt_hi"] + 1))
        for _ in range(s["n"] - 2)]
    prompts = [rs.randint(0, cfg.vocab_size, size=n).tolist() for n in lens]
    block = 16                                  # the engine's default
    num_blocks = sum(-(-(n + s["new_tokens"]) // block) for n in lens) + 4
    metrics.registry().reset()
    eng = serving.LLMEngine(model, num_blocks=num_blocks, block_size=block,
                            max_running=s["max_running"],
                            prefill_chunk=s["prefill_chunk"])
    say(f"serve: {s['n']} greedy requests, prompts {sorted(lens)}, "
        f"{s['new_tokens']} new tokens, pool {num_blocks} x {block}")

    # half the requests arrive while the first half is in flight
    t0 = time.perf_counter()
    reqs, steps, first = [], [], s["n"] // 2
    for p in prompts[:first]:
        reqs.append(eng.add_request(p, max_new_tokens=s["new_tokens"]))
    for _ in range(4):
        steps.append(eng.step())
    for p in prompts[first:]:
        reqs.append(eng.add_request(p, max_new_tokens=s["new_tokens"]))
    while eng.has_work:
        steps.append(eng.step())
    say(f"serve: drained in {len(steps)} engine steps, "
        f"{time.perf_counter() - t0:.1f} s incl. compiles (smoke line)")

    reg = metrics.registry()
    check(all(r.finish_reason == "length"
              and len(r.generated) == s["new_tokens"] for r in reqs),
          f"{s['n']} requests served {s['new_tokens']} tokens each")
    prefill_steps = sum(1 for st in steps if st["prefilled"])
    chunks_longest = -(-(s["prompt_hi"] - 1) // s["prefill_chunk"])
    check(reg.counter("serving_prefill_tokens_total").value
          == sum(n - 1 for n in lens)
          and max(st["prefilled"] for st in steps) <= s["prefill_chunk"]
          and prefill_steps >= chunks_longest >= 2,
          f"chunked prefill: {prefill_steps} prefill steps of at most "
          f"{s['prefill_chunk']} tokens; the longest prompt alone needs "
          f"{chunks_longest}")
    batch = reg.histogram("serving_decode_batch")
    mixed = sum(1 for st in steps if st["prefilled"] and st["decoded"])
    check(batch.sum > batch.count and mixed > 0,
          f"continuous batching: mean decode batch "
          f"{batch.sum / max(batch.count, 1):.2f}, {mixed} steps "
          f"prefilled and decoded together")
    builder, structs = eng.program_structs(("decode",))
    if on_tpu:
        check(KERNEL in builder().lower(*structs).as_text(),
              "paged kernel is in the decode program")
    check(eng.pool.check_leaks() == ([], []), "no pool leaks")
    eng.close()
    peak = peak_hbm_gb(jax.devices()[0])
    say("serve: peak HBM (process, train phase included) "
        + (f"{peak:.2f} GiB" if peak is not None else "not reported")
        + " (smoke line)")

    # sequential reference for the shortest and the longest prompt
    for i in (0, 1):
        ref = generation.generate(
            model, pt.to_tensor(np.asarray([prompts[i]], "int64")),
            max_new_tokens=s["new_tokens"], shape_buckets="on")
        ref = np.asarray(ref._array)[0, lens[i]:].tolist()
        if ref != reqs[i].generated:
            at = next(j for j, (a, b) in
                      enumerate(zip(ref, reqs[i].generated)) if a != b)
            say(f"serve: stream {i} differs at token {at}: engine "
                f"{reqs[i].generated[at:at + 4]} generate {ref[at:at + 4]}")
        check(ref == reqs[i].generated,
              f"stream {i} (prompt {lens[i]}) token-identical to generate")


# ------------------------------------------------------------- four chips
def llama_losses(size, degrees):
    """(loss series, compiled step text, model) of the Llama-block step
    under `degrees` = (dp, mp), on the first dp * mp devices."""
    import paddle_tpu as pt
    import paddle_tpu.nn.functional as F
    from paddle_tpu.distributed import fleet, mesh as mesh_mod
    from paddle_tpu.text.llama import LlamaConfig, LlamaForCausalLM

    c = size["llama"]
    dp, mp = degrees
    mesh_mod.clear_mesh()
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {
        "dp_degree": dp, "mp_degree": mp, "pp_degree": 1,
        "sharding_degree": 1, "sharding_stage": 2}
    fleet.init(is_collective=True, strategy=strategy)
    pt.seed(SEED)
    cfg = LlamaConfig(vocab_size=c["vocab"], hidden_size=c["hidden"],
                      num_layers=c["layers"], num_heads=c["heads"],
                      intermediate_size=c["inter"],
                      max_position_embeddings=c["seq"], use_recompute=True,
                      tensor_parallel=True)
    with pt.LazyGuard():
        model = LlamaForCausalLM(cfg)
    opt = pt.optimizer.Adafactor(learning_rate=1e-4,
                                 parameters=model.parameters())
    model, opt = pt.amp.decorate(models=model, optimizers=opt,
                                 dtype="bfloat16", master_weight=False)

    def loss_fn(m, ids, labels):
        return F.cross_entropy(m(ids), labels, reduction="mean")

    step = fleet.build_train_step(model, loss_fn, opt)
    ids = pt.randint(0, cfg.vocab_size, [c["batch"], c["seq"]])
    labels = pt.randint(0, cfg.vocab_size, [c["batch"], c["seq"]])
    t0 = time.perf_counter()
    text = step.lower(ids, labels).compile().as_text()
    say(f"dp{dp} x mp{mp}: compile {time.perf_counter() - t0:.1f} s "
        f"(smoke line)")
    losses = run_steps(step, (ids, labels), c["steps"], f"dp{dp} x mp{mp}")
    return losses, text, model


def phase_four_chips(size, on_tpu):
    import jax
    import numpy as np

    check(len(jax.devices()) >= 4, "four devices for --chips 4")
    losses, text, model = llama_losses(size, (2, 2))
    dev0, placed, sharded, whole_on_0 = jax.devices()[0], set(), 0, []
    for name, p in model.named_parameters():
        shards = p._array.addressable_shards
        placed |= {s.device.id for s in shards}
        if p.pspec is None or not any(p.pspec):
            continue            # replicated by its annotation (norms)
        sharded += 1
        if any(s.device == dev0 and s.data.shape == p._array.shape
               for s in shards):
            whole_on_0.append(name)
    check(len(placed) == 4, f"parameter shards live on 4 devices {placed}")
    check(sharded and not whole_on_0,
          f"none of the {sharded} mp-annotated parameters is whole on "
          f"device 0 (the rest, norms, are replicated by annotation)")
    check("all-reduce" in text, "all-reduce in the compiled dp2 x mp2 step")
    if on_tpu:
        check(KERNEL in text,
              "flash kernel is in the compiled dp2 x mp2 step")
    say(f"dp2 x mp2: collectives in the step: "
        + ", ".join(f"{k} x{text.count(k + '(') + text.count(k + '-start(')}"
                    for k in ("all-reduce", "all-gather",
                              "reduce-scatter", "all-to-all")))
    peak = max((peak_hbm_gb(d) or 0) for d in jax.devices()[:4])
    say(f"dp2 x mp2: peak HBM on a device {peak:.2f} GiB (smoke line)")
    del model
    release()

    ref, _, model = llama_losses(size, (1, 1))
    del model
    check(bool(np.isfinite(losses + ref).all()), "all losses finite")
    check(np.allclose(losses, ref, rtol=LOSS_RTOL),
          f"dp2 x mp2 loss series within rtol {LOSS_RTOL} of the "
          f"1-device series")
    release()
    pipeline_leg(size, on_tpu)


def pipeline_leg(size, on_tpu):
    """A few 1F1B steps under pp2 x mp2: the flash kernel runs inside the
    pipeline's pp-manual stage body, nested in its own shard_map."""
    import numpy as np
    import paddle_tpu as pt
    from paddle_tpu.distributed import fleet, mesh as mesh_mod
    from paddle_tpu.text import GPTConfig, GPTForCausalLM, gpt_loss_fn

    c = dict(size["pipe"])
    preset, vocab, seq = c.pop("preset"), c.pop("vocab"), c.pop("seq")
    batch, mb, steps = c.pop("batch"), c.pop("microbatches"), c.pop("steps")
    mesh_mod.clear_mesh()
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {
        "dp_degree": 1, "mp_degree": 2, "pp_degree": 2,
        "sharding_degree": 1, "accumulate_steps": mb}
    fleet.init(is_collective=True, strategy=strategy)
    pt.seed(SEED)
    kw = dict(vocab_size=vocab, max_position_embeddings=seq,
              hidden_dropout=0.0, attention_dropout=0.0,
              use_recompute=True, tensor_parallel=True, **c)
    cfg = GPTConfig.from_preset(preset, **kw) if preset else GPTConfig(**kw)
    with pt.LazyGuard():
        model = GPTForCausalLM(cfg)
    opt = pt.optimizer.Adafactor(learning_rate=1e-4,
                                 parameters=model.parameters())
    model, opt = pt.amp.decorate(models=model, optimizers=opt,
                                 dtype="bfloat16", master_weight=False)
    step = fleet.build_train_step(model, gpt_loss_fn, opt)
    ids = pt.randint(0, cfg.vocab_size, [batch, seq])
    labels = pt.randint(0, cfg.vocab_size, [batch, seq])
    t0 = time.perf_counter()
    text = step.lower(ids, labels).compile().as_text()
    say(f"pp2 x mp2: {step.pp_schedule} schedule, {cfg.num_layers} layers, "
        f"compile {time.perf_counter() - t0:.1f} s (smoke line)")
    losses = run_steps(step, (ids, labels), steps, "pp2 x mp2")
    check(bool(np.isfinite(losses).all()) and losses[-1] < losses[0],
          f"{steps} finite pipeline losses, the last below the first")
    check("collective-permute" in text,
          "collective-permute (the stage ring) in the compiled step")
    if on_tpu:
        check(KERNEL in text,
              "flash kernel is in the compiled pp2 x mp2 step")


# -------------------------------------------------------------------- main
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the dp2 x mp2 step and its 1-device "
                         "comparison")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any backend; never prints the "
                         "ok line")
    args = ap.parse_args(argv)

    import jax
    import jaxlib
    from importlib import metadata
    from paddle_tpu.jit import compile_cache as cc

    cache_dir = cc.place_jax_cache()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    say(f"device {json.dumps(device)} jax {jax.__version__} "
        f"jaxlib {jaxlib.__version__} libtpu {libtpu}")
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.rehearse:
        print(f"chip_smoke: no TPU (jax found {dev.platform}); nothing "
              f"was run", file=sys.stderr)
        return 1
    size = TINY if args.rehearse else FULL
    say(f"compile cache {cache_dir}: "
        f"{cc.jax_cache_entries(cache_dir)} entries at start")

    t0 = time.perf_counter()
    if args.chips == 4:
        phase_four_chips(size, on_tpu)
    else:
        phase_train(size, on_tpu)
        release()
        phase_serve(size, on_tpu)
    say(f"compile cache {cache_dir}: "
        f"{cc.jax_cache_entries(cache_dir)} entries after the run")
    say(f"all phases passed in {time.perf_counter() - t0:.0f} s")
    if args.rehearse:
        say("rehearsal only: not a chip result")
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
