"""Training cells: the fused step of ``pt.jit.train_step`` on a fresh
batch from host memory every step.

Set-up builds ONE object -- model, optimizer, compiled step -- loads the
seed's weights into it, drives it through its first three steps by the
window's own call and feed, keeps what the comparison needs (losses, the
first gradient's norms from the optimizer state, the parameters' change),
and hands the same object to the window.
"""
import time

import numpy as np

from benchmark import check, harness, traffic
from benchmark.references import adafactor, training

CHECK_STEPS = 3


class TrainCell:
    def __init__(self, spec, seed):
        import paddle_tpu as pt
        self.pt = pt
        self.cfg, self.mix = spec["config"], spec["mix"]
        self.chips = int(spec["cell"]["chips"])
        self.seed = seed
        self.ref = harness.load_reference(self.cfg["reference"])
        self.lr = float(self.cfg["assumed"]["learning_rate"])
        self.batch, self.seq = int(self.mix["batch"]), int(self.mix["seq"])
        self.positions = int(self.cfg["max_position_embeddings"])
        self.rows = traffic.train_tokens(self.mix, seed,
                                         int(self.cfg["vocab_size"]))
        self.i = 0                      # steps dispatched so far
        self._build()

    # ---- the system under test, built as chip_smoke.py builds it
    def _build(self):
        pt, cfg = self.pt, self.cfg
        pt.seed(0)
        t = time.perf_counter()
        model = harness.build_model(cfg)
        harness.say(f"model built in {time.perf_counter() - t:.1f} s")
        opt = pt.optimizer.Adafactor(learning_rate=self.lr,
                                     parameters=model.parameters())
        model, opt = pt.amp.decorate(models=model, optimizers=opt,
                                     dtype="bfloat16", master_weight=False)
        harness.load_weights(model, self.ref, cfg, self.seed)
        harness.say(f"weights loaded at +{time.perf_counter() - t:.1f} s")
        self.step = pt.jit.train_step(model, harness.resolve(cfg["loss"]),
                                      opt)
        self.model = model

    # ---- the window's own call and feed
    def feed(self, i):
        rows = self.rows[i % len(self.rows)]
        return (self.pt.to_tensor(rows[:, :-1]),
                self.pt.to_tensor(rows[:, 1:]))

    def one_step(self):
        with harness.span("train.feed"):
            batch = self.feed(self.i)
        with harness.span("train.step"):
            loss = self.step(*batch)
        self.i += 1
        return loss

    @staticmethod
    def read(loss):
        with harness.span("train.read_loss"):
            return float(loss)

    # ---- readings of the first steps, from the program's own state
    def first_steps(self):
        import jax
        import jax.numpy as jnp
        names = self.ref.leaf_names(self.cfg)
        losses, gnorm = [], None
        for i in range(CHECK_STEPS):
            t = time.perf_counter()
            losses.append(self.read(self.one_step()))
            harness.say(f"step {i + 1}: {time.perf_counter() - t:.2f} s")
            if i == 0:
                state = self.step.state_dict()["opt_state"]
                params = dict(self.model.named_parameters())
                order = [n for n, _ in self.model.named_parameters()]
                shapes = [tuple(params[n].shape) for n in order]
                norms = jax.jit(lambda st: jnp.stack([
                    adafactor.grad_norm_from_state(s, shp)
                    for s, shp in zip(st, shapes)]))(state)
                by_name = dict(zip(order, np.asarray(norms)))
                gnorm = np.asarray([by_name[n] for n in names])
                harness.say(f"gradient norms read at "
                            f"+{time.perf_counter() - t:.1f} s")
        params = dict(self.model.named_parameters())
        ref, cfg, seed = self.ref, self.cfg, self.seed
        t = time.perf_counter()

        # the seed's weights again, from the SAME standalone program that
        # made them for the model: inlined into another program, XLA may
        # fuse the draw differently and round a weight the other way
        start = ref.init_weights(cfg, self.positions, seed)

        def change(arrays, start):
            before = ref.to_program(start, cfg)
            return jnp.stack([
                jnp.sqrt(jnp.sum(jnp.square(
                    a.astype(jnp.float32) - before[n].astype(jnp.float32))))
                for n, a in zip(names, arrays)])
        change_norms = np.asarray(jax.jit(change)(
            [params[n]._array for n in names], start))
        del start
        harness.say(f"change norms read in {time.perf_counter() - t:.1f} s")
        return {"losses": losses, "grad_norms": gnorm,
                "change_norms": change_norms}

    def free(self):
        import gc
        import jax
        self.step = self.model = None
        gc.collect()
        jax.clear_caches()
        gc.collect()


def measure(cell, seconds, every):
    """Steps until `seconds` have passed; the clock stops after the last
    step's loss is ready.  Returns (steps, elapsed, loss reads, finite)."""
    import jax
    t0 = time.perf_counter()
    n, reads, loss, finite = 0, [], None, True
    while time.perf_counter() - t0 < seconds:
        loss = cell.one_step()
        n += 1
        if n % every == 0:
            v = cell.read(loss)
            finite = finite and np.isfinite(v)
            reads.append((time.perf_counter() - t0, n))
    if loss is not None:
        jax.block_until_ready(loss._array)
        finite = finite and np.isfinite(float(loss))
    return n, time.perf_counter() - t0, reads, bool(finite)


def run(spec, args, t_start, device):
    cfg, mix = spec["config"], spec["mix"]
    chips = int(spec["cell"]["chips"])
    tokens_per_step = int(mix["batch"]) * int(mix["seq"])
    every = int(mix.get("read_loss_every", 10))
    cell = args.build(spec, args.seed) if getattr(args, "build", None) \
        else TrainCell(spec, args.seed)
    harness.say(f"built at {time.perf_counter() - t_start:.1f} s")
    program = cell.first_steps()
    harness.say("first steps: losses "
                + " ".join(f"{v:.5f}" for v in program["losses"]))
    compiles = harness.CompileCounter()
    setup_s = time.perf_counter() - t_start
    compiles.open()
    # ------------------------------------------------------------ window
    trace_steps = int(mix.get("trace_steps", 8)) if args.trace else 0
    steps, elapsed, reads, finite = measure(cell, args.seconds, every)
    run_data, breakdown, dev_extra = {}, None, {}
    if args.trace:
        import jax
        prof = harness.Profiler(spec["name"])
        prof.start()
        loss = None
        for _ in range(trace_steps):
            loss = cell.one_step()
        jax.block_until_ready(loss._array)
        prof.stop()
    n_compiles = compiles.close()
    peak = harness.memory_peak_bytes(chips) if not args.rehearse else 0
    rate = steps * tokens_per_step / elapsed
    harness.say(f"window: {steps} steps in {elapsed:.3f} s, "
                f"{rate:.1f} tokens/s; compilations inside: {n_compiles}")
    if args.trace:
        tr = prof.read()
        harness.say(f"profiler: start {prof.start_s:.2f} s, stop "
                    f"{prof.stop_s:.2f} s, {trace_steps} steps traced")
        if tr["devices"]:
            dev_extra, breakdown = harness.reduce_trace(tr, chips)
        run_data = {"trace": tr, "traced_steps": trace_steps,
                    "tokens_per_s": rate, "tokens_per_step": tokens_per_step}
    # --------------------------------- the comparison, program state freed
    cell.free()
    del cell
    t = time.perf_counter()
    ref = harness.load_reference(cfg["reference"])
    rows = traffic.train_tokens(mix, args.seed, int(cfg["vocab_size"]))
    reference = training.first_steps(
        ref, cfg, int(cfg["max_position_embeddings"]), args.seed,
        rows[:CHECK_STEPS], float(cfg["assumed"]["learning_rate"]))
    numbers, where = check.training_numbers(program, reference)
    names = ref.leaf_names(cfg)
    for key in ("grad_norms", "change_norms"):
        p, r = np.asarray(program[key]), np.asarray(reference[key])
        gap = np.abs(p - r) / np.maximum(r, np.median(r))
        harness.say(f"{key}: median leaf reference {np.median(r):.6g}; "
                    "worst leaves " + "; ".join(
                        f"{names[i]} program {p[i]:.6g} reference {r[i]:.6g}"
                        for i in np.argsort(-gap)[:3]))
    harness.say(f"reference: {time.perf_counter() - t:.1f} s; losses "
                + " ".join(f"{v:.5f}" for v in reference["losses"])
                + f"; worst leaves: gradient {names[where['grad_leaf']]}, "
                f"change {names[where['change_leaf']]}; "
                f"{where['dead_leaves']} leaves left out of the change")
    limits = spec["mix"]["limits"]
    # a number with no limit in the mix's file has no upper reading
    # (PERF.md): it is printed, not compared
    checks = [(n, v, limits[n]) for n, v in numbers if n in limits]
    harness.say("not compared: " + " ".join(
        f"{n}={v:.3g}" for n, v in numbers if n not in limits))
    checks.append(("compiles_in_window", float(n_compiles), 0.0))
    checks.append(("nonfinite_losses", 0.0 if finite else 1.0, 0.0))
    return {"e2e": {"train_tokens_per_s": rate, "setup_s": setup_s},
            "attempted": steps, "failed": 0 if finite else steps,
            "checks": checks, "peak": peak, "run": run_data,
            "device_extra": dev_extra, "breakdown": breakdown}
