"""A training cell: FFModel.compile() -> fit(), one chip or four.

Set-up builds ONE object (the compiled model with its state), gives it the
benchmark's weights from the seed, drives it through its first steps with
fit() itself on rows that all differ, reads what those steps produced, and
hands the same object to the window, which calls fit() over and over. Once
the window has closed and the program's state is freed, the plain reference
follows the same first steps and the two readings are compared.
"""
import contextlib
import dataclasses
import sys
import time

import numpy as np

from . import check, runctx, traffic, window


def _quiet():
    # fit() prints its ELAPSED/THROUGHPUT line whatever `verbose` says; the
    # result's line has to stay the last on standard output
    return contextlib.redirect_stdout(sys.stderr)


class TrainCell:
    def __init__(self, cell, builder, ref, spans):
        self.cell, self.builder, self.ref, self.spans = cell, builder, ref, spans
        self.mix, self.config = cell.mix, cell.config
        self.model = None

    # -- set-up ------------------------------------------------------------
    def build(self):
        from flexflow_tpu import (AdamOptimizer, FFConfig, FFModel, LossType,
                                  MetricsType)

        mix, opt = self.mix, self.mix["optimizer"]
        fc = FFConfig()
        fc.batch_size = mix["batch"]
        fc.workersPerNode = self.cell.chips
        fc.allow_mixed_precision = \
            self.config["dtype_policy"]["allow_mixed_precision"]
        fc.search_budget = mix.get("search_budget", -1)
        self.model = model = FFModel(fc)
        self.builder.build(model, self.config, mix["batch"], mix["seq"])
        with self.spans.span("search_s"), _quiet():
            model.compile(
                optimizer=AdamOptimizer(
                    alpha=opt["alpha"], beta1=opt["beta1"], beta2=opt["beta2"],
                    weight_decay=opt["weight_decay"], epsilon=opt["epsilon"]),
                loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
                metrics=[MetricsType.METRICS_SPARSE_CATEGORICAL_CROSSENTROPY])
        self.names = names = self.builder.names(self.config)
        self._refs = {}  # precision -> the reference's jitted pieces
        import jax
        import jax.numpy as jnp

        b1 = opt["beta1"]

        @jax.jit
        def grad_norms(m):
            return {k: jnp.linalg.norm(m[op][w].astype(jnp.float32)) / (1 - b1)
                    for k, (op, w) in names.items()}

        @jax.jit
        def moved_norms(p, p0):
            return {k: jnp.linalg.norm(p[op][w] - p0[k])
                    for k, (op, w) in names.items()}

        self._grad_norms, self._moved_norms = grad_norms, moved_norms

    def load_seed(self, seed):
        """The benchmark's own weights, made on the device from the seed, in
        the program's place; fresh optimizer state; the seed's batches."""
        self.free()
        model = self.model
        tree = runctx.program_tree(self.names,
                                   self.ref.init(self.config, seed))
        model.state = dataclasses.replace(
            model.state, params=tree, step=0,
            opt_state=model.optimizer.init_state(tree))
        self.x, self.y = traffic.train_batches(
            self.mix, self.ref.sizes(self.config)["vocab"], seed)

    def _fit(self, lo, hi):
        x = self.x[lo:hi].reshape(-1, self.x.shape[-1])
        y = self.y[lo:hi].reshape(-1, *self.y.shape[-2:])
        with _quiet():
            pm = self.model.fit(x, y, batch_size=self.mix["batch"], epochs=1,
                                verbose=False)
        return pm.sparse_cce_loss / pm.train_rows

    def first_steps(self, seed):
        """The program's reading of its first steps, each one fit() call on
        its own batch: the loss of each, the first gradient as the optimizer
        got it (from Adam's first moment after one step), and how far every
        leaf moved over them all."""
        losses = []
        # fit()'s first call builds the step's program; its second builds it
        # again (the first state holds host scalars, the second the step's
        # own outputs): both are set-up
        with self.spans.span("compile_s"):
            losses.append(self._fit(0, 1))
            grad = self._grad_norms(self.model.state.opt_state["m"])
            for i in range(1, self.mix["check_steps"]):
                losses.append(self._fit(i, i + 1))
        p0 = self.ref.init(self.config, seed)
        moved = self._moved_norms(self.model.state.params, p0)
        runctx.free(p0)
        to_float = lambda d: {k: float(v) for k, v in d.items()}  # noqa: E731
        return {"loss": [float(v) for v in losses], "grad": to_float(grad),
                "moved": to_float(moved)}

    # -- the window ----------------------------------------------------------
    def window(self, seconds, tracer):
        """fit() over `steps_per_fit` batches again and again until the time
        is up; every call ends in block_until_ready on the new state. Returns
        (tokens, t_open, t_close); the profiler's own start, made between two
        calls while the device is idle, is taken out of the window's time."""
        import jax

        lo = self.mix["check_steps"]
        hi = lo + self.mix["steps_per_fit"]
        per_fit = (hi - lo) * self.mix["batch"] * self.mix["seq"]
        tokens, paused = 0, 0.0
        t_open = time.perf_counter()
        while True:
            elapsed = time.perf_counter() - t_open - paused
            if elapsed >= seconds:
                break
            paused += tracer.maybe_start(elapsed, seconds)
            with jax.profiler.TraceAnnotation("perfbench.fit"):
                self.last_loss = self._fit(lo, hi)
            tokens += per_fit
        t_close = time.perf_counter()
        tracer.stop()
        return tokens, t_open + paused, t_close

    def free(self):
        runctx.free((self.model.state.params, self.model.state.opt_state))

    # -- the reference, and what may stand in the program's place -------------
    def reference_steps(self, seed, precision="f32", rows=None, stuck=False):
        """The same first steps by the plain reference, from the same seed:
        its own weights, the same batches, Adam as the mix states it.
        `precision` below f32 is the control; `rows` fewer than the batch is
        the fault that leaves part of the batch out; `stuck` the fault of a
        step that returns its state unchanged."""
        import jax.numpy as jnp

        ref, opt = self.ref, self.mix["optimizer"]
        assert opt["weight_decay"] == 0.0
        hyper = (opt["alpha"], opt["beta1"], opt["beta2"], opt["epsilon"])
        if precision not in self._refs:
            self._refs[precision] = ref.Reference(self.config, precision)
        model = self._refs[precision]
        p0 = ref.init(self.config, seed)
        params, state = p0, ref.adam_init(p0)
        out = {"loss": []}
        for i in range(self.mix["check_steps"]):
            ids = jnp.asarray(self.x[i][:rows])
            labels = jnp.asarray(self.y[i][:rows, :, 0])
            loss, grads = model.loss_and_grads(params, ids, labels)
            out["loss"].append(float(loss))
            if i == 0:
                out["grad"] = {k: float(v)
                               for k, v in ref.norms(grads).items()}
            if not stuck:
                params, state = ref.adam(params, grads, state, hyper)
        out["moved"] = {k: float(v)
                        for k, v in ref.diff_norms(params, p0).items()}
        return out


def run(cell, builder, ref, args, run_ctx):
    """One run of a training cell; fills run_ctx and returns its checks."""
    spans, tracer = run_ctx.spans, run_ctx.tracer
    tc = TrainCell(cell, builder, ref, spans)
    tc.build()
    with spans.span("weights_s"):
        tc.load_seed(args.seed)
    program = tc.first_steps(args.seed)
    run_ctx.before_window()
    run_ctx.open_window()
    tokens, t_open, t_close = tc.window(args.seconds, tracer)
    run_ctx.close_window()
    steps = tokens // (cell.mix["batch"] * cell.mix["seq"])
    run_ctx.facts.update(tokens=tokens, steps=steps, last_loss=tc.last_loss,
                         window_s=t_close - t_open)
    run_ctx.attempted, run_ctx.failed = steps, 0
    run_ctx.end_to_end.update(
        window.train_metrics(tokens, t_open, t_close, cell.chips))
    run_ctx.read_memory()
    tc.free()
    t0 = time.perf_counter()
    reference = tc.reference_steps(args.seed)
    print(f"reference: {time.perf_counter() - t0:.1f} s", file=sys.stderr)
    checks = check.Checks(cell.params["limits"])
    for name, value in check.train_numbers(program, reference).items():
        if name in checks.limits:
            checks.add(name, value)
        else:  # the worst leaf's name, or a number PERF.md says why not
            print(f"not compared: {name} {value}", file=sys.stderr)
    if not np.isfinite(tc.last_loss):
        print(f"last loss of the window not finite: {tc.last_loss}",
              file=sys.stderr)
    return checks
