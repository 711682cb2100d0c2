"""A serving cell: AdmissionQueue.offer() -> ContinuousBatcher, in this
process, under an open-loop schedule made from the seed.

The generator runs on a thread of its own, stays off the device and reports
how late it offered against its schedule. The schedule starts before the
window (the mix's `preroll`), so the window opens on a server already under
the cell's load; it opens and closes at the end of a decode iteration, and
every output token is counted where it was produced. At the close the
serving thread is stopped, the memory peak is read and the program's state
is freed, and only then the plain reference runs over the requests the
server answered and over what it had given those still in their slots, all
of them: the number compared is the widest gap by which a served
token's reference logit lies below the reference's best.
"""
import contextlib
import dataclasses
import gc
import sys
import threading
import time

import numpy as np

from . import check, runctx, traffic, window


class ServeCell:
    def __init__(self, cell, builder, ref, spans):
        self.cell, self.builder, self.ref, self.spans = cell, builder, ref, spans
        self.mix, self.config, self.serving = \
            cell.mix, cell.config, cell.params["serving"]

    # -- set-up ------------------------------------------------------------
    def build(self):
        from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer

        sv = self.serving
        fc = FFConfig()
        fc.batch_size = sv["slots"]
        fc.workersPerNode = self.cell.chips
        fc.allow_mixed_precision = \
            self.config["dtype_policy"]["allow_mixed_precision"]
        fc.search_budget = sv.get("search_budget", -1)
        self.model = model = FFModel(fc)
        self.builder.build(model, self.config, sv["slots"], sv["max_len"])
        with self.spans.span("search_s"), \
                contextlib.redirect_stdout(sys.stderr):
            # compile() wants an optimizer and a loss; serving uses neither
            model.compile(
                optimizer=SGDOptimizer(lr=0.0),
                loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
                metrics=[])
            model.compile_decode()
        self.names = self.builder.names(self.config)

    def load_seed(self, seed):
        model = self.model
        runctx.free(model.state.params)
        model.state = dataclasses.replace(
            model.state, params=runctx.program_tree(
                self.names, self.ref.init(self.config, seed)))

    def start(self):
        """Boot the batcher (its `precompile` builds the batched step and the
        prefill buckets) and serve two short requests, so that everything on
        the served path, the eager cache insert too, has run once."""
        from flexflow_tpu.runtime.serving import (AdmissionQueue,
                                                  ContinuousBatcher,
                                                  ServingConfig)

        sv = self.serving
        scfg = ServingConfig(max_len=sv["max_len"], slots=sv["slots"],
                             page_size=sv["page_size"], precompile=True,
                             default_deadline_s=sv["deadline_s"])
        self.queue = AdmissionQueue(max_depth=sv["queue_depth"])
        with self.spans.span("compile_s"):
            self.batcher = ContinuousBatcher(self.model, scfg,
                                             self.queue).start()
            vocab = self.ref.sizes(self.config)["vocab"]
            warm = [self._offer(np.arange(n, dtype=np.int32) % vocab, 4)
                    for n in (5, 9)]
            self.drain(warm, 3600.0)
            for r in warm:
                r.result(timeout=1.0)

    def _offer(self, ids, out_tokens):
        from flexflow_tpu.runtime.serving import GenerationRequest

        req = GenerationRequest(ids, out_tokens,
                                deadline_s=self.serving["deadline_s"])
        self.queue.offer(req)
        return req

    def _alive(self):
        if self.batcher.dead:
            raise RuntimeError(f"serve thread died: "
                               f"{self.batcher.death_cause!r}")

    def drain(self, reqs, timeout):
        """Wait until every one of `reqs` is answered; False at the timeout."""
        end = time.monotonic() + timeout
        for req in reqs:
            while not req.done():
                self._alive()
                if time.monotonic() > end:
                    return False
                req.wait(0.05)
        return True

    # -- the window ----------------------------------------------------------
    def _iteration_edge(self, timeout=10.0):
        """The moment the decode iteration now running ends (now, on an idle
        server): the window's edges lie there, so that it holds whole
        iterations and no token is counted on the wrong side."""
        stats, end = self.batcher.stats, time.monotonic() + timeout
        n = stats["iterations"]
        while stats["iterations"] == n and self.batcher.active_slots \
                and time.monotonic() < end:
            self._alive()
            time.sleep(0.001)
        return time.monotonic()

    def _produced(self, rows):
        """Output tokens each request of `rows` has been given by now."""
        live = {id(s.req): len(s.tokens) - s.prompt_len
                for s in self.batcher.in_flight()}
        out = []
        for row in rows:
            req = row["req"]
            if req is None or not req.done():
                out.append(live.get(id(req), 0))
            else:
                out.append(len(req.tokens) - len(row["prompt"])
                           if req.tokens is not None else 0)
        return out

    def window(self, schedule, seconds, tracer, on_open=lambda: None,
               on_close=lambda: None):
        """Offer `schedule` on its clock: what is due before 0 is the
        pre-roll, 0 is where the window opens (at the end of the iteration
        then running), and it closes at the end of the iteration running
        `seconds` later. Returns a Window; one row per request of the
        schedule, offered or not."""
        import jax

        rows = [{"due": None, "req": None, "offered": None, "error": None,
                 "prompt": ids, "out_tokens": out, "tokens": None}
                for _, ids, out in schedule]
        closed = threading.Event()
        t_zero = time.monotonic() - min(0.0, schedule[0][0])

        def generate():
            for row, (due, _, _) in zip(rows, schedule):
                row["due"] = t_zero + due
                if closed.wait(max(0.0, row["due"] - time.monotonic())):
                    return
                with jax.profiler.TraceAnnotation("perfbench.offer"):
                    try:
                        row["req"] = self._offer(row["prompt"],
                                                 row["out_tokens"])
                    except Exception as e:  # shed at the door: a failure
                        row["error"] = e
                    row["offered"] = time.monotonic()

        gen = threading.Thread(target=generate, name="perfbench-generator")
        gen.start()
        try:
            while time.monotonic() < t_zero:  # the pre-roll
                self._alive()
                time.sleep(min(0.05, max(0.0, t_zero - time.monotonic())))
            t_open = self._iteration_edge()
            on_open()
            at_open, stats_open = self._produced(rows), dict(self.batcher.stats)
            positions, seen, traced_from = [], None, None
            while time.monotonic() < t_open + seconds:
                self._alive()
                tracer.maybe_start(time.monotonic() - t_open, seconds)
                n = self.batcher.stats["iterations"]
                if tracer.t_start is not None and n != seen:
                    if traced_from is None:
                        traced_from = n
                    seen = n
                    # the positions the iteration now running reads up to
                    positions.append([s.pos for s in self.batcher.in_flight()])
                time.sleep(min(0.05, max(0.0, t_open + seconds
                                         - time.monotonic())))
            t_close = self._iteration_edge()
            on_close()
            at_close, stats_close = self._produced(rows), dict(self.batcher.stats)
            tracer.stop()
        finally:
            closed.set()
            gen.join(timeout=30.0)
        if gen.is_alive():
            raise RuntimeError("the generator did not end")
        for row, a, b in zip(rows, at_open, at_close):
            row["produced_at_open"], row["produced_at_close"] = a, b
        traced = None
        if traced_from is not None:
            traced = {"iterations": stats_close["iterations"] - traced_from,
                      "positions": positions}
        return Window(rows, t_open, t_close, stats_open, stats_close, traced)

    def stop(self, rows=()):
        """Stop the serve thread; each of `rows` then gets the tokens it was
        served: its answer, or what a request still in its slot had been
        given (None while it queued)."""
        self.batcher.stop(timeout=60.0)
        if self.batcher.thread_alive():
            raise RuntimeError("the serve thread did not stop")
        partial = {id(s.req): np.asarray(s.tokens)
                   for s in self.batcher.in_flight()}
        for row in rows:
            req = row["req"]
            if req is not None and req.done():
                row["tokens"] = None if req.tokens is None \
                    else np.asarray(req.tokens)
            else:
                row["tokens"] = partial.get(id(req))

    def free(self, keep_model=False):
        """Free weights and caches; `keep_model` keeps the compiled model for
        another seed's weights."""
        runctx.free(self.model.state.params)
        self.batcher = self.queue = None
        if not keep_model:
            self.model = None
        gc.collect()


@dataclasses.dataclass
class Window:
    rows: list
    t_open: float
    t_close: float
    stats_open: dict
    stats_close: dict
    traced: dict  # the profiler's slice: decode iterations, slots' positions

    @property
    def stats(self):
        return {k: v - self.stats_open[k] for k, v in self.stats_close.items()
                if isinstance(v, (int, float))}


def table(rows):
    """Plain numbers of every request offered by the close: what the window
    arithmetic and the metric readers take."""
    out = []
    for row in rows:
        req = row["req"]
        if req is None and row["error"] is None:
            continue  # due after the close: never offered
        done = req is not None and req.done()
        ok = done and req.error is None and req.tokens is not None
        served = 0 if row["tokens"] is None \
            else len(row["tokens"]) - len(row["prompt"])
        out.append({
            "due": row["due"], "offered": row["offered"],
            "admitted": req.admitted_t if req is not None else None,
            "first_token": req.first_token_t if ok else None,
            "finished": req.finished_t if ok else None,
            "prompt_tokens": len(row["prompt"]),
            "asked_tokens": row["out_tokens"],
            "out_tokens": served if ok else 0,
            "served_tokens": served,
            "produced_at_open": row["produced_at_open"],
            "produced_at_close": row["produced_at_close"],
            "ok": ok, "failed": row["error"] is not None or (done and not ok),
            "row": row,
        })
    return out


def wrong_answer(t, vocab):
    """A served sequence that is not its own prompt followed by ids of the
    vocabulary, as many as asked for once the request has finished."""
    row = t["row"]
    toks, plen = row["tokens"], t["prompt_tokens"]
    if toks is None:
        return False
    return bool(not np.array_equal(toks[:plen], row["prompt"])
                or toks.min() < 0 or toks.max() >= vocab
                or t["served_tokens"] > t["asked_tokens"]
                or (t["ok"] and t["served_tokens"] != t["asked_tokens"]))


def served_rows(tab):
    """The rows with a served token, the longest sequence first: all of them
    are compared."""
    return [t["row"] for t in sorted(
        tab, key=lambda t: -(t["prompt_tokens"] + t["served_tokens"]))
        if t["served_tokens"] > 0]


def logit_gaps(ref, config, seed, rows, precision="f32", models=None):
    """Per row, the gap at every served position between the reference's
    best logit and the reference's logit of the token judged: the served
    token, or, with `precision` below f32 (the control), the token the lower
    precision puts first at that position. `models` keeps the reference's
    jitted pieces from one call to the next."""
    models = {} if models is None else models
    for p in {"f32", precision} - set(models):
        models[p] = ref.Reference(config, p)
    model = models["f32"]
    low = models[precision] if precision != "f32" else None
    params = ref.init(config, seed)
    gaps = []
    for row in rows:
        tokens, plen = row["tokens"], len(row["prompt"])
        positions = np.arange(plen - 1, len(tokens) - 1)
        logits = model.logits_at(params, tokens[:-1], positions)
        judged = tokens[plen:] if low is None else \
            low.logits_at(params, tokens[:-1], positions).argmax(-1)
        gaps.append(logits.max(-1)
                    - logits[np.arange(len(positions)), judged])
    runctx.free(params)
    return gaps


def numbers(tab, gaps, vocab):
    """What a serving cell compares."""
    return {"failed_requests": sum(1 for t in tab if t["failed"]),
            "wrong_answers": sum(1 for t in tab if wrong_answer(t, vocab)),
            "worst_logit_gap": max((float(g.max()) for g in gaps),
                                   default=float("inf"))}


def run(cell, builder, ref, args, run_ctx):
    sc = ServeCell(cell, builder, ref, run_ctx.spans)
    sc.build()
    with run_ctx.spans.span("weights_s"):
        sc.load_seed(args.seed)
    sc.start()
    vocab = ref.sizes(cell.config)["vocab"]
    schedule = traffic.serve_schedule(cell.mix, vocab, args.seed, args.seconds)
    run_ctx.facts["decode_strategy_active"] = sc.batcher.decode_strategy_active
    run_ctx.before_window()
    t_offered = time.monotonic()
    w = sc.window(schedule, args.seconds, run_ctx.tracer,
                  on_open=run_ctx.open_window, on_close=run_ctx.close_window)
    run_ctx.spans.seconds["preroll_s"] = w.t_open - t_offered
    run_ctx.read_memory()
    sc.stop(w.rows)
    tab = table(w.rows)
    run_ctx.facts.update(
        requests=tab, serving=cell.params["serving"], stats=w.stats,
        traced=w.traced, window_s=w.t_close - w.t_open, t_open=w.t_open,
        t_close=w.t_close)
    for t in tab:
        print("request due %7.3f prompt %4d asked %4d served %4d in window "
              "%4d done %8.3f" % (
                  t["due"] - w.t_open, t["prompt_tokens"], t["asked_tokens"],
                  t["served_tokens"], window.produced(t),
                  t["finished"] - w.t_open if t["ok"] else float("nan")),
              file=sys.stderr)
    run_ctx.attempted = len(tab)
    run_ctx.failed = sum(1 for t in tab if t["failed"])
    run_ctx.end_to_end.update(window.serve_metrics(tab, w.t_open, w.t_close))
    picks = served_rows(tab)
    sc.free()
    t0 = time.perf_counter()
    gaps = logit_gaps(ref, cell.config, args.seed, picks)
    print(f"reference: {time.perf_counter() - t0:.1f} s over {len(picks)} "
          f"requests, {sum(len(g) for g in gaps)} served tokens",
          file=sys.stderr)
    checks = check.Checks(cell.params["limits"])
    for name, value in numbers(tab, gaps, vocab).items():
        checks.add(name, value)
    return checks
