"""Device seconds of one training step's forward pass in the traced slice:
chip 0's operations whose scope path is under `ff.fwd` (the loss with it)
and not under `transpose(`, over the executions of the step's program.
Prints the share of device-busy time under no `ff.` scope."""
import sys

from perfbench.harness import program_spans


def step_scope_ms(facts, inside, outside=None):
    """Milliseconds a step under the scope, or None (no slice, no scope)."""
    spans, step = program_spans.of(facts), facts["trace"].program()
    if spans is None or step is None:
        return None
    seconds = spans.scope_seconds(inside, outside)
    return None if seconds is None else 1e3 * seconds / step[1]


def read(facts):
    value = step_scope_ms(facts, "ff.fwd", outside="transpose(")
    if value is not None:
        spans, steps = program_spans.of(facts), facts["trace"].program()[1]
        print(f"device-busy time under no ff. scope: "
              f"{100.0 * spans.unscoped_seconds() / spans.busy_s:.2f}% of "
              f"{spans.busy_s:.4f} s; ff.loss "
              f"{step_scope_ms(facts, 'ff.loss', outside='transpose('):.3f} "
              f"ms forward + {step_scope_ms(facts, 'transpose(jvp(ff.fwd))/ff.loss'):.3f}"
              " ms backward a step", file=sys.stderr)
        # a fusion carries ONE scope: the kinds say what else it holds
        for label, inside, outside in (
                ("ff.fwd", "ff.fwd", "transpose("),
                ("transpose(jvp(ff.fwd))", "transpose(jvp(ff.fwd))", None),
                ("ff.opt", "ff.opt", None)):
            kinds = ", ".join(f"{k} {1e3 * v / steps:.2f}" for k, v in
                              spans.scope_kinds(inside, outside))
            print(f"ms a step under {label} by instruction kind: {kinds}",
                  file=sys.stderr)
    return value
