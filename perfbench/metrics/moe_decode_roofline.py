"""The expert layers' share of their roofline in the traced slice's decode
steps: the least time the chip could take for one expert layer's decode step,
over the device time of that layer's operations in the decode iterations (the
operations under the scope `ff.moe` that ran inside a `ff.serve.decode` span:
a decode step ends in a sync, so its device work lies inside the span; a
prefill's lies inside `ff.serve.admit`). The count is of the work, whatever kernel or fusion does it:
the DISTINCT held experts a step touched in a layer (the window's mean, the
batcher's own count `stats["moe_experts_touched"]` over iterations x expert
layers), two matrices each, plus the shared expert and the router, once at 2
bytes; and 4 x hidden x expert width operations an assignment held here (the
window's mean of `stats["moe_assignments_held"]`), the shared expert and the
router for every slot. Bytes bound it at a decode batch. Silent where the
program counts no such thing or no such operation ran."""
from perfbench.harness import program_spans, spec, trace

SCOPE = "ff.moe"


def layer_need(z, touched, held_assignments, slots):
    """(operations, bytes) of one expert layer's decode step."""
    h, f = z["hidden"], z["expert_width"]
    fixed = h * (2 * z["shared_width"] + z["experts"])  # shared + router
    return (4 * h * f * held_assignments + 2 * fixed * slots,
            2 * (2 * h * f * touched + fixed))


def moe_ops(spans):
    """[(start, end)] of chip 0's operations of the expert layers."""
    return [(s, e) for name, s, e in spans.ops
            if SCOPE in spans.scopes.get(name, "")]


def seconds_in(spans, ops, span_name):
    """Seconds of `ops` (their union) inside the host spans `span_name`."""
    total = 0.0
    for host in (s for s in spans.spans if s.name == span_name):
        inside = trace._union([s, e] for s, e in ops
                              if host.start_ns <= s and e <= host.end_ns)
        total += sum(e - s for s, e in inside) * 1e-9
    return total


def expert_layers(z):
    return sum(1 for t in z["layer_types"] if t == "E")


def read(facts):
    cell, stats = facts["cell"], facts.get("stats") or {}
    spans = program_spans.of(facts)
    steps = spans.count("ff.serve.decode") if spans is not None else 0
    iterations = stats.get("iterations", 0)
    if not steps or not iterations or "moe_experts_touched" not in stats:
        return None
    seconds = seconds_in(spans, moe_ops(spans), "ff.serve.decode")
    if not seconds:
        return None
    _, ref = spec.family(cell.config)
    z = ref.sizes(cell.config)
    layers = expert_layers(z)
    flops, moved = layer_need(
        z, stats["moe_experts_touched"] / (iterations * layers),
        stats["moe_assignments_held"] / (iterations * layers),
        facts["serving"]["slots"])
    peaks = facts["peaks"]
    least = max(flops / peaks["flops_bf16"], moved / peaks["hbm_bytes_per_s"])
    return 100.0 * steps * layers * least / seconds
