"""The chunked gated delta rule's share of its roofline over the admissions
of the traced slice: the least time the chip could take for the REAL tokens
of each prompt, over the device time of the operations under the scope
`ff.linear_attn.scan` that ran inside that admission's `ff.serve.admit`
span (the prefill ends in a sync, so its device work lies inside the span;
the span carries the prompt's length). The count is of the work, whatever
chunk or kernel does it, so a bucket's padding reads as waste: per real
token and linear layer q, k, v, the gate and the output once at 2 bytes,
the state (float32) once an admission, and 6 dv dk operations a head.
Silent where no admission of the slice ran an operation under that scope."""
from perfbench.harness import program_spans, spec, trace

SCOPE = "ff.linear_attn.scan"


def scan_need(z, tokens):
    """(operations, bytes) of one linear layer's pass over `tokens` real
    tokens of one prompt."""
    h, dk, dv = z["lin_heads"], z["lin_dk"], z["lin_dv"]
    return (6 * h * dv * dk * tokens,
            2 * tokens * h * (2 * dk + 3 * dv) + 4 * h * dv * dk)


def read(facts):
    cell = facts["cell"]
    spans = program_spans.of(facts)
    if spans is None or not spans.count("ff.serve.admit"):
        return None
    _, ref = spec.family(cell.config)
    z = ref.sizes(cell.config)
    layers = sum(1 for t in z["layer_types"] if t == "linear_attention")
    peaks = facts["peaks"]
    scan = [(s, e) for _, s, e in spans._under(SCOPE)]
    least = seconds = 0.0
    for admit in (s for s in spans.spans if s.name == "ff.serve.admit"):
        inside = trace._union([s, e] for s, e in scan
                              if admit.start_ns <= s and e <= admit.end_ns)
        if not inside or "prompt_len" not in admit.args:
            continue
        seconds += sum(e - s for s, e in inside) * 1e-9
        flops, moved = scan_need(z, int(admit.args["prompt_len"]))
        least += layers * max(flops / peaks["flops_bf16"],
                              moved / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds if seconds else None
