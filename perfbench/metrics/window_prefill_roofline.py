"""The banded prefill's share of its roofline over the admissions of the
traced slice: the least time the chip could take for the band of the REAL
tokens of each prompt in the sliding layers, over the device time of the
operations under the scope `ff.attn.window` that ran inside that admission's
`ff.serve.admit` span (the prefill ends in a sync, so its device work lies
inside the span; the span carries the prompt's length). The count is of the
work, whatever block or kernel does it, so a bucket's padding and whatever a
block computes outside the band read as waste: per real token at position t
and query head, 4 operations a channel and key seen, min(t + 1, window) of
them; q, k, v and the output once at 2 bytes. Operations bound it past a few
hundred tokens. Silent where no admission of the slice ran an operation under
that scope."""
from perfbench.harness import program_spans, spec, trace

SCOPE = "ff.attn.window"


def band_need(z, heads, tokens):
    """(operations, bytes) of one sliding layer's attention over `tokens`
    real tokens of one prompt."""
    w = z["window"]
    full = min(tokens, w)
    keys = full * (full + 1) // 2 + (tokens - full) * w
    d, kv = z["head_dim"], z["kv_heads"]
    return 4 * heads * d * keys, 2 * tokens * d * (2 * heads + 2 * kv)


def read(facts):
    cell = facts["cell"]
    spans = program_spans.of(facts)
    if spans is None or not spans.count("ff.serve.admit"):
        return None
    _, ref = spec.family(cell.config)
    z = ref.sizes(cell.config)
    sliding = [k for k in z.get("layer_types", ())
               if isinstance(k, tuple) and k[0] == "sliding_attention"]
    if not sliding:
        return None
    peaks = facts["peaks"]
    band = [(s, e) for _, s, e in spans._under(SCOPE)]
    least = seconds = 0.0
    for admit in (s for s in spans.spans if s.name == "ff.serve.admit"):
        inside = trace._union([s, e] for s, e in band
                              if admit.start_ns <= s and e <= admit.end_ns)
        if not inside or "prompt_len" not in admit.args:
            continue
        seconds += sum(e - s for s, e in inside) * 1e-9
        for _, _, heads in sliding:
            flops, moved = band_need(z, heads, int(admit.args["prompt_len"]))
            least += max(flops / peaks["flops_bf16"],
                         moved / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds if seconds else None
