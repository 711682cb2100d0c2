"""A loop region's share of its roofline in the admissions' prefills: for
each admission of the traced slice, the least time the chip could take for
the REAL prompt tokens through every step of the loop (the operations of the
layers' matrices and of causal attention, the family's `forward_flops`
without the head; or reading every step's pass of the matrices once, where
that bounds it), summed, over chip 0's device time under the scope `ff.loop`
inside those admissions' `ff.serve.admit` spans (the prefill ends in a sync,
so its device work lies inside the span, which carries the prompt's length).
A bucket's padding reads as waste. Silent where the family has no loop or no
admission of the slice ran an operation under that scope."""
from perfbench.harness import program_spans, spec

_decode = spec.module("metrics", "loop_decode_roofline.py")


def read(facts):
    found = _decode.family(facts)
    spans = program_spans.of(facts)
    if found is None or spans is None:
        return None
    ref, cfg = found
    z = ref.sizes(cfg)
    weights = 2 * z["steps"] * ref.counts(cfg)["pass_matmul_params"]
    peaks = facts["peaks"]
    least = seconds = 0.0
    for span, busy in _decode.loop_seconds(spans, "ff.serve.admit"):
        if "prompt_len" not in span.args:
            continue
        tokens = int(span.args["prompt_len"])
        seconds += busy
        least += max(ref.forward_flops(cfg, range(tokens), 0)
                     / peaks["flops_bf16"],
                     weights / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds if seconds else None
