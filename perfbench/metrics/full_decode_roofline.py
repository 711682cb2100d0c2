"""The paged-decode kernel's share of its roofline on the FULL layers' caches
in the traced slice, where key-value heads are fewer than query heads: the
pool is `bf16[slots x max_len / page, page size, key-value heads x head
size]`, which `paged_decode_roofline` (a hidden-wide pool) cannot read. The
least time the chip could take to read the live keys and values of one full
layer (a slot at position p has p + 1), over the mean device time of the
kernel's calls on that pool; `window_decode_roofline` holds the count.
Silent when no Mosaic call ran on that layout."""
from perfbench.harness import spec


def read(facts):
    ring = spec.module("metrics", "window_decode_roofline.py")
    return ring.read_kind(facts, "full_attention",
                          lambda z, serving: serving["max_len"],
                          lambda z, p: p + 1)
