#!/usr/bin/env python
"""Time a decode step's attention at a serving shape, on the chip.

Two readings per profile of slot lengths, `--layers` attention ops chained
in one program with a cache pair each, as the batched decode step holds
them (default: the shape of `serve-opt1.3b-saturated`, 16 slots x 32 heads
x 64, max_len 1,024, bf16):

  kernel  `paged_flash_decode` alone, us a call, and the live keys' and
          values' bytes over that time as a share of the HBM peak;
  op      the whole attention op (`forward_decode`: projections, append,
          attention, output) under FF_DECODE_IMPL=paged and =dense, us a
          layer. The difference is the attention alone: it says at which
          live share, if any, XLA's dense branch beats the kernel.

    chiprun -- python scripts/decode_attn_bench.py --out chiprun_out/attn.json

A rate comes only from a chip: on the CPU this exits 2. PERF.md (PR 26)
holds the readings and what `auto` was decided from.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import jax
import jax.numpy as jnp
import numpy as np

HBM_BYTES_PER_S = 819e9     # TPU v5e (perfbench/harness/peaks.py)


def profiles(slots, max_len):
    """Slot lengths by name: the cell as it runs since PR 26 (3 live slots,
    the rest one token), every slot a third full, half full, full."""
    few = np.ones(slots, np.int32)
    few[:3] = [max_len * 42 // 100, max_len * 42 // 100 - 3,
               max_len * 41 // 100]
    return {
        "3-live": few,
        "third": np.linspace(max_len // 5, max_len * 46 // 100, slots)
        .astype(np.int32),
        "half": np.full(slots, max_len // 2, np.int32),
        "full": np.full(slots, max_len, np.int32),
    }


def timed(fn, state, reps):
    """(seconds a call, the last state); `fn(state) -> state`, and the
    first call, which compiles, is not timed."""
    state = jax.block_until_ready(fn(state))
    t0 = time.perf_counter()
    for _ in range(reps):
        state = fn(state)
    state = jax.block_until_ready(state)
    return (time.perf_counter() - t0) / reps, state


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", type=int, default=16)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--max-len", type=int, default=1024)
    ap.add_argument("--layers", type=int, default=24)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--out", default="")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="debug the script off the chip (the kernel in "
                         "the interpreter); its times mean nothing")
    a = ap.parse_args()
    on_chip = jax.default_backend() == "tpu"
    if not on_chip and not a.cpu_rehearsal:
        print("decode_attn_bench: no TPU; a CPU run gives no rate",
              file=sys.stderr)
        return 2

    from flexflow_tpu.kernels.decode import (decode_page_size,
                                             paged_decode_reference,
                                             paged_flash_decode,
                                             paged_view_of_cache)
    from flexflow_tpu.ff_types import OperatorType
    from flexflow_tpu.ops.attention import (MultiHeadAttentionParams,
                                            init_decode_cache)
    from flexflow_tpu.ops.registry import FwdCtx, get_op_def

    b, h, d, n = a.slots, a.heads, a.head_dim, a.layers
    e, dt = h * d, jnp.bfloat16
    page = decode_page_size(a.max_len)
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 8)
    # one cache pair a layer, as the step holds them (no reuse to hide in)
    caches = [tuple(jax.random.normal(jax.random.fold_in(ks[i], j),
                                      (b, a.max_len, e), dt)
                    for i in (0, 1)) for j in range(n)]
    q0 = jax.random.normal(ks[2], (b, h, d), dt)
    params = MultiHeadAttentionParams(embed_dim=e, num_heads=h, causal=True,
                                      bias=True)
    weights = {w: jax.random.normal(ks[3 + i], (e, h, d), dt) * 0.02
               for i, w in enumerate(("wq", "wk", "wv"))}
    weights["wo"] = jax.random.normal(ks[6], (h, d, e), dt) * 0.02
    weights["bias_o"] = jnp.zeros((e,), dt)
    forward_decode = get_op_def(
        OperatorType.OP_MULTIHEAD_ATTENTION).forward_decode
    ctx = FwdCtx(training=False, compute_dtype=dt, op_name="bench")
    assert init_decode_cache(params, b, a.max_len, dt)[0].shape \
        == caches[0][0].shape

    @jax.jit
    def kernel_chain(q, caches, lengths):
        for kc, vc in caches:
            kp, vp, table = paged_view_of_cache(kc, vc, page)
            q = paged_flash_decode(q, kp, vp, table, lengths,
                                   interpret=not on_chip)
        return q

    def op_chain(impl):
        def run(x, caches, lengths):
            # read when the op is traced, at the chain's first call
            os.environ["FF_DECODE_IMPL"] = impl
            out = []
            for cache in caches:
                (x,), cache = forward_decode(params, weights, [x, x, x], ctx,
                                             cache, lengths - 1)
                out.append(cache)
            return x, out
        return jax.jit(run, donate_argnums=(1,))

    op_chains = {impl: op_chain(impl) for impl in ("paged", "dense")}

    result = {"shape": vars(a), "page": page, "on_chip": on_chip,
              "profiles": {}}
    x0 = jax.random.normal(ks[7], (b, 1, e), dt)
    for name, lens in profiles(b, a.max_len).items():
        lengths = jnp.asarray(lens)
        live = int(lens.sum())
        row = {"live_positions": live}
        kp, vp, table = paged_view_of_cache(*caches[0], page)
        got = paged_flash_decode(q0, kp, vp, table, lengths,
                                 interpret=not on_chip)
        want = paged_decode_reference(
            q0, kp.reshape(kp.shape[:2] + (h, d)),
            vp.reshape(vp.shape[:2] + (h, d)), table, lengths)
        row["kernel_rel_err"] = float(
            jnp.max(jnp.abs(got.astype(jnp.float32)
                            - want.astype(jnp.float32)))
            / jnp.max(jnp.abs(want.astype(jnp.float32))))
        s, _ = timed(lambda q: kernel_chain(q, caches, lengths), q0, a.reps)
        moved = 2 * live * e * 2                 # K and V rows, bf16
        row["kernel_us"] = s / n * 1e6
        row["kernel_hbm_share_pct"] = 100 * moved / HBM_BYTES_PER_S / (s / n)
        for impl, fn in op_chains.items():
            # the donated caches come back as the state's second half
            s, (x_out, caches) = timed(lambda st: fn(x0, st[1], lengths),
                                       (x0, caches), a.reps)
            row[f"op_{impl}_us"] = s / n * 1e6
            row[f"op_{impl}_out"] = float(
                jnp.linalg.norm(x_out.astype(jnp.float32)))
        result["profiles"][name] = row
        print(name, json.dumps(row), flush=True)
    os.environ.pop("FF_DECODE_IMPL", None)
    if a.out:
        os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
        with open(a.out, "w") as f:
            json.dump(result, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
