#!/usr/bin/env python3
"""Latent attention's reads alone on the chip, at the sizes the
``serve-moonlight-16b-reason`` cell runs them.

    chiprun --chips 1 -- python3 perf/tools/mla_kernel_bench.py

For the builder (PERF.md section 6, PR 38), not a cell. Two questions:

* the decode read (``mla_decode``: 64 slots, one query row of 16 heads
  each) at a mean of 1,024 / 3,072 cached positions a slot, against the
  bytes of the latent rows it must read;
* the prefill chunk (128 tokens of one slot = 2,048 query-head rows of
  576) behind a 2k and a 4k prefix, in the two forms of the one
  mathematics: ABSORBED (the program's: the same kernel over the pages in
  place) against EXPAND-THE-PREFIX (gather the slot's rows through its
  table, rebuild K and V of the prefix with ``W_kvb``, attend a head at
  192 / 128 wide; given its best case here, a static length of exactly
  prefix + chunk where a served program would take a bucket or the whole
  context). The faster is THE code of ``LatentAttention``'s chunk path.

Times are of ``CALLS`` calls inside one jitted ``fori_loop`` over the
layers of the leaf, best of three. Prints one JSON object; with no TPU it
fails at start-up like perf/run.py."""

import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

CALLS = 28
L, P, PS = 7, 3072, 128
H, R, DN, DR, DV = 16, 512, 128, 64, 128
W = R + DR
SLOTS, PER_SLOT, CHUNK = 64, 64, 128
HBM = 819e9


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops.attention.latent_attention import latent_attention
    from perf import device

    found = device.open_device(1, False)
    bf16 = jnp.bfloat16
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    pages = jax.random.normal(keys[0], (L, P, W, PS), bf16)
    w_kvb = jax.random.normal(keys[1], (R, H, DN + DV), bf16) / math.sqrt(R)
    scale = 1.0 / math.sqrt(DN + DR)
    out = {"device": found, "calls": CALLS, "decode": {}, "chunk": {}}

    def best(fn, *args):
        jax.block_until_ready(fn(*args))
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            times.append((time.perf_counter() - t0) / CALLS * 1e3)
        return min(times)

    def tables(lengths):
        """Each slot's pages in order, disjoint, sentinel after them."""
        table = np.full((len(lengths), PER_SLOT), P, np.int32)
        nxt = 0
        for b, n in enumerate(lengths):
            k = -(-(n + 1) // PS)
            table[b, :k] = np.arange(nxt, nxt + k)
            nxt += k
        assert nxt <= P
        return jnp.asarray(table)

    def absorbed(q, pages, table, starts):
        def body(i, acc):
            ctx = latent_attention(q + acc[:, :, :, :1].astype(bf16) * 0,
                                   pages, table, starts, layer=i % L,
                                   rank=R, scale=scale, page_size=PS)
            return acc + ctx.astype(jnp.float32)
        return jax.lax.fori_loop(0, CALLS, body,
                                 jnp.zeros(q.shape[:3] + (R,), jnp.float32))

    # -- decode ------------------------------------------------------------
    rng = np.random.default_rng(0)
    for mean in (1024, 3072):
        lengths = np.clip(rng.normal(mean, mean / 4, SLOTS), 256,
                          8000).astype(np.int64)
        table, starts = tables(lengths), jnp.asarray(lengths, jnp.int32)
        q = jax.random.normal(keys[2], (SLOTS, 1, H, W), bf16)
        ms = best(jax.jit(absorbed), q, pages, table, starts)
        tokens = int(lengths.sum() + SLOTS)
        live = int(np.sum(-(-(lengths + 1) // PS)))
        least_ms = tokens * W * 2 / HBM * 1e3
        out["decode"][str(mean)] = {
            "ms_a_call": ms, "latent_tokens": tokens, "live_pages": live,
            "us_a_page": ms * 1e3 / live, "least_ms": least_ms,
            "roofline_share": least_ms / ms}

    # -- the chunk: absorbed against expand-the-prefix -----------------------
    for prefix in (2048, 4096):
        S = prefix + CHUNK
        table = tables([S - 1])
        starts = jnp.asarray([prefix], jnp.int32)
        q = jax.random.normal(keys[3], (1, CHUNK, H, W), bf16)
        q_n = jax.random.normal(keys[2], (1, CHUNK, H, DN), bf16)

        def absorbed_chunk(q_n, q, pages, table, starts):
            # with the absorption itself: q~ = W_kvb^K^T q_n, and the
            # values' half applied to the result
            def body(i, acc):
                qa = jnp.einsum("bthd,rhd->bthr", q_n, w_kvb[..., :DN])
                qq = jnp.concatenate([qa, q[..., R:]], -1) \
                    + acc[:, :, :, :1].astype(bf16) * 0
                ctx = latent_attention(qq, pages, table, starts,
                                       layer=i % L, rank=R, scale=scale,
                                       page_size=PS)
                y = jnp.einsum("bthr,rhd->bthd", ctx, w_kvb[..., DN:])
                return acc + y.astype(jnp.float32)
            return jax.lax.fori_loop(
                0, CALLS, body, jnp.zeros((1, CHUNK, H, DV), jnp.float32))

        def expanded_chunk(q_n, q, pages, table, starts):
            n = S // PS

            def body(i, acc):
                rows = jnp.take(pages[i % L], table[0, :n], axis=0)
                rows = rows.transpose(1, 0, 2).reshape(W, S)   # (W, S)
                c, k_r = rows[:R].T, rows[R:].T                # (S, R) ...
                kv = jnp.einsum("sr,rhd->shd", c, w_kvb)
                qq = q_n + acc[:, :, :, :1].astype(bf16) * 0
                att = (jnp.einsum("bthd,shd->bhts", qq, kv[..., :DN],
                                  preferred_element_type=jnp.float32)
                       + jnp.einsum("bthd,sd->bhts", q[..., R:], k_r,
                                    preferred_element_type=jnp.float32)
                       ) * scale
                seen = jnp.arange(S)[None, :] \
                    <= starts[0] + jnp.arange(CHUNK)[:, None]
                p = jax.nn.softmax(jnp.where(seen, att, -1e30), -1)
                y = jnp.einsum("bhts,shd->bthd", p.astype(bf16),
                               kv[..., DN:],
                               preferred_element_type=jnp.float32)
                return acc + y
            return jax.lax.fori_loop(
                0, CALLS, body, jnp.zeros((1, CHUNK, H, DV), jnp.float32))

        args = (q_n, q, pages, table, starts)
        out["chunk"][str(prefix)] = {
            "absorbed_ms_a_call": best(jax.jit(absorbed_chunk), *args),
            "expanded_ms_a_call": best(jax.jit(expanded_chunk), *args)}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
