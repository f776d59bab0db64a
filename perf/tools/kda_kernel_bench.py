#!/usr/bin/env python3
"""The KDA state kernels alone on the chip, at the sizes the
``serve-kimi-linear-48b-longform`` cell runs them.

    chiprun --chips 1 -- python3 perf/tools/kda_kernel_bench.py

For the builder (PERF.md section 6, PR 50), not a cell. Two questions:

* ``kda_decode``: 128 rows' one token through a layer of the pool's stacked
  leaf (9 layers x 128 slots x 32 heads of (128, 128) float32, 2.4 GB),
  against the bytes of state any implementation must read and write;
* ``kda_chunk``: one row's 128-token chunk, the kernel with the XLA work
  before it (the decays, the pairs, the inverse: ``kda_chunk_prep``).

Times are of ``CALLS`` calls inside one jitted ``fori_loop`` over the
layers of the leaf, best of three. Prints one JSON object; with no TPU it
fails at start-up like perf/run.py."""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

CALLS = 27
L, SLOTS, H, K, V, CHUNK = 9, 128, 32, 128, 128, 128
HBM = 819e9


def main() -> int:
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops import kda
    from perf import device

    found = device.open_device(1, False)
    f32 = jnp.float32
    keys = jax.random.split(jax.random.PRNGKey(0), 8)

    def operands(lead):
        q = jax.random.normal(keys[0], lead + (H, K), f32)
        k = jax.random.normal(keys[1], lead + (H, K), f32)
        k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
        q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / K ** 0.5
        v = jax.random.normal(keys[2], lead + (H, V), f32)
        g = -0.05 * jnp.abs(jax.random.normal(keys[3], lead + (H, K), f32))
        beta = jax.nn.sigmoid(jax.random.normal(keys[4], lead + (H,), f32))
        return q, k, v, g, beta

    def best(fn, *args):
        out = fn(*args)
        jax.block_until_ready(out)
        times = []
        for _ in range(3):
            leaf = out[1]
            t0 = time.perf_counter()
            out = fn(*args[:-1], leaf)
            jax.block_until_ready(out)
            times.append((time.perf_counter() - t0) / CALLS * 1e3)
        return min(times)

    def loop(kernel, rows):
        def run(q, k, v, g, beta, leaf):
            def body(i, carry):
                acc, leaf = carry
                o, leaf = kernel(q + acc[..., :1] * 0, k, v, g, beta, leaf,
                                 i % L, rows, jnp.zeros(rows.shape, bool))
                return acc + o[..., :K], leaf
            return jax.lax.fori_loop(
                0, CALLS, body, (jnp.zeros(q.shape, f32), leaf))
        return jax.jit(run, donate_argnums=5)

    leaf = jax.random.normal(keys[5], (L, SLOTS, H, K, V), f32) * 0.1
    out = {"device": found, "calls": CALLS}
    rows = jnp.arange(SLOTS, dtype=jnp.int32)
    ms = best(loop(kda.kda_decode, rows), *operands((SLOTS,)), leaf)
    least = SLOTS * 2 * H * K * V * 4 / HBM * 1e3
    out["decode"] = {"ms_a_call": ms, "least_ms": least,
                     "roofline_share": least / ms}
    leaf = jax.random.normal(keys[6], (L, SLOTS, H, K, V), f32) * 0.1
    ms = best(loop(kda.kda_chunk, jnp.asarray([3], jnp.int32)),
              *operands((1, CHUNK)), leaf)
    out["chunk"] = {"ms_a_call_with_prep": ms}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
