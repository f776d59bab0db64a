#!/usr/bin/env python3
"""The routed FFN's expert products alone on the chip, at the sizes the
``serve-mellum2-12b-ide`` cell runs them, against ``jax.lax.ragged_dot`` on
one layer sliced out of the stacked leaf.

    chiprun --chips 1 -- python3 perf/tools/moe_kernel_bench.py

For the builder (PERF.md section 6), not a cell. Times are of ``CALLS``
calls inside one jitted ``fori_loop``, best of three, a call = one layer's
gate, up and down products over rows already routed and grouped. Prints one
JSON object; with no TPU it fails at start-up like perf/run.py."""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

CALLS = 50
L, E, C, F, K = 2, 64, 2304, 896, 8


def main() -> int:
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.moe import routed_ffn as rf
    from perf import device

    found = device.open_device(1, False)
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    gate, up = (jax.random.normal(k, (L, E, C, F), jnp.bfloat16) * 0.02
                for k in keys[:2])
    down = jax.random.normal(keys[2], (L, E, F, C), jnp.bfloat16) * 0.02
    router = jax.random.normal(keys[3], (C, E), jnp.float32) * 0.02
    out = {"device": found, "shape": {"E": E, "C": C, "F": F, "k": K},
           "calls": CALLS, "rows": {}}

    def best(fn, *args):
        fn(*args).block_until_ready()
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            fn(*args).block_until_ready()
            times.append((time.perf_counter() - t0) / CALLS * 1e3)
        return min(times)

    for n in (16, 64, 128, 144):
        h = jax.random.normal(jax.random.PRNGKey(n), (n, C), jnp.bfloat16)

        @jax.jit
        def pallas(h, gate, up, down):
            def body(i, acc):
                y, _ = rf.routed_ffn(h + acc[:1].astype(h.dtype) * 0, router,
                                     gate, up, down, i % L, k=K,
                                     norm_topk_prob=True)
                return acc + y.astype(jnp.float32)
            return jax.lax.fori_loop(0, CALLS, body,
                                     jnp.zeros((n, C), jnp.float32))

        def ragged(sliced):
            @jax.jit
            def run(h, gate, up, down):
                def body(i, acc):
                    hh = h + acc[:1].astype(h.dtype) * 0
                    w, experts = rf.route(hh, router, K, True)
                    flat = experts.reshape(-1)
                    order = jnp.argsort(flat, stable=True)
                    sizes = jnp.zeros((E,), jnp.int32).at[flat].add(1)
                    x = hh[order // K]
                    if sliced:          # the layer handed over as it is
                        g, u, d = gate[0], up[0], down[0]
                    else:               # cut out of the stack each call
                        layer = i % L
                        g, u, d = gate[layer], up[layer], down[layer]
                    act = jax.nn.silu(jax.lax.ragged_dot(x, g, sizes)) \
                        * jax.lax.ragged_dot(x, u, sizes)
                    y = jax.lax.ragged_dot(act.astype(h.dtype), d, sizes,
                                           preferred_element_type=jnp.float32)
                    back = jnp.zeros_like(order).at[order].set(
                        jnp.arange(order.size))
                    y = (y[back].reshape(n, K, C) * w[..., None]).sum(1)
                    return acc + y
                return jax.lax.fori_loop(0, CALLS, body,
                                         jnp.zeros((n, C), jnp.float32))
            return run

        out["rows"][str(n)] = {
            "moe_kernels_ms": best(pallas, h, gate, up, down),
            "ragged_dot_layer_in_hand_ms": best(ragged(True), h, gate, up,
                                                down),
            "ragged_dot_layer_cut_from_stack_ms": best(ragged(False), h, gate,
                                                       up, down),
            "weights_of_64_experts_at_819GBs_ms":
                3 * E * C * F * 2 / 819e9 * 1e3}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
