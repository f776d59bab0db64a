"""Block-sparse attention benchmark on the real chip (VERDICT r2 next #2).

Per sequence length, times training fwd+bwd for:
  - dense XLA fused attention (causal)
  - dense Pallas flash attention
  - gather-formulation block-sparse (jnp)
  - fused Pallas block-sparse (splash-style)

using a Fixed unidirectional sparsity config at the TPU-native granule
(block 512 — the MXU-efficient flash-tile size; the reference's Triton
granule is 16) with a 2k-token local window + Fixed-pattern globals — the
analog of the reference's block-16 Triton benchmarks
(docs/_posts/2020-09-09-sparse-attention.md: up to 6.3x faster BERT
pretraining). Writes ``benchmarks/sparse_attn_bench_results.json``.
Run WITHOUT a platform override (needs the real TPU).
"""

from __future__ import annotations

import json
import os

from attn_bench import timed


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops.attention.flash_attention import flash_attention
    from deepspeed_tpu.ops.sparse_attention.pallas_kernel import (
        block_sparse_flash_attention,
        layout_to_schedule,
    )
    from deepspeed_tpu.ops.sparse_attention.sparse_self_attention import (
        block_sparse_attention,
    )
    from deepspeed_tpu.ops.sparse_attention.sparsity_config import (
        FixedSparsityConfig,
    )
    import math

    print("backend:", jax.default_backend(), jax.devices())
    H, D, BLOCK = 12, 64, 512  # TPU-native granule: the flash-tile size (128 = Triton-analog minimum, but MXU efficiency wants 512)
    rng = np.random.default_rng(0)
    results = []

    def xla_attn(q, k, v):
        s = jnp.einsum("bthd,bshd->bhts", q, k) / math.sqrt(D)
        mask = jnp.tril(jnp.ones((q.shape[1], q.shape[1]), bool))
        s = jnp.where(mask[None, None], s, -1e30)
        p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
        return jnp.einsum("bhts,bshd->bthd", p, v)

    def loss_of(attn):
        def f(q, k, v):
            return attn(q, k, v).astype(jnp.float32).sum()

        grad_f = jax.grad(f, argnums=(0, 1, 2))

        def scalar(q, k, v):
            gq, gk, gv = grad_f(q, k, v)
            return (gq.astype(jnp.float32).sum() +
                    gk.astype(jnp.float32).sum() +
                    gv.astype(jnp.float32).sum())

        return scalar

    for seq in (4096, 8192, 16384, 32768):
        B = max(1, 8192 // seq)
        cfg = FixedSparsityConfig(num_heads=H, block=BLOCK,
                                  num_local_blocks=4, num_global_blocks=1,
                                  attention="unidirectional")
        layout = cfg.make_layout(seq)
        _, cnt = layout_to_schedule(layout)
        density = float(layout.sum()) / layout[0].size / H
        shape = (B, seq, H, D)
        q, k, v = (jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
                   for _ in range(3))
        row = {"kind": "sparse_train_fwd_bwd", "seq": seq, "batch": B,
               "heads": H, "head_dim": D, "block": BLOCK,
               "layout_density": round(density, 4),
               "max_live_blocks": int(cnt.max())}

        candidates = [
            ("xla_dense", xla_attn),
            ("flash_dense", lambda q, k, v: flash_attention(q, k, v,
                                                            causal=True)),
            ("gather_sparse", lambda q, k, v: block_sparse_attention(
                q, k, v, layout, BLOCK, causal=True)),
            ("pallas_sparse", lambda q, k, v: block_sparse_flash_attention(
                q, k, v, layout, BLOCK, causal=True)),
        ]
        for name, attn in candidates:
            try:
                dt = timed(loss_of(attn), q, k, v, iters=10)
                row[f"{name}_ms"] = round(dt * 1e3, 3)
            except Exception as e:  # OOM for dense paths at long seq
                row[f"{name}_ms"] = None
                row[f"{name}_error"] = str(e)[:160]
        if row.get("xla_dense_ms") and row.get("pallas_sparse_ms"):
            row["vs_xla_dense"] = round(
                row["xla_dense_ms"] / row["pallas_sparse_ms"], 2)
        if row.get("gather_sparse_ms") and row.get("pallas_sparse_ms"):
            row["vs_gather"] = round(
                row["gather_sparse_ms"] / row["pallas_sparse_ms"], 2)
        if row.get("flash_dense_ms") and row.get("pallas_sparse_ms"):
            row["vs_flash_dense"] = round(
                row["flash_dense_ms"] / row["pallas_sparse_ms"], 2)
        results.append(row)
        print(row)

    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "sparse_attn_bench_results.json")
    with open(out, "w") as f:
        json.dump(results, f, indent=2)
    print("wrote", out)


if __name__ == "__main__":
    main()
