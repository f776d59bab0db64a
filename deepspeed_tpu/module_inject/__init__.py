"""Module injection, TPU-native.

The reference rewrites module *objects*: per-model policies select fused CUDA
containers (``module_inject/replace_module.py:283 replace_transformer_layer``)
and AutoTP swaps ``nn.Linear`` for ``LinearLayer``/``LinearAllreduce``
(``module_inject/auto_tp.py:13``, ``module_inject/layers.py:15,32``). On TPU
nothing needs rewriting — XLA already fuses, and tensor parallelism is a
*sharding annotation*. So "injection" here produces :class:`ShardingRules`:

* :func:`get_policy_rules` — per-family explicit rules (the policy path);
* :func:`auto_tp_rules` — shape/name-heuristic classification of an arbitrary
  param pytree (the AutoTP path): down/output projections are row-parallel
  (their input dim sharded ⇒ XLA inserts the allreduce the reference's
  LinearAllreduce does by hand), everything else column-parallel.
"""

from __future__ import annotations

import re
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

from ..parallel.mesh import MODEL_AXIS
from ..runtime.zero.policy import ShardingRules, _path_str

# name fragments marking the SECOND linear of a pair (row-parallel: shard the
# input dim, allreduce output) — mirrors auto_tp.py's allreduce-linear
# heuristics (o_proj/out_proj/down_proj/dense_4h_to_h/fc2/...)
ROW_PARALLEL_PAT = re.compile(
    r"(o_proj|out_proj|down_proj|dense_4h_to_h|attention/dense|fc2|proj_out"
    r"|c_proj|wo)(/|$)", re.IGNORECASE)
EMBED_PAT = re.compile(r"(embedding|wte|embed_tokens)(/|$)", re.IGNORECASE)
POS_EMBED_PAT = re.compile(r"(wpe|embed_pos|position)", re.IGNORECASE)


def auto_tp_rules(params: Any, tp_size: int,
                  exclude: Sequence[str] = ()) -> ShardingRules:
    """Infer tensor-parallel sharding rules for an arbitrary param pytree
    (≅ AutoTP, reference module_inject/auto_tp.py:13).

    Classification per leaf (rightmost dims; leading dims — e.g. a scanned
    layer stack — stay unsharded):
      - embeddings: vocab-parallel (dim -2 over model) unless positional;
      - kernels matching ROW_PARALLEL_PAT: input dim (-2) over model;
      - other >=2D kernels: output dim (-1) over model, plus their biases;
      - anything indivisible by ``tp_size``: replicated (the reference
        likewise falls back to no-TP for odd shapes).
    """
    import jax

    rules: List[Tuple[str, tuple]] = []
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    for path, leaf in leaves:
        p = _path_str(path)
        if any(x in p for x in exclude):
            continue
        shape = np.shape(leaf)
        nd = len(shape)
        spec: Optional[tuple] = None
        if EMBED_PAT.search(p) and not POS_EMBED_PAT.search(p) and nd >= 2:
            if shape[-2] % tp_size == 0:
                spec = (None,) * (nd - 2) + (MODEL_AXIS, None)
        elif p.endswith("kernel") and nd >= 2:
            if ROW_PARALLEL_PAT.search(p):
                if shape[-2] % tp_size == 0:
                    spec = (None,) * (nd - 2) + (MODEL_AXIS, None)
            else:
                if shape[-1] % tp_size == 0:
                    spec = (None,) * (nd - 1) + (MODEL_AXIS,)
        elif p.endswith("bias") and nd >= 1 and not ROW_PARALLEL_PAT.search(p):
            if shape[-1] % tp_size == 0:
                spec = (None,) * (nd - 1) + (MODEL_AXIS,)
        if spec is not None:
            rules.append((re.escape(p) + "$", spec))
    return ShardingRules(rules)


def get_policy_rules(model: Any) -> Optional[ShardingRules]:
    """Explicit per-family rules when the model type is known (≅ the policy/
    container path, reference module_inject/replace_policy.py)."""
    from ..models.gpt2 import GPT2LMHeadModel, gpt2_sharding_rules
    from ..models.lm_config import transformer_sharding_rules
    from ..models.transformer_lm import TransformerLM

    if isinstance(model, TransformerLM):
        return ShardingRules(transformer_sharding_rules())
    if isinstance(model, GPT2LMHeadModel):
        return ShardingRules(gpt2_sharding_rules())
    return None


def replace_module(model: Any, params: Any = None, tp_size: int = 1,
                   injection_policy=None) -> ShardingRules:
    """Top-level injection entry (≅ replace_transformer_layer /
    replace_module, reference module_inject/replace_module.py:283,751):
    policy rules when the family is known, AutoTP otherwise."""
    if injection_policy:
        pairs = injection_policy.items() if hasattr(injection_policy, "items") \
            else injection_policy
        return ShardingRules(list(pairs))
    rules = get_policy_rules(model)
    if rules is not None:
        return rules
    if params is None:
        raise ValueError("AutoTP needs the param pytree for unknown models")
    return auto_tp_rules(params, tp_size)
