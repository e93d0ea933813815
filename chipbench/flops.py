"""Operations and bytes that the algorithm needs, from a configuration
file's shapes alone (never from the program's own accounting).

``decode_token_flops``: a decoded token's model FLOPs, 2 per active
parameter (the attention projections, the routed top-k experts and the
router, or the dense MLP, and the output head) plus the attention itself,
``4 * ctx * heads * head_dim`` per layer.  A mixture of experts counts
only the experts a token is routed to, whatever the program computes.

``paged_attn_need``: one decode step of the paged-attention kernel on one
layer, for lanes attending ``ctx`` positions each: every live K and V row
read once from one tier, the query and output once, and the new K and V
rows once; FLOPs ``4 * ctx * heads * head_dim`` per lane.
"""

from __future__ import annotations

import numpy as np


def _dims(mc: dict) -> tuple:
    d = mc["hidden_size"]
    H = mc["num_attention_heads"]
    KV = mc["num_key_value_heads"]
    hd = mc.get("head_dim") or d // H
    return d, H, KV, hd


def dtype_bytes(mc: dict) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[mc["torch_dtype"]]


def active_params_per_token(mc: dict) -> int:
    """Matrix parameters a token multiplies by, embedding lookup excluded
    and the output head included."""
    d, H, KV, hd = _dims(mc)
    ff = mc["intermediate_size"]
    attn = d * H * hd * 2 + d * KV * hd * 2
    E = mc.get("num_local_experts", 0)
    if E:
        mlp = 3 * d * ff * mc["num_experts_per_tok"] + d * E
    else:
        mlp = 3 * d * ff
    return mc["num_hidden_layers"] * (attn + mlp) + mc["vocab_size"] * d


def decode_token_flops(mc: dict, ctx) -> np.ndarray:
    """FLOPs of decoding one token that attends ``ctx`` positions."""
    d, H, KV, hd = _dims(mc)
    ctx = np.asarray(ctx, np.float64)
    return (2.0 * active_params_per_token(mc)
            + 4.0 * ctx * H * hd * mc["num_hidden_layers"])


def paged_attn_need(mc: dict, ctx) -> tuple[float, float]:
    """(FLOPs, bytes) one layer's kernel call needs for lanes at ``ctx``."""
    d, H, KV, hd = _dims(mc)
    b = dtype_bytes(mc)
    ctx = np.asarray(ctx, np.float64)
    flops = float((4.0 * ctx * H * hd).sum())
    kv_rows = float((ctx * KV * hd * 2 * b).sum())
    per_lane = (H * hd * 2 + KV * hd * 2) * b      # q, out; new K, V rows
    return flops, kv_rows + per_lane * ctx.size


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak: dict) -> tuple[float, str]:
    """Least time the chip could take over the time taken, and which bound
    sets that least time."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    bound = "memory" if t_bytes >= t_flops else "compute"
    return max(t_flops, t_bytes) / seconds, bound
