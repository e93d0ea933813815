"""Model FLOPs of the decode tokens harvested in the window over the
window's seconds times the chip's peak bf16 FLOP/s
(``chipbench/flops.decode_token_flops``: 2 per active parameter, routed
experts only, plus the attention over each token's context)."""

from chipbench import flops


def read(v):
    if v.peak is None:
        return None
    ctx = [len(r.prompt) + j for r, j, _ in v.window_tokens() if j >= 1]
    if not ctx:
        return None
    total = float(flops.decode_token_flops(v.mc, ctx).sum())
    return 100.0 * total / (v.seconds * v.chips * v.peak["bf16_flops_per_s"])
