"""The fused paged-attention decode kernel's share of its roofline.

Work is what the algorithm needs, counted from the configuration's shapes
(``chipbench/flops.paged_attn_need``): for every decode step in the traced
window, on every layer, each live K and V row read once from one tier, the
query, the output and the new rows, and ``4 * ctx * heads * head_dim``
FLOPs per lane.  It is never what the kernel happens to fetch.  The least
time that work could take at the peaks of ``chipbench/peaks.json`` is
divided by the kernel's summed device time in the trace; the record says
which bound sets the least time.  Silent where no kernel event is found.
"""

from chipbench import flops

# the fused kernel's custom call as the device trace names it (one per
# layer and decode step): ``paged_attention_fused_op.<n>``
KERNEL = "paged_attention_fused_op"


def kernel_seconds(trace) -> float:
    return sum(s for n, s in trace.op_seconds.items() if KERNEL in n)


def read(v):
    if v.trace is None or v.peak is None:
        return None
    secs = kernel_seconds(v.trace)
    if secs <= 0:
        return None
    L = v.mc["num_hidden_layers"]
    ctx = [len(r.prompt) + j for r, j, _ in v.window_tokens() if j >= 1]
    if not ctx:
        return None
    f, b = flops.paged_attn_need(v.mc, ctx)
    share, bound = flops.roofline_share(L * f, L * b, secs, v.peak)
    return {"value": 100.0 * share, "bound": bound}
