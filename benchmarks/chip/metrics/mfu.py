"""Useful model flops of the traced steps over window x chips x peak (%).

A decode token counts 2 flops per parameter it multiplies (attention
projections, router, its top-k experts, the LM head) plus 4 * ctx * H * hd
per layer; a prompt counts the same per token under causal attention and
one LM head.  Expert capacity padding is not work the model needs."""
from benchmarks.chip import flops


def read(rec, red):
    if red is None or rec["peaks"] is None or red.window_s <= 0:
        return None
    d = rec["dims"]
    work = 0.0
    for s in rec["traced_steps"]:
        work += sum(flops.prefill_flops(d, P) for P in s.prefill)
        work += sum(flops.decode_token_flops(d, c) for c in s.decode_ctx)
    if work <= 0:
        return None
    return 100.0 * work / (red.window_s * rec["chips"]
                           * rec["peaks"]["flops_bf16"])
