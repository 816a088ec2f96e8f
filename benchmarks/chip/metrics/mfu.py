"""Useful model flops of the traced steps over window x chips x peak (%).

A decode token and a prompt count the flops that the configuration's
reference module says they need (``dims.decode_token_flops``,
``dims.prefill_flops``).  Expert capacity padding is not work the model
needs."""


def read(rec, red):
    if red is None or rec["peaks"] is None or red.window_s <= 0:
        return None
    d = rec["dims"]
    work = 0.0
    for s in rec["traced_steps"]:
        work += sum(d.prefill_flops(P) for P in s.prefill)
        work += sum(d.decode_token_flops(c) for c in s.decode_ctx)
    if work <= 0:
        return None
    return 100.0 * work / (red.window_s * rec["chips"]
                           * rec["peaks"]["flops_bf16"])
