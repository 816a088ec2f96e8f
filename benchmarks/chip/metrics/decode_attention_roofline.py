"""Roofline share of the ``decode_attention`` kernel in the traced window:
the least time the chip needs for the attention the traced decode steps
ask of it (q, the output and the K/V of the positions each row holds, as
``dims.decode_attention_cost`` counts one layer's) over the summed device
time of the kernel's events (%)."""
from benchmarks.chip import flops

KERNEL = "decode_attention"


def read(rec, red):
    if red is None or rec["peaks"] is None:
        return None
    d = rec["dims"]
    t_kernel = red.op_time_s(KERNEL)
    if t_kernel <= 0:
        return None
    least = 0.0
    for s in rec["traced_steps"]:
        if s.decode_ctx:
            f, b = d.decode_attention_cost(s.decode_ctx)
            least += d.n_layers * flops.roofline_time(f, b, rec["peaks"])[0]
    return 100.0 * least / t_kernel
