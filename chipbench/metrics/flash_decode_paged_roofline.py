"""The paged decode attention kernel's share of its roofline: the least
time the chip could take for the attention the decode steps need (the
larger of their FLOPs over the bf16 peak and their distinct live K/V,
query and output bytes over HBM bandwidth, from ``work.segment``) over the
kernel's device time in the trace."""

KERNEL = "flash_decode_paged"


def read(rec):
    if rec.trace is None or rec.decode.attn_flops <= 0:
        return None
    t = rec.trace.op_s(KERNEL)
    if t <= 0:
        return None
    least = max(rec.decode.attn_flops / rec.peak["bf16_flops_per_s"],
                rec.decode.attn_bytes / rec.peak["hbm_bytes_per_s"])
    return 100.0 * least / t
