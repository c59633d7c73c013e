"""The ragged prefill attention kernel's share of its roofline: the least
time the chip could take for the attention the admissions need (the larger
of their FLOPs over the bf16 peak and their bytes over HBM bandwidth, from
``work.admission``) over the kernel's device time in the trace."""

KERNEL = "flash_prefill_ragged"


def read(rec):
    if rec.trace is None or rec.prefill.attn_flops <= 0:
        return None
    t = rec.trace.op_s(KERNEL)
    if t <= 0:
        return None
    least = max(rec.prefill.attn_flops / rec.peak["bf16_flops_per_s"],
                rec.prefill.attn_bytes / rec.peak["hbm_bytes_per_s"])
    return 100.0 * least / t
