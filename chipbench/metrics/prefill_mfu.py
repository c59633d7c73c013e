"""Admission prefill's share of the chip's bf16 peak: the FLOPs the
window's admissions need (``work.admission``: valid suffix tokens, the
lm_head at each request's last position) over the device time of the
admission program's runs."""

PROGRAM = "jit__admit_batch_impl"


def read(rec):
    if rec.trace is None or rec.prefill.flops <= 0:
        return None
    t = rec.trace.module_s(PROGRAM)
    if t <= 0:
        return None
    return 100.0 * rec.prefill.flops / t / rec.peak["bf16_flops_per_s"]
