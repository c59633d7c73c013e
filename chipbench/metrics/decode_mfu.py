"""Decode segment's share of the chip's bf16 peak: the FLOPs the active
rows' decode steps need (``work.segment``) over the device time of the
segment program's runs."""

PROGRAM = "jit__segment_impl"


def read(rec):
    if rec.trace is None or rec.decode.flops <= 0:
        return None
    t = rec.trace.module_s(PROGRAM)
    if t <= 0:
        return None
    return 100.0 * rec.decode.flops / t / rec.peak["bf16_flops_per_s"]
