"""Device milliseconds per decode step of the segment program's head: the
self time of its operations under the ``lm_head`` and ``sample`` scopes
(final norm, head matmul, poison add, finite check, argmax) in the runs
of ``jit__segment_impl``, over the window's decode steps (each
``serving.commit`` span's ``steps``)."""

from chipbench import scopes


def read(rec):
    return scopes.ms_per(rec, "jit__segment_impl", ("lm_head", "sample"),
                         "serving.commit", "steps")
