"""Device milliseconds per decode step of the segment program's K/V
writes: the self time of its operations under the ``kv_write`` scope (each
layer's scatter of the step's K/V into the pages) in the runs of
``jit__segment_impl``, over the window's decode steps (each
``serving.commit`` span's ``steps``).  XLA's copies of the whole pool
that no scope holds are not in it."""

from chipbench import scopes


def read(rec):
    return scopes.ms_per(rec, "jit__segment_impl", ("kv_write",),
                         "serving.commit", "steps")
