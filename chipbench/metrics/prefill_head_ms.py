"""Device milliseconds per admitted request of the admission program's
head: the self time of its operations under the ``lm_head`` and
``sample`` scopes (final norm, head matmul, pick of each request's last
position, argmax) in the runs of ``jit__admit_batch_impl``, over the
requests the window's ``serving.admit`` spans dispatched (their
``rows``)."""

from chipbench import scopes


def read(rec):
    return scopes.ms_per(rec, "jit__admit_batch_impl", ("lm_head", "sample"),
                         "serving.admit", "rows")
