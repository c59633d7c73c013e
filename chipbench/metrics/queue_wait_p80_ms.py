"""80th percentile (nearest rank, ``run.percentile``) over the window's
requests of the wait in the admission queue: the program's
first-admission stamp (``Request.t_admitted``, set when the scheduler
admits it) minus the request's due time; a request never admitted counts
the time to the window's end."""

from chipbench.run import percentile


def read(rec):
    tracks = rec.window.tracks
    if not tracks:
        return None
    end = rec.window.end_s
    return percentile([(t.req.t_admitted if t.req.t_admitted is not None
                        else end) - t.item.due for t in tracks], 0.8) * 1e3
