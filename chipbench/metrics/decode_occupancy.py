"""Share of the decode segments' row steps that emitted a token: the
program's ``serving_decode_tokens_total`` over
``serving_decode_row_steps_total`` in the window, summed from the
increments each segment's ``serving.commit`` span carries (``tokens``;
``steps`` x ``rows``)."""

from chipbench import scopes


def read(rec):
    marks = scopes.of(rec)
    if marks is None:
        return None
    commits = marks.serving_args("serving.commit")
    steps = sum(a["steps"] * a["rows"] for a in commits)
    if not steps:
        return None
    return 100.0 * sum(a["tokens"] for a in commits) / steps
