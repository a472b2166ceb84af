"""Host self time of ``dispatch.submit`` in the traced window, in ms per
work item (``dispatch.submit`` span): ``DispatchQueue.submit``: the
submit check and the queue's bookkeeping, staging excluded."""

from bench import program_spans


def read(ctx):
    return program_spans.ms_per(ctx, "dispatch.submit", "dispatch.submit")
