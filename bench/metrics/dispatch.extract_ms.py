"""Host self time of ``dispatch.extract`` in the traced window, in ms per
work item (``dispatch.submit`` span): ``engine.extract`` and the build's
``post`` (``NMCFuture.result``)."""

from bench import program_spans


def read(ctx):
    return program_spans.ms_per(ctx, "dispatch.extract", "dispatch.submit")
