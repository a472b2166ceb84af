"""Host self time of ``dispatch.launch`` in the traced window, in ms per
work item (``dispatch.submit`` span): ``DispatchQueue._launch_wave``:
install, patch and ``ResidentPool.dispatch`` (stack, stream upload,
executor call), deferred staging excluded."""

from bench import program_spans


def read(ctx):
    return program_spans.ms_per(ctx, "dispatch.launch", "dispatch.submit")
