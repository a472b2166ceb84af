"""Host self time of ``dispatch.stage`` in the traced window, in ms per
work item (``dispatch.submit`` span): ``DispatchQueue._stage``: the
host-to-device copy of the item's image."""

from bench import program_spans


def read(ctx):
    return program_spans.ms_per(ctx, "dispatch.stage", "dispatch.submit")
