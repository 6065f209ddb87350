"""L3 round step: device time of ``telemetry.device_round_metrics`` (norms and
the log-magnitude histograms: the ``fed_telemetry_metrics`` scope), per round
of the traced window."""

import _program_trace


def read(ctx):
    return _program_trace.read_stages(ctx, ("fed_telemetry_metrics",))
