"""Aggregate: percent of busy device time spent in the fused Pallas
segment kernel (engine.aggregate on the pallas backend: the Mosaic
custom calls, the only Pallas kernel these programs run), from the trace
of the window's first call."""

from bench import trace

KERNEL = (":tpu_custom_call",)


def read(run):
    share = trace.op_share(run.summary, KERNEL) if run.summary else None
    return None if share is None else 100.0 * share
