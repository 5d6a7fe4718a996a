"""Signature dispatch, the host's own part: `serving/validate`,
`serving/pad` and `device/host_to_device` spans, summed per request,
median."""

from perfbench import spans, stats

STAGES = ("serving/validate", "serving/pad", "device/host_to_device")


def read(run):
    return stats.percentile(spans.per_request_ms(run.requests, STAGES), 50)
