"""Signature dispatch, the host's wait for the device: `device/execute`
and `device/device_to_host` spans, summed per request, median."""

from perfbench import spans, stats

STAGES = ("device/execute", "device/device_to_host")


def read(run):
    return stats.percentile(spans.per_request_ms(run.requests, STAGES), 50)
