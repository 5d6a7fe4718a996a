"""Transport: what a request spends outside every stage on its way in
(gRPC handler entry to the first stage span: proto to arrays, servable
lookup) plus its `serving/serialize` span on the way out. Median per
request."""

from perfbench import stats


def read(run):
    values = []
    for r in run.requests:
        if not r["spans"]:
            continue
        head = min(ts for _, ts, _, _ in r["spans"]) - r["ts"]
        tail = sum(d for n, _, d, _ in r["spans"] if n == "serving/serialize")
        values.append((head + tail) / 1e3)
    return stats.percentile(values, 50)
