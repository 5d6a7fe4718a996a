"""Batching: examples in executed batches over batches times
max_batch_size, in percent. A batch is one distinct `batching/execute`
span (it is written onto every rider's trace); its examples are the
`batch_size` the batcher annotated on its riders."""


def read(run):
    batches = {}
    for r in run.requests:
        for name, ts, dur, _ in r["spans"]:
            if name == "batching/execute" and "batch_size" in r["args"]:
                batches[(ts, dur)] = r["args"]["batch_size"]
    if not batches:
        return None
    cap = run.config["serve"]["batching"]["max_batch_size"]
    return 100.0 * sum(batches.values()) / (len(batches) * cap)
