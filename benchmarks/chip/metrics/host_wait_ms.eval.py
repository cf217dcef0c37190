"""Host wait per eval batch, ms: the benchmark's own clock around each
request for the next staged batch (hooks, sampling, staging, prefetch
queue), summed over the window and divided by the batches."""


def read(run):
    if run.loop != "eval":
        return None
    waits = run.records["waits"]
    return 1e3 * sum(waits) / len(waits)
