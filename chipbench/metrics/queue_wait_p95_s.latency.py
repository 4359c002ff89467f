"""Log + transport: for each request due in the window, the time the
server consumer's ``poll`` returned it minus its due time; the 95th
percentile. Covers the producer's append, the broker, and the consumer's
cached end offsets and read-ahead."""
from chipbench import stats


def read(run):
    return stats.percentile([r["polled"] - r["due"] for r in run.requests.values()
                             if r["polled"] is not None], 95)
