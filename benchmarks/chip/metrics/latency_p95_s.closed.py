"""95th percentile of submit-to-answer seconds over every request of a
closed-loop window, a failed or unanswered request counting as an
infinite latency. A closed loop keeps the server saturated, so its tail
swings with the order in which batches happen to fill: it is read here,
beside the cell's throughput, and not held to a bound."""
import bench


def read(rec):
    lat = bench.latencies(rec)
    return bench.percentile(lat, 95) if lat else None
