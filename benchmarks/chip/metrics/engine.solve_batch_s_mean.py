"""Mean wall seconds of one micro-batch solve in the window: the
repro_service_solve_batch_seconds histogram's increase in sum over its
increase in count. Each solve ends in the host transfer of its results,
so it holds the device time of the batch."""


def read(rec):
    c0, c1 = rec["counters"]
    n = c1["solve_batch_count"] - c0["solve_batch_count"]
    if n <= 0:
        return None
    return (c1["solve_batch_sum"] - c0["solve_batch_sum"]) / n
