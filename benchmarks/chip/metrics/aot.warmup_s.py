"""Seconds the server's sync AOT warmup took over the configuration's
buckets (`AutotuneServer.warmup.seconds`, core/aot.py): tracing,
lowering and loading or compiling each bucket's executable."""


def read(rec):
    return float(rec["warmup_s"])
