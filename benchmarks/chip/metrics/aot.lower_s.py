"""Seconds the process spent tracing and lowering executables, AOT
warmup's included (`aot.cache_stats()["lower_s"]`, core/aot.py: the
`aot.lower` timer). Nothing where the program does not report it."""


def read(rec):
    v = rec.get("cache", {}).get("lower_s")
    return None if v is None else float(v)
