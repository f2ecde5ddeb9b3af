"""Seconds the process spent compiling executables or loading them from
the persistent cache, AOT warmup's included
(`aot.cache_stats()["compile_s"]`, core/aot.py: the `aot.compile`
timer). Nothing where the program does not report it."""


def read(rec):
    v = rec.get("cache", {}).get("compile_s")
    return None if v is None else float(v)
