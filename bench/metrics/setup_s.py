"""setup_s: seconds from process start to the first timed step: loading,
making weights or fields, warming up, and compiling where the cache
misses."""


def read(rec):
    return rec["setup_s"]
