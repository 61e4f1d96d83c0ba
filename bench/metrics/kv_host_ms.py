"""KV manager layer (``serve/kv_cache.py``): host time spent in
``PagedKVStore.decode_view`` and ``commit_decode`` per decode round of
the window (the harness's spans around both calls)."""


def read(run):
    s = run.spans
    rounds = s.calls.get("kv.decode_view", 0)
    if rounds == 0:
        return None
    host = s.seconds.get("kv.decode_view", 0.0) + s.seconds.get(
        "kv.commit_decode", 0.0)
    return host / rounds * 1e3
