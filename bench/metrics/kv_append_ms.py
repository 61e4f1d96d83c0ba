"""KV manager layer (``serve/kv_cache.py``): host time of
``PagedKVStore.append_tokens``, the page writes after each prefill, in
ms: the mean of the program's ``kv.append`` spans in the traced
window.  Their seconds by span inside them go to standard error."""

from bench import program


def read(run):
    spans = program.recorded()
    if spans is None:
        return None
    t = program.Tree(spans)
    d = t.named("kv.append")
    if not d:
        return None
    program.log_split("kv.append host seconds by span", t.self_seconds(d))
    return 1e3 * sum(s.dur for s in d) / len(d)
