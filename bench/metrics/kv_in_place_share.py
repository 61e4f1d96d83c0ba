"""KV manager layer (``serve/kv_cache.py``): the share of the traced
window's decode rounds that ran in place on the onboard pool, in %:
the program's ``kv.view`` spans whose ``in_place`` arg is 1, over
those that carry the arg.  A program whose views carry no such arg
gives nothing."""

from bench import program


def read(run):
    spans = program.recorded()
    if spans is None:
        return None
    flags = [s.args["in_place"] for s in spans
             if s.name == "kv.view" and "in_place" in s.args]
    if not flags:
        return None
    return 100.0 * sum(flags) / len(flags)
