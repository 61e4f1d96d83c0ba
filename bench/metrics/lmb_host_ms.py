"""LMB tier (``core/buffer.py``): host time inside the ``LinkedBuffer``
calls (``lmb.*`` spans) less the executor's array ops within them
(``exec.*`` spans), per decode round (``decode.paged`` span) of the
traced window, in ms: the tier's bookkeeping, which runs even where the
tier holds no page."""

from bench import program


def read(run):
    spans = program.recorded()
    if spans is None:
        return None
    t = program.Tree(spans)
    rounds = len(t.named("decode.paged"))
    if rounds == 0:
        return None
    host = sum(s.dur - t.seconds("exec.", under=s)
               for s in t.outermost("lmb."))
    return 1e3 * host / rounds
