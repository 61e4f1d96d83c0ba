"""Scheduler layer (``serve/engine.py``): host time of a decode round
that the device waits on, in ms: per ``serve.round`` span holding a
``decode.paged`` span in the traced window, its duration less the time
the host blocked on device results in it (``engine.sync`` spans),
mean over those rounds.  Their seconds by span go to standard
error."""

from bench import program


def read(run):
    spans = program.recorded()
    if spans is None:
        return None
    t = program.Tree(spans)
    rounds = [r for r in t.named("serve.round")
              if any(c.name == "decode.paged" for c in t.kids[r.span_id])]
    if not rounds:
        return None
    program.log_split("decode rounds' host seconds by span",
                      t.self_seconds(rounds))
    host = sum(r.dur - t.seconds("engine.sync", under=r) for r in rounds)
    return 1e3 * host / len(rounds)
