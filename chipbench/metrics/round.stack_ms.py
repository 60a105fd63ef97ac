"""Self time of the engine's ``round/stack`` spans per batch of the
window: building a round's row matrix on a memo miss (stacking the decoded
rows and gathering them into work-list order, the dense-window and score
-row gathers, joining the fused buckets' rows).  Self time is a span's
duration less the part of it that its children (same ``parent_sid``)
cover.  The engine tracer is on for the whole window in the traced run;
None where the program records no such span."""

NAME = "round/stack"


def self_s(span, children) -> float:
    """``span``'s duration less the union of its children's intervals."""
    covered, end = 0.0, span.t0
    for a, b in sorted((max(c.t0, span.t0), min(c.t1, span.t1))
                       for c in children):
        a = max(a, end)
        if b > a:
            covered, end = covered + b - a, b
    return (span.t1 - span.t0) - covered


def read(run):
    spans = [s for s in run.spans if s.name == NAME]
    if not spans or not run.batches:
        return None
    # the server's spans come from a tracer of their own, whose sids can
    # equal an engine span's
    kids: dict = {}
    for s in run.spans:
        if not s.name.startswith("serve/"):
            kids.setdefault(s.parent_sid, []).append(s)
    total = sum(self_s(s, kids.get(s.sid, ())) for s in spans)
    return total * 1e3 / len(run.batches)
