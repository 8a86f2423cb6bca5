"""Device milliseconds per application of the operations that no stage
scope of the plan executor claims (``scopes.scope_of`` gives them no
stage): what XLA adds at the operator's boundary, such as a relayout copy
of an argument, averaged over the cell's devices."""

import scopes


def read(ctx):
    found = scopes.window(ctx) if ctx["run"]["span"] == "apply" else None
    if found is None or not scopes.has_scopes(found[1]):
        return None
    trace, hlo = found
    t = scopes.unscoped_s(trace, scopes.scope_of(hlo), "apply")
    return None if t is None else t * 1e3
