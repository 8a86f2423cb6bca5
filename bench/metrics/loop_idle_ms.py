"""Device-idle milliseconds per solve whose innermost host span is the
program's ``pcg.solve``: the host-driven Krylov loop's own dispatch gaps,
outside its blocking reads."""

import scopes


def read(ctx):
    return scopes.idle_ms_per_call(ctx, "pcg.solve", "solve")
