"""The program's ``pcg.sync`` spans per solve in the traced window: the
host round trips of the Krylov loop (one per iteration, two at its
start), the count an on-device loop would cut."""

import scopes


def read(ctx):
    return scopes.spans_per_call(ctx, "pcg.sync", "solve")
