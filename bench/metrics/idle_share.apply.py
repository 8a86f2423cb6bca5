"""Percent of the traced window of applications in which the devices ran
no operation: 1 - busy / window, busy the union of the device's
operation intervals."""

import tracing


def read(ctx):
    return tracing.idle_share(ctx["trace"], "apply")
