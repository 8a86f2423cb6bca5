"""Device-idle milliseconds per solve inside the program's ``pcg.sync``
spans: the host waits for a device value and the device has nothing
queued (transfer and launch latency, runtime stalls)."""

import scopes


def read(ctx):
    return scopes.idle_ms_per_call(ctx, "pcg.sync", "solve")
