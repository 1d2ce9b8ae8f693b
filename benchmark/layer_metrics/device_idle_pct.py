"""Device: 1 minus busy over the traced stretch, the mean over the
chips the trace saw."""

NAME = "device_idle_pct"
UNIT = "%"


def read(run):
    shares = run.busy_shares()
    if not shares:
        return None
    return 100.0 * (1.0 - sum(shares.values()) / len(shares))
