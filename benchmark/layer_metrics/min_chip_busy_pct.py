"""Mesh tier: the busy share of the least busy chip over the traced
stretch.  Cells on several chips."""

NAME = "min_chip_busy_pct"
UNIT = "%"


def read(run):
    shares = run.busy_shares()
    if run.cell.chips < 2 or len(shares) < 2:
        return None
    return 100.0 * min(shares.values())
