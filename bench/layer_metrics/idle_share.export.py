"""Percent of the traced window in which no program ran on the device:
1 - (union of the device's program executions) / window."""
from spans import idle_share


def read(ctx):
    return idle_share(ctx)
