"""Share of the traced window in which no op ran on the chip (%), for the
closed full-SVD loop: 1 - union of device op intervals / window."""

from bench.trace import idle_percent


def read(run):
    return idle_percent(run.get("trace"))
