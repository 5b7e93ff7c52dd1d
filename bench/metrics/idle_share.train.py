"""Share of the traced window in which no operation ran on the device,
averaged over the cell's chips."""
from bench.stats import idle_percent


def read(run):
    return idle_percent(run)
