"""95th percentile (nearest rank from above) of the interval between
consecutive step completions, all steps of the window. Host clock."""
import math


def read(run):
    steps = sorted(b - a for a, b in zip(run["done"], run["done"][1:]))
    if steps:
        return 1e3 * steps[min(len(steps), math.ceil(0.95 * len(steps))) - 1]
