"""The fullest held expert's arrivals over the held experts' mean, in one
worker's step and expert layer; averaged over the window's steps and workers,
then the worst layer's. 1 = an even load; the grouped product's time follows
the sum, a deployment's slowest expert-parallel chip follows the maximum."""

from benchmark import routing_reduce


def read(ctx):
    rows = routing_reduce.window_rows(ctx)
    if not rows:
        return None
    worst = 0.0
    for layer in range(len(rows[0])):
        ratios = [max(row[layer][:-1]) * len(row[layer][:-1]) / max(sum(row[layer][:-1]), 1)
                  for row in rows]
        worst = max(worst, sum(ratios) / len(ratios))
    return worst
