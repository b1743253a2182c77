"""Share of the (token, choice) pairs that fall on experts this chip holds:
mean over the window's steps, workers and expert layers. 100 x held /
published experts under an even routing (12.5 at 16 of 128)."""

from benchmark import routing_reduce


def read(ctx):
    rows = routing_reduce.window_rows(ctx)
    if not rows:
        return None
    shares = [sum(layer[:-1]) / max(sum(layer), 1) for row in rows for layer in row]
    return 100.0 * sum(shares) / len(shares)
