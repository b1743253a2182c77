"""Share of the global batch that worker 0 holds in the window's last plan.
The inverse-time fixed point is 10 % under a 3:1:1:1 straggler and 25 % for
even workers. Only where the balancer runs over several workers."""


def read(ctx):
    traffic = ctx["traffic"]
    plans = [e["batches"] for e in ctx["epochs"] if e.get("batches")]
    if not traffic["dbs"] or traffic["world_size"] < 2 or not plans:
        return None
    return 100.0 * plans[-1][0] / sum(plans[-1])
