"""Loop kind ``traversal``: one call is one breadth-first traversal as
Rodinia's ``run_bfs_gpu`` drives it, through upstream's public API alone
(``ClArray`` flags as properties, ``compute()``, ``no_compute_mode``;
``enqueue_mode`` off).  The host initialises the four state arrays for the
call's source and marks them for upload; then level by level ``over[0] = 0``,
ONE synchronous ``compute()`` of both kernels, and a look at ``over[0]``:
after the first level every graph and state array has ``read = false, write =
false`` and stays on the chip, ``over`` alone crosses (``write_all``: one byte
up, one back).  When ``over`` stays 0, ``cost`` comes back by upstream's
``noComputeMode``: one ``compute()`` that runs nothing and writes back what
has ``write`` set.

The source is none of the kernels' arguments, so the harness's cycle of
scalar arguments cannot carry it (a compute refuses a value the kernels do
not take): the loop keeps the cycle of the configuration's ``sources`` itself
and takes ``source_apart`` where the harness hands it the arguments it set
apart (``ctx.values is ctx.apart``: the last warm-up call and the fresh
call).  Every call logs ``(source, levels)`` in ``ctx.data["traversals"]``
(base labels), where the comparison finds the last calls' sources."""

GRAPH = ("starting", "no_of_edges", "edges")
STATE = ("mask", "updating", "visited", "cost")


def items_per_call(params: dict) -> int:
    return int(params["n"])


def enter(ctx) -> None:
    ctx.cr.enqueue_mode = False


def make_call(ctx):
    cr, cfg, data, arrays = ctx.cr, ctx.cfg, ctx.data, ctx.arrays
    names = [s["name"] for s in cfg["arrays"]]
    first, *rest = (arrays[k] for k in names)
    group = first.next_param(*rest)
    host = {k: arrays[k].host() for k in STATE + ("over",)}
    over, cost = arrays["over"], arrays["cost"]
    relabel, log = data["relabel"], data["traversals"]
    sources, apart = list(cfg["sources"]), int(cfg["source_apart"])
    kernel, n, lr = cfg["kernel"], ctx.n, int(cfg["local_range"])
    made = [0]  # calls through the cycle so far

    def compute() -> None:
        group.compute(cr, ctx.cid, kernel, n, lr, values=ctx.values)

    def call() -> None:
        if ctx.values is ctx.apart:
            base = apart
        else:
            base = sources[made[0] % len(sources)]
            made[0] += 1
        source = int(relabel[base])
        for k in ("mask", "updating", "visited"):
            host[k][:] = 0
        host["cost"][:] = -1
        host["mask"][source] = host["visited"][source] = 1
        host["cost"][source] = 0
        for k in STATE:
            arrays[k].read, arrays[k].write = True, False
        over.read = over.write = True
        levels = 0
        while True:
            host["over"][0] = 0
            compute()
            levels += 1
            if levels == 1:  # from here on the state stays on the chip
                for k in GRAPH + STATE:
                    arrays[k].read = False
            if not host["over"][0]:
                break
        over.read = over.write = False
        cost.write = True
        cr.no_compute_mode = True
        try:
            compute()
        finally:
            cr.no_compute_mode = False
        log.append((base, levels))

    return call


def leave(ctx) -> None:
    """Nothing is deferred: every call ended with ``cost`` on the host."""
