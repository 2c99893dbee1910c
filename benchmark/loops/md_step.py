"""Loop kind ``md_step``: one call is one force step of a molecular-dynamics
code whose integrator lives on the host, through upstream's public API alone
(``ClArray`` flags as properties, ``compute()``; ``enqueue_mode`` off).  The
host writes the call's positions into the bound array (what the integrator's
last step left: here the base positions plus the ``k``-th of the
configuration's seeded displacements, made in set-up), then ONE synchronous
``compute()`` of ``compute_lj_force`` with that call's ``(lj1, lj2)``: the
positions go up whole, the forces of every atom are in the caller's array when
it returns.  The neighbour list is built rarely and stays: it goes up with the
first compute (the harness's, ahead of the loop), and ``enter`` then sets what
the configuration gives the array under ``after_first_upload`` (flags by their
public names: ``read = false``, upstream's own idiom for data that lives on the
device), exactly as the loop ``reduction`` does; an array without the entry is
left alone.

``k`` is the call's place in the harness's cycle of scalar arguments (the set
apart: the last frame), so a skipped upload, or the previous call's ``lj``
pair, is a wrong result.  Every call logs ``(k, lj1, lj2)`` in
``ctx.data["calls"]``."""


def items_per_call(params: dict) -> int:
    return int(params["n"])


def enter(ctx) -> None:
    ctx.cr.enqueue_mode = False
    for spec in ctx.cfg["arrays"]:
        for flag, value in spec.get("after_first_upload", {}).items():
            setattr(ctx.arrays[spec["name"]], flag, value)


def make_call(ctx):
    position = ctx.arrays["position"].host()
    frames, log = ctx.data["frames"], ctx.data["calls"]
    cycle = ctx.cycle

    def step() -> None:
        k = len(cycle) if ctx.values is ctx.apart else cycle.index(ctx.values)
        position[:] = frames[k]
        ctx.compute()
        log.append((k, float(ctx.values[2]), float(ctx.values[3])))

    return step


def leave(ctx) -> None:
    """Nothing is deferred: every call ended with its forces on the host."""
