"""Stores of the compute's kernels that were lowered to a scatter, as the
program counted them when it built the kernels: the ``scatter`` count of the
``access`` field on the lane's ``ck/launch`` spans
(``access=slice:8;strided:0;uniform:1;gather:2;scatter:2;carried:1``), read
off the first call of the traced window (``levels_per_call.reduce``).
Rodinia's ``BFS_1`` has two (``cost[id]``, ``updating[id]``); the flag's store
``over[0] = true`` is the same element from every lane and counts as
``uniform``.  A program whose spans carry no such field leaves nothing to
read."""

import cells


def read(ctx):
    r = cells.load_reader("levels_per_call").of(ctx)
    if r is None or r.access is None:
        return None
    parse = cells.load_reader("mvt_gathered_accesses").parse
    return float(parse(r.access).get("scatter", 0))
