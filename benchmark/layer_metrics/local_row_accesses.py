"""Access sites of the kernel's ``__local`` arrays that fell to the FALLBACK,
a gather / scatter inside the group's row: the ``row`` count of the ``local``
field's ``sites`` on the lane's ``ck/launch`` spans
(``group_barriers_per_launch.of``).  0 where every site is a shift along the
row (``tile[tid + u]``) or one element a group (``tile[u]``), as the seven
sites of SHOC's ``reduce`` are."""

import cells


def read(ctx):
    f = cells.load_reader("group_barriers_per_launch").of(ctx)
    return None if f is None or "row" not in f else float(f["row"])
