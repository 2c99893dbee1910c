"""Buffer reads of the launched kernel that were lowered to ONE window a
work-group (an index ``local id + u`` with ``u`` the same in every active
work item of a group: ``codegen._group_slice``): the ``group`` count of the
``access`` field on the lane's ``ck/launch`` spans
(``reduce_gathered_accesses.of``).  0 where the field is there without the
key (a program that knows no such read); the two reads of ``g_idata`` in the
walk of SHOC's ``reduce`` where it does."""

import cells


def read(ctx):
    f = cells.load_reader("reduce_gathered_accesses").of(ctx)
    return None if f is None else float(f.get("group", 0))
