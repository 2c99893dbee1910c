"""The ONE statement of which bytes cross before and after a lane's phase
(``core/phase.classify``), over the flag table: read-only, write-only,
``partial_read``, ``write_all`` owner / not owner, covered / not covered,
roaming stores — in enqueue mode and out of it, for an engine that cuts the
lane's range into parts and for one that moves it in one piece, on one lane
and on several.  The four engines take their transfers from this answer; it is
the only place the rule can be wrong.

The expectations below are written out by hand from the reference's contract
(Worker.cs:821-885: whole or ranged writes of what is read, ranged reads of
what is written, ``write_all`` from one device) and the two additions of this
port (residency across enqueued computes; an array that takes scattered stores
never crosses part by part)."""

from types import SimpleNamespace

import numpy as np
import pytest

from cekirdekler_tpu import ClArray
from cekirdekler_tpu.core.phase import (
    DEFER, ENSURE, LATE, OWNER, PART, WHOLE, classify)

N, LANE, OFFSET, SIZE = 4096, 1, 1024, 1024


def arrays():
    f32 = np.float32
    return [
        ClArray(N, f32, name="ro_part", partial_read=True, read_only=True),
        ClArray(N, f32, name="ro_whole", read_only=True),
        ClArray(N, f32, name="wo", write_only=True),
        ClArray(N, f32, name="io_part", partial_read=True),
        ClArray(N, f32, name="io_whole"),
        ClArray(N, f32, name="all_mine", read=False, write_all=True),
        ClArray(N, f32, name="all_other", read=False, write_all=True),
        ClArray(2 * N, np.int16, name="roam", partial_read=True,
                elements_per_work_item=2),
    ]


OWNERS = {5: LANE, 6: LANE + 1}
ROAM = frozenset({7})


def expected(enqueue: bool, covered: bool, cut: bool, single: bool):
    resident = enqueue and covered
    # a partial_read array crosses by the lane's range where that saves
    # something: the engine overlaps the parts, or another lane has the rest
    part = None if resident else PART if cut or not single else WHOLE
    whole = None if resident else WHOLE
    back = DEFER if enqueue else PART
    return {
        "ro_part": (part, None),
        "ro_whole": (whole, None),
        "wo": (ENSURE, back),
        "io_part": (part, back),
        "io_whole": (whole, back),
        "all_mine": (ENSURE, DEFER if enqueue else OWNER),
        "all_other": (ENSURE, None),
        # scattered stores: whole before the first part, the range after
        # the last; an engine that moves the range in one piece has no part
        # to bury a store under
        "roam": ((None if resident else WHOLE) if cut else part,
                 DEFER if enqueue else LATE if cut else PART),
    }


@pytest.mark.parametrize("single", [True, False], ids=["one-lane", "lanes"])
@pytest.mark.parametrize("cut", [True, False], ids=["cut", "one-piece"])
@pytest.mark.parametrize("covered", [True, False],
                         ids=["covered", "not-covered"])
@pytest.mark.parametrize("enqueue", [True, False], ids=["enqueue", "sync"])
def test_the_rule_over_the_flag_table(enqueue, covered, cut, single):
    params = arrays()
    asked = []

    def upload_covers(p, off, n):
        asked.append((p.name, off, n))
        return covered

    program = SimpleNamespace(
        roaming_stores=lambda names, epws: ROAM)
    got = classify(
        program, ("k",), params, SimpleNamespace(
            index=LANE, upload_covers=upload_covers),
        OFFSET, SIZE, cut=cut, single=single, enqueue=enqueue,
        owners=OWNERS)
    want = expected(enqueue, covered, cut, single)
    assert {p.name: (u, b) for p, u, b in zip(params, got.up, got.back)} \
        == want
    # residency is asked in enqueue mode alone, of what the kernels read:
    # the items' own elements of a partial_read array, else the whole
    assert sorted(asked) == (sorted([
        ("io_part", OFFSET, SIZE), ("io_whole", 0, N),
        ("ro_part", OFFSET, SIZE), ("ro_whole", 0, N),
        ("roam", 2 * OFFSET, 2 * SIZE)]) if enqueue else [])
    # the autotuner's key: the bytes that move by the lane's range,
    # whatever the engine then does with an array that roams
    ranged_up = 0 if enqueue and covered else (4 + 4 + 2 * 2) * SIZE
    ranged_back = 0 if enqueue else (4 + 4 + 4 + 2 * 2) * SIZE
    assert got.key_bytes == ranged_up + ranged_back


def test_roaming_stores_are_asked_of_a_cutting_engine_alone():
    asked = []
    program = SimpleNamespace(
        roaming_stores=lambda names, epws: asked.append((names, epws))
        or frozenset())
    w = SimpleNamespace(index=0, upload_covers=lambda *a: False)
    for cut in (False, True):
        classify(program, ["a", "b"], arrays()[:2], w, 0, N, cut=cut,
                 single=True, enqueue=False, owners={})
    assert asked == [(("a", "b"), (1, 1))]
