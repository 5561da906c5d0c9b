"""The prefill wave planner (serve/prefill_plan.py) as a pure function of
lengths, widths, buckets and the chunk cap.  The engine running its plans
is in test_engine_timeline.py.
"""
import random

import pytest

from ray_tpu.serve.prefill_plan import plan_wave, program_cost

BUCKETS = [32, 64, 128, 256, 512, 1024, 2048]
SHAPES = {"b32": ([1, 8, 16], 16),      # the benchmark's max_batch 32
          "b8": ([1, 8], 8),            # its max_batch 8
          "b4": ([1, 4], 4)}


def _cost(programs):
    return sum(program_cost(w, b) for w, b in programs)


def _arrival_order(lengths, widths, chunk):
    """What the engine did before the planner: chunks of `chunk` rows in
    arrival order, each at the next width and its longest row's bucket."""
    out = []
    for c0 in range(0, len(lengths), chunk):
        rows = lengths[c0:c0 + chunk]
        out.append((next(w for w in widths if w >= len(rows)),
                    next(b for b in BUCKETS if b >= max(rows))))
    return out


def _check(lengths, shape):
    """Every invariant of a plan; returns it as the span's `plan` string."""
    widths, chunk = SHAPES[shape]
    plan = plan_wave(lengths, widths, BUCKETS, chunk)
    # every row in exactly one group
    assert sorted(i for rows, _, _ in plan for i in rows) \
        == list(range(len(lengths)))
    own = [next(b for b in BUCKETS if b >= n) for n in lengths]
    for rows, w, b in plan:
        assert w in widths and b in BUCKETS
        assert len(rows) <= min(w, chunk)
        # the smallest width that holds the group, its longest row's bucket
        assert w == next(x for x in widths if x >= len(rows))
        assert b == max(own[i] for i in rows)
    # shortest first, and never dearer than arrival-order chunking
    assert [b for _, _, b in plan] == sorted(b for _, _, b in plan)
    assert _cost((w, b) for _, w, b in plan) \
        <= _cost(_arrival_order(lengths, widths, chunk))
    return ",".join(f"{w}x{b}" for _, w, b in plan)


def _equal_rows():
    """`w` equal rows are one w-wide program, for every width and bucket:
    what a warm-up that submits exactly that relies on."""
    for shape, (widths, _) in SHAPES.items():
        for w in widths:
            for b in (64, 1024):
                yield (f"warmup-{shape}-{w}x{b}", [b] * w, shape, f"{w}x{b}")


CASES = [
    ("issue-example", [100, 250, 300, 700], "b32",
     "1x128,1x256,1x512,1x1024"),
    ("arrival-order-is-not-kept", [700, 100, 300, 250], "b32",
     "1x128,1x256,1x512,1x1024"),
    ("many-short-rows-stay-one-program", [64] * 5, "b32", "8x64"),
    ("two-short-rows-stay-one-program", [40, 64], "b32", "8x64"),
    ("full-burst-is-one-call", [500] * 16, "b32", "16x512"),
    ("lone-request", [300], "b32", "1x512"),
    ("seventeen-rows", [500] * 17, "b32", None),
    ("thirty-two-mixed", [33 + 31 * i for i in range(32)], "b32", None),
    ("short-pair-beside-long-rows", [40, 50, 700, 900, 1000], "b32",
     "8x64,1x1024,1x1024,1x1024"),
    ("narrow-engine", [100, 250, 300, 700], "b8",
     "1x128,1x256,1x512,1x1024"),
    ("four-lanes-three-equal", [100] * 3, "b4", "4x128"),
    *_equal_rows(),
    *((f"random-200-{shape}", None, shape, None) for shape in SHAPES),
]


@pytest.mark.parametrize("lengths,shape,expect",
                         [c[1:] for c in CASES], ids=[c[0] for c in CASES])
def test_plan(lengths, shape, expect):
    if lengths is None:
        rng = random.Random(26)
        top = 2 * SHAPES[shape][1]
        for _ in range(200):
            _check([rng.choice([rng.randint(1, 2048),
                                int(rng.lognormvariate(5.5, 0.9)) % 2048 + 1])
                    for _ in range(rng.randint(1, top))], shape)
        return
    got = _check(lengths, shape)
    if expect is not None:
        assert got == expect
