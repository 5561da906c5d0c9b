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
    plan, _ = plan_wave(lengths, widths, BUCKETS, chunk)
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


# ----------------------------------------------- the ceiling on positions
from ray_tpu.serve import prefill_plan  # noqa: E402
from ray_tpu.serve.prefill_plan import PREFILL_MAX_TOKENS  # noqa: E402

LONG_BUCKETS = [32, 64, 128, 256, 512, 1024, 2048, 4096, 8192, 9216]


def _shapes(plan):
    return [(w, b) for _, w, b in plan]


def _without_the_ceiling(monkeypatch, *args):
    with monkeypatch.context() as m:
        m.setattr(prefill_plan, "PREFILL_MAX_TOKENS", 1 << 40)
        plan, capped = plan_wave(*args)
    assert not capped
    return plan


@pytest.mark.parametrize("lengths,expect", [
    ([8000] * 16, [(1, 8192)] * 16),          # 16 x 8192 = 131,072: split
    ([5000, 8192, 6000], [(1, 8192)] * 3),
    ([4000] * 8, [(8, 4096)]),                # 32,768 positions: allowed
    ([4000] * 9, [(1, 4096), (8, 4096)]),     # 16 x 4096 is over it
    ([9000], [(1, 9216)]),                    # one row, whatever its length
    ([300] * 16, [(16, 512)]),
], ids=["sixteen-long", "three-long", "eight-at-the-ceiling",
        "nine-at-4096", "one-row-over-it", "short-rows-untouched"])
def test_no_program_of_more_rows_than_one_holds_more_than_the_ceiling(
        lengths, expect, monkeypatch):
    plan, capped = plan_wave(lengths, [1, 8, 16], LONG_BUCKETS, 16)
    assert sorted(i for rows, _, _ in plan for i in rows) \
        == list(range(len(lengths)))
    assert sorted(_shapes(plan)) == sorted(expect)
    for _, w, b in plan:
        assert w == 1 or w * b <= PREFILL_MAX_TOKENS
    unbounded = _without_the_ceiling(monkeypatch, lengths, [1, 8, 16],
                                     LONG_BUCKETS, 16)
    assert capped == (_shapes(plan) != _shapes(unbounded))


@pytest.mark.parametrize("widths,chunk,max_len", [
    ([1, 8, 16], 16, 2048),       # mistral-7b-v0.3-d16: 32 lanes
    ([1, 8], 8, 8192),            # codestral-22b-v0.1-d8: 8 lanes, 8 x 4096
    ([1, 8, 16], 16, 2048),       # lfm2-24b-a2b-d9: 64 lanes
    ([1, 8], 8, 2048),            # chip_smoke
], ids=["mistral7b", "codestral22b", "lfm2moe", "chip_smoke"])
def test_the_ceiling_changes_no_plan_of_the_existing_cells(widths, chunk,
                                                            max_len,
                                                            monkeypatch):
    """Every (width, bucket) program those engines can form holds at most
    PREFILL_MAX_TOKENS positions, so their plans are what they were:
    random waves of their traffic's lengths plan the same with and
    without the ceiling."""
    buckets = [b for b in LONG_BUCKETS if b < max_len] + [max_len]
    top = min(max_len, 4096)      # Codestral's prompts end at 4,096
    assert max(widths) * top <= PREFILL_MAX_TOKENS
    rng = random.Random(34)
    for _ in range(300):
        lengths = [rng.randint(1, top) for _ in range(rng.randint(1, 2 * chunk))]
        plan, capped = plan_wave(lengths, widths, buckets, chunk)
        assert not capped
        assert plan == _without_the_ceiling(monkeypatch, lengths, widths,
                                            buckets, chunk)
