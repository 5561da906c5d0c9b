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


# ------------------------------- a routed model's floor and its programs
from ray_tpu.serve.prefill_plan import (  # noqa: E402
    FLOOR_TOKENS, PREFILL_MAX_STATE_BYTES, floor_positions, programs_under)

ROUTED_FLOOR = 2500                   # LFM2-24B-A2B at 9 layers reads 2,512
ROUTED_WIDTHS = [1, 2, 4, 8, 16]
NARROW = frozenset({2, 4})
ROUTED = programs_under(ROUTED_FLOOR, ROUTED_WIDTHS, BUCKETS, NARROW)


def test_the_programs_a_far_floor_leaves():
    """Widths 2 and 4 only where they are free, and of one width's
    buckets well under the floor only the longest: 14 programs over the
    buckets prompts of 33-1024 tokens reach, against 3 x 5."""
    assert floor_positions(7, 7) == FLOOR_TOKENS
    assert floor_positions(5_043_650_560, 513_802_240) == 2512
    by_width = {w: [b for x, b in ROUTED if x == w] for w in ROUTED_WIDTHS}
    assert by_width == {1: [1024, 2048], 2: [512, 1024], 4: [256, 512],
                        8: [128, 256, 512, 1024, 2048],
                        16: [64, 128, 256, 512, 1024, 2048]}
    assert len([p for p in ROUTED if 64 <= p[1] <= 1024]) == 14
    # a floor at the dense one prunes what lies under HALF of it only
    assert set(programs_under(FLOOR_TOKENS, [1, 8, 16], BUCKETS)) \
        == {(w, b) for w in (1, 8, 16) for b in BUCKETS} \
        - {(1, 32), (1, 64)}


def _check_routed(lengths, floor=ROUTED_FLOOR, buckets=BUCKETS,
                  row_state_bytes=0):
    programs = programs_under(floor, ROUTED_WIDTHS, buckets, NARROW)
    plan, capped = plan_wave(lengths, ROUTED_WIDTHS, buckets, 16,
                             row_state_bytes, floor, programs)
    assert sorted(i for rows, _, _ in plan for i in rows) \
        == list(range(len(lengths)))
    for rows, w, b in plan:
        assert (w, b) in programs
        assert len(rows) <= w and max(lengths[i] for i in rows) <= b
        assert w == 1 or (w * b <= PREFILL_MAX_TOKENS
                          and w * row_state_bytes <= PREFILL_MAX_STATE_BYTES)
    # never dearer under the new price than the parent's plan of the wave
    assert sum(program_cost(w, b, floor) for _, w, b in plan) <= sum(
        program_cost(w, b, floor)
        for _, w, b in _parent_plan_wave(lengths, [1, 8, 16], buckets, 16,
                                         row_state_bytes)[0])
    return ",".join(f"{w}x{b}" for _, w, b in plan), capped


ROUTED_CASES = [
    ("two-rows-two-buckets", [100, 300], {}, "2x512", False),
    ("four-rows-three-buckets", [100, 150, 300, 400], {}, "4x512", False),
    ("one-long-three-short", [1000, 50, 60, 70], {}, "4x256,1x1024", False),
    ("sixteen-equal", [500] * 16, {}, "16x512", False),
    ("lone-short-row-rides-the-long-program", [40], {}, "1x1024", False),
    ("three-long-rows-no-4x1024", [900] * 3, {}, "2x1024,1x1024", False),
    ("nine-rows-past-the-floor", [500] * 9, {}, "8x512,1x1024", False),
    # 16 x 4096 = 65,536 positions is over PREFILL_MAX_TOKENS: two of 8
    ("token-ceiling", [4000] * 16,
     {"buckets": LONG_BUCKETS, "floor": 1096}, "8x4096,8x4096", True),
    # 8 x 8192 is over it too, and one row each costs less anyway
    ("token-ceiling-one-row-each", [8000] * 4,
     {"buckets": LONG_BUCKETS, "floor": 1096},
     "1x8192,1x8192,1x8192,1x8192", False),
    # 16 rows x 76.4 MB of lane state is over PREFILL_MAX_STATE_BYTES
    ("state-ceiling", [500] * 16, {"row_state_bytes": 76_400_000},
     "8x512,8x512", True),
]


@pytest.mark.parametrize("lengths,kw,expect,expect_capped",
                         [c[1:] for c in ROUTED_CASES],
                         ids=[c[0] for c in ROUTED_CASES])
def test_plan_under_a_far_floor(lengths, kw, expect, expect_capped):
    got, capped = _check_routed(lengths, **kw)
    assert got == expect
    assert capped == expect_capped


@pytest.mark.parametrize("floor", [1096, ROUTED_FLOOR, 6000])
def test_random_waves_under_a_far_floor(floor):
    rng = random.Random(40)
    for _ in range(300):
        _check_routed([max(33, min(1024, int(rng.lognormvariate(5.545, 0.9))))
                       for _ in range(rng.randint(1, 20))], floor=floor)


def _walk(widths, buckets, chunk, lo, hi, **plan_kw):
    """`bench_warmup`'s walk (benchmarks/harness/replica.py): for every
    bucket a prompt of lo..hi tokens maps to and every width, `w` equal
    rows; returns each pair's plan as a list of (width, bucket)."""
    def bucket(n):
        return next(b for b in buckets if b >= n)

    return {(w, b): _shapes(plan_wave([min(b, hi)] * w, widths, buckets,
                                      chunk, **plan_kw)[0])
            for b in buckets if bucket(lo) <= b <= bucket(hi)
            for w in widths}


@pytest.mark.parametrize("lo,hi", [(33, 1024), (1, 2048)],
                         ids=["the-cell's-prompts", "every-bucket"])
def test_a_warmup_of_equal_rows_runs_every_built_program(lo, hi):
    """`w` equal rows plan into built programs only, into ONE wherever a
    program of that width holds the bucket, and the walk over widths x
    buckets leaves every built program of those buckets run."""
    walked = _walk(ROUTED_WIDTHS, BUCKETS, 16, lo, hi, floor=ROUTED_FLOOR,
                   programs=ROUTED)
    assert len(walked) == len(ROUTED_WIDTHS) * len(
        [b for b in BUCKETS if 64 <= b <= 1024] if hi == 1024 else BUCKETS)
    for (w, b), plan in walked.items():
        assert set(plan) <= set(ROUTED)
        assert sum(x for x, _ in plan) >= w
        if any(x == w and y >= b for x, y in ROUTED):
            assert len(plan) == 1 and plan[0][0] == w
    assert walked[4, 1024] == [(2, 1024)] * 2
    reach = [p for p in ROUTED if p[1] <= 1024] if hi == 1024 else ROUTED
    assert {p for plan in walked.values() for p in plan} == set(reach)
    assert len(reach) == (14 if hi == 1024 else 17)


# ----------- the cells whose plans must be the parent's, wave for wave
def _parent_plan_wave(lengths, widths, buckets, chunk, row_state_bytes=0):
    """The planner as it stood before PR 40 (flat floor, every width x
    bucket built), kept here as the yardstick."""
    order = sorted(range(len(lengths)), key=lengths.__getitem__)
    bucket_of = [next(b for b in buckets if b >= lengths[i]) for i in order]
    width_of = [0] + [next(w for w in widths if w >= g)
                      for g in range(1, chunk + 1)]
    best, beaten = [(0, 0, 0)], [False]
    for i in range(1, len(order) + 1):
        allowed, forbidden = [], []
        for g in range(1, min(chunk, i) + 1):
            over = g > 1 and (
                width_of[g] * bucket_of[i - 1] > PREFILL_MAX_TOKENS
                or width_of[g] * row_state_bytes > PREFILL_MAX_STATE_BYTES)
            (forbidden if over else allowed).append(
                (best[i - g][0] + max(width_of[g] * bucket_of[i - 1], 256),
                 best[i - g][1] + 1, g))
        best.append(min(allowed))
        beaten.append(any(c < best[i] for c in forbidden))
    plan, capped, i = [], False, len(order)
    while i:
        g = best[i][2]
        plan.append((order[i - g:i], width_of[g], bucket_of[i - 1]))
        capped |= beaten[i]
        i -= g
    return plan[::-1], capped


def _buckets_to(max_len):
    return [b for b in LONG_BUCKETS if b < max_len] + [max_len]


# name: (lanes, max_len, prompt clip, median, bytes of lane state a row,
#        (streamed, multiplied) of the served config or None)
CELL_SHAPES = {
    "mistral7b": (32, 2048, (33, 1024), 256, 0, None),
    "codestral22b": (8, 8192, (513, 4096), 1536, 0, None),
    "granite4h": (64, 2048, (33, 1024), 256, 76_437_504, None),
    "sarvam105b": (32, 9216, (4097, 8192), 6144, 0,
                   (4_924_112_896, 1_149_239_296)),
}


@pytest.mark.parametrize("cell", list(CELL_SHAPES))
def test_the_other_cells_plan_and_build_what_the_parent_did(cell):
    """The floor, the programs a `bench_warmup`-style walk runs and the
    plan of every wave of a sample of the cell's lengths are the
    parent's: for a dense model by construction (ratio 1: the parent's
    floor, widths and every program), for the latent model because its
    one bucket lies above its floor."""
    lanes, max_len, (lo, hi), median, state, streams = CELL_SHAPES[cell]
    chunk, buckets = min(16, lanes), _buckets_to(max_len)
    parent_widths = sorted(w for w in {1, 8, chunk} if w <= lanes)
    if streams is None:
        floor, widths, programs = FLOOR_TOKENS, parent_widths, None
    else:
        floor = floor_positions(*streams)
        assert floor == 1096
        widths = sorted({1, 2, 4, 8, chunk})
        programs = programs_under(floor, widths, buckets, NARROW)
        assert not [p for p in programs if p[0] in NARROW and p[1] > 512]
    kw = dict(row_state_bytes=state, floor=floor, programs=programs)
    ran = {p for plan in _walk(widths, buckets, chunk, lo, hi,
                               **kw).values() for p in plan}
    parent_ran = {p for plan in _walk(parent_widths, buckets, chunk, lo, hi,
                                      row_state_bytes=state).values()
                  for p in plan}
    assert ran == parent_ran
    rng = random.Random(4040)
    import math
    for _ in range(400):
        lengths = [max(lo, min(hi, int(rng.lognormvariate(
            math.log(median), 0.9)))) for _ in range(rng.randint(1, 2 * chunk))]
        got = plan_wave(lengths, widths, buckets, chunk, **kw)
        assert got == _parent_plan_wave(lengths, parent_widths, buckets,
                                        chunk, state)
        assert {(w, b) for _, w, b in got[0]} <= parent_ran
