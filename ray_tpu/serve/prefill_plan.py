"""Plan one prefill wave over the (width, length) programs an engine
has, and say which programs those are.  Pure host arithmetic: no jax, no
engine state."""
from __future__ import annotations

import functools

# Token positions under which a prefill program's device time stops
# falling: the program streams its weights once however few tokens it
# holds.  For a model whose every weight multiplies every position this
# follows the chip's ridge (peak FLOP/s over peak bytes/s) and not the
# model's size.  Read on a v5e at Mistral-7B widths (PERF.md section 7
# item 2): 1 x 64 12.1 ms, 1 x 128 12.7, 1 x 256 15.1, 1 x 512 29.0, and
# 0.0465 ms a position in the wide programs, at which 12.1 ms is 259
# positions.
#
# A ROUTED model streams more than a position multiplies: a program of
# ~100 positions already hits every expert a layer and the grouped
# matmul reads a hit expert whole, while a position multiplies only its
# `top_k`.  Its ridge lies `streamed / multiplied` times further out
# (`floor_positions`; the serving module counts both, `prefill_params`):
# 2,512 positions for LFM2-24B-A2B at 9 layers (5.04 B parameters
# streamed, 0.51 B multiplied), 1,096 for one chip's share of
# sarvam-105b (4.92 B / 1.15 B), and FLOOR_TOKENS itself for a dense
# model (ratio 1).  Read on a v5e for that LFM2 (the engine's own
# prefill program alone, true lengths = the bucket; my chip run, PR 40),
# ms by POSITIONS whatever the shape: 64 16.0, 128 18.2, 256 21.1,
# 512 21.3-21.7 (1 x 512, 2 x 256, 4 x 128, 8 x 64), 1,024 25.7-26.2
# (1 x 1024 ... 16 x 64), 2,048 33.5-34.0, 4,096 56.8-57.0, 8,192 110,
# 16,384 208: a pass of ~15.5 ms plus 8-12 us a position, NOT flat
# under the floor (1 x 64 to 1 x 1024 is +63 %) but additive, so one
# 2 x 512 (26.2) still replaces a 1 x 128 and a 1 x 512 (39.8), and
# the price crosses the pass near 1,300-1,500 positions: the prefill
# multiplies at about half the chip's peak.
FLOOR_TOKENS = 256

# Token positions over which no program goes unless it has ONE row: a
# program's temporaries grow with its positions (a routed layer gathers
# [positions x experts per token, dim] rows: 4.3 GB at 16 x 8192 x 8 x
# 4096), and past a few tens of thousands a wider program buys nothing
# (the floor above is long amortised).  At the widest programs the
# benchmark's dense cells reach (8 x 4096, 16 x 2048), so no plan of
# theirs changes.
PREFILL_MAX_TOKENS = 32768

# Bytes of lane state over which no program's rows hand the scatter
# program unless it has ONE row: a model whose lanes carry state beside
# the page pool returns each row's state from the prefill program, a
# temporary that lives until the scatter has written it into the lane
# (a state-space model's matrices: 76.4 MB a ROW at 36 layers x 64 heads
# x 64 x 128 float32).  What it buys, for that model whole on a 16.9 GB
# chip with 12.35 GB resident (sandbox compile for the chip, PR 39): an
# 8 x 1024 program holds 0.67 GB of temporaries and hands over 0.68 GB,
# 13.70 GB in all, and the chip's peak reads 14.07-14.68 GB with the
# decode program's own beside it; a 16 x 1024 program holds 1.32 + 1.36
# GB, 15.03 GB in all, which the same 0.4-1.0 GB on top puts at
# 15.4-16.0 GB: over the 15.5 GB a deployment is sized to.  So that
# model's widest program is 8 rows (and it builds 5 programs fewer, ~10 s
# each at 40 layers).  A model without such state gives 0 bytes a row and
# no plan of its changes.
PREFILL_MAX_STATE_BYTES = 1024 * 1024 * 1024


def floor_positions(streamed: int, multiplied: int) -> int:
    """The floor for a model whose prefill program reads `streamed`
    matmul parameters whatever it holds and whose every position
    multiplies `multiplied` of them: FLOOR_TOKENS where the two are one
    number (every weight multiplies every position)."""
    return FLOOR_TOKENS * streamed // multiplied


def program_cost(width: int, bucket: int, floor: int = FLOOR_TOKENS) -> int:
    """Token positions a (width, bucket) prefill program is charged."""
    return max(width * bucket, floor)


def programs_under(floor: int, widths: list[int], buckets: list[int],
                   narrow: frozenset = frozenset()) -> tuple:
    """The (width, bucket) programs an engine builds for a model whose
    floor lies far above its buckets, where a program of two rows costs
    what one of one row does.  A width of `narrow` exists only where it
    is free to hold its rows (width x bucket <= floor; beyond, the next
    width serves); and of one width's buckets that lie well under the
    floor (width x bucket <= floor / 2) only the longest is built: it
    holds whatever the shorter ones would, for the same pass.  Fewer
    programs to build, and every group of rows still has a program: the
    longest bucket of every other width is always built."""
    out = []
    for w in widths:
        own = [b for b in buckets if w not in narrow or w * b <= floor]
        longest_free = max((b for b in own if 2 * w * b <= floor), default=0)
        out += [(w, b) for b in own if b >= longest_free]
    return tuple(out)


@functools.lru_cache(maxsize=64)
def _holders(programs: tuple, buckets: tuple, chunk: int, floor: int,
             row_state_bytes: int, max_tokens: int, max_state: int) -> dict:
    """(rows of a group, their longest row's bucket) -> (the cheapest
    program the ceilings allow that holds the group as (cost, width,
    bucket), or None; what the cheapest would cost without the ceilings,
    or None where nothing holds it).  Among programs of one cost the one
    of fewest positions, then of fewest rows: padding ROWS repeat a live
    row and are computed whole.  Everything the table depends on is an
    argument, the two ceilings too: the cache keys on them."""
    table = {}
    for g in range(1, chunk + 1):
        for b0 in buckets:
            held = sorted((program_cost(w, b, floor), w * b, w, b)
                          for w, b in programs if w >= g and b >= b0)
            ok = next(((c, w, b) for c, n, w, b in held if w == 1 or (
                n <= max_tokens and w * row_state_bytes <= max_state)), None)
            table[g, b0] = (ok, held[0][0] if held else None)
    return table


def plan_wave(lengths: list[int], widths: list[int], buckets: list[int],
              chunk: int, row_state_bytes: int = 0,
              floor: int = FLOOR_TOKENS, programs: tuple | None = None
              ) -> tuple[list[tuple[list[int], int, int]], bool]:
    """Partition a wave's rows into prefill programs of least total cost.

    `lengths[i]` is the token count row i's prefill pads (prompt, or the
    uncached suffix); `widths` and `buckets` are the engine's ascending
    width and length buckets; no group exceeds `chunk` rows; `floor` is
    what a program is charged at least (`program_cost`); `programs` are
    the (width, bucket) pairs the engine builds (default: all of widths
    x buckets).  Rows are ordered by length and cut into contiguous
    groups; a group runs in the cheapest built program that holds it
    (with every program built: the smallest width that holds it and the
    length bucket of its longest row), so no program lies outside
    `programs`; a program of more than one row that would hold more than
    PREFILL_MAX_TOKENS positions, or whose rows would hand over more
    than PREFILL_MAX_STATE_BYTES of lane state (`row_state_bytes` a row,
    padding rows counted: the program returns theirs too), is not used.
    Ties go to fewer programs: `w` equal rows stay ONE w-wide program
    where one is built (what a warm-up that submits exactly that relies
    on).
    Returns the plan, (row indices, width, bucket) per program, shortest
    rows first, and whether a ceiling shaped it: whether a group of the
    plan stands where a forbidden one would have been taken."""
    order = sorted(range(len(lengths)), key=lengths.__getitem__)
    bucket_of = [next(b for b in buckets if b >= lengths[i]) for i in order]
    if programs is None:
        programs = tuple((w, b) for w in widths for b in buckets)
    holder = _holders(programs, tuple(buckets), chunk, floor,
                      row_state_bytes, PREFILL_MAX_TOKENS,
                      PREFILL_MAX_STATE_BYTES)
    # best[i] = (cost, programs, size of the last group) over rows [0, i);
    # beaten[i]: a group the ceiling forbids would have been taken there
    best, beaten = [(0, 0, 0)], [False]
    for i in range(1, len(order) + 1):
        allowed, forbidden = [], []
        for g in range(1, min(chunk, i) + 1):
            ok, free = holder[g, bucket_of[i - 1]]
            cost, n = best[i - g][0], best[i - g][1] + 1
            if ok is not None:
                allowed.append((cost + ok[0], n, g))
            if free is not None and (ok is None or free < ok[0]):
                forbidden.append((cost + free, n, g))
        best.append(min(allowed))
        beaten.append(any(c < best[i] for c in forbidden))
    plan, capped, i = [], False, len(order)
    while i:
        g = best[i][2]
        _, w, b = holder[g, bucket_of[i - 1]][0]
        plan.append((order[i - g:i], w, b))
        capped |= beaten[i]
        i -= g
    return plan[::-1], capped
