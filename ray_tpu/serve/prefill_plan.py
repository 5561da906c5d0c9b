"""Plan one prefill wave over the (width, length) programs an engine
already has.  Pure host arithmetic: no jax, no engine state."""
from __future__ import annotations

# Token positions under which a prefill program's device time stops
# falling: the program streams every weight once however few tokens it
# holds.  Read on a v5e at Mistral-7B widths (PERF.md §7 item 2): 1 x 64
# 12.1 ms, 1 x 128 12.7, 1 x 256 15.1, 1 x 512 29.0, and 0.0465 ms a
# position in the wide programs, at which 12.1 ms is 259 positions.  In
# token positions, so it follows the chip's ridge (peak FLOP/s over peak
# bytes/s), not the model's size.
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


def program_cost(width: int, bucket: int) -> int:
    """Token positions a (width, bucket) prefill program is charged."""
    return max(width * bucket, FLOOR_TOKENS)


def plan_wave(lengths: list[int], widths: list[int], buckets: list[int],
              chunk: int, row_state_bytes: int = 0
              ) -> tuple[list[tuple[list[int], int, int]], bool]:
    """Partition a wave's rows into prefill programs of least total cost.

    `lengths[i]` is the token count row i's prefill pads (prompt, or the
    uncached suffix); `widths` and `buckets` are the engine's ascending
    width and length buckets; no group exceeds `chunk` rows.  Rows are
    ordered by length and cut into contiguous groups; a group runs at
    the smallest width that holds it and the length bucket of its
    longest row, so no program lies outside widths x buckets or outside
    the span of the rows' own buckets; a group of more than one row whose
    program would hold more than PREFILL_MAX_TOKENS positions, or whose
    rows would hand over more than PREFILL_MAX_STATE_BYTES of lane state
    (`row_state_bytes` a row, padding rows counted: the program returns
    theirs too), is not formed.  Ties go to fewer programs: `w` equal
    rows stay ONE w-wide program (what a warm-up that submits exactly
    that relies on).
    Returns the plan, (row indices, width, bucket) per program, shortest
    first, and whether a ceiling shaped it: whether a group of the plan
    stands where a forbidden one would have been taken."""
    order = sorted(range(len(lengths)), key=lengths.__getitem__)
    bucket_of = [next(b for b in buckets if b >= lengths[i]) for i in order]
    width_of = [0] + [next(w for w in widths if w >= g)
                      for g in range(1, chunk + 1)]
    # best[i] = (cost, programs, size of the last group) over rows [0, i);
    # beaten[i]: a group the ceiling forbids would have been taken there
    best, beaten = [(0, 0, 0)], [False]
    for i in range(1, len(order) + 1):
        allowed, forbidden = [], []
        for g in range(1, min(chunk, i) + 1):
            over = g > 1 and (
                width_of[g] * bucket_of[i - 1] > PREFILL_MAX_TOKENS
                or width_of[g] * row_state_bytes > PREFILL_MAX_STATE_BYTES)
            (forbidden if over else allowed).append(
                (best[i - g][0] + program_cost(width_of[g], bucket_of[i - 1]),
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
