"""One decode step's sparse attention in both forms (`ops/sparse_attention.
decode_attend`: the walk over a lane's pages under a bias, and `select_rows`
+ `dsa_decode_attention`) on the same inputs, beside an oracle that shares
nothing with either: the set a sort of the scores names and a masked
softmax over it.  Shared by tests/test_glm5_next.py (groups of 4) and
tests/test_dots3_note.py (a key a token, the own row forced)."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ray_tpu.ops import paged_attention, sparse_attention as dsa, ssm

CASES = ("sparse", "dense", "held", "ties", "cut_page")
B, H, DK, DV, PAGE, MAXP, K, J, WI = 4, 4, 24, 16, 16, 5, 8, 2, 8
LOUD = 1.0e3        # a row no query may attend: one leak moves the output


def _inputs(case: str, group: int, top: int, seed: int):
    """Lane 1 holds no request (its table row starts at the trash page).
    Returns (arrays for `decode_select` / `decode_attend`, scores [B, G +
    R] the indexer is made to return)."""
    rng = np.random.default_rng(seed)
    R = -(-K // group)
    G = MAXP * PAGE // group
    # block starts: past the selection's size, idle, a group cut by the
    # start, the table's last page partly below it
    ts = np.array([60, 0, 37, 71], np.int32)
    if case == "dense":
        ts = np.array([9, 0, 3, 0], np.int32)
    if case == "cut_page":
        ts = np.array([65, 0, 17, 79], np.int32)    # a page's first rows
    pos = ts + np.array([3, 0, 5, 7], np.int32)
    table = np.arange(1, 1 + B * MAXP, dtype=np.int32).reshape(B, MAXP)
    table[1] = 0
    pool = rng.normal(size=(1 + B * MAXP, 1, PAGE, DK)).astype(np.float32)
    tail = rng.normal(size=(B, 1, K, DK)).astype(np.float32)
    for b in range(B):      # what lies at or past a lane's cut is LOUD
        for p in range(MAXP * PAGE):
            if p >= ts[b]:
                pool[table[b, p // PAGE], 0, p % PAGE] = LOUD
        tail[b, 0, pos[b] - ts[b] + 1:] = LOUD
    pool[0] = LOUD
    scores = rng.normal(size=(B, G + R)).astype(np.float32)
    if case == "ties":
        scores = np.round(scores)           # a handful of values: ties at kth
    if case == "held":
        scores[:, G:] += 50.0               # every complete tail group chosen
    q = rng.normal(size=(B, H, DK)).astype(np.float32)
    arrays = dict(
        q=q, pool=pool, tail=tail, table=table, pos=pos, ts=ts,
        idx_pool=np.zeros((1 + B * MAXP, 1, PAGE // group, WI), np.float32),
        idx_tail=np.zeros((B, 1, R, WI), np.float32),
        qi=np.zeros((B, J, WI), np.float32), w=np.ones((B, J), np.float32))
    return {k: jnp.asarray(v) for k, v in arrays.items()}, scores


def oracle_rows(scores, ts: int, pos: int, group: int, top: int, own: bool
                ) -> list[int]:
    """The positions one query attends: those of the `top // group` best
    complete groups (of equal scores the lower first; with `own` the
    query's own row whatever its score) and of its own incomplete group,
    none past the query."""
    G = MAXP * PAGE // group
    g0 = ts // group
    number = [g for g in range(G)] + [g0 + r for r in range(len(scores) - G)]
    complete = [i for i, g in enumerate(number)
                if (g < g0 if i < G else (g + 1) * group - 1 <= pos)]
    key = {i: (-np.inf if own and i >= G and number[i] * group == pos
               else -scores[i], i) for i in complete}
    best = sorted(complete, key=key.get)[:top // group]
    rows = {number[i] * group + d for i in best for d in range(group)}
    rows |= set(range((pos + 1) // group * group, pos + 1))
    return sorted(r for r in rows if r <= pos)


def run(case: str, group: int, top: int, own: bool, monkeypatch, seed=0):
    """Both forms and the oracle on one case.  Returns {form: (o [B, H,
    DV], [the sorted positions lane b attends])}, form in ("walk",
    "gather", "oracle")."""
    a, scores = _inputs(case, group, top, seed)
    monkeypatch.setattr(dsa, "index_scores", lambda q, w, kbar: jnp.asarray(
        scores)[:, None, :])
    groups, ok, chosen = dsa.decode_select(
        a["qi"], a["w"], a["idx_pool"], a["idx_tail"], a["table"], a["pos"],
        a["ts"], group, top, own=own)
    lanes, count = ssm.live_lanes(paged_attention.lanes_live(a["table"]))
    out = {}
    for form, ratio in (("walk", 10 ** 6), ("gather", 0)):
        monkeypatch.setattr(dsa, "RATIO", ratio)
        o, rpos, admit = dsa.decode_attend(
            a["q"], a["pool"], a["tail"], a["table"], a["pos"], a["ts"],
            groups, ok, chosen, lanes, count, group=group, dv=DV,
            sm_scale=0.3)
        rpos, admit = np.asarray(rpos), np.asarray(admit)
        out[form] = (np.asarray(o), [sorted(rpos[b][admit[b]].tolist())
                                     for b in range(B)])
    ts, pos, table = (np.asarray(a[k]) for k in ("ts", "pos", "table"))
    want, sets = np.zeros((B, H, DV), np.float32), []
    for b in range(B):
        rows = oracle_rows(scores[b], int(ts[b]), int(pos[b]), group, top,
                           own) if table[b, 0] else []
        sets.append(rows)
        if not rows:
            continue
        kv = np.stack([np.asarray(a["tail"])[b, 0, r - ts[b]] if r >= ts[b]
                       else np.asarray(a["pool"])[table[b, r // PAGE], 0,
                                                  r % PAGE] for r in rows])
        s = np.asarray(a["q"])[b] @ kv.T * 0.3
        p = np.exp(s - s.max(-1, keepdims=True))
        want[b] = (p / p.sum(-1, keepdims=True)) @ kv[:, :DV]
    out["oracle"] = (want, sets)
    return out


def check(case: str, group: int, top: int, own: bool, monkeypatch):
    """The admitted SETS equal position for position (a gathered group
    nobody chose repeats a position: a set drops it), the outputs equal,
    the idle lane reads 0."""
    out = run(case, group, top, own, monkeypatch)
    (ow, sw), (og, sg), (oo, so) = (out[k] for k in
                                    ("walk", "gather", "oracle"))
    for b in (0, 2, 3):
        assert sw[b] == sorted(set(sg[b])) == so[b], (case, b)
        assert len(sw[b]) == len(set(sw[b]))
    assert np.abs(ow - og).max() < 1e-5 and np.abs(ow - oo).max() < 1e-5
    assert np.abs(ow[1]).max() == 0.0 and np.abs(og[1]).max() == 0.0
    return out
