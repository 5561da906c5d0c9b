"""The serving seam: what a model module gives `serve/llm.LLMEngine`.
`ray_tpu.models._SERVING` says which module serves a config type; the
engine asks there, reads the module's ONE declaration (`serving_spec(cfg)
-> ServingSpec`) once, and names no model and none of their kernels
itself.  No jax is imported here.

Beside `serving_spec` and `serving_configs()` (its presets by name) a
serving module gives, under ONE signature each:
  init_params(key, cfg); init_paged_cache(cfg, batch, n_pages, page) ->
    {"pos", "state", and the page pool under names of the model's own}:
    each pool entry a list of [n_pages, heads, rows, width] leaves, one
    a layer that keeps rows ({"k", "v"}: a K and a V pool; {"latent"}:
    one row a token that every head shares; {"latent", "index"}: that
    beside a pooled index key a GROUP of positions), from which the
    engine takes its tails' and merges' shapes and the word
    stats()["cache"]["kind"] gives ("kv" for a K and a V pool, else the
    first entry's name): `rows` is the page size where a leaf holds a
    row a token, and page size / g where a row covers g positions (its
    tail then holds the rows a window's positions COMPLETE, and the
    model's step writes a row when its token completes one); and
    `state`, a pytree of whatever a lane carries that no page holds (an
    empty list if nothing; a few rows a lane; or gigabytes: a
    state-space layer's matrices, every lane's in one array), which the
    engine never looks inside, allocates once, donates through the
    scatter and decode programs and never copies or selects over: the
    module's scatter writes a row's state where the lanes' state lies,
    and its decode step updates it in place (a kernel that aliases it).
    A dict's keys are the kinds `stats()["lane_state"]["by_kind"]`
    reports;
  serve_prefill(params, tokens, cfg, true_lens, lora) -> (hidden, ks,
    vs, state taken at each row's TRUE length, counts); ks and vs are
    the rows for the pool, handed unopened to serve_scatter (a latent
    pool's rows and an empty list);
  serve_scatter(cache, ks, vs, state, page_ids, rows, slots, true_lens,
    aligned=True) -> cache;
  serve_decode_step(params, pages, tails, state, tokens, pos,
    tail_start, j, page_table, cfg, lora, plan) -> (logits, tails,
    state, counts); `plan` is the window's
    ops.paged_attention.attention_plan, built once by the engine;
  project_logits(params, h);
and, with a capability of `caps`, its hooks: "prefix":
prefill_with_prefix; "lora": LORA_TARGETS, lora_target_dims(cfg) and
the adapter arguments of the programs above; "kv_transfer": a K and a V
pool the engine's KV export/import/graft programs read and write.
"""
from __future__ import annotations

import dataclasses
from collections.abc import Callable, Mapping


def no_work(*_args) -> tuple[dict, dict]:
    return {}, {}


def merged(*pairs) -> tuple[dict, dict]:
    """One (work, shown) of several kernels' (a family with more than
    one kind of layer)."""
    work, shown = {}, {}
    for w, s in pairs:
        work.update(w)
        shown.update(s)
    return work, shown


@dataclasses.dataclass(frozen=True)
class ServingSpec:
    """One served config's facts, as the engine and the wave planner
    read them; a field left out is what a dense decoder has."""
    # the optional capabilities, under their own names (the engine
    # refuses at construction what a model lacks)
    caps: frozenset = frozenset()
    # layers whose per-lane state no page holds (0: the prefix cache may
    # stay on)
    lane_state_layers: int = 0
    # bytes of that state ONE prefill row hands the scatter program: the
    # wave planner bounds a program's width by it
    prefill_state_bytes: int = 0
    # a model whose prefill program reads weights a position does not
    # multiply (a routed layer's experts): the matmul parameters a
    # program STREAMS whatever it holds and those ONE position
    # multiplies; the planner's floor and the programs the engine builds
    # follow their ratio (None: the ratio is 1)
    prefill_params: tuple[int, int] | None = None
    # the rows of the programs' `counts` (int32 [routed layers, columns
    # of the model's own]; 0 rows: nothing is fetched): the engine
    # fetches them in the sync of the tokens they belong to and hands
    # them to `routed_work` unread
    routed_layers: int = 0
    # the work counters the family reports, name -> help text: each a
    # key of stats()["loop"] and a Prometheus counter serve_llm_<name>.
    # The three functions below are host arithmetic (no jax call) and
    # return (work, shown): {counter name: what to add} and the
    # attributes the phase's span shows for it.
    counters: Mapping[str, str] = dataclasses.field(default_factory=dict)
    # decode_work(rows, K, page, maxp): one decode window of K steps over
    # the live lanes, `rows` their cached rows at its start, under the
    # engine's table of `maxp` columns of `page` rows (span
    # llm.loop.decode_dispatch)
    decode_work: Callable[..., tuple[dict, dict]] = no_work
    # prefill_work(true_lens, bucket): one full-prompt prefill program
    # of len(true_lens) rows padded to `bucket` (span
    # llm.loop.prefill_dispatch, summed over the wave's programs)
    prefill_work: Callable[..., tuple[dict, dict]] = no_work
    # routed_work(counts, steps, rows, shape_rows, prefill): a program's
    # `counts` on the host, summed over its `steps`; `rows` it routed a
    # layer over those steps, `shape_rows` a step is shaped for (span
    # llm.loop.deliver for a decode window; a prefill program's shows
    # nothing)
    routed_work: Callable[..., tuple[dict, dict]] = no_work
