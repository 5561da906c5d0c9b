"""Model zoo: TPU-first functional implementations (pure param pytrees +
jit-able apply functions; no framework lock-in, shardings are declared as
logical-axes pytrees consumed by ray_tpu.parallel)."""
import importlib

from ray_tpu.models.llama import (LlamaConfig, llama_configs, init_params,
                                  forward, loss_fn, param_logical_axes)
from ray_tpu.models.resnet import ResNetConfig, resnet_configs
from ray_tpu.models.vit import ViTConfig, vit_configs

# The serving seam: which module serves a config, keyed on the config's
# type (by name, so nothing is imported until asked for).  serve/llm.py
# asks here and names no model itself.  Every serving module gives, under
# ONE signature each:
#   init_params(key, cfg); init_paged_cache(cfg, batch, n_pages, page) ->
#     {"pos", "state", and the page pool under names of the model's own}:
#     each pool entry a list of [n_pages, heads, rows, width] leaves, one
#     a layer that keeps rows ({"k", "v"}: a K and a V pool; {"latent"}:
#     one row a token that every head shares; {"latent", "index"}: that
#     beside a pooled index key a GROUP of positions), from which the
#     engine takes its tails' and merges' shapes: `rows` is the page
#     size where a leaf holds a row a token, and page size / g where a
#     row covers g positions (its tail then holds the rows a window's
#     positions COMPLETE, and the model's step writes a row when its
#     token completes one); and `state`, a pytree of
#     whatever a lane carries that no page holds (an empty list if
#     nothing; a few rows a lane; or gigabytes: a state-space layer's
#     matrices, every lane's in one array), which the engine never looks
#     inside, allocates once, donates through the scatter and decode
#     programs and never copies or selects over: the module's scatter
#     writes a row's state where the lanes' state lies, and its decode
#     step updates it in place (a kernel that aliases it).  A dict's keys
#     are the kinds `stats()["lane_state"]["by_kind"]` reports;
#   serve_prefill(params, tokens, cfg, true_lens, lora) -> (hidden, ks,
#     vs, state taken at each row's TRUE length, counts); ks and vs are
#     the rows for the pool, handed unopened to serve_scatter (a latent
#     pool's rows and an empty list);
#   serve_scatter(cache, ks, vs, state, page_ids, rows, slots, true_lens,
#     aligned=True) -> cache;
#   serve_decode_step(params, pages, tails, state, tokens, pos,
#     tail_start, j, page_table, cfg, lora, plan) -> (logits, tails,
#     state, counts); `plan` is the window's
#     ops.paged_attention.attention_plan, built once by the engine;
#   project_logits(params, h); lane_state_layers(cfg) (0: the prefix
#     cache may stay on); optionally, for state that a chunked scan
#     fills and a one-step kernel updates: scan_chunk(cfg), the scan's
#     chunk (the engine's `ssm_lane_steps` and `prefill_scan_chunks`
#     counters) and prefill_state_bytes(cfg) (the state ONE prefill row
#     hands the scatter: the wave planner bounds a program's width by
#     it); selection(cfg) (a model whose attention reads its pool through
#     a learned selection: (layers that do, positions a pooled index key,
#     the selection's size in tokens), from which the engine counts
#     `dsa_rows_context`, `dsa_groups_scored`, `dsa_rows_selected`);
#     prefill_params(cfg) (a model whose prefill program reads
#     weights a position does not multiply, a routed layer's experts:
#     the matmul parameters a program STREAMS whatever it holds and
#     those ONE position multiplies; the planner's floor and the
#     programs the engine builds follow their ratio; without it the
#     ratio is 1); routed_layers(cfg): the rows of `counts`, int32
#     [routed layers, 4] = experts that held a row, the largest load,
#     assignments computed, visits of the grouped matmul that were work
#     (0 rows: nothing is counted; a config with routed layers has
#     `top_k`, and its module `routed_visits(cfg, rows)`: the length of
#     the visit list a routed layer pads for a program of `rows` rows);
#     `CACHE_KIND`, the word
#     stats()["cache"]["kind"] gives for the pool ("kv": K and V rows);
# and `SERVING_CAPS`: the optional capabilities it has, under their own
# names ("prefix": prefill_with_prefix; "lora": the adapter hooks;
# "kv_transfer": KV export/import/graft).
_SERVING = {"LlamaConfig": "ray_tpu.models.llama",
            "Lfm2MoeConfig": "ray_tpu.models.lfm2",
            "MlaMoeConfig": "ray_tpu.models.mla_moe",
            "SsmHybridConfig": "ray_tpu.models.ssm_hybrid",
            "Glm5NextConfig": "ray_tpu.models.glm5_next"}


def serving_model(cfg):
    """The module that serves `cfg` through serve/llm.LLMEngine (a
    subclass of a served config type is served by its base's module)."""
    for klass in type(cfg).__mro__:
        if klass.__name__ in _SERVING:
            return importlib.import_module(_SERVING[klass.__name__])
    raise TypeError(f"no serving model for a {type(cfg).__name__}; the "
                    f"engine serves {sorted(_SERVING)}")


def named_config(name: str):
    """A preset config by name (the `LLMServer(model="debug")` form)."""
    for mod in _SERVING.values():
        presets = importlib.import_module(mod).serving_configs()
        if name in presets:
            return presets[name]
    raise KeyError(f"no preset model config {name!r}")


__all__ = ["LlamaConfig", "llama_configs", "init_params", "forward",
           "loss_fn", "param_logical_axes", "serving_model", "named_config",
           "ResNetConfig", "resnet_configs",
           "ViTConfig", "vit_configs"]
