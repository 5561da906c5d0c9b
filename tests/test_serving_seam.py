"""The serving seam (PR 44): a served model is ONE declaration,
`serving_spec(cfg) -> models/serving.ServingSpec`, and the engine adds up
what the model's own arithmetic counts.  A family the engine has never
heard of is served, with a work counter of its own, by `serve/llm.py` as
it stands.  CPU, the smallest preset of each family, no cluster.
"""
import dataclasses
import functools
import io
import sys
import tokenize
import types

import numpy as np
import pytest

from ray_tpu import models
from ray_tpu.models import (ServingSpec, llama, named_config, routed,
                            serving_model)
from ray_tpu.serve import llm
from ray_tpu.serve.llm import LLMEngine

PRESETS = ("debug", "lfm2-debug", "mla-debug", "ssm-hybrid-debug",
           "glm5-next-debug", "dots3-note-debug", "nemotron-h-debug",
           "mimo-v2-debug", "cohere2-moe-debug", "solar-open2-debug")


def _spec(preset):
    cfg = named_config(preset)
    return cfg, serving_model(cfg).serving_spec(cfg)


def _reported(spec) -> set:
    """The counters the spec's three functions return, on any input."""
    names = set(spec.decode_work([5, 20], 4, 16, 4)[0])
    names |= set(spec.prefill_work(np.array([9, 17]), 32)[0])
    if spec.routed_layers:
        counts = np.ones((spec.routed_layers, routed.COUNTS), np.int32)
        for prefill in (False, True):
            names |= set(spec.routed_work(counts, 4, 8, 4, prefill)[0])
    return names


@functools.cache
def _family_names() -> frozenset:
    """Every work counter any of the served families declares."""
    return frozenset().union(*(_spec(p)[1].counters for p in PRESETS))


@pytest.mark.parametrize("preset", PRESETS)
def test_a_family_reports_what_its_spec_declares(preset):
    cfg, spec = _spec(preset)
    assert isinstance(spec, ServingSpec)
    assert all(isinstance(text, str) and text
               for text in spec.counters.values())
    # the functions and the table name the same counters
    assert _reported(spec) == set(spec.counters)
    eng = LLMEngine(cfg, max_batch=2, max_len=64, page_size=16)
    loop = eng.stats()["loop"]
    assert set(loop) & _family_names() == set(spec.counters)
    assert set(llm._LOOP_WORK) <= set(loop)
    assert set(eng.work) == set(llm._LOOP_WORK) | set(spec.counters)


def test_no_flash_counter_for_a_prefill_that_never_calls_flash():
    assert all("prefill_attn_blocks" in _spec(p)[1].counters
               for p in PRESETS[:4])
    assert "prefill_attn_blocks" not in _spec("glm5-next-debug")[1].counters
    # a window layer's prefill IS `flash_fwd`, under a band
    assert {"prefill_attn_blocks", "prefill_swa_blocks"} <= set(
        _spec("dots3-note-debug")[1].counters)


@dataclasses.dataclass(frozen=True)
class SixthConfig(llama.LlamaConfig):
    """A family `serve/llm.py` has never heard of."""


def _sixth_spec(cfg):
    base = llama.serving_spec(cfg)

    def decode_work(rows, k, page, maxp):
        work = {"sixth_lane_windows": len(rows)}
        return work, work

    return dataclasses.replace(
        base, counters={**base.counters,
                        "sixth_lane_windows": "Live lanes, summed over "
                                              "decode windows"},
        decode_work=decode_work)


def test_a_sixth_family_is_served_by_an_unedited_engine(monkeypatch):
    from ray_tpu import tracing

    sixth = types.ModuleType("sixth_family")
    vars(sixth).update(vars(llama), serving_spec=_sixth_spec)
    monkeypatch.setitem(sys.modules, "sixth_family", sixth)
    monkeypatch.setitem(models._SERVING, "SixthConfig", "sixth_family")
    cfg = SixthConfig(**dataclasses.asdict(named_config("debug")))
    assert serving_model(cfg) is sixth

    eng = LLMEngine(cfg, max_batch=2, max_len=64, page_size=16,
                    steps_per_sync=4, name="sixth")
    eng.start()
    try:
        out = eng.submit(list(range(1, 12)),
                         max_new_tokens=9).result(timeout=120.0)
        st = eng.stats()                # forces a metrics flush
    finally:
        eng.stop()
    assert len(out["tokens"]) == 9
    windows = st["loop"]["decode_steps"] // 4
    assert st["loop"]["sixth_lane_windows"] == windows == 2
    spans = [s for s in tracing.snapshot()
             if s["name"] == "llm.loop.decode_dispatch"
             and s["tid"] == eng._loop_trace[0]]
    assert [s["attrs"]["sixth_lane_windows"] for s in spans] == [1, 1]
    counter = llm._engine_metrics()["sixth_lane_windows"].snapshot()
    assert counter["name"] == "serve_llm_sixth_lane_windows"
    assert [v["value"] for v in counter["values"]
            if v["tags"]["engine"] == "sixth"] == [windows]


def _code_tokens(path):
    """The file's tokens, comments and docstrings aside."""
    with open(path) as f:
        toks = list(tokenize.generate_tokens(io.StringIO(f.read()).readline))
    skip = (tokenize.NL, tokenize.COMMENT)
    starts = (tokenize.NEWLINE, tokenize.INDENT, tokenize.DEDENT,
              tokenize.ENCODING)
    out, prev = [], tokenize.NEWLINE
    for t in toks:
        if t.type in skip:
            continue
        if not (t.type == tokenize.STRING and prev in starts):
            out.append(t.string)
        prev = t.type
    return out


@pytest.mark.parametrize("words", [
    ("mimo", "kv_ring", "swa_"),
    ("cohere", "command", "ring_plan", "layernorm")],
    ids=["eighth", "ninth"])
def test_no_line_under_serve_names_the_newest_families(words):
    """PR 52's family (window K/V rings with a sink beside pages of
    another kv-head count) and PR 54's (a parallel block under one
    LayerNorm, rings of 4,096 rows walked in blocks, a tied head) are
    served by `ray_tpu/serve/` as it stood."""
    import os

    root = os.path.dirname(llm.__file__)
    for dirpath, _, files in os.walk(root):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(dirpath, name),
                          encoding="utf-8") as f:
                    text = f.read().lower()
                for word in words:
                    assert word not in text, (name, word)


def test_the_engine_names_no_family_and_none_of_their_kernels():
    code = _code_tokens(llm.__file__)
    for word in ("ssm", "dsa_", "selection", "scan_chunk", "top_k",
                 "flash_attention", "sparse_attention"):
        assert [t for t in code if word in t] == [], word
    probes = [(a, c) for a, b, c in zip(code, code[1:], code[2:])
              if a in ("getattr", "hasattr") and b == "("
              and c in ("model", "served_by")]
    assert probes == []
