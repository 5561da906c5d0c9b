"""Library-layering invariant as a checked test (ISSUE 5 satellite).

CLAUDE.md: "Every library feature (data/train/tune/serve/rl) builds ONLY
on core primitives (tasks/actors/objects/PGs/KV) — never on runtime
internals."  This walks the import statements of every module in the
library layers (plus `collective`, which round 10 rebuilt as pure
library code) and fails on any `ray_tpu._private` import beyond the
sanctioned facades.  Static AST scan — no imports executed, so a
violation can't hide behind lazy/function-local imports either (those
are scanned too).
"""
import ast
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "ray_tpu")

LIBRARY_LAYERS = ("data", "train", "tune", "serve", "rl", "collective")

# The runtime-internal modules library code may import: none.
# Everything must come through public surfaces: the ray_tpu core
# API, ray_tpu.profiling, ray_tpu.failpoints, ray_tpu.exceptions, ...
SANCTIONED: set[str] = set()


def _imports_of(path: str):
    """Every (module, lineno) imported anywhere in the file, including
    inside functions (lazy imports are still layering violations)."""
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), filename=path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield alias.name, node.lineno
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            mod = node.module or ""
            yield mod, node.lineno
            # `from ray_tpu import _private` smuggles the package in
            # under a from-import; flag the combined path too.
            for alias in node.names:
                yield f"{mod}.{alias.name}", node.lineno


def _violations():
    out = []
    for layer in LIBRARY_LAYERS:
        root = os.path.join(PKG, layer)
        assert os.path.isdir(root), root
        for dirpath, _dirs, files in os.walk(root):
            if "__pycache__" in dirpath:
                continue
            for fname in files:
                if not fname.endswith(".py"):
                    continue
                path = os.path.join(dirpath, fname)
                rel = os.path.relpath(path, REPO)
                for mod, lineno in _imports_of(path):
                    if not (mod == "ray_tpu._private"
                            or mod.startswith("ray_tpu._private.")):
                        continue
                    if mod in SANCTIONED:
                        continue
                    # a from-import of a sanctioned module's name
                    # yields "<module>.<name>" — still sanctioned.
                    if any(mod.startswith(s + ".") for s in SANCTIONED):
                        continue
                    out.append(f"{rel}:{lineno}: imports {mod}")
    return out


def test_library_layers_never_import_runtime_internals():
    violations = _violations()
    assert not violations, (
        "library-layering invariant violated (CLAUDE.md): library code "
        "must build on core primitives and public facades only —\n  "
        + "\n  ".join(violations))


def test_sanctioned_facades_exist():
    """A stale sanction (module renamed away) must fail loudly, not
    silently allow-list nothing."""
    for mod in SANCTIONED:
        rel = mod.replace(".", os.sep) + ".py"
        assert os.path.exists(os.path.join(REPO, rel)), mod


@pytest.mark.parametrize("mod", ["ray_tpu.collective",
                                 "ray_tpu.collective.ring"])
def test_collective_is_importable_standalone(mod):
    """The rebuilt collective layer imports cleanly (its only runtime
    coupling is the lazily-bound public facade surface)."""
    import importlib

    assert importlib.import_module(mod) is not None


# ------------------------------------------------------- RLHF modules
RLHF_MODULES = ("rl/rlhf.py", "rl/rollout_llm.py")

# The rlhf subsystem's sanctioned surfaces: the core API (bare ray_tpu
# / object_ref / exceptions), public facades (failpoints), and sibling
# LIBRARY layers (collective, the serve engine, models/ops, train's
# checkpoint, utils.metrics, parallel's sharding rules).  Anything
# else — above all _private — is a layering regression.
RLHF_ALLOWED_PREFIXES = (
    "ray_tpu.collective", "ray_tpu.models", "ray_tpu.ops",
    "ray_tpu.serve", "ray_tpu.rl", "ray_tpu.train.checkpoint",
    "ray_tpu.utils", "ray_tpu.parallel", "ray_tpu.failpoints",
    "ray_tpu.tracing", "ray_tpu.object_ref", "ray_tpu.exceptions",
)


def test_rlhf_modules_are_walked_by_the_layering_scan():
    """The new rlhf modules live under rl/ — prove the AST walk really
    covers them (a file the scan misses can't be kept honest)."""
    for rel in RLHF_MODULES:
        path = os.path.join(PKG, rel)
        assert os.path.exists(path), path
        assert list(_imports_of(path)), f"no imports parsed in {rel}?"


def test_rlhf_modules_import_only_core_and_public_facades():
    """Stricter than the _private ban: every ray_tpu import in the
    rlhf modules must be the core API or a sanctioned public/library
    surface (the ISSUE 9 satellite contract)."""
    bad = []
    for rel in RLHF_MODULES:
        path = os.path.join(PKG, rel)
        for mod, lineno in _imports_of(path):
            if not (mod == "ray_tpu" or mod.startswith("ray_tpu.")):
                continue
            if mod == "ray_tpu" or any(
                    mod == p or mod.startswith(p + ".")
                    for p in RLHF_ALLOWED_PREFIXES):
                continue
            # from ray_tpu import collective, failpoints → combined
            # paths like "ray_tpu.collective" are handled above; a
            # bare `from ray_tpu import X` also yields "ray_tpu.X".
            bad.append(f"ray_tpu/{rel}:{lineno}: imports {mod}")
    assert not bad, (
        "rlhf modules must build on core primitives and public "
        "facades only —\n  " + "\n  ".join(bad))


@pytest.mark.parametrize("mod", ["ray_tpu.rl.rlhf",
                                 "ray_tpu.rl.rollout_llm"])
def test_rlhf_modules_importable_standalone(mod):
    import importlib

    assert importlib.import_module(mod) is not None


# --------------------------------------------- flight recorder (ISSUE 10)
# Library code reaches the recorder ONLY through the ray_tpu.tracing
# facade (the failpoints shape); the implementation module stays a
# runtime internal.
TRACED_LIBRARY_MODULES = (
    "serve/handle.py", "serve/replica.py", "serve/llm.py",
    "collective/collective.py", "train/elastic.py", "rl/rlhf.py",
)


def test_tracing_facade_exists_and_layers_hold():
    """The facade and its implementation exist, and the instrumented
    library modules import tracing through the facade — never
    ray_tpu._private.spans (the generic _private ban in _violations()
    enforces the negative; this pins the positive so a refactor can't
    silently drop the instrumentation)."""
    assert os.path.exists(os.path.join(PKG, "tracing.py"))
    assert os.path.exists(os.path.join(PKG, "_private", "spans.py"))
    for rel in TRACED_LIBRARY_MODULES:
        path = os.path.join(PKG, rel)
        mods = {m for m, _ in _imports_of(path)}
        assert ("ray_tpu.tracing" in mods), (
            f"{rel} lost its flight-recorder instrumentation "
            f"(no ray_tpu.tracing import)")
        assert not any(m.startswith("ray_tpu._private.spans")
                       for m in mods), rel


def test_tracing_modules_are_walked_by_the_layering_scan():
    for rel in TRACED_LIBRARY_MODULES:
        assert list(_imports_of(os.path.join(PKG, rel))), rel


# --------------------------------- SLO autoscaling/admission (ISSUE 11)
# The serve SLO loop spans policy (slo.py), control (controller.py),
# admission (replica.py), and surfacing (handle.py) — all must build on
# core primitives and public facades only (the RLHF-shape contract):
# the ray_tpu core API, sibling serve modules, and the public
# tracing/failpoints/exceptions/autoscaler surfaces.
SLO_MODULES = ("serve/slo.py", "serve/controller.py",
               "serve/replica.py", "serve/handle.py")

SLO_ALLOWED_PREFIXES = (
    "ray_tpu.serve", "ray_tpu.exceptions", "ray_tpu.failpoints",
    "ray_tpu.tracing", "ray_tpu.autoscaler", "ray_tpu.actor",
    "ray_tpu.object_ref", "ray_tpu.utils", "ray_tpu.runtime_context",
)


def test_slo_modules_are_walked_by_the_layering_scan():
    for rel in SLO_MODULES:
        path = os.path.join(PKG, rel)
        assert os.path.exists(path), path
        assert list(_imports_of(path)), f"no imports parsed in {rel}?"


def test_slo_modules_import_only_core_and_public_facades():
    bad = []
    for rel in SLO_MODULES:
        path = os.path.join(PKG, rel)
        for mod, lineno in _imports_of(path):
            if not (mod == "ray_tpu" or mod.startswith("ray_tpu.")):
                continue
            if mod == "ray_tpu" or any(
                    mod == p or mod.startswith(p + ".")
                    for p in SLO_ALLOWED_PREFIXES):
                continue
            bad.append(f"ray_tpu/{rel}:{lineno}: imports {mod}")
    assert not bad, (
        "serve SLO/admission modules must build on core primitives "
        "and public facades only —\n  " + "\n  ".join(bad))


def test_slo_module_importable_standalone():
    import importlib

    assert importlib.import_module("ray_tpu.serve.slo") is not None


@pytest.mark.parametrize("mod", ["ray_tpu.tracing",
                                 "ray_tpu._private.spans"])
def test_tracing_importable_standalone(mod):
    import importlib

    assert importlib.import_module(mod) is not None


# -------------------------------- cluster prefix store (ISSUE 12)
# The tiered KV store must build ONLY on core primitives (objects /
# arena through the ray_tpu api, ObjectRef), public facades (tracing,
# failpoints, exceptions) and serve siblings — never _private runtime
# internals (the generic ban in _violations() covers the negative;
# this pins the allowed-surface contract like the RLHF/SLO sections).
PREFIX_STORE_MODULES = ("serve/prefix_store.py",)

PREFIX_STORE_ALLOWED_PREFIXES = (
    "ray_tpu.serve", "ray_tpu.exceptions", "ray_tpu.failpoints",
    "ray_tpu.tracing", "ray_tpu.object_ref", "ray_tpu.actor",
    "ray_tpu.runtime_context", "ray_tpu.memledger",
)


def test_prefix_store_is_walked_by_the_layering_scan():
    for rel in PREFIX_STORE_MODULES:
        path = os.path.join(PKG, rel)
        assert os.path.exists(path), path
        assert list(_imports_of(path)), f"no imports parsed in {rel}?"


def test_prefix_store_imports_only_core_and_public_facades():
    bad = []
    for rel in PREFIX_STORE_MODULES:
        path = os.path.join(PKG, rel)
        for mod, lineno in _imports_of(path):
            if not (mod == "ray_tpu" or mod.startswith("ray_tpu.")):
                continue
            if mod == "ray_tpu" or any(
                    mod == p or mod.startswith(p + ".")
                    for p in PREFIX_STORE_ALLOWED_PREFIXES):
                continue
            bad.append(f"ray_tpu/{rel}:{lineno}: imports {mod}")
    assert not bad, (
        "prefix_store must build on core primitives and public "
        "facades only —\n  " + "\n  ".join(bad))


def test_prefix_store_importable_standalone():
    import importlib

    assert importlib.import_module(
        "ray_tpu.serve.prefix_store") is not None


# --------------------------------------- memory ledger (ISSUE 13)
# Library code reaches the object ledger ONLY through the
# ray_tpu.memledger facade (the tracing-facade shape); the
# implementation module stays a runtime internal.
LEDGER_TAGGED_LIBRARY_MODULES = (
    "serve/llm.py", "serve/prefix_store.py", "serve/lora.py",
    "collective/collective.py", "collective/ring.py",
)


def test_memledger_facade_exists_and_layers_hold():
    """The facade and its implementation exist, and the tagging
    library modules import the ledger through the facade — never
    ray_tpu._private.memledger (the generic _private ban in
    _violations() enforces the negative; this pins the positive so a
    refactor can't silently drop the tagging)."""
    assert os.path.exists(os.path.join(PKG, "memledger.py"))
    assert os.path.exists(os.path.join(PKG, "_private", "memledger.py"))
    for rel in LEDGER_TAGGED_LIBRARY_MODULES:
        path = os.path.join(PKG, rel)
        mods = {m for m, _ in _imports_of(path)}
        assert ("ray_tpu.memledger" in mods), (
            f"{rel} lost its memory-ledger tagging "
            f"(no ray_tpu.memledger import)")
        assert not any(m.startswith("ray_tpu._private.memledger")
                       for m in mods), rel


def test_memledger_modules_are_walked_by_the_layering_scan():
    for rel in LEDGER_TAGGED_LIBRARY_MODULES:
        assert list(_imports_of(os.path.join(PKG, rel))), rel


@pytest.mark.parametrize("mod", ["ray_tpu.memledger",
                                 "ray_tpu._private.memledger"])
def test_memledger_importable_standalone(mod):
    import importlib

    assert importlib.import_module(mod) is not None


# ------------------------------------- telemetry timeline (ISSUE 15)
# Library layers and tooling reach the timeline ring ONLY through the
# ray_tpu.telemetry facade (the tracing/memledger shape); the
# implementation module stays a runtime internal.  The metric SERIES
# themselves flow through the public ray_tpu.utils.metrics registry —
# a library module never needs the _private sampler at all.
TELEMETRY_CONSUMER_MODULES = (
    "dashboard/head.py", "scripts/cli.py",
)


def test_telemetry_facade_exists_and_layers_hold():
    """The facade and its implementation exist, and the harvesting
    tooling imports the timeline through the facade — never
    ray_tpu._private.telemetry (the generic _private ban in
    _violations() enforces the library-layer negative; this pins the
    positive so a refactor can't silently drop the surfaces)."""
    assert os.path.exists(os.path.join(PKG, "telemetry.py"))
    assert os.path.exists(os.path.join(PKG, "_private", "telemetry.py"))
    for rel in TELEMETRY_CONSUMER_MODULES:
        path = os.path.join(PKG, rel)
        mods = {m for m, _ in _imports_of(path)}
        assert ("ray_tpu.telemetry" in mods), (
            f"{rel} lost its telemetry-timeline surface "
            f"(no ray_tpu.telemetry import)")
        assert not any(m.startswith("ray_tpu._private.telemetry")
                       for m in mods), rel


def test_telemetry_series_emitters_stay_on_public_metrics():
    """The serve/train series feeding the timeline are plain
    utils.metrics registrations — the library layers never touch the
    sampler module directly."""
    for rel in ("serve/llm.py", "serve/replica.py",
                "train/session.py"):
        path = os.path.join(PKG, rel)
        mods = {m for m, _ in _imports_of(path)}
        assert any(m.startswith("ray_tpu.utils.metrics")
                   or m == "ray_tpu.utils" for m in mods), (
            f"{rel} lost its metric series "
            f"(no ray_tpu.utils.metrics import)")
        assert not any(m.startswith("ray_tpu._private.telemetry")
                       for m in mods), rel


def test_telemetry_modules_are_walked_by_the_layering_scan():
    for rel in TELEMETRY_CONSUMER_MODULES:
        assert list(_imports_of(os.path.join(PKG, rel))), rel


@pytest.mark.parametrize("mod", ["ray_tpu.telemetry",
                                 "ray_tpu._private.telemetry"])
def test_telemetry_importable_standalone(mod):
    import importlib

    assert importlib.import_module(mod) is not None


# ----------------------------------- multi-LoRA serving (ISSUE 18)
# The adapter registry must build ONLY on core primitives (objects
# through the ray_tpu api, ObjectRef), public facades (memledger,
# exceptions) and serve siblings (kv_router) — never _private runtime
# internals (the generic ban in _violations() covers the negative;
# this pins the allowed surface like the prefix-store section).
LORA_MODULES = ("serve/lora.py",)

LORA_ALLOWED_PREFIXES = (
    "ray_tpu.serve", "ray_tpu.exceptions", "ray_tpu.failpoints",
    "ray_tpu.tracing", "ray_tpu.object_ref", "ray_tpu.actor",
    "ray_tpu.runtime_context", "ray_tpu.memledger",
)


def test_lora_is_walked_by_the_layering_scan():
    for rel in LORA_MODULES:
        path = os.path.join(PKG, rel)
        assert os.path.exists(path), path
        assert list(_imports_of(path)), f"no imports parsed in {rel}?"


def test_lora_imports_only_core_and_public_facades():
    bad = []
    for rel in LORA_MODULES:
        path = os.path.join(PKG, rel)
        for mod, lineno in _imports_of(path):
            if not (mod == "ray_tpu" or mod.startswith("ray_tpu.")):
                continue
            if mod == "ray_tpu" or any(
                    mod == p or mod.startswith(p + ".")
                    for p in LORA_ALLOWED_PREFIXES):
                continue
            bad.append(f"ray_tpu/{rel}:{lineno}: imports {mod}")
    assert not bad, (
        "serve/lora.py must build on core primitives and public "
        "facades only —\n  " + "\n  ".join(bad))


def test_lora_importable_standalone():
    import importlib

    assert importlib.import_module("ray_tpu.serve.lora") is not None
