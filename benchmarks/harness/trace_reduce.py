"""From a profiler trace (`*.xplane.pb`) to numbers, with nothing but
`jax.profiler.ProfileData`.  Runs in the process that holds the chip (the
driver stays off JAX) and returns plain data.

What a v5e trace looks like (looked at by hand, PR 24): one plane per
chip, `/device:TPU:<n>`; its line `XLA Ops` holds one event per executed
HLO op (a `while` or `call` event encloses the events of its body, so
time is attributed to the innermost event: self time), its line
`XLA Modules` one event per executed program, named
`jit_<function>(<fingerprint>)`.  A Pallas kernel is an op event of kind
`custom-call` (flash forward: `%attention.N`, paged decode attention:
`%closed_call.N`); the line `Async XLA Ops` (copies in flight) is not
read.  Event times are ns from the start of
the profile; the plane `Task Environment` carries `profile_start_time`,
ns since the epoch on the host clock, which sets spans (host wall clock)
beside device events.
"""
from __future__ import annotations

import glob
import os
import re

from . import stats

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE_RE = re.compile(
    r"(^| )(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)")


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return files[-1]


def start(trace_dir: str) -> None:
    """Start the profiler on a fresh directory.  The host's Python stacks
    are not read, so they are not recorded: the trace stays small."""
    import shutil

    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def reduce_dir(trace_dir: str, dump_to: str | None = None,
                    margin_s: float = 0.0) -> dict:
    """Reduce the (stopped) trace under trace_dir; with dump_to, also write its
    structure there for reading by hand."""
    path = find_xplane(trace_dir)
    if dump_to:
        from benchmarks.tools import trace_dump

        os.makedirs(os.path.dirname(dump_to), exist_ok=True)
        with open(dump_to, "w", encoding="utf-8") as f:
            trace_dump.dump(path, 60, out=f)
    return reduce(path, margin_s=margin_s)


def load(path: str) -> dict:
    """{"start_wall_s", "devices": {plane: {"ops": [...], "modules":
    [...]}}} with events as (name, start_s, dur_s, module) tuples."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = {"start_wall_s": None, "devices": {}}
    for plane in pd.planes:
        if plane.name == "Task Environment":
            st = dict(plane.stats)
            if "profile_start_time" in st:
                out["start_wall_s"] = st["profile_start_time"] / 1e9
        elif plane.name.startswith("/device:TPU:"):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev["ops"] = [_event(e) for e in line.events]
                elif line.name == MODULES_LINE:
                    dev["modules"] = [_event(e) for e in line.events]
            out["devices"][plane.name] = dev
    if not out["devices"]:
        out["devices"] = _host_executed_ops(pd)
    return out


_HLO_RE = re.compile(r"^%(\S+) = \(?(\w+\[[\d,]*\])?")
_KIND_RE = re.compile(r"[\}\)\]] ([a-z][a-z0-9\-]*)\(")


def short_name(name: str) -> str:
    """An op event is named by its whole HLO instruction,
    `%fusion.164 = bf16[8,1024,4096]{...} fusion(...)`; keep
    `fusion.164 fusion bf16[8,1024,4096]`: the instruction's name, its
    kind (`fusion`, `custom-call`, `while`, `all-reduce`, ...) and the
    first output shape."""
    m = _HLO_RE.match(name)
    if m is None:
        return name[:120]
    kind = _KIND_RE.search(name)
    return " ".join(x for x in (m.group(1), kind.group(1) if kind else "",
                                m.group(2) or "") if x)


def _event(e) -> tuple:
    return (short_name(e.name), e.start_ns / 1e9, e.duration_ns / 1e9, None)


def _host_executed_ops(pd) -> dict:
    """The CPU backend has no device plane: its ops run on host threads
    and carry an `hlo_module` stat.  Used by the rehearsal only, whose
    numbers are never reported as device numbers."""
    ops = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                st = dict(e.stats)
                if "hlo_module" in st:
                    ops.append((e.name, e.start_ns / 1e9,
                                e.duration_ns / 1e9, st["hlo_module"]))
    return {"/host:CPU": {"ops": sorted(ops, key=lambda o: o[1]),
                          "modules": []}} if ops else {}


def module_base(name: str) -> str:
    """`jit__decode_k_paged(1234567)` -> `jit__decode_k_paged`."""
    return re.sub(r"\(\d+\)$", "", name)


def self_times(ops: list[tuple]) -> list[tuple]:
    """(name, start_s, self_s) per op event of ONE line: an event's
    duration less the part its enclosed events cover."""
    out, stack = [], []      # stack of [name, start, end, child_s]

    def close(item):
        name, s, e, child = item
        out.append((name, s, max(0.0, (e - s) - child)))

    for name, s, d, _ in sorted(ops, key=lambda o: (o[1], -o[2])):
        e = s + d
        while stack and s >= stack[-1][2] - 1e-12:
            close(stack.pop())
        if stack:
            stack[-1][3] += min(e, stack[-1][2]) - s
        stack.append([name, s, e, 0.0])
    while stack:
        close(stack.pop())
    return out


def assign_modules(ops: list[tuple], modules: list[tuple]) -> list[str]:
    """For each op event (sorted by start) the base name of the program
    whose module event covers its start, or ''."""
    mods = sorted(modules, key=lambda m: m[1])
    out, j = [], 0
    for name, s, d, mod in sorted(ops, key=lambda o: o[1]):
        if mod is not None:
            out.append(module_base(mod))
            continue
        while j < len(mods) and mods[j][1] + mods[j][2] <= s:
            j += 1
        hit = j < len(mods) and mods[j][1] <= s
        out.append(module_base(mods[j][0]) if hit else "")
    return out


def reduce_device(dev: dict, t_lo: float, t_hi: float) -> dict:
    """One chip's events inside [t_lo, t_hi) (seconds from profile start)."""
    ops = [o for o in dev["ops"] if t_lo <= o[1] < t_hi]
    mods = [m for m in dev["modules"] if t_lo <= m[1] < t_hi]
    spans = [(s, s + d) for _, s, d, _ in ops]
    busy = stats.union_length(spans)
    by_op: dict = {}
    owner = assign_modules(ops, mods)
    for (name, s, self_s), mod in zip(
            sorted(self_times(ops), key=lambda o: o[1]), owner):
        key = (mod, name)
        cnt, tot = by_op.get(key, (0, 0.0))
        by_op[key] = (cnt + 1, tot + self_s)
    return {
        "busy_s": busy,
        "modules": [(module_base(n), s, d) for n, s, d, _ in mods],
        "by_op": [[mod, name, cnt, tot]
                  for (mod, name), (cnt, tot) in by_op.items()],
        "gaps": sorted(((e - s, s, e) for s, e in
                        stats.gaps(spans, t_lo, t_hi)), reverse=True)[:40],
    }


def reduce(path: str, margin_s: float = 0.0) -> dict:
    """The whole trace, reduced.  The window is the span between the
    first and last device op over all chips, less `margin_s` at each
    end; `busy_s` is averaged over the chips."""
    tr = load(path)
    devs = tr["devices"]
    if not devs or not any(d["ops"] for d in devs.values()):
        return {"window_s": 0.0, "busy_s": 0.0, "n_devices": 0,
                "start_wall_s": tr["start_wall_s"], "devices": []}
    t_lo = min(d["ops"][0][1] for d in devs.values() if d["ops"]) + margin_s
    t_hi = max(max(s + du for _, s, du, _ in d["ops"])
               for d in devs.values() if d["ops"]) - margin_s
    per = [reduce_device(devs[k], t_lo, t_hi) for k in sorted(devs)]
    return {"window_s": t_hi - t_lo, "t_lo": t_lo, "t_hi": t_hi,
            "busy_s": sum(p["busy_s"] for p in per) / len(per),
            "n_devices": len(per), "start_wall_s": tr["start_wall_s"],
            "devices": per}


# ------------------------------------------------- readers' arithmetic
def module_durations(red: dict, pattern: str, device: int = 0
                     ) -> list[float]:
    """Durations (s) of the program events whose base name matches."""
    if not red.get("devices"):
        return []
    rx = re.compile(pattern)
    return [d for n, _, d in red["devices"][device]["modules"]
            if rx.search(n)]


def op_time(red: dict, module_pattern: str, op_pattern: str,
            device: int = 0) -> tuple[int, float]:
    """(count, self seconds) of op events matching, inside programs
    matching."""
    if not red.get("devices"):
        return 0, 0.0
    mrx, orx = re.compile(module_pattern), re.compile(op_pattern)
    cnt = tot = 0
    for mod, name, c, t in red["devices"][device]["by_op"]:
        if mrx.search(mod) and orx.search(name):
            cnt, tot = cnt + c, tot + t
    return cnt, tot


def top_ops(red: dict, n: int = 10, device: int = 0) -> list[list]:
    """[[name, seconds]] of the ops that took most self time, named
    `<program>/<op>`."""
    if not red.get("devices"):
        return []
    rows = sorted(red["devices"][device]["by_op"], key=lambda r: -r[3])
    return [[f"{mod or '?'}/{name}", tot] for mod, name, _, tot in rows[:n]]


def collective_exposed_s(red: dict, device: int = 0) -> float:
    """Self time of collective ops on the chip's op line: the line is one
    stream, so while a collective's event runs there, no compute does."""
    return op_time(red, "", COLLECTIVE_RE.pattern, device)[1]


def attribute_gaps(red: dict, spans: list[dict], n: int = 10,
                   device: int = 0) -> list[list]:
    """[[what the host was doing, seconds]] for the longest idle gaps of
    the chip: the flight-recorder span (host wall clock) that covers most
    of the gap, if it covers at least half of it, or "no span"."""
    if not red.get("devices") or red.get("start_wall_s") is None:
        return []
    base = red["start_wall_s"]
    out = []
    for dur, s, e in red["devices"][device]["gaps"][:n]:
        ws, we = base + s, base + e
        best, best_cov = "no span", 0.5 * dur
        for sp in spans:
            cov = min(we, sp["t1"]) - max(ws, sp["t0"])
            if cov >= best_cov:
                best, best_cov = sp["name"], cov
        out.append([best, dur])
    return out
