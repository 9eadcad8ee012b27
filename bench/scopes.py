#!/usr/bin/env python3
"""Device time by the program's named scopes.

The program names each layer of its steps with ``jax.named_scope``
(``embed``, ``layers``, ``norm``, ``mamba.ssd``, ``optimizer``, ...; the
list is ``SCOPES``).  A name reaches the ``op_name`` metadata of the
compiled HLO ops: a forward op reads ``jit(step)/jvp(layers)/while/body/
.../mamba.ssd/...``, its backward op ``transpose(jvp(layers))/...`` and a
recomputed op ``.../rematted_computation/mamba.ssd/...``.  So a profiler
trace of the device, joined with the text of the compiled programs, says
how much device time each scope takes:

* an op's scope is the innermost component of its ``op_name`` path that is
  one of the program's scope names;
* a fusion's path is its matmul's (``convolution`` or ``dot``, in it or in
  a fusion nested in it), else the path of the scope and phase most of its
  fused instructions carry.  XLA gives a fusion the metadata of one
  instruction, often elementwise work or the layer scan's bookkeeping
  around the real work: the decode step's SSM update fuses into the
  ``broadcast_in_dim`` that stacks its output, and a backward matmul into
  the norm's elementwise gradient;
* an op with no metadata (a copy XLA inserted) takes the scope of the
  innermost device event around it, such as the layer scan's ``while``;
* anything else is ``unscoped``;
* each op's time is its self time (``bench.trace.self_times``), split into
  forward, backward and recompute by its path.

    python3 bench/scopes.py --workload <cell> --seed <n> --seconds <s>

makes one traced run of the cell, as ``bench/run.py --trace 1`` does (whose
run record carries the same reduction under ``trace.scopes``, read by the
``*_ms.*`` per-layer metrics), prints the table of device time per step by
scope and ends its output with the reduction as one JSON line.
``--keep <dir>`` also writes the trace and the compiled programs' text
there, for :func:`reduce` to read again.
"""

from __future__ import annotations

import contextlib
import re

#: the program's scope names (``repro.models``, ``repro.dist.step``)
SCOPES = (
    "embed", "layers", "norm", "attn", "mlp", "kv.encode", "kv.decode",
    "mamba.in_proj", "mamba.conv", "mamba.ssd", "mamba.ssm_update", "mamba.out",
    "head", "grad_stats", "optimizer", "serve.weights",
)
#: scope families named per call: ``kernel.<op>.<fmt>``, ``wire.hop.<fmt>``, ...
FAMILIES = ("kernel.", "wire.hop.", "wire.ring.", "pipe.hop.")
UNSCOPED = "unscoped"
PHASES = ("fwd", "bwd", "recompute")
MODULES_LINE = "XLA Modules"

_HEADER = re.compile(r"^HloModule ([^\s,]+)")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=%]+)\s*=\s")
_OP_NAME = re.compile(r'\bmetadata=\{[^}]*?op_name="((?:[^"\\]|\\.)*)"')
_OPCODE = re.compile(r"[\]})]\s+([a-z][\w\-]*)\(")
_CALLS = re.compile(r"\bcalls=%?([^\s,]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s.*\{$")
#: instructions that do no work of their own cast no vote in a fusion
_NO_VOTE = {"parameter", "constant", "bitcast", "tuple", "get-tuple-element"}
_MATMUL = {"convolution", "dot"}
_WRAPPED = re.compile(r"^[\w.]+\((.*)\)$")
_RUN_ID = re.compile(r"\(\d+\)$")


def op_names(text: str) -> dict[tuple[str, str], str | None]:
    """``{(module, instruction): op_name}`` from a compiled program's text
    (``Compiled.as_text()``); None for an instruction without metadata.  A
    fusion gets the path of the work in it (module docstring)."""
    m = _HEADER.match(text)
    module = m.group(1) if m else ""
    comps: dict[str, list[tuple]] = {}  # computation -> [(name, opcode, path, fused)]
    comp: list = []
    for line in text.splitlines():
        hit = _INSTR.match(line)
        if not hit:
            head = _COMPUTATION.match(line)
            if head:
                comp = comps.setdefault(head.group(1), [])
            continue
        path = _OP_NAME.search(line)
        rhs = line[hit.end():]
        opcode = _OPCODE.search(rhs)
        opcode = opcode.group(1) if opcode else None
        fused = _CALLS.search(rhs) if opcode == "fusion" else None
        comp.append((hit.group(1), opcode, path.group(1) if path else None,
                     fused.group(1) if fused else None))

    done: dict[str, tuple[str | None, bool]] = {}

    def resolve(opcode, path, fused) -> tuple[str | None, bool]:
        """(path, holds a matmul) of one instruction."""
        if fused is None or fused not in comps:
            return path, opcode in _MATMUL and path is not None
        if fused not in done:
            done[fused] = (None, False)  # a cycle reads as empty
            parts = [resolve(*ins[1:]) + (ins[1],) for ins in comps[fused]]
            mm = [p for p, has, _ in parts if has]
            votes = [p for p, _, op in parts if p is not None and op not in _NO_VOTE]
            done[fused] = ((mm[0], True) if mm else
                           (_majority(votes, path) if votes else None, False))
        got, has = done[fused]
        return (got if got is not None else path), has

    return {(module, ins[0]): resolve(*ins[1:])[0]
            for instrs in comps.values() for ins in instrs}


def _majority(paths: list[str], own: str | None) -> str:
    """The path whose (scope, phase) most of ``paths`` share; on a tie the
    fusion's ``own`` path, where it is among the tied, else the first."""
    votes: dict[tuple, list[str]] = {}
    for p in paths:
        votes.setdefault((scope_of(p), phase_of(p)), []).append(p)
    most = max(len(v) for v in votes.values())
    tied = [v for v in votes.values() if len(v) == most]
    if own is not None:
        mine = (scope_of(own), phase_of(own))
        for v in tied:
            if (scope_of(v[0]), phase_of(v[0])) == mine:
                return own
    return tied[0][0]


def _component(part: str) -> str:
    """``transpose(jvp(layers))`` -> ``layers``."""
    while (m := _WRAPPED.match(part)):
        part = m.group(1)
    return part


def scope_of(op_name: str) -> str | None:
    """The innermost program scope on the path, or None.  XLA joins the
    paths of merged ops with ``;``: the first is read."""
    for part in reversed(op_name.split(";")[0].split("/")):
        name = _component(part)
        if name in SCOPES or name.startswith(FAMILIES):
            return name
    return None


def phase_of(op_name: str) -> str:
    if "rematted_computation" in op_name:
        return "recompute"
    return "bwd" if "transpose(" in op_name else "fwd"


def _stat(event, key: str):
    for k, v in event.stats:
        if k == key:
            return v
    return None


def _module(name) -> str | None:
    return None if name is None else _RUN_ID.sub("", str(name))


def device_events(pd) -> dict[int, list[tuple[int, int, str, str | None]]]:
    """Device id -> its ops as (start_ns, end_ns, instruction, module): the
    module from the op's ``hlo_module`` stat, else from the ``XLA Modules``
    event around it."""
    from bench import trace

    out = {}
    for plane in pd.planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        ops, mods = [], []
        for line in plane.lines:
            for e in line.events:
                s = int(e.start_ns)
                if line.name == trace.OPS_LINE:
                    ops.append((s, s + int(e.duration_ns), trace.op_name(e.name),
                                _module(_stat(e, "hlo_module"))))
                elif line.name == MODULES_LINE:
                    mods.append((s, s + int(e.duration_ns), _module(e.name)))
        mods.sort()
        evs, j = [], 0
        for a, b, nm, mod in sorted(ops):
            if mod is None:
                while j < len(mods) and mods[j][1] <= a:
                    j += 1
                if j < len(mods) and mods[j][0] <= a:
                    mod = mods[j][2]
            evs.append((a, b, nm, mod))
        out[int(m.group(1))] = evs
    return out


def _parents(ops) -> list[int | None]:
    """Index of the innermost op around each op (ops sorted by start, the
    longer first), as ``self_times`` nests them."""
    parent, stack = [None] * len(ops), []
    for i, (a, b, *_) in enumerate(ops):
        while stack and ops[stack[-1]][1] <= a:
            stack.pop()
        parent[i] = stack[-1] if stack else None
        stack.append(i)
    return parent


def attribute(ops, names: dict) -> list[tuple[str, str, float, str]]:
    """(scope, phase, self seconds, instruction) for each of ``ops``
    [(start_ns, end_ns, instruction, module)], by the rules of the module
    docstring.  ``names`` is :func:`op_names` over every program."""
    from bench import trace

    owners: dict[str, list[str]] = {}
    for mod, nm in names:
        owners.setdefault(nm, []).append(mod)
    ops = sorted(ops, key=lambda o: (o[0], -o[1]))
    self_s = trace.self_times([(a, b, i) for i, (a, b, *_) in enumerate(ops)])
    parent = _parents(ops)
    labels: list[tuple[str, str]] = []
    for i, (_, _, nm, mod) in enumerate(ops):
        if mod is None and len(owners.get(nm, ())) == 1:
            mod = owners[nm][0]  # no module on the trace: the one program with the name
        if (mod, nm) not in names:
            label = (UNSCOPED, "fwd")  # a program not compiled by the harness
        elif (path := names[(mod, nm)]) is not None:
            label = (scope_of(path) or UNSCOPED, phase_of(path))
        elif parent[i] is not None:
            label = labels[parent[i]]  # no metadata: the op around it
        else:
            label = (UNSCOPED, "fwd")
        labels.append(label)
    return [(sc, ph, self_s.get(i, 0.0), ops[i][2]) for i, (sc, ph) in enumerate(labels)]


def reduce(pd, names: dict, *, steps: int | None = None, top: int = 5) -> dict:
    """Device self time by scope inside the ``bench.window`` span, averaged
    over devices: ``scopes`` {scope: s}, ``phases`` {scope: {phase: s}},
    ``busy_s`` (the sum of self times), ``unscoped_share`` of it and
    ``unscoped_top`` [[instruction, s]]; per step where ``steps`` is given."""
    from bench import trace

    wins = [(s, e) for s, e, n in trace.host_spans(pd) if n == trace.WINDOW]
    if not wins:
        raise ValueError(f"trace holds no {trace.WINDOW!r} span")
    lo, hi = max(wins, key=lambda w: w[1] - w[0])
    devs = device_events(pd)
    if not devs:
        raise ValueError("trace holds no device plane with an 'XLA Ops' line")
    div = len(devs) * (steps or 1)
    scopes: dict[str, float] = {}
    phases: dict[str, dict[str, float]] = {}
    loose: dict[str, float] = {}
    for evs in devs.values():
        ops = [(max(a, lo), min(b, hi), nm, mod) for a, b, nm, mod in evs
               if min(b, hi) > max(a, lo)]
        for scope, phase, t, nm in attribute(ops, names):
            scopes[scope] = scopes.get(scope, 0.0) + t / div
            ph = phases.setdefault(scope, dict.fromkeys(PHASES, 0.0))
            ph[phase] += t / div
            if scope == UNSCOPED:
                loose[nm] = loose.get(nm, 0.0) + t / div
    busy = sum(scopes.values())
    return {
        "scopes": scopes,
        "phases": phases,
        "busy_s": busy,
        "unscoped_share": scopes.get(UNSCOPED, 0.0) / busy if busy else 0.0,
        "unscoped_top": sorted(([k, v] for k, v in loose.items()), key=lambda kv: -kv[1])[:top],
    }


def table(red: dict) -> str:
    """The reduction as a table of ms per step (``reduce(..., steps=n)``)."""
    rows = [f"{'scope':<20}{'ms':>10}{'fwd':>10}{'bwd':>10}{'recompute':>10}{'share':>8}"]
    for scope, t in sorted(red["scopes"].items(), key=lambda kv: -kv[1]):
        ph = red["phases"][scope]
        rows.append(f"{scope:<20}{1e3 * t:>10.3f}" + "".join(
            f"{1e3 * ph[p]:>10.3f}" for p in PHASES) + f"{100 * t / red['busy_s']:>7.2f}%")
    rows.append(f"unscoped share of busy time: {100 * red['unscoped_share']:.3f}%")
    rows += [f"  unscoped {nm}: {1e3 * t:.3f} ms" for nm, t in red["unscoped_top"]]
    return "\n".join(rows)


# ---------------------------------------------------------------------------
# a traced run of one cell
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def compiled_texts(texts: list):
    """Keeps ``as_text()`` of every program compiled through
    ``jax.stages.Lowered.compile`` (the harness compiles its steps so).

    JAX's persistent compilation cache leaves metadata out of its key, so
    an executable cached from the same program under other names (or
    none: a build from before the scopes) would answer with its own stale
    ``op_name``s.  Inside this context the key takes the metadata in."""
    import jax

    orig = jax.stages.Lowered.compile
    keyed = jax.config.jax_compilation_cache_include_metadata_in_key

    def compile(self, *args, **kw):
        out = orig(self, *args, **kw)
        texts.append(out.as_text())
        return out

    jax.stages.Lowered.compile = compile
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    try:
        yield texts
    finally:
        jax.stages.Lowered.compile = orig
        jax.config.update("jax_compilation_cache_include_metadata_in_key", keyed)


def program_names(texts: list[str]) -> dict:
    """:func:`op_names` over the programs' texts, in the order compiled: a
    later program wins a shared name (the window's decode step, compiled
    after the prefill)."""
    names = {}
    for text in texts:
        names.update(op_names(text))
    return names


def step_ms(r: dict, kind: str, scope: str) -> float | None:
    """Device ms per step in ``scope`` from a traced run record of a
    ``kind`` cell (train or decode); None where the scope is absent."""
    if r["kind"] != kind or not r["trace"]:
        return None
    t = r["trace"]["scopes"]["scopes"].get(scope)
    return None if t is None else 1e3 * t


def run(cell: dict, seed: int, seconds: float, keep: str | None = None) -> dict:
    """One traced run of the cell as ``bench/run.py --trace 1`` makes it
    (reference included); returns the scope reduction per step."""
    import time

    from bench import harness

    cfg, tr = cell["config"], cell["traffic"]
    dev = harness.device_check(cell["chips"], require_tpu=True)
    tracer = harness.Tracer(True, cell["name"], keep=keep)
    with tracer.programs():
        _, record, *_ = harness.RUNNERS[tr["kind"]](
            harness.model_dict(cfg), cfg, tr, seed, seconds, tracer,
            harness.CompileCounter.get(), dev, time.perf_counter, False)
    return {**record["trace"]["scopes"], "steps": record["steps"]}


def main() -> None:
    import argparse
    import json
    import os
    import pathlib
    import sys

    root = pathlib.Path(__file__).resolve().parents[1]
    sys.path.insert(0, str(root))
    os.environ.setdefault("TPU_LOG_DIR", str(root / ".bench_out" / "tpu_logs"))
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--keep", default=None, help="directory for the trace and program texts")
    args = ap.parse_args()

    from bench import program

    program.configure_compile_cache()
    from bench import harness

    cell = harness.load_cell(args.workload)
    red = run(cell, args.seed, args.seconds, args.keep)  # logs the table
    print(json.dumps({"workload": args.workload, "seed": args.seed, **red}), flush=True)


if __name__ == "__main__":
    main()
