"""The device hop's half of the telemetry: the process tracer's annotation
factory and compile spans (``install``), and a table from each HLO op of
a registered jitted program to the named scope of the model part it
computes (``op_scopes``), so that device time in a profiler trace can be
summed per part under names that survive a recompile.

The model puts ``jax.named_scope`` on its parts (``SCOPES``). The compiler
keeps the scope path in each instruction's ``metadata={op_name="..."}``,
wrapped by the transformations it went through, e.g.
``jit(step)/transpose(jvp(layer_stack))/while/body/closed_call/checkpoint/
rematted_computation/ssd/exp``; an op belongs to the innermost scope of
the vocabulary on that path.
"""
from __future__ import annotations

import re
from dataclasses import dataclass

import jax

from ..core import telemetry

#: the model parts, by the names the model's ``jax.named_scope``s give them
SCOPES = ("embed", "mixer_proj", "conv", "ssd", "gate_norm", "attention",
          "mlp", "layer_stack", "head", "optimizer")
UNSCOPED = "unscoped"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_installed = False


def _annotation(name: str):
    TA = jax.profiler.TraceAnnotation
    return TA(name) if TA.is_enabled() else None


def _on_duration(event: str, duration: float, **_kw) -> None:
    if event == COMPILE_EVENT:
        tr = telemetry.tracer()
        t1 = tr.now()
        tr.record("compile", t1 - duration, t1)
        tr.count("compiles")


def install() -> None:
    """Point the process tracer at the profiler: each span opens a
    ``TraceAnnotation`` while a profiler session runs, and each XLA compile
    leaves a back-dated ``compile`` span and a ``compiles`` count.
    Idempotent."""
    global _installed
    if _installed:
        return
    _installed = True
    telemetry.tracer().set_annotation(_annotation)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)


# -- programs and their op -> scope tables -------------------------------------
@dataclass
class _Program:
    fn: object                 # the jitted function
    args: tuple                # abstract arguments of its first call
    text: str | None = None    # its compiled module's text, once asked for
    table: dict | None = None
    mixed: dict | None = None


_PROGRAMS: dict[str, _Program] = {}


def _abstract(x):
    if isinstance(x, jax.Array):
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, weak_type=x.weak_type,
            sharding=x.sharding if x.committed else None)
    return x


def register(name: str, fn, args: tuple) -> None:
    """Record jitted ``fn`` and the shapes, dtypes and shardings of
    ``args`` (taken before a call that donates them) for ``op_scopes``.
    Compiles nothing."""
    _PROGRAMS[name] = _Program(fn, jax.tree.map(_abstract, args))


def _program(name: str) -> _Program:
    prog = _PROGRAMS.get(name)
    if prog is None:
        raise KeyError(f"no program registered as {name!r}; "
                       f"registered: {sorted(_PROGRAMS)}")
    if prog.text is None:
        prog.text = prog.fn.lower(*prog.args).compile().as_text()
    return prog


def op_scopes(name: str) -> dict[str, str]:
    """``{hlo_op_name: scope}`` for every instruction of the registered
    program ``name``, scope being one of ``SCOPES`` or ``UNSCOPED``.
    Lowers and compiles the program from its recorded arguments on first
    use (a persistent-cache hit where the cache holds the program)."""
    prog = _program(name)
    if prog.table is None:
        prog.table = hlo_op_scopes(prog.text)
    return prog.table


def op_mixed_scopes(name: str) -> dict[str, tuple[str, ...]]:
    """``{hlo_op_name: scopes}`` for the instructions of program ``name``
    whose fused members come from more than one scope; ``op_scopes``
    gives each of them one (see ``hlo_mixed_scopes``)."""
    prog = _program(name)
    if prog.mixed is None:
        prog.mixed = hlo_mixed_scopes(prog.text)
    return prog.mixed


_WRAPPED = re.compile(r"^[A-Za-z_][\w\-]*\((.*)\)$")


def scope_of(op_name: str) -> str:
    """The innermost vocabulary scope on an ``op_name`` path, its
    transformation wrappers (``transpose(jvp(ssd))``) stripped; for a
    ``;``-joined name, that of the first part that has one."""
    for part in op_name.split(";"):
        found = None
        for comp in part.split("/")[:-1]:      # the last is the primitive
            m = _WRAPPED.match(comp)
            while m:
                comp = m.group(1)
                m = _WRAPPED.match(comp)
            if comp in SCOPES:
                found = comp
        if found:
            return found
    return UNSCOPED


_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?%?([^\s=]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"\bcalls=(\{[^}]*\}|%?[\w.\-]+)")


@dataclass
class _Module:
    own: dict        # instruction -> scope of its own op_name, else None
    calls: dict      # instruction -> the computations it calls
    members: dict    # computation -> its instructions
    root: dict       # computation -> its root instruction


def _parse(hlo_text: str) -> _Module:
    mod = _Module({}, {}, {}, {})
    comp = None
    for line in hlo_text.splitlines():
        if not line.startswith((" ", "\t")):
            m = _COMPUTATION.match(line)
            comp = m.group(1) if m else None
            continue
        m = _INSTRUCTION.match(line)
        if m is None or comp is None:
            continue
        name = m.group(2)
        mod.members.setdefault(comp, []).append(name)
        if m.group(1):
            mod.root[comp] = name
        op = _OP_NAME.search(line)
        mod.own[name] = scope_of(op.group(1)) if op else None
        c = _CALLS.search(line)
        if c:
            mod.calls[name] = [x.strip().lstrip("%")
                               for x in c.group(1).strip("{}").split(",")
                               if x.strip()]
    return mod


def hlo_op_scopes(hlo_text: str) -> dict[str, str]:
    """``{instruction name: scope}`` from a compiled module's text. An
    instruction without an ``op_name`` (some fusions) takes the scope of
    the computation it calls: that of its root, else of the first
    instruction there that has one."""
    mod = _parse(hlo_text)

    def resolve(name: str, seen: frozenset) -> str | None:
        if mod.own.get(name) is not None:
            return mod.own[name]
        for callee in mod.calls.get(name, ()):
            if callee in seen:
                continue
            order = [mod.root[callee]] if callee in mod.root else []
            order += mod.members.get(callee, [])
            for inner in order:
                s = resolve(inner, seen | {callee})
                if s is not None:
                    return s
        return None

    return {name: resolve(name, frozenset()) or UNSCOPED for name in mod.own}


def hlo_mixed_scopes(hlo_text: str) -> dict[str, tuple[str, ...]]:
    """``{instruction name: sorted scopes}`` for each instruction whose own
    ``op_name`` and those of the instructions in the computations it calls
    (a fusion's members, at any depth) name more than one scope. Such an
    op's device time is one event, credited whole to its ``hlo_op_scopes``
    scope."""
    mod = _parse(hlo_text)

    def scopes(name: str, seen: frozenset) -> set:
        out = {mod.own[name]} if mod.own.get(name) is not None else set()
        for callee in mod.calls.get(name, ()):
            if callee not in seen:
                for inner in mod.members.get(callee, ()):
                    out |= scopes(inner, seen | {callee})
        return out

    mixed = {}
    for name in mod.own:
        found = scopes(name, frozenset())
        if len(found) > 1:
            mixed[name] = tuple(sorted(found))
    return mixed
