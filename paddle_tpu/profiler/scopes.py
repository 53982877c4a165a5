"""Names in the compiled programs, and device time by those names.

`scope("attention")` is `jax.named_scope("pt.attention")`: metadata only,
the optimised program is the same with and without it. The name lands in
the `op_name` of every HLO instruction traced under it, next to the marks
JAX's own wrappers leave (`transpose(jvp(...))` on the backward pass,
`rematted_computation` on recompute).

A device trace does not carry `op_name`: an event's name is its
instruction's text without `metadata={...}`. The instruction's NAME
(`%fusion.228`) is in both, so device time by phase is a join, by that
name, of the trace's events with a map read from `compiled.as_text()`.
A `Program` is a way to get that text again (`register_program`); its map
is built on demand, by lowering and compiling the function for its abstract
arguments (a hit in the persistent compilation cache), never on the step's
path. A trainer's step is kept by name, so its map can be asked for after
the trainer is gone; an engine keeps its own programs, since several
engines of one process each own a `serving_step`.
"""
from __future__ import annotations

import re
import weakref
from collections import Counter
from typing import Dict, Iterable, Optional, Tuple

import jax

PREFIX = "pt."
UNATTRIBUTED = "(unattributed)"
NO_BLOCK = "(other)"
PASSES = ("forward", "recompute", "backward", "optimizer")
OPTIMIZER_BLOCKS = ("clip", "adamw")

Phase = Tuple[str, str]                      # (pass, block)

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ", re.M)
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+) \(.*\{\s*$", re.M)
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_OPERANDS = re.compile(r"\((%[^()]*)\)")         # the operand list
_NAME = re.compile(r"%([\w.\-]+)")
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_BLOCK = re.compile(re.escape(PREFIX) + r"(\w+)")


def scope(name: str):
    """`with scope("mlp"):` names what is traced inside `pt.mlp`."""
    return jax.named_scope(PREFIX + name)


def abstract(tree):
    """The arguments of a call as shapes: a committed array keeps its
    sharding, anything else (host arrays, uncommitted scalars) goes without
    one, as the call itself passes it. Holds no array."""
    def one(a):
        sharding = a.sharding if getattr(a, "committed", False) else None
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
    return jax.tree.map(one, tree)


class PhaseMap(dict):
    """{instruction name: (pass, block), or None where nothing names it}.
    `inherited` holds the instructions whose phase is not their own
    `op_name`'s but was taken from what they call or read."""
    __slots__ = ("inherited",)

    def __init__(self, *args, inherited=()):
        super().__init__(*args)
        self.inherited = set(inherited)


class Program:
    """One compiled program: its name and how to compile it again."""
    __slots__ = ("name", "_ref", "_args", "_phases", "__weakref__")

    def __init__(self, name, jitted, abstract_args, weak):
        self.name = name
        self._ref = weakref.ref(jitted) if weak else (lambda: jitted)
        self._args, self._phases = abstract_args, None

    def alive(self) -> bool:
        return self._phases is not None or self._ref() is not None

    def phases(self) -> Optional[PhaseMap]:
        """The map, built at the first call and kept; None once the
        function is gone with no map built. A built map lets go of the
        function (and so of its executable)."""
        if self._phases is None:
            jitted = self._ref()
            if jitted is None:
                return None
            self._phases = parse_phases(
                jitted.lower(*self._args).compile().as_text())
            self._ref, self._args = (lambda: None), None
        return self._phases


_named: Dict[str, Program] = {}        # kept by name: the newest wins
_live = weakref.WeakSet()              # every Program some owner still holds


def register_program(name: str, jitted, abstract_args,
                     weak: bool = False) -> Program:
    """Remember how to compile `jitted` for `abstract_args` (a tuple, as
    `abstract` makes it). By default the entry is kept under `name`
    (`instruction_phases(name)`), replacing an older one, and holds the
    function until its map is built: for a function whose closure holds no
    array (the trainer's step). `weak=True` keeps nothing here: the caller
    holds the returned `Program`, which only weakly references a function
    whose closure holds what must be freed with its owner (the serving
    step's holds the model)."""
    prog = Program(name, jitted, abstract_args, weak)
    _live.add(prog)
    if not weak:
        _named[name] = prog
    return prog


def live_programs():
    """Every `Program` that can still give a map, by name."""
    return sorted((p for p in _live if p.alive()), key=lambda p: p.name)


def classify(op_name: str) -> Phase:
    """(pass, block) of one `op_name` path."""
    blocks = _BLOCK.findall(op_name)
    block = blocks[-1] if blocks else NO_BLOCK
    if "rematted_computation" in op_name:
        return "recompute", block
    if "transpose(" in op_name:
        return "backward", block
    if block in OPTIMIZER_BLOCKS:
        return "optimizer", block
    return "forward", block


def parse_phases(hlo_text: str) -> PhaseMap:
    """{instruction name: (pass, block)} for every instruction of a
    compiled module's text; None where nothing names it. An instruction's
    text runs to the next instruction's (a kernel's `kernel_metadata`
    spreads it over several lines). What XLA makes itself carries no
    `op_name`: such an instruction takes the commonest phase inside the
    computation it calls (a fusion), else the phase of its first named
    operand (a layout copy, or the slices XLA assembles a gather from: the
    serving step's largest operation); the map's `inherited` names these:
    a guess, which a reader bounds. The text lists callees before callers
    and operands before users, so one pass does."""
    heads = [(m.start(), m.group(1)) for m in _COMPUTATION.finditer(hlo_text)]
    starts = list(_INSTRUCTION.finditer(hlo_text))
    ends = [m.start() for m in starts[1:]] + [len(hlo_text)]
    out = PhaseMap()
    inside: Dict[str, Counter] = {}      # computation -> its named phases
    h = -1
    for m, end in zip(starts, ends):
        while h + 1 < len(heads) and heads[h + 1][0] < m.start():
            h += 1
        op = _OP_NAME.search(hlo_text, m.end(), end)
        if op:
            phase = classify(op.group(1))
            if h >= 0:
                inside.setdefault(heads[h][1], Counter())[phase] += 1
        else:
            called = _CALLS.search(hlo_text, m.end(), end)
            phase = inside[called.group(1)].most_common(1)[0][0] \
                if called and called.group(1) in inside else None
            operands = _OPERANDS.search(hlo_text, m.end(), end)
            if phase is None and operands:
                phase = next((out[o] for o in _NAME.findall(operands.group(1))
                              if out.get(o)), None)
            if phase is not None:
                out.inherited.add(m.group(1))
        out[m.group(1)] = phase
    return out


def instruction_phases(name: str) -> Optional[PhaseMap]:
    """The map of the program kept under `name`; None if there is none or
    its function went before a map was built."""
    prog = _named.get(name)
    return prog.phases() if prog is not None else None


def merge_phases(maps) -> PhaseMap:
    """One map for several programs of one name (two engines in a process
    each own a `serving_step`, and a trace does not say whose event is
    whose): an instruction keeps its phase where every map that has it
    agrees, else None. Instruction names repeat across compilations, so
    programs that differ lose what differs to "(unattributed)" rather
    than be read through each other's map."""
    out = PhaseMap()
    for phases in maps:
        for name, phase in phases.items():
            if out.setdefault(name, phase) != phase:
                out[name] = None
        out.inherited |= phases.inherited
    out.inherited = {name for name in out.inherited if out[name]}
    return out


def instruction_name(event_name: str) -> str:
    """`fusion.228` of a device event named by its instruction's text,
    `%fusion.228 = bf16[...] fusion(...)`."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def device_time_by_phase(events: Iterable[Tuple[str, float, float]],
                         program):
    """(seconds, found, inherited) of the `(name, start, end)` events
    under `program`, a name kept by `register_program` or a `PhaseMap`.
    `seconds` maps each (pass, block), and UNATTRIBUTED for instructions
    with no phase or not in the program, to the summed duration; `found`
    is the share of that time whose instruction is in the program's text,
    `inherited` the share whose phase was inherited (see `parse_phases`).
    None where the program has no map."""
    phases = instruction_phases(program) if isinstance(program, str) \
        else program
    if phases is None:
        return None
    seconds: Dict[object, float] = {}
    total = found = inherited = 0.0
    for name, start, end in events:
        dur = end - start
        total += dur
        key = instruction_name(name)
        if key in phases:
            found += dur
        if key in phases.inherited:
            inherited += dur
        phase = phases.get(key) or UNATTRIBUTED
        seconds[phase] = seconds.get(phase, 0.0) + dur
    if total <= 0:
        return seconds, 0.0, 0.0
    return seconds, found / total, inherited / total
