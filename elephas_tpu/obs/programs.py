"""What a compiled program's instructions are, by model part.

The device trace names each event by its HLO instruction (``%fusion.12 =
...``) and carries no ``op_name``; the compiled program's own text does
(``metadata={op_name="jit(f)/Block_0/attention/kv_write/scatter"}``), with
the ``jax.named_scope``s and flax module paths the model was written in.
``ProgramReport`` parses that text once and keeps, for every instruction
that can appear as a device event, which part of the model issued it and
how many bytes it writes; ``obs.devprof.device_seconds_by_part`` joins a
trace to it.

    report = ProgramReport.from_compiled(jitted.lower(*args).compile())
    report.instructions["fusion.12"].part   # "TransformerLM/Block_*/mlp/Dense_0"
    report.copy_bytes()                     # bytes a call only moves

Host-side text processing only: nothing here touches a device, and
nothing builds a report unless it is asked for
(``InferenceEngine.program_report``, ``SyncTrainer.program_report``).
"""

from __future__ import annotations

import json
import re
from dataclasses import asdict, dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

__all__ = ["Instruction", "ProgramReport", "load_reports", "save_reports", "shapes_of"]

UNSCOPED = "(unscoped)"
TOP = "(top)"  # the jitted function's own body, outside every scope
# opcodes that issue no device work of their own
_FREE = {"parameter", "constant", "get-tuple-element", "tuple", "bitcast",
         "after-all", "partition-id", "replica-id", "opt-barrier"}
# instructions that only move data, where they stand on their own
_MOVERS = {"copy", "transpose", "reshape", "slice", "dynamic-slice"}
# scope components that wrap a part and name none
_WRAPPERS = {"while", "body", "cond", "closed_call", "pjit", "checkpoint",
             "rematted_computation", "custom_jvp_call", "custom_vjp_call",
             "custom_vjp_call_jaxpr", "core_call", "remat"}
_ITEMSIZE = {"pred": 1, "s4": 0.5, "u4": 0.5, "s8": 1, "u8": 1, "s16": 2,
             "u16": 2, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f32": 4,
             "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16}

_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_ARRAY = re.compile(r"\b([a-z]+\d*[a-z0-9]*)\[([\d,\s]*)\]")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="((?:[^"\\]|\\.)*)"')
_CALLED = re.compile(
    r"\b(calls|body|condition|to_apply|true_computation|false_computation)"
    r"=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_OPERAND = re.compile(r"%([\w.\-]+)")
_INDEXED = re.compile(r"^(.*?)_?(\d+)$")


def instruction_name(hlo: str) -> str:
    """`%fusion.12 = f32[...] fusion(...)` (a trace event's name) or
    `fusion.12` -> `fusion.12`: the key of `ProgramReport.instructions`."""
    return hlo.split(" = ", 1)[0].strip().lstrip("%")


def kind_of(name: str) -> str:
    """`convert_reduce_fusion.12` -> `convert_reduce_fusion`: an
    instruction's name less its number, as the benchmark's ledger lists
    device operations."""
    return re.sub(r"(\.\d+)+$", "", name)


def shape_bytes(shape: str) -> int:
    """Bytes of every array in a shape's text (`f32[4,32]{1,0}`, or a tuple
    of them), by logical size: tiling pads are not counted."""
    total = 0.0
    for dtype, dims in _ARRAY.findall(shape):
        size = _ITEMSIZE.get(dtype, 1 if dtype.startswith("f8") else None)
        if size is None:
            continue  # token[], an opaque type
        n = 1
        for d in dims.split(","):
            if d.strip():
                n *= int(d)
        total += n * size
    return int(total)


def _plain(shape: str) -> str:
    """A shape's types and sizes, its layouts and memory spaces left out."""
    return ",".join(f"{t}[{d}]" for t, d in _ARRAY.findall(shape))


def _balanced(text: str, start: int) -> int:
    """Index just past the bracket that closes the one at `start`."""
    pairs = {"(": ")", "{": "}", "[": "]"}
    depth, i, quoted = 0, start, False
    while i < len(text):
        c = text[i]
        if c == '"' and text[i - 1] != "\\":
            quoted = not quoted
        elif not quoted:
            if c in pairs:
                depth += 1
            elif c in pairs.values():
                depth -= 1
                if depth == 0:
                    return i + 1
        i += 1
    return len(text)


def _split_instruction(rest: str) -> Tuple[str, str, str, str]:
    """`<shape> <opcode>(<operands>)<attributes>` -> its four pieces."""
    rest = rest.lstrip()
    end = _balanced(rest, 0) if rest.startswith("(") else rest.find(" ")
    shape, rest = rest[:end], rest[end:].lstrip()
    paren = rest.find("(")
    opcode = rest[:paren].strip()
    close = _balanced(rest, paren)
    return shape, opcode, rest[paren + 1:close - 1], rest[close:]


def scope_of(op_name: str) -> str:
    """An `op_name` less the `jit(...)` head and the primitive at its tail;
    an argument's `params['Block_0']['kernel']` as `params/Block_0/kernel`."""
    # where the compiler merged instructions it lists every `op_name`: the first
    op_name = op_name.replace("\\'", "'").replace('\\"', '"').split(";")[0]
    if "/" not in op_name and not op_name.startswith("jit("):
        return re.sub(r"\['?([^'\]]*)'?\]", r"/\1", op_name).replace(".", "/")
    parts = op_name.split("/")
    if re.fullmatch(r"(jit|pjit)\(.*\)", parts[0]):
        parts.pop(0)
        if parts and parts[0] == "jit(main)":
            parts.pop(0)
    return "/".join(parts[:-1])


def _components(scope: str) -> List[str]:
    """A scope's components that name a part: no `jit(...)` wrapper, no
    control-flow wrapper, no `Module._method` frame; a transformed scope
    (`transpose(jvp(forward))`) by what it transforms. Where nothing but
    jitted helpers name it (`jit(_shuffle)/while/body/add`), by those."""
    out, jitted = [], []
    for c in scope.split("/"):
        inner = re.fullmatch(r"(?:\w+\()+([^()]*)\)+", c)
        if inner and c.startswith(("jit(", "pjit(")):
            jitted.append(inner.group(1))
            continue
        if inner:
            c = inner.group(1)
        if not c or c in _WRAPPERS or re.fullmatch(r"branch_\d+_fun", c) or \
                "." in c or "->" in c:  # a method's frame, an einsum's spec
            continue
        out.append(c)
    return out or [c for c in jitted if c]


@dataclass
class Instruction:
    name: str
    opcode: str
    computation: str
    scope: str = ""
    part: str = UNSCOPED
    out_bytes: int = 0
    out_shape: str = ""
    # the first operand: what a copy or an asynchronous pair moves
    operand: Optional[str] = None
    operand_shape: str = ""
    # a fusion of more than one part: the root's part above, all of them here
    mixed: bool = False
    parts: List[str] = field(default_factory=list)
    start: Optional[str] = None  # a `-done`'s `-start`
    # what an `async-start` / `async-done` wraps (`slice`): the chip's runtime
    # prints `%slice-start.4 = ... async-start(...), calls=%async_computation`
    # where a compile for a described chip prints `slice-start(...)`
    wraps: str = ""
    # the asynchronous operation whose computation holds this instruction: the
    # chip may name an event by it, and the pair's `-done` counts its bytes
    inside: str = ""
    called: List[str] = field(default_factory=list)

    @property
    def kind(self) -> str:
        return kind_of(self.name)

    @property
    def moves_data(self) -> bool:
        if self.inside:
            return False
        return self.opcode in _MOVERS or (self.opcode.endswith("-done") and (
            self.opcode[:-5] in _MOVERS or self.wraps in _MOVERS))


class ProgramReport:
    """Every instruction of one compiled program that can appear as a device
    event: the entry computation and the bodies of its `while`,
    `conditional` and `call`; of a fused computation's inside only a fusion
    nested in it."""

    def __init__(self, program: str, instructions: Dict[str, Instruction]):
        self.program = program
        self.instructions = instructions

    # -- building ----------------------------------------------------------

    @classmethod
    def from_compiled(cls, compiled) -> "ProgramReport":
        """From `jitted.lower(...).compile()`."""
        return cls.from_text(compiled.as_text())

    @classmethod
    def from_text(cls, text: str) -> "ProgramReport":
        program, computations, entry, operands, bare = _parse(text)
        live, seen, inside = [], set(), []

        def nested(fusion: Instruction) -> None:
            # a fusion inside a fusion is an event of its own on the chip (the
            # outer one's self time is what the inner ones leave)
            for called in fusion.called:
                for ins in computations.get(called, ()):
                    if ins.opcode == "fusion":
                        live.append(ins)
                        inside.append((ins, fusion))
                        nested(ins)

        def walk(comp: str) -> None:
            if comp in seen or comp not in computations:
                return
            seen.add(comp)
            for ins in computations[comp]:
                live.append(ins)
                # a fusion's, a reduction's or a sort's computation runs inside
                # its instruction and shows as no event of its own; an
                # asynchronous operation's may (`inside`)
                if ins.opcode in ("while", "conditional", "call"):
                    for called in ins.called:
                        walk(called)
                elif ins.opcode == "fusion":
                    nested(ins)
                elif ins.opcode.endswith("-start"):
                    for called in ins.called:
                        before = len(live)
                        walk(called)
                        for inner in live[before:]:
                            inner.inside = inner.inside or ins.name

        walk(entry)
        everything = {i.name: i for body in computations.values() for i in body}
        # an `op_name` of one word is an argument's (`table`, and the copies of
        # it), or one the compiler made up (`sort`, `ragged-dot-metadata`)
        arguments = {i.scope for i in computations.get(entry, ())
                     if i.opcode == "parameter"}
        for name in bare:
            if everything[name].scope not in arguments:
                everything[name].scope = ""
        for ins in live:
            if ins.opcode.startswith("async-") and ins.called:
                wrapped = computations.get(ins.called[0]) or [ins]
                ins.wraps = wrapped[-1].opcode  # its root: the last instruction
                ins.scope = ins.scope or wrapped[-1].scope
        _resolve_scopes(live, everything, operands)
        for ins, fusion in inside:  # it reads the outer one's parameters
            ins.scope = ins.scope or fusion.scope
        layers = _layer_names(i.scope for i in live)
        for ins in everything.values():
            ins.part = _part(ins.scope, layers)
        for ins in live:
            if ins.opcode == "fusion":
                _fusion_parts(ins, computations)
            if ins.start is not None:
                start = everything.get(ins.start)
                if start is not None:
                    ins.scope, ins.part, ins.wraps = start.scope, start.part, start.wraps
                    ins.operand, ins.operand_shape = start.operand, start.operand_shape
        return cls(program, {i.name: i for i in live})

    # -- reading -----------------------------------------------------------

    def lookup(self, hlo: str) -> Optional[Instruction]:
        """The instruction a trace event (named by its HLO text) is: the one
        of its name and, where the event's text gives a result, of that shape
        (two compiles of one function may number their instructions apart;
        a name that coincides on another shape is no join)."""
        found = self.instructions.get(instruction_name(hlo))
        if found is not None and " = " in hlo:
            shape = hlo.split(" = ", 1)[1]
            end = _balanced(shape, 0) if shape.startswith("(") else shape.find(" ")
            if end > 0 and _plain(shape[:end]) != _plain(found.out_shape):
                return None
        return found

    def copies(self) -> List[Instruction]:
        """The instructions that only move data, an asynchronous pair once
        (its `-done`, which holds the result's shape and the `-start`'s
        scope and operand)."""
        return [i for i in self.instructions.values() if i.moves_data]

    def copy_bytes(self) -> int:
        """Bytes one call of the program writes in instructions that only
        move data (an instruction in a loop's body counted once)."""
        return sum(i.out_bytes for i in self.copies())

    def copies_by_part(self, n: Optional[int] = None) -> List[list]:
        """`[part, bytes, instructions]`, largest first."""
        total: Dict[str, list] = {}
        for i in self.copies():
            row = total.setdefault(i.part, [i.part, 0, 0])
            row[1] += i.out_bytes
            row[2] += 1
        return sorted(total.values(), key=lambda r: -r[1])[:n]

    def parts(self) -> Dict[str, int]:
        """Instructions that issue device work, counted by part."""
        out: Dict[str, int] = {}
        for i in self.instructions.values():
            if i.opcode not in _FREE:
                out[i.part] = out.get(i.part, 0) + 1
        return out

    # -- keeping -----------------------------------------------------------

    def to_dict(self) -> dict:
        return {"program": self.program,
                "instructions": [asdict(i) for i in self.instructions.values()]}

    @classmethod
    def from_dict(cls, doc: dict) -> "ProgramReport":
        return cls(doc["program"],
                   {d["name"]: Instruction(**d) for d in doc["instructions"]})


def shapes_of(tree, sharding=None):
    """A tree's leaves (arrays or ``jax.ShapeDtypeStruct``s alike) as shapes,
    types and placement: what a program is lowered from. An array that is
    committed to its devices keeps its sharding and one that is not says
    nothing of it, as the call with the arrays themselves lowers: a sharding
    stated where the live call states none is another module, which the
    compiler may number apart (`doc-mix-32k`'s decode program: PERF.md,
    Findings PR 38). With ``sharding``, every leaf placed there instead (a
    described chip's)."""
    import jax

    def place(a):
        if sharding is not None:
            return sharding
        return getattr(a, "sharding", None) if getattr(a, "committed", True) else None

    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=place(a)), tree)


def save_reports(path: str, reports: Iterable[ProgramReport]) -> None:
    with open(path, "w") as f:
        json.dump({"reports": [r.to_dict() for r in reports]}, f)


def load_reports(path: str) -> List[ProgramReport]:
    """A file `save_reports` wrote, or a compiled program's text as
    `compiled.as_text()` gives it."""
    with open(path) as f:
        text = f.read()
    if text.lstrip().startswith("HloModule"):
        return [ProgramReport.from_text(text)]
    return [ProgramReport.from_dict(d) for d in json.loads(text)["reports"]]


# -- parsing ---------------------------------------------------------------


def _parse(text: str):
    header = re.match(r"\s*HloModule\s+([\w.\-]+)", text)
    program = header.group(1) if header else "unknown"
    computations: Dict[str, List[Instruction]] = {}
    operands: Dict[str, List[str]] = {}
    bare = set()
    entry = current = None
    for line in text.splitlines():
        if current is None:
            m = _COMPUTATION.match(line)
            if m:
                current = m.group(2)
                computations[current] = []
                if m.group(1):
                    entry = current
            continue
        if line.startswith("}"):
            current = None
            continue
        m = _INSTRUCTION.match(line)
        if m is None:
            continue
        shape, opcode, reads, attrs = _split_instruction(m.group(3))
        ins = Instruction(m.group(2), opcode, current, out_shape=shape,
                          out_bytes=shape_bytes(shape))
        named = operands[ins.name] = _OPERAND.findall(reads)
        if named:
            ins.operand = named[0]
        op_name = _OP_NAME.search(attrs)
        if op_name:
            ins.scope = scope_of(op_name.group(1))
            if "/" not in op_name.group(1):
                bare.add(ins.name)
            elif not ins.scope:
                ins.scope = TOP
        ins.called = [c for _, c in _CALLED.findall(attrs)]
        for group in _BRANCHES.findall(attrs):
            ins.called += _OPERAND.findall(group)
        if opcode.endswith("-done") and ins.operand:
            ins.start = ins.operand
        if opcode.endswith("-start"):
            ins.out_bytes = 0  # what lands is the `-done`'s result
        computations[current].append(ins)
    if entry is None and computations:
        entry = list(computations)[-1]
    return program, computations, entry, operands, bare


def _resolve_scopes(live: List[Instruction], everything: Dict[str, Instruction],
                    operands: Dict[str, List[str]]) -> None:
    """An instruction that carries no `op_name` of its own (a `copy-start`,
    a `get-tuple-element`) takes the scope of what it reads, through to the
    argument it came from; failing that, of what reads it."""
    for ins in live:
        if ins.operand in everything:
            ins.operand_shape = everything[ins.operand].out_shape
    for ins in live:  # in program order: what it reads is resolved already
        if not ins.scope:
            ins.scope = next((everything[o].scope for o in operands[ins.name]
                              if o in everything and everything[o].scope), "")
    users: Dict[str, str] = {}
    for ins in reversed(live):
        if not ins.scope:
            ins.scope = users.get(ins.name, "")
        if ins.scope:
            for o in operands[ins.name]:
                users[o] = ins.scope


def _layer_names(scopes: Iterable[str]) -> set:
    """(prefix, stem) of every numbered component that the program holds
    with more than one number at that place."""
    seen: Dict[tuple, set] = {}
    for scope in scopes:
        comps = _components(scope)
        for at, c in enumerate(comps):
            m = _INDEXED.match(c)
            if m:
                seen.setdefault((tuple(comps[:at]), m.group(1)), set()).add(m.group(2))
    return {key for key, numbers in seen.items() if len(numbers) > 1}


def _part(scope: str, layers: set) -> str:
    """The scope's naming components with the OUTERMOST of `layers` in it
    collapsed: the model's layers. `Dense_0` and `Dense_1` inside a block are
    parts of one layer and stay apart."""
    comps = _components(scope)
    for at, c in enumerate(comps):
        m = _INDEXED.match(c)
        if m and (tuple(comps[:at]), m.group(1)) in layers:
            comps[at] = c[:m.start(2)] + "*"
            break
    # a scope of wrappers and frames alone is the jitted function's own body
    return "/".join(comps) or (TOP if scope else UNSCOPED)


def _fusion_parts(ins: Instruction, computations: Dict[str, List[Instruction]]) -> None:
    found: List[str] = []
    for called in ins.called:
        for inner in computations.get(called, ()):
            if inner.opcode in _FREE or not inner.scope:
                continue
            if inner.part not in found:
                found.append(inner.part)
    if ins.part == UNSCOPED and found:
        ins.part = found[-1]  # the root is a computation's last instruction
    if len(found) > 1:
        ins.mixed, ins.parts = True, found
