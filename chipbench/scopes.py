"""Device time of the program's DP stages, read through its named scopes.

The program names the stages of a DP step with ``jax.named_scope``:
``dp.norm_pass`` (forward, first backward, per-sample norms, clip factors),
``dp.tap_norm`` and the tap's name inside it (each tap's norm work: Gram
products, instantiated per-sample gradients, pads, the ghost-norm kernel),
``dp.second_pass`` (the gradient stage), ``dp.noise`` and ``dp.update``.
XLA keeps the scopes in each instruction's ``op_name`` metadata, which the
compiled program's text shows.

The cell's compiled programs are the ``jax.stages.Compiled`` values among
the trainer's attributes.  A device op event names its instruction
(``%name = <shape> <opcode>(...)``).  Instruction names are unique within a
program, not across programs: an event belongs to the program whose
instruction of that name has the event's result shape and opcode; where
several fit, to the program of the nearest event in time that fits one
alone, since a program's ops run together.  Events that fit no program
(the batch maker's, say) are left out.

The stage of an instruction is the first component of its ``op_name`` path
that starts with ``dp.`` (of the first ``;``-joined name that has one); it
is a tap-norm op where ``dp.tap_norm`` appears anywhere in the path.  The
compiler's own instructions (copies, slices, prefetches, argument
relayouts) name no traced op and take the label of the nearest traced
instruction that uses them, else of their operands.  A ``while`` op's interval holds its body's ops, so
every instant of device time goes to the innermost op running then: a
stage's time is the time its ops own within the window, averaged over the
chips, over the steps (``chipbench.sync`` spans), and the stages and the
unscoped ops add up to the programs' busy time.  On a program without the
scopes every stage is empty and the readers return nothing.
"""
from __future__ import annotations

import collections
import dataclasses
import math
import re
import sys
from typing import Optional

from chipbench import trace as trace_mod

INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%(\S+) = (.*)$", re.M)
HEAD = re.compile(r"^(.*?) ([\w\-]+)\(")
OP_NAME = re.compile(r'op_name="([^"]*)"')
REFERENCE = re.compile(r"%([\w.\-]+)")
TRACED = "jit("  # the op_name of every op traced under jit starts so
TAP_NORM = "dp.tap_norm"
STAGES = ("dp.norm_pass", "dp.second_pass", "dp.noise", "dp.update")


def stage_of(op_name: str) -> Optional[str]:
    for name in op_name.split(";"):
        for part in name.split("/"):
            if part.startswith("dp."):
                return part
    return None


def head(text: str) -> Optional[str]:
    """``<result shape> <opcode>`` of an instruction's text after ``=``."""
    m = HEAD.match(text)
    return f"{m.group(1)} {m.group(2)}" if m else None


@dataclasses.dataclass(frozen=True)
class Instruction:
    program: str
    head: Optional[str]
    stage: Optional[str]
    tap_norm: bool


def _labels(text: str) -> dict[str, tuple[str, Optional[str], bool]]:
    """Instruction name -> (head, stage, tap-norm) in one program's text.

    Instructions the compiler made itself (its copies, slices, prefetches)
    name no traced op: no ``op_name``, or an argument's path; each takes the
    label of the nearest traced instruction among those that use it, else
    among its operands.
    """
    found = INSTRUCTION.findall(text)
    op_names, heads, operands = {}, {}, {}
    for name, rest in found:
        m = OP_NAME.search(rest)
        op_names[name] = m.group(1) if m and m.group(1).startswith(TRACED) else ""
        heads[name] = head(rest)
        operands[name] = REFERENCE.findall(rest.split(", metadata=")[0])
    users: dict[str, list[str]] = {}
    for name, refs in operands.items():
        refs[:] = [r for r in refs if r in op_names and r != name]  # not computations
        for ref in refs:
            users.setdefault(ref, []).append(name)

    def nearest(name, links):
        seen, frontier = {name}, [name]
        while frontier:
            frontier = [n for f in frontier for n in links.get(f, ()) if n not in seen]
            for n in frontier:
                if op_names[n]:
                    return op_names[n]
                seen.add(n)
        return None

    out = {}
    for name, op_name in op_names.items():
        if not op_name:
            op_name = nearest(name, users) or nearest(name, operands) or ""
        out[name] = (heads[name], stage_of(op_name), TAP_NORM in op_name)
    return out


def instructions(programs: dict[str, str]) -> dict[str, list[Instruction]]:
    """Instruction name -> the programs' instructions of that name, from
    the programs' HLO texts (``{program: text}``)."""
    table: dict[str, list[Instruction]] = {}
    for program, text in programs.items():
        for name, (h, stage, tap) in _labels(text).items():
            table.setdefault(name, []).append(Instruction(program, h, stage, tap))
    return table


def _resolve(events: list[trace_mod.Event], table) -> list[Optional[Instruction]]:
    """The instruction each event (in start order) ran, or None."""
    cands: list[list[Instruction]] = []
    for ev in events:
        name, _, rest = ev.name.partition(" = ")
        found = table.get(name.lstrip("%"), [])
        h = head(rest) if found else None
        if h is not None:  # None: the event's name was cut before its opcode
            found = [i for i in found if i.head == h]
        cands.append(found)
    progs = {i.program for c in cands for i in c}
    if len(progs) <= 1:
        return [c[0] if c else None for c in cands]
    # the nearest event before and after each one that fits a single program
    before: list = [None] * len(cands)
    after: list = [None] * len(cands)
    last = None
    for k in range(len(cands)):
        before[k] = last
        if len(cands[k]) == 1:
            last = (events[k].end, cands[k][0].program)
    last = None
    for k in range(len(cands) - 1, -1, -1):
        after[k] = last
        if len(cands[k]) == 1:
            last = (events[k].start, cands[k][0].program)
    out = []
    for ev, c, b, a in zip(events, cands, before, after):
        if len(c) <= 1:
            out.append(c[0] if c else None)
            continue
        near = sorted(([(ev.start - b[0], b[1])] if b else [])
                      + ([(a[0] - ev.end, a[1])] if a else []))
        out.append(next((i for _, p in near for i in c if i.program == p), c[0]))
    return out


@dataclasses.dataclass
class StageTimes:
    """Device seconds per step: of each stage, of the tap-norm ops, of the
    ops in no stage, and of all the programs' ops."""
    stages: dict[str, float]
    tap_norm: float
    noise_update: float
    unscoped: float
    programs: float


def _innermost(spans) -> dict:
    """Time of each label when every instant goes to the innermost span
    open then; ``spans`` are (start, end, label), outer spans first among
    those that start together.  The labels' times sum to the union."""
    out: dict = collections.Counter()
    stack: list = []  # (end, label) of the open spans, innermost last
    at = None

    def run_to(t):
        nonlocal at
        while stack and stack[-1][0] <= t:
            end, label = stack.pop()
            if end > at:
                out[label] += end - at
                at = end
        if stack and t > at:
            out[stack[-1][1]] += t - at
        at = max(at, t)

    for start, end, label in spans:
        at = start if at is None else at
        run_to(start)
        stack.append((end, label))
    if stack:
        run_to(math.inf)
    return out


def stage_times(trace: trace_mod.Trace, programs: dict[str, str]) -> Optional[StageTimes]:
    """Per-step stage times of ``programs`` (``{name: HLO text}``) in the
    trace's window; None when the trace has no device op or no step."""
    steps = trace.count_spans("chipbench.sync")
    if not trace.ops or steps == 0:
        return None
    lo, hi = trace.window
    table = instructions(programs)
    total: dict = collections.Counter()
    for plane_events in trace.ops.values():
        events = sorted((e for e in plane_events if e.end > lo and e.start < hi),
                        key=lambda e: (e.start, -e.end))
        labels = [None if i is None else (i.stage if i.stage in STAGES else None, i.tap_norm)
                  for i in _resolve(events, table)]
        total.update(_innermost(
            (max(e.start, lo), min(e.end, hi), label) for e, label in zip(events, labels)))
    per = collections.Counter()
    for label, ns in total.items():
        if label is None:  # another program's op
            continue
        stage, tap = label
        per[stage] += ns
        per["tap_norm"] += ns if tap else 0.0
        per["programs"] += ns
    per = {k: v / 1e9 / len(trace.ops) / steps for k, v in per.items()}
    stages = {s: per.get(s, 0.0) for s in STAGES}
    return StageTimes(stages=stages, tap_norm=per.get("tap_norm", 0.0),
                      noise_update=stages["dp.noise"] + stages["dp.update"],
                      unscoped=per.get(None, 0.0), programs=per.get("programs", 0.0))


def compiled_programs(trainer) -> dict[str, str]:
    """HLO text of each compiled program the trainer holds."""
    import jax

    return {name: value.as_text() for name, value in vars(trainer).items()
            if isinstance(value, jax.stages.Compiled)}


_last: list = [None, None]  # the trace last read, and its stage times


def read(ctx) -> Optional[StageTimes]:
    """The cell's stage times, computed once per trace; a line on stderr
    gives them all, with the coverage the metrics leave out."""
    if _last[0] is not ctx.trace:
        times = stage_times(ctx.trace, compiled_programs(ctx.trainer)) if ctx.trace.ops else None
        _last[:] = [ctx.trace, times]
        if times is not None:
            parts = [f"{k}={1e3 * v:.3f}" for k, v in times.stages.items()] + [
                f"{k}={1e3 * getattr(times, k):.3f}"
                for k in ("tap_norm", "noise_update", "unscoped", "programs")]
            print(f"chipbench: stage ms/step {' '.join(parts)}", file=sys.stderr)
    return _last[1]


def stage_ms(ctx, pick) -> Optional[float]:
    """Milliseconds per step of ``pick(times)``; None where it is empty."""
    times = read(ctx)
    value = pick(times) if times is not None else 0.0
    return 1e3 * value if value > 0 else None
