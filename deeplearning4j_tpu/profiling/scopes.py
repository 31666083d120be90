"""The compiled step's own scope table: which layer a number-named
operation of a device trace belongs to.

A profile of a compiled step names what XLA made: ``fusion.14``,
``while.35``. The program knows more. Every node of a container runs under
``jax.named_scope(<node>)``, the layers write scopes of the form
``<family>:<part>`` (``gdn:conv``, ``moe:dispatch``, ``train:update``), and
both reach the ``op_name`` of every instruction of the compiled step. The
trace's operation names ARE that step's instruction names, and the program
holds the executable that ran. So the join is made here:

- ``record_step(name, fn, args)``, called by the fit loop once a compiled
  step after its first dispatch, asks jax for the executable it has just
  run (``fn.lower(specs).compile()`` on the shapes that ran is a cache hit:
  no trace, no lowering, no compile) and keeps its HLO module, a host-side
  object, and nothing else: no net, no function, no ``Compiled``, no
  buffer. One module a name: a newer step takes the older one's place.
- ``step_table(name)`` makes of that module's text, on first request,
  ``{instruction: op_name}`` over every computation of the module
  (``analysis/shardcheck.parse_hlo_module`` reads the text), and keeps the
  table in the module's place.
- ``split(op_name)`` gives ``(node, scope, phase)``.
- ``by_scope(events, table)`` gives one device line's busy time by
  ``(node, scope, phase)``, containers (a ``while``, a conditional, a
  grouped product) counted less what runs inside them.

No jax import at module load, as the package's other legs.
"""

from __future__ import annotations

import collections
import functools
import logging
import re
import threading
from typing import Dict, Iterable, NamedTuple, Optional, Tuple

logger = logging.getLogger(__name__)


# ---------------------------------------------------------------------------
# the registry
# ---------------------------------------------------------------------------

class StepTable(dict):
    """``{instruction: op_name}`` of one compiled program (``""`` where XLA
    left none), with each instruction's HLO ``opcode`` and two sets:
    ``inherited``, the instructions XLA left without an ``op_name`` that
    carry the one most of the computation they call carries, and
    ``products``, the matrix products: a ``convolution`` or ``dot`` and
    every fusion that holds one (a scope's MXU work told from the
    elementwise passes beside it)."""

    def __init__(self):
        super().__init__()
        self.opcode: Dict[str, str] = {}
        self.inherited: set = set()
        self.products: set = set()


_lock = threading.Lock()
_kept: Dict[str, object] = {}   # name -> its HLO module; its table once asked
_warned = False


def _spec(a):
    """What ``lower`` needs to find the computation that ran: shape, dtype,
    weak type and, of a committed array, its sharding (jit keys its
    computations by an uncommitted argument as unspecified)."""
    import jax
    return jax.ShapeDtypeStruct(
        a.shape, a.dtype, weak_type=a.aval.weak_type,
        sharding=a.sharding if a.committed else None)


def record_step(name: str, fn, args) -> bool:
    """Keep the HLO module of the program that ``fn(*args)`` has just run,
    under ``name`` (its module's name in a trace: ``jit_train_step``), in
    the place of whatever was kept under that name. ``args`` may have been
    donated: a deleted array still has its shape, dtype and sharding. It
    costs host time alone, most of it the runtime's copy of the optimized
    module (``hlo_modules()``: 0.2 to 0.4 s for a step of 13,000 to 28,000
    instructions), and no compile: if ``lower().compile()`` compiled
    (``jax_compile_total`` moved, so the specs did not meet the cached
    computation), or anything raises, one warning is logged and nothing is
    kept. Training goes on either way."""
    global _warned
    import jax

    from deeplearning4j_tpu.profiling.metrics import get_registry
    compiles = get_registry().counter(
        "jax_compile_total", help="number of jit:compile events")
    before = compiles.value
    try:
        compiled = fn.lower(*jax.tree.map(_spec, args)).compile()
        if compiles.value != before:
            raise RuntimeError("the step compiled again: its specs did "
                               "not meet the computation that ran")
        module = compiled.runtime_executable().hlo_modules()[0]
    except Exception as e:  # noqa: BLE001 — a profile must not stop a fit
        if not _warned:
            _warned = True
            logger.warning("scopes: no table kept for %s: %s: %s",
                           name, type(e).__name__, e)
        return False
    with _lock:
        _kept[name] = module
    return True


def kept() -> dict:
    """What the registry holds: ``{name: the HLO module, or the table that
    was made of it}``."""
    with _lock:
        return dict(_kept)


def step_table(name: str = "jit_train_step") -> Optional[StepTable]:
    """The table of the newest step kept under ``name``, or None. Two nets'
    steps of one name in one process are told apart by recency alone."""
    with _lock:
        held = _kept.get(name)
        if held is not None and not isinstance(held, StepTable):
            # the table is all a reader wants: the module goes
            held = _kept[name] = parse(held.to_string())
        return held


def clear() -> None:
    with _lock:
        _kept.clear()


# ---------------------------------------------------------------------------
# the text of a compiled module -> {instruction: op_name}
# ---------------------------------------------------------------------------

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\b(?:calls|body)=%?([\w.\-]+)")
_PRODUCTS = ("convolution", "dot")


def parse(text: str) -> StepTable:
    """The table of one module's text (``HloModule.to_string()``,
    ``Compiled.as_text()``): every instruction of every computation, a
    loop's body and a fused computation included. An instruction without
    an ``op_name`` that calls a computation (a fusion; a ``while`` by its
    body) inherits from it: of the ``(node, scope, phase)`` most of that
    computation's instructions carry, the ``op_name`` most of those
    carry."""
    from deeplearning4j_tpu.analysis.shardcheck import parse_hlo_module
    computations = parse_hlo_module(text).computations
    table = StepTable()
    callers = []                        # (instruction, callee), unnamed
    fusions = []                        # (instruction, fused computation)
    for instructions in computations.values():
        for ins in instructions:
            op = _OP_NAME.search(ins.line)
            table[ins.name] = op.group(1) if op is not None else ""
            table.opcode[ins.name] = ins.opcode
            callee = _CALLS.search(ins.line)
            if callee is not None and not table[ins.name]:
                callers.append((ins.name, callee.group(1)))
            if ins.opcode in _PRODUCTS:
                table.products.add(ins.name)
            elif ins.opcode == "fusion" and callee is not None:
                fusions.append((ins.name, callee.group(1)))
    inside = lambda callee: [i.name for i in computations.get(callee, ())]
    # a callee's own unnamed callers first: the text defines a computation
    # before the one that calls it, and so lists them in that order
    for name, callee in callers:
        groups: Dict[tuple, collections.Counter] = {}
        for inner in inside(callee):
            if table[inner]:
                groups.setdefault(split(table[inner]),
                                  collections.Counter())[table[inner]] += 1
        if groups:
            most = max(groups.values(), key=lambda c: sum(c.values()))
            table[name] = most.most_common(1)[0][0]
            table.inherited.add(name)
    table.products.update(
        name for name, callee in fusions
        if not table.products.isdisjoint(inside(callee)))
    return table


# ---------------------------------------------------------------------------
# an op_name -> (node, scope, phase)
# ---------------------------------------------------------------------------

class Scope(NamedTuple):
    node: Optional[str]     # the container's node, None in the step's shell
    scope: Optional[str]    # the innermost ``<family>:<part>``, or None
    phase: str              # "fwd", "remat" (a forward run again) or "bwd"


_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")
_PROGRAM_SCOPE = re.compile(r"^[A-Za-z_]\w*:[\w.\-]+$")
# what jax itself writes between the scopes: a call's, a loop's, a
# conditional's and a checkpoint's own levels
_STRUCTURAL = re.compile(
    r"^(closed_call|core_call|checkpoint|rematted_computation|remat\d*|"
    r"while|body|cond|branch_\d+_fun|custom_[jv][jv]p_call(_jaxpr)?|"
    r"pallas_call|shard_map|scan)$")


def _parts(path: str) -> list:
    """``path`` cut at the slashes outside every bracket."""
    out, depth, start = [], 0, 0
    for i, ch in enumerate(path):
        depth += (ch == "(") - (ch == ")")
        if ch == "/" and depth == 0:
            out.append(path[start:i])
            start = i + 1
    out.append(path[start:])
    return out


@functools.lru_cache(maxsize=65536)
def split(op_name: str) -> Scope:
    """``(node, scope, phase)`` of an ``op_name``, read from left to right.

    A path is ``jit(train_step)/<level>/.../<primitive>``; a level is a
    scope's name, bare or wrapped in the transforms it was traced under:
    ``jvp(b0_mix)``, ``transpose(jvp(gdn:conv))``, ``transpose(jvp())``.
    The node is the first name that is neither jax's own (``while``,
    ``body``, ``closed_call``, ``checkpoint``, a ``jit(...)``'s function) nor
    a program scope, and stands before every program scope: the containers
    open a node's scope outermost. The scope is the LAST ``<family>:
    <part>``. The phase: forward until a level is transposed, then
    backward; a level inside the backward that is differentiated afresh
    (``jvp`` and no ``transpose``) is a forward run again, which is how
    ``nn/remat.py`` rebuilds (``jax.vjp`` inside the backward rule), and so
    is everything under ``jax.checkpoint``'s ``rematted_computation``. One
    exception, as jax 0.9.0 writes a ``custom_vjp``'s backward rule that
    was met inside such a rebuild (a kernel's): a level transposed TWICE
    (the rule's context, transposed) and after it the levels of the call as
    the forward wrote them, ``jvp(ssm:scan)`` among them: after a level
    transposed twice a ``jvp`` level with a name is the forward's record
    and the phase stays backward, and only a bare ``jvp()`` starts a
    rebuild. Of instructions merged by XLA (``a;b``) the first speaks."""
    parts = _parts(op_name.partition(";")[0])
    node = scope = None
    phase, recorded = "fwd", False
    for i, part in enumerate(parts):
        transforms = []
        m = _WRAPPED.match(part)
        while m is not None:
            transforms.append(m.group(1))
            part = m.group(2)
            m = _WRAPPED.match(part)
        if "transpose" in transforms:
            phase = "bwd"
            recorded = recorded or transforms.count("transpose") > 1
        elif "jvp" in transforms and phase == "bwd" and not (
                recorded and part):
            phase = "remat"
        if "jit" in transforms or "pjit" in transforms:
            continue                    # a function's name, not a scope's
        if _PROGRAM_SCOPE.match(part):
            scope = part
        elif (node is None and scope is None and part and "->" not in part
              and i < len(parts) - 1 and not _STRUCTURAL.match(part)):
            node = part
    if "rematted_computation" in parts:
        phase = "remat"
    return Scope(node, scope, phase)


# ---------------------------------------------------------------------------
# one device line's events -> seconds by scope
# ---------------------------------------------------------------------------

def self_seconds(events: Iterable) -> Dict[str, float]:
    """``{instruction: seconds}`` over ``(name, start_ns, dur_ns)`` events
    of one device line: each event's duration less what the events nested
    in its interval take (a ``while`` and the operations of its body lie on
    one line), summed by the name's first word (a custom call's event
    carries its target and kernel after it). The values add up to the
    line's busy time."""
    order = sorted(events, key=lambda e: (e[1], -e[2]))
    own = []                            # [instruction, self ns]
    open_ = []                          # (end, index into own), outermost first
    for name, start, dur in order:
        end = start + dur
        while open_ and open_[-1][0] <= start:
            open_.pop()
        if open_:
            end = min(end, open_[-1][0])    # held to its container
            own[open_[-1][1]][1] -= end - start
        open_.append((end, len(own)))
        own.append([name.split(" ", 1)[0], end - start])
    out: Dict[str, float] = {}
    for name, ns in own:
        out[name] = out.get(name, 0.0) + ns / 1e9
    return out


def by_scope(events: Iterable, table: dict) -> Tuple[dict, float, float]:
    """``({(node, scope, phase): seconds}, unknown, unlabelled)``: the self
    time of every event whose instruction ``table`` names, by what
    ``split`` makes of its ``op_name``; the seconds of the events it does
    not hold (another program's, a stale table); and those whose
    ``op_name`` names no node and no scope. The three add up to the line's
    busy time."""
    scoped: Dict[Scope, float] = {}
    unknown = unlabelled = 0.0
    for name, seconds in self_seconds(events).items():
        op_name = table.get(name)
        if op_name is None:
            unknown += seconds
            continue
        where = split(op_name)
        if where.node is None and where.scope is None:
            unlabelled += seconds
        else:
            scoped[where] = scoped.get(where, 0.0) + seconds
    return scoped, unknown, unlabelled
