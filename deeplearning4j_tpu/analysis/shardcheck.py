"""shardcheck: static analysis of COMPILED step programs.

The third analysis layer. graphcheck validates the *config* before any
array exists; jaxlint validates the *source* before any trace runs;
shardcheck validates the *emitted program* — the jaxpr + StableHLO from
``jit(step).lower(...)`` and the post-SPMD optimized HLO from
``.compile()`` — because every compiled-program invariant the repo's
bitwise-parity discipline depends on ("XLA folded the gradient
all-reduce + shard slice into a reduce-scatter", "GSPMD did not
repartition the ga-scan body", "the fp32 preset gated every cast out",
"donation landed") lives in the program XLA emits, not in the Python
that requested it. Until now those invariants were guarded only by
minutes-long runtime smoke gates (``tools/zero1_smoke.py`` etc.) or by
comments pinned in ``parallel/trainer.py``; shardcheck re-proves them
on CPU in seconds, with no training run.

Rules (stable ids; severities in parentheses):

- SC001 full-grad-allreduce (error)   a zero1/zero2 update path carries
        a param-sized gradient all-reduce that is CONSUMED at full size
        — the reduce-scatter layout the mode promises never formed
        (the update runs replicated; updater-HBM and comm wins are
        gone). An all-reduce whose every consumer shrinks it to the
        1/dp shard is the CPU backend's *unfolded but equivalent*
        reduce-scatter form and passes (TPU/GPU pipelines fold it into
        a literal ``reduce-scatter``; XLA:CPU leaves the pair).
- SC002 collective-inventory (info)   per-step collective census: op
        kind, count, shapes, per-chip ring-model bytes; (warning) under
        zero1/zero2 more full-size ``(dp, chunk)`` all-gathers than
        param leaves — something beyond the single param all-gather the
        ZeRO contract allows ships full tensors every update.
- SC003 scan-body-repartition (error) an ALL-GATHER inside the
        gradient-accumulation scan's while-loop body — the exact GSPMD
        repartition hazard the ``to_shards`` comment in
        ``parallel/trainer.py`` pins: sharded weights re-gathered per
        MICROBATCH means the per-microbatch replicated anchor was lost
        and bitwise parity dies with it. (Per-microbatch all-REDUCEs in
        the body are the contract's expected traffic — a gradient
        reduction per microbatch is exactly the ``(k+1)``-unit comm
        model — and are not flagged.)
- SC004 precision-boundary (error)    under a mixed policy (bf16/fp16)
        the program must actually compute in the half dtype (>= 1
        dot/conv with half operands in the StableHLO) while the master
        weights, updater state, and loss cross the step boundary in
        fp32; under the fp32 preset the program must be CONVERT-OP-
        IDENTICAL to the pre-policy baseline program (the bitwise-
        parity surface).
- SC005 donation-dropped (error)      the step was expected to donate
        its state buffers but the lowered program requests no donation
        (``donate_argnums`` missing), or the request did not survive
        compilation (no ``input_output_alias`` in the compiled module)
        — either way old params/opt state stay alive across the update
        and peak HBM doubles.
- SC006 host-transfer (error)         an ``infeed``/``outfeed``/host
        callback custom-call/host send-recv inside the compiled step: a
        host round-trip serialized with every step.
- SC007 comm-bytes-calibration (info/warning) HLO-derived per-chip
        collective bytes (ring model) vs the
        ``profiling/cost.dp_comm_bytes_per_update`` prediction — the
        measured-vs-predicted calibration metric the cost-model
        autotuner (ROADMAP item 4) consumes. Outside the tolerance it
        warns; otherwise it reports the delta.

Entry points: :func:`lower_step_program` (jitted fn + example args ->
:class:`StepProgram`), :func:`check_step_program` (program + declared
layout context -> findings), plus ``net.shardcheck(batch)`` installed on
both containers (``nn/netcommon.ShardCheckMixin``) and
``trainer.shardcheck(batch)`` on the three data-parallel trainers. The
CLI (fixture self-check + the zero1/zero2/bf16 contract gate
``tools/run_checks.sh`` runs before any bitwise smoke) lives in
``tools/shardcheck.py``; compiled-program fixtures in
``analysis/fixtures.py``.

The module itself imports no jax — parsing is pure text over the HLO
dumps — so findings can be produced from a saved ``.hlo`` file on any
machine. jax is needed only by :func:`lower_step_program`.
"""

from __future__ import annotations

import re
from collections import Counter
from dataclasses import dataclass, field
from math import ceil
from typing import Dict, List, Optional, Sequence, Tuple

from deeplearning4j_tpu.analysis.findings import Finding, Severity

RULES: Dict[str, Tuple[str, str]] = {
    "SC001": ("full-grad-allreduce",
              "zero1/zero2 update path consumes a param-sized gradient "
              "all-reduce at full size (no reduce-scatter layout formed)"),
    "SC002": ("collective-inventory",
              "per-step collective census; under zero1/zero2, more "
              "full-size param all-gathers than param leaves"),
    "SC003": ("scan-body-repartition",
              "all-gather inside the gradient-accumulation scan body "
              "(GSPMD repartitioned the scan; the replicated anchor "
              "was lost and sharded weights re-gather per microbatch)"),
    "SC004": ("precision-boundary",
              "mixed policy without half-precision compute / half "
              "dtypes crossing the master boundary; fp32 preset not "
              "convert-op-identical to the pre-policy program"),
    "SC005": ("donation-dropped",
              "expected buffer donation missing from the lowered "
              "program or dropped by the backend (2x param HBM)"),
    "SC006": ("host-transfer",
              "infeed/outfeed/host-callback inside the compiled step"),
    "SC007": ("comm-bytes-calibration",
              "HLO-derived collective bytes vs the cost-model "
              "prediction (tolerance-gated calibration metric)"),
    "SC008": ("sp-ring-absent",
              "trainer claims sp>1 sequence parallelism but the "
              "compiled step contains no collective-permute — the "
              "ring attention never formed (every chip attends over "
              "the full sequence, or the layer declined the ring)"),
    "SC009": ("kv-cache-not-donated",
              "decode-step program claiming KV-cache donation does "
              "not show the cache buffers in input_output_alias — "
              "every decode step copies the whole cache instead of "
              "updating it in place"),
    "SC010": ("paged-kv-indirection",
              "decode-step program claiming a block-paged KV pool "
              "either lowered no page-table gather (the indirection "
              "never formed — a dense cache path compiled instead) or "
              "dropped the pool's donation through the indirection "
              "(2x resident pool HBM plus a full-pool copy per token)"),
}

#: severity when the rule FIRES as a defect (SC002/SC007 also emit
#: informational findings; see the rule functions)
RULE_SEVERITY = {
    "SC001": Severity.ERROR,
    "SC002": Severity.WARNING,
    "SC003": Severity.ERROR,
    "SC004": Severity.ERROR,
    "SC005": Severity.ERROR,
    "SC006": Severity.ERROR,
    "SC007": Severity.WARNING,
    "SC008": Severity.ERROR,
    "SC009": Severity.ERROR,
    "SC010": Severity.ERROR,
}

#: default SC007 gate: |HLO - predicted| / predicted above this warns
COMM_BYTES_TOLERANCE = 0.25

#: SC001 ignores all-reduces below this element count: for near-scalar
#: leaves (tiny biases) the full-vs-shard distinction is a couple of
#: elements and XLA's fusion packing (dynamic-update-slice into concat
#: buffers) produces consumers "larger" than the payload — noise, not
#: layout evidence. The HBM/comm contract the rule protects lives in
#: the large leaves.
SC001_MIN_GRAD_ELEMS = 16

DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                     "collective-permute", "all-to-all")

#: ops that forward their operand unchanged (same element count) —
#: followed transparently when classifying all-reduce consumers
_PASS_THROUGH_OPS = {"bitcast", "copy", "reshape", "transpose", "convert",
                     "get-tuple-element"}

# `  %name = f32[16,8]{1,0} all-reduce(...)` / tuple-typed results. A TPU
# layout has brackets of its own (`bf16[8,128]{1,0:T(8,128)(2,1)S(1)}`) and a
# tuple may hold tuples: the type is what stands before ` <opcode>(`
_INSTR_RE = re.compile(
    r"^\s+(?:ROOT\s+)?%(?P<name>[\w.\-]+)\s*=\s*"
    r"(?P<type>\(.*?\)|\S+)\s+(?P<op>[\w\-]+)\(")
_SHAPE_RE = re.compile(r"([a-z]+\d*(?:e\d+m\d+(?:fn)?)?)\[([\d,]*)\]")
_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s*\(.*\)\s*->")
_REPLICA_GROUPS_RE = re.compile(
    r"replica_groups=(?:\[\d+,(\d+)\]|\{\{([\d,]+)\})")
_ALIAS_RE = re.compile(r"\{[\d\s,]*\}:\s*\(\d+")
_WHILE_BODY_RE = re.compile(r"body=%([\w.\-]+)")
# StableHLO main results: `tensor<16x8xf32> {jax.result_info = "[0]"}`
_ST_RESULT_RE = re.compile(
    r"tensor<([^>]*)>(?:\s*\{[^}]*jax\.result_info\s*=\s*\"([^\"]*)\"[^}]*\})?")
_ST_CONVERT_RE = re.compile(
    r"stablehlo\.convert\s[^:]*:\s*\(tensor<([^>]*)>\)\s*->\s*tensor<([^>]*)>")


def _parse_shape(dtype_dims: str) -> Tuple[str, Tuple[int, ...]]:
    """'f32[16,8]' -> ('f32', (16, 8)); scalars have () dims."""
    m = _SHAPE_RE.match(dtype_dims)
    if not m:
        return "", ()
    dims = tuple(int(d) for d in m.group(2).split(",") if d)
    return m.group(1), dims


def _elems(dims: Tuple[int, ...]) -> int:
    n = 1
    for d in dims:
        n *= d
    return n


def _tensor_dtype(tensor_body: str) -> str:
    """'16x8xf32' or 'f32' (scalar) -> 'f32'. StableHLO spells half
    precision 'bf16'/'f16' like HLO does."""
    return tensor_body.rsplit("x", 1)[-1].strip()


@dataclass
class HloInstr:
    name: str
    opcode: str
    dtype: str
    dims: Tuple[int, ...]
    line: str
    computation: str

    @property
    def elems(self) -> int:
        return _elems(self.dims)

    @property
    def bytes(self) -> int:
        return self.elems * DTYPE_BYTES.get(self.dtype, 4)


@dataclass
class CollectiveOp:
    """One collective instruction with the ring-model payload resolved:
    ``full_bytes`` is the LOGICAL full payload (the gathered result for
    all-gather, the pre-scatter operand for reduce-scatter, the reduced
    tensor for all-reduce)."""
    instr: HloInstr
    kind: str
    group_size: int
    full_dtype: str
    full_dims: Tuple[int, ...]
    in_loop_body: bool
    reduce_scatter_form: bool = False   # set by SC001's consumer walk

    @property
    def full_elems(self) -> int:
        return _elems(self.full_dims)

    @property
    def full_bytes(self) -> int:
        return self.full_elems * DTYPE_BYTES.get(self.full_dtype, 4)

    def ring_bytes(self) -> int:
        """Per-chip bytes on the standard ring model. The CPU backend's
        unfolded all-reduce+slice pair is costed as the reduce-scatter
        it folds to on TPU/GPU (one payload unit, not two) so the SC007
        calibration compares like with like."""
        g = max(2, self.group_size)
        unit = self.full_bytes * (g - 1) // g
        if self.kind == "all-reduce" and not self.reduce_scatter_form:
            return 2 * unit
        if self.kind == "collective-permute":
            return self.full_bytes
        return unit


@dataclass
class HloModule:
    """Parsed compiled-HLO text: instructions grouped by computation,
    collectives resolved, donation aliasing and while-loop bodies."""
    text: str
    computations: Dict[str, List[HloInstr]] = field(default_factory=dict)
    entry: str = ""
    alias_pairs: int = 0
    while_bodies: Dict[str, str] = field(default_factory=dict)  # body->owner
    collectives: List[CollectiveOp] = field(default_factory=list)


def parse_hlo_module(text: str) -> HloModule:
    mod = HloModule(text=text)
    header = text.splitlines()[0] if text else ""
    if "input_output_alias={" in header:
        # pairs look like `{0}: (0, {}, may-alias)`; count the `{i}: (p`
        seg = header.split("input_output_alias={", 1)[1]
        mod.alias_pairs = len(_ALIAS_RE.findall(seg.split("}},", 1)[0]
                                                if "}}," in seg else seg))
    cur = None
    for raw in text.splitlines():
        if raw and not raw.startswith(" "):
            m = _COMP_HDR_RE.match(raw.strip())
            if m:
                cur = m.group(1)
                mod.computations.setdefault(cur, [])
                if raw.strip().startswith("ENTRY"):
                    mod.entry = cur
            continue
        if cur is None:
            continue
        m = _INSTR_RE.match(raw)
        if not m:
            continue
        t = m.group("type")
        if t.startswith("("):
            dtype, dims = "", ()          # tuple-typed (while, ROOT tuple)
        else:
            dtype, dims = _parse_shape(t)
        instr = HloInstr(name=m.group("name"), opcode=m.group("op"),
                         dtype=dtype, dims=dims, line=raw.strip(),
                         computation=cur)
        mod.computations[cur].append(instr)
        wb = _WHILE_BODY_RE.search(raw) if " while(" in raw else None
        if wb:
            mod.while_bodies[wb.group(1)] = cur
    # resolve collectives (never inside fusions — XLA does not fuse them)
    for comp, instrs in mod.computations.items():
        for ins in instrs:
            kind = next((k for k in _COLLECTIVE_KINDS
                         if ins.opcode == k or ins.opcode in
                         (k + "-start", k + "-done")), None)
            if kind is None or ins.opcode.endswith("-done"):
                continue
            g = 0
            gm = _REPLICA_GROUPS_RE.search(ins.line)
            if gm:
                g = (int(gm.group(1)) if gm.group(1)
                     else len(gm.group(2).split(",")))
            full_dtype, full_dims = ins.dtype, ins.dims
            if kind == "reduce-scatter":
                # operand carries the full payload; result is the shard
                args = ins.line.split("(", 1)[1]
                sm = _SHAPE_RE.search(args)
                if sm:
                    full_dtype, full_dims = _parse_shape(sm.group(0))
            mod.collectives.append(CollectiveOp(
                instr=ins, kind=kind, group_size=g or 2,
                full_dtype=full_dtype, full_dims=full_dims,
                in_loop_body=comp in mod.while_bodies))
    return mod


def _operand_refs(line: str) -> List[str]:
    """%names referenced in the operand list (the first balanced paren
    group after the opcode) — excludes `to_apply=%..`/`calls=%..` attrs."""
    start = line.find("(")
    if start < 0:
        return []
    depth, end = 0, len(line)
    for i in range(start, len(line)):
        if line[i] == "(":
            depth += 1
        elif line[i] == ")":
            depth -= 1
            if depth == 0:
                end = i
                break
    return re.findall(r"%([\w.\-]+)", line[start:end])


def _consumers(mod: HloModule, comp: str, name: str) -> List[HloInstr]:
    out = []
    for ins in mod.computations.get(comp, ()):
        if ins.name == name:
            continue
        if name in _operand_refs(ins.line):
            out.append(ins)
    return out


def _full_size_consumers(mod: HloModule, coll: CollectiveOp,
                         limit: int, depth: int = 5) -> List[HloInstr]:
    """Consumers (pass-through ops followed) whose result is larger than
    ``limit`` elements — evidence the collective's payload stays full-
    size on the update path. ``tuple`` roots are terminal (returning a
    value is not computing on it)."""
    hits: List[HloInstr] = []
    seen = set()
    frontier = [(coll.instr.computation, coll.instr.name)]
    while frontier and depth > 0:
        depth -= 1
        nxt = []
        for comp, name in frontier:
            for c in _consumers(mod, comp, name):
                if c.name in seen:
                    continue
                seen.add(c.name)
                if c.opcode == "tuple":
                    continue
                if c.opcode in _PASS_THROUGH_OPS and c.elems >= coll.full_elems:
                    nxt.append((comp, c.name))
                    continue
                if c.elems > limit:
                    hits.append(c)
        frontier = nxt
    return hits


# ---------------------------------------------------------------------------
# program capture
# ---------------------------------------------------------------------------

@dataclass
class StepProgram:
    """One lowered+compiled step program: the StableHLO text (backend-
    independent — dot dtypes, converts, donation requests, result
    paths), the post-SPMD optimized HLO (collectives, aliasing, loop
    bodies), the jaxpr when available, and the XLA cost-model numbers
    the compile already paid for."""
    stablehlo: str
    hlo: str
    jaxpr: Optional[str] = None
    cost: Dict[str, float] = field(default_factory=dict)
    _module: Optional[HloModule] = None

    @property
    def module(self) -> HloModule:
        if self._module is None:
            self._module = parse_hlo_module(self.hlo)
        return self._module

    @property
    def donation_requested(self) -> bool:
        return ("jax.buffer_donor" in self.stablehlo
                or "tf.aliasing_output" in self.stablehlo)

    @property
    def donation_landed(self) -> bool:
        return self.module.alias_pairs > 0

    def result_dtypes(self) -> List[Tuple[str, str]]:
        """[(result_info_path, dtype)] for the StableHLO main results —
        '[0]...' = first element of the step's return tuple, etc."""
        m = re.search(r"func\.func public @main\(.*?\)\s*->\s*\((.*?)\)\s*\{",
                      self.stablehlo, re.DOTALL)
        if not m:
            return []
        out = []
        for tensor, info in _ST_RESULT_RE.findall(m.group(1)):
            out.append((info, _tensor_dtype(tensor)))
        return out

    def dot_dtypes(self) -> Counter:
        """Result dtypes of every StableHLO dot_general/convolution."""
        c: Counter = Counter()
        for line in self.stablehlo.splitlines():
            if ("stablehlo.dot_general" not in line
                    and "stablehlo.convolution" not in line):
                continue
            m = re.search(r"->\s*tensor<([^>]*)>\s*$", line.strip())
            if m:
                c[_tensor_dtype(m.group(1))] += 1
        return c

    def convert_signatures(self) -> Counter:
        """(src dtype, dst dtype) multiset of StableHLO convert ops —
        the fp32-preset identity surface."""
        return Counter((_tensor_dtype(a), _tensor_dtype(b))
                       for a, b in _ST_CONVERT_RE.findall(self.stablehlo))


def lower_step_program(jitted, *args, capture_jaxpr: bool = False,
                       **kwargs) -> StepProgram:
    """Lower + compile a jitted step for the given example args and
    capture every surface shardcheck reads. One real XLA compile (the
    same cost as ``profiling/cost.compiled_cost``, whose seam this
    reuses); no execution, so donated example buffers stay alive.
    ``capture_jaxpr`` additionally records the jaxpr text for human
    debugging — OFF by default because it costs a second full trace
    and no rule reads it."""
    from deeplearning4j_tpu.profiling.cost import (
        _normalize_cost, lower_and_compile,
    )
    lowered, compiled = lower_and_compile(jitted, *args, **kwargs)
    jaxpr = None
    if capture_jaxpr:
        try:
            jaxpr = str(jitted.trace(*args, **kwargs).jaxpr)
        except Exception:  # noqa: BLE001 — jaxpr capture is best-effort
            pass
    return StepProgram(stablehlo=lowered.as_text(),
                       hlo=compiled.as_text(), jaxpr=jaxpr,
                       cost=_normalize_cost(compiled.cost_analysis()))


def hlo_comm_bytes(program: StepProgram, dp: Optional[int] = None) -> int:
    """Per-chip collective bytes of the compiled program on the ring
    model (loop-body collectives counted once — static trip counts are
    not recovered from the HLO). The number SC007 gates against the
    cost model."""
    _classify_reduce_scatter_form(program.module, dp)
    return sum(c.ring_bytes() for c in program.module.collectives)


def _classify_reduce_scatter_form(mod: HloModule,
                                  dp: Optional[int] = None) -> None:
    """Mark all-reduces whose every consumer shrinks the payload to the
    1/group shard: the unfolded CPU form of a reduce-scatter."""
    for coll in mod.collectives:
        if coll.kind != "all-reduce" or coll.full_elems <= 1:
            continue
        g = dp or coll.group_size
        limit = ceil(coll.full_elems / max(2, g))
        coll.reduce_scatter_form = not _full_size_consumers(
            mod, coll, limit)


# ---------------------------------------------------------------------------
# rules
# ---------------------------------------------------------------------------

def _fmt_shape(dtype: str, dims: Tuple[int, ...]) -> str:
    return f"{dtype}[{','.join(str(d) for d in dims)}]"


def _wus_mode(weight_update_sharding) -> str:
    if weight_update_sharding is None:
        return "off"
    return str(getattr(weight_update_sharding, "mode",
                       weight_update_sharding)).lower()


def _precision_compute(precision) -> str:
    """Normalized compute dtype of a precision spec (None / preset str /
    PrecisionPolicy) without importing the jax-heavy nn layer."""
    from deeplearning4j_tpu.analysis.graphcheck import _precision_fields
    compute, _ = _precision_fields(precision)
    return compute or "float32"


_HALF_SHORT = {"bfloat16": "bf16", "bf16": "bf16",
               "float16": "f16", "fp16": "f16", "half": "f16"}


def _check_sc001(findings, mod: HloModule, wus: str, dp: int) -> None:
    if wus not in ("zero1", "zero2"):
        return
    for coll in mod.collectives:
        if (coll.kind != "all-reduce" or coll.in_loop_body
                or coll.full_elems < SC001_MIN_GRAD_ELEMS):
            continue
        g = coll.group_size or dp
        limit = ceil(coll.full_elems / max(2, g))
        hits = _full_size_consumers(mod, coll, limit)
        if hits:
            coll.reduce_scatter_form = False
            findings.append(Finding(
                "SC001", Severity.ERROR,
                f"%{coll.instr.name}",
                f"{wus} update path all-reduces "
                f"{_fmt_shape(coll.full_dtype, coll.full_dims)} and "
                f"consumes it at full size (e.g. %{hits[0].name} -> "
                f"{_fmt_shape(hits[0].dtype, hits[0].dims)}) — the "
                "reduce-scatter layout never formed, so every replica "
                "still applies the full update and the updater-HBM/comm "
                "wins are gone",
                "constrain the gradient to the (dp, chunk) sharded view "
                "before the update (parallel/trainer.py to_shards) so "
                "XLA folds the all-reduce + shard slice into a "
                "reduce-scatter"))
        else:
            coll.reduce_scatter_form = True


def _padded_leaf_shapes(leaf_sizes: Sequence[int], dp: int
                        ) -> Counter:
    """(dp, chunk) shapes the param all-gathers produce, per leaf."""
    return Counter((dp, ceil(int(s) / dp)) for s in leaf_sizes)


def _check_sc002(findings, mod: HloModule, wus: str, dp: int,
                 param_leaf_sizes: Optional[Sequence[int]]) -> None:
    colls = mod.collectives
    if colls:
        kinds = Counter(c.kind + (" (rs-form)" if c.reduce_scatter_form
                                  else "") for c in colls)
        in_body = sum(1 for c in colls if c.in_loop_body)
        total = sum(c.ring_bytes() for c in colls)
        census = ", ".join(f"{n}x {k}" for k, n in sorted(kinds.items()))
        findings.append(Finding(
            "SC002", Severity.INFO, "<program>",
            f"collectives per step: {census}"
            + (f" ({in_body} inside loop bodies)" if in_body else "")
            + f"; ~{total:,} ring-model bytes/chip",
            ""))
    if wus not in ("zero1", "zero2") or not param_leaf_sizes:
        return
    leaf_shapes = _padded_leaf_shapes(param_leaf_sizes, dp)
    ag_shapes = Counter(c.full_dims for c in colls
                        if c.kind == "all-gather" and not c.in_loop_body
                        and len(c.full_dims) == 2)
    excess = {s: n - leaf_shapes.get(s, 0)
              for s, n in ag_shapes.items()
              if s in leaf_shapes and n > leaf_shapes[s]}
    if excess:
        detail = ", ".join(f"{n} extra of shape {s}"
                           for s, n in excess.items())
        findings.append(Finding(
            "SC002", Severity.WARNING, "<program>",
            f"more full-size (dp, chunk) all-gathers than param leaves "
            f"({detail}) — under {wus} the single param all-gather is "
            "the only full-size collective the update should ship",
            "look for a stray replicated constraint re-gathering "
            "sharded state mid-step"))


def _check_sc003(findings, mod: HloModule, check_scan: bool,
                 dp: int) -> None:
    if not check_scan:
        return
    for coll in mod.collectives:
        if not coll.in_loop_body:
            continue
        # per-microbatch all-REDUCEs (gradient/loss reductions) ARE the
        # ga-scan contract — a reduction per microbatch is the (k+1)
        # comm model. The repartition hazard is sharded WEIGHTS being
        # re-GATHERED each microbatch (measured: the forward matmuls
        # all-gather when the anchor is lost).
        if coll.kind not in ("all-gather", "all-to-all"):
            continue
        if coll.full_elems <= max(2, dp):
            continue  # trivially small gathers are not weight traffic
        owner = mod.while_bodies.get(coll.instr.computation, "?")
        findings.append(Finding(
            "SC003", Severity.ERROR,
            f"%{coll.instr.name} in %{coll.instr.computation}",
            f"{coll.kind} of "
            f"{_fmt_shape(coll.full_dtype, coll.full_dims)} INSIDE the "
            f"gradient-accumulation scan body (while loop of %{owner}) "
            "— GSPMD repartitioned the scan: sharded state is "
            "re-gathered per MICROBATCH, and the per-microbatch "
            "replicated anchor the bitwise gate depends on is gone",
            "keep the replicated anchor inside the scan "
            "(parallel/trainer.py to_shards in_scan=True); see the "
            "pinned comment — measured on CPU dp=2"))


def _check_sc004(findings, program: StepProgram, precision,
                 baseline: Optional[StepProgram]) -> None:
    compute = _precision_compute(precision)
    half = _HALF_SHORT.get(compute)
    dots = program.dot_dtypes()
    if half is not None:
        if dots and not any(dt == half for dt in dots):
            findings.append(Finding(
                "SC004", Severity.ERROR, f"compute={compute}",
                f"policy declares {compute} compute but no "
                f"dot/convolution in the program produces {half} "
                f"(dot dtypes: {dict(dots)}) — the step-boundary casts "
                "were gated out and the program runs full precision",
                "check PrecisionPolicy threading (trainer precision= / "
                "conf.training.precision) reaches the compiled step"))
        bad_out = [(info, dt) for info, dt in program.result_dtypes()
                   if dt in ("bf16", "f16")
                   and (info.startswith("[0]") or info.startswith("[1]"))]
        if bad_out:
            info, dt = bad_out[0]
            findings.append(Finding(
                "SC004", Severity.ERROR, f"result {info}",
                f"master weights/updater state leave the step as {dt} "
                f"({len(bad_out)} result(s)) — masters must stay fp32 "
                "(checkpoints persist fp32; bf16 masters destroy the "
                "restore-equals-unbroken-run guarantee)",
                "cast gradients/updates back to the params dtype before "
                "optax (nn/updater.precision_value_and_grad seams)"))
        return
    # fp32 policy: the program must be convert-op-identical to the
    # pre-policy program — the bitwise-parity surface
    if baseline is not None:
        a, b = program.convert_signatures(), baseline.convert_signatures()
        if a != b:
            diff = (a - b) + (b - a)
            findings.append(Finding(
                "SC004", Severity.ERROR, "fp32-preset",
                "fp32 preset is NOT convert-op-identical to the "
                f"pre-policy program (convert delta: {dict(diff)}) — "
                "a cast leaked through the gate and the compiled step "
                "is a different program than the parity smokes proved",
                "the fp32 preset must gate every cast out "
                "(PrecisionPolicy.mixed False -> plain value_and_grad)"))
        elif program.dot_dtypes() != baseline.dot_dtypes():
            findings.append(Finding(
                "SC004", Severity.ERROR, "fp32-preset",
                "fp32 preset changed the program's dot/conv dtypes vs "
                f"the pre-policy baseline ({dict(program.dot_dtypes())} "
                f"vs {dict(baseline.dot_dtypes())})",
                "the fp32 preset must leave the compiled step "
                "bit-identical"))
    elif any(dt in ("bf16", "f16") for dt in dots):
        findings.append(Finding(
            "SC004", Severity.ERROR, "fp32-policy",
            f"policy is fp32 but the program computes dots in half "
            f"precision (dot dtypes: {dict(dots)})",
            "a cast escaped the fp32 gate — find the stray astype"))


def _check_sc005(findings, program: StepProgram,
                 expect_donation: Optional[bool]) -> None:
    if expect_donation:
        if program.donation_landed:
            return  # aliases present in the compiled module: honored
        if not program.stablehlo:
            # HLO-only dump (CLI file mode without --stablehlo): the
            # request marker lives in the StableHLO we don't have, but
            # the compiled module provably carries no aliasing
            findings.append(Finding(
                "SC005", Severity.ERROR, "<entry>",
                "step was expected to donate its state buffers but the "
                "compiled module carries no input_output_alias — old "
                "params/opt state stay alive across every update: 2x "
                "peak param HBM (pass --stablehlo to distinguish "
                "'never requested' from 'dropped by the backend')",
                "pass donate_argnums for the state arguments the "
                "caller overwrites"))
        elif not program.donation_requested:
            findings.append(Finding(
                "SC005", Severity.ERROR, "<entry>",
                "step was expected to donate its state buffers but the "
                "lowered program requests no donation (no "
                "donate_argnums reached jit) — old params/opt state "
                "stay alive across every update: 2x peak param HBM",
                "pass donate_argnums for the state arguments the "
                "caller overwrites"))
        else:
            findings.append(Finding(
                "SC005", Severity.ERROR, "<entry>",
                "donation was requested (jax.buffer_donor in the "
                "lowered program) but no input_output_alias survived "
                "compilation — the backend dropped the aliasing and "
                "peak HBM doubles anyway",
                "check for dtype/layout mismatches between the donated "
                "input and its output (aliasing needs identical "
                "shapes), or a backend that cannot alias"))
    elif (expect_donation is None and program.donation_requested
          and not program.donation_landed):
        findings.append(Finding(
            "SC005", Severity.WARNING, "<entry>",
            "donation requested but no input_output_alias in the "
            "compiled module",
            "see SC005"))


_HOST_CALLBACK_RE = re.compile(
    r'custom_call_target="([^"]*(?:callback|CallbackTo|host)[^"]*)"',
    re.IGNORECASE)


def _check_sc006(findings, mod: HloModule) -> None:
    hits: List[Tuple[str, str]] = []
    for comp, instrs in mod.computations.items():
        for ins in instrs:
            if ins.opcode in ("infeed", "outfeed"):
                hits.append((ins.opcode, ins.name))
            elif ins.opcode in ("send", "recv", "send-done", "recv-done") \
                    and "is_host_transfer=true" in ins.line:
                hits.append(("host " + ins.opcode, ins.name))
            elif ins.opcode == "custom-call":
                m = _HOST_CALLBACK_RE.search(ins.line)
                if m:
                    hits.append((m.group(1), ins.name))
    if hits:
        kind, name = hits[0]
        findings.append(Finding(
            "SC006", Severity.ERROR, f"%{name}",
            f"host transfer inside the compiled step: {kind}"
            + (f" (+{len(hits) - 1} more)" if len(hits) > 1 else "")
            + " — every step pays a host round-trip serialized with "
            "the device compute",
            "move debug prints/callbacks outside jit (or behind a "
            "debug flag); feed data as step arguments, not infeed"))


def _check_sc008(findings, mod: HloModule, sp: int) -> None:
    """SC008: an sp>1 claim must show the ring — ring attention's KV
    rotation lowers to collective-permute ops (one per ring hop,
    typically inside the ring scan's while body). A compiled step with
    NO collective-permute under an sp claim means the sequence axis is
    sharded but never ringed: every attention layer declined the ring
    (non-divisible T, ``sequence_parallel=False``, or no attention
    layer at all — graphcheck GC017's config-time warning, proven here
    on the compiled program) and the sp chips buy nothing."""
    if sp <= 1:
        return
    if any(c.kind == "collective-permute" for c in mod.collectives):
        return
    findings.append(Finding(
        "SC008", Severity.ERROR, f"sp={sp}",
        "trainer claims sp-axis sequence parallelism but the compiled "
        "step contains no collective-permute — the ring attention "
        "never formed",
        "check the model has a SelfAttentionLayer with "
        "sequence_parallel=True, the sequence length divides the sp "
        "axis, and the batch divides the data axis (the layer "
        "declines the ring otherwise); or drop the sp axis"))


def _check_sc009(findings, program: StepProgram,
                 expect_cache_alias: Optional[int]) -> None:
    """SC009 (ISSUE 15): a token-level decode step threads its KV
    caches as carry state and must DONATE them — the claim is the
    number of cache leaf buffers (2 per attention layer); the compiled
    module must carry at least that many ``input_output_alias`` pairs.
    Without the aliasing every decode step materializes a second full
    [rows, H, max_len, D] cache per attention layer: 2x resident cache
    HBM plus a full-cache memcpy PER GENERATED TOKEN — the exact
    throughput cliff iteration-level scheduling exists to avoid."""
    if not expect_cache_alias or expect_cache_alias < 1:
        return
    landed = program.module.alias_pairs
    if landed >= expect_cache_alias:
        return
    if program.stablehlo and not program.donation_requested:
        findings.append(Finding(
            "SC009", Severity.ERROR, "<entry>",
            f"decode step claims {expect_cache_alias} donated KV-cache "
            "buffers but the lowered program requests no donation (no "
            "donate_argnums reached jit) — every decode step copies "
            "the full cache instead of updating it in place",
            "jit the decode step with donate_argnums on the cache "
            "argument (keras/generation.py donates argnum 2)"))
    else:
        findings.append(Finding(
            "SC009", Severity.ERROR, "<entry>",
            f"decode step claims {expect_cache_alias} donated KV-cache "
            f"buffers but only {landed} input_output_alias pair(s) "
            "survived compilation — un-aliased cache buffers double "
            "the resident KV HBM and pay a full-cache copy per token",
            "check the cache dtypes/shapes match between the donated "
            "input and its output (aliasing needs identical shapes), "
            "or a backend that cannot alias"))


_GATHER_OP_RE = re.compile(r"\bstablehlo\.(?:dynamic_)?gather\b")


def _check_sc010(findings, program: StepProgram,
                 expect_paged_gather: Optional[int]) -> None:
    """SC010 (ISSUE 20): a block-paged decode step reads its KV state
    through a page-table indirection — ``pool[page_table]`` — so the
    lowered program must carry at least one ``stablehlo.gather`` (or
    ``dynamic_gather``) PER POOL LEAF (2 per attention node: k and v).
    The claim is that leaf count. Fewer gathers means the indirection
    never formed and a dense whole-row cache path compiled instead —
    page eviction and prefix sharing silently stop meaning anything.
    The pool must also stay donated THROUGH the indirection: at least
    as many ``input_output_alias`` pairs as pool leaves, else every
    token pays a full-pool copy on top of 2x resident pool HBM (the
    SC009 cliff, scaled up to the whole pool)."""
    if not expect_paged_gather or expect_paged_gather < 1:
        return
    gathers = len(_GATHER_OP_RE.findall(program.stablehlo))
    if gathers < expect_paged_gather:
        findings.append(Finding(
            "SC010", Severity.ERROR, "<entry>",
            f"decode step claims a block-paged KV pool with "
            f"{expect_paged_gather} leaf buffers but the lowered "
            f"program carries only {gathers} gather op(s) — the "
            "page-table indirection never formed; this is a dense "
            "cache program wearing a paged signature, so page-level "
            "eviction and prefix sharing cannot be in effect",
            "build the step via paged_decode_fn (nn/graph.py): the "
            "cache read must be gather_kv_pages(pool, page_table), "
            "not a direct dense-cache read"))
        return
    landed = program.module.alias_pairs
    if landed >= expect_paged_gather:
        return
    if program.stablehlo and not program.donation_requested:
        findings.append(Finding(
            "SC010", Severity.ERROR, "<entry>",
            f"paged decode step claims {expect_paged_gather} donated "
            "pool buffers but the lowered program requests no "
            "donation (no donate_argnums reached jit) — every decode "
            "step copies the FULL page pool instead of updating it in "
            "place",
            "jit the paged decode step with donate_argnums on the "
            "pool argument (keras/generation.py donates argnum 2)"))
    else:
        findings.append(Finding(
            "SC010", Severity.ERROR, "<entry>",
            f"paged decode step claims {expect_paged_gather} donated "
            f"pool buffers but only {landed} input_output_alias "
            "pair(s) survived compilation — the donation did not make "
            "it through the page-table indirection, so the pool is "
            "resident twice and copied once per token",
            "check the pool leaf dtypes/shapes are unchanged through "
            "the step (aliasing needs identical shapes) and that the "
            "scatter writes back into the SAME pool leaves"))


def _check_sc007(findings, program: StepProgram, wus: str, dp: int,
                 gradient_accumulation: int,
                 param_count: Optional[int],
                 tolerance: float, gate: bool) -> None:
    if not param_count or dp < 2:
        return
    from deeplearning4j_tpu.profiling.cost import dp_comm_bytes_per_update
    hlo_bytes = sum(c.ring_bytes() for c in program.module.collectives)
    predicted = dp_comm_bytes_per_update(
        param_count, dp, 4, gradient_accumulation, wus)
    if not predicted:
        return
    delta = (hlo_bytes - predicted) / predicted
    loc = f"dp={dp},{wus},k={gradient_accumulation}"
    if gate and abs(delta) > tolerance:
        findings.append(Finding(
            "SC007", Severity.WARNING, loc,
            f"HLO collective bytes {hlo_bytes:,}/chip vs cost-model "
            f"prediction {predicted:,} — {delta:+.0%} is outside the "
            f"{tolerance:.0%} tolerance; either the program ships "
            "collectives the layout does not need or "
            "profiling/cost.dp_comm_bytes_per_update mis-models this "
            "config (the autotuner calibrates on this gap)",
            "read the SC002 inventory to see which collective is "
            "unaccounted for"))
    else:
        findings.append(Finding(
            "SC007", Severity.INFO, loc,
            f"comm bytes: HLO {hlo_bytes:,}/chip vs predicted "
            f"{predicted:,} ({delta:+.0%})"
            + ("" if gate else
               " [gate skipped: loop-body trip counts not modeled on "
               "the gradient-accumulation scan path]"),
            ""))


def check_step_program(program: StepProgram, *,
                       weight_update_sharding="off",
                       dp: int = 1,
                       gradient_accumulation: int = 1,
                       sp: int = 1,
                       precision=None,
                       baseline: Optional[StepProgram] = None,
                       expect_donation: Optional[bool] = None,
                       param_leaf_sizes: Optional[Sequence[int]] = None,
                       param_count: Optional[int] = None,
                       cost_tolerance: float = COMM_BYTES_TOLERANCE,
                       check_scan: Optional[bool] = None,
                       check_cost: bool = True,
                       expect_cache_alias: Optional[int] = None,
                       expect_paged_gather: Optional[int] = None,
                       ) -> List[Finding]:
    """Run every SC rule over one captured step program.

    The keyword context declares what the program CLAIMS to be — the
    layout (``weight_update_sharding``/``dp``/``gradient_accumulation``),
    the precision policy (with ``baseline`` as the pre-policy program
    for the fp32 identity check), whether donation was expected, and
    the param leaf sizes the collective census is reconciled against.
    Pure text analysis; no jax, no execution.
    """
    findings: List[Finding] = []
    wus = _wus_mode(weight_update_sharding)
    dp = int(dp or 1)
    sp = int(sp or 1)
    mod = program.module
    if param_leaf_sizes and param_count is None:
        param_count = sum(int(s) for s in param_leaf_sizes)
    if check_scan is None:
        check_scan = wus in ("zero1", "zero2") and gradient_accumulation > 1
    # On an sp mesh the trainer deliberately runs the layout-
    # UNCONSTRAINED zero path (the anchored (dp, chunk) view without
    # the sharding-constraint op — see the sp_mesh note in
    # parallel/trainer.py: the constraint makes GSPMD double-apply the
    # sp psum to pure-reduction gradient leaves). The reduce-scatter
    # layout contract (SC001) and the dp ring-model calibration (SC007)
    # therefore do not apply; SC008 instead proves the sp claim's OWN
    # program contract — the ring's collective-permute must be present.
    sp_unconstrained = sp > 1 and wus in ("zero1", "zero2")
    _check_sc001(findings, mod, "off" if sp_unconstrained else wus, dp)
    _classify_reduce_scatter_form(mod, dp)         # for off-mode census
    _check_sc002(findings, mod,
                 "off" if sp_unconstrained else wus, dp, param_leaf_sizes)
    _check_sc003(findings, mod, check_scan and not sp_unconstrained, dp)
    _check_sc004(findings, program, precision, baseline)
    _check_sc005(findings, program, expect_donation)
    _check_sc006(findings, mod)
    _check_sc008(findings, mod, sp)
    _check_sc009(findings, program, expect_cache_alias)
    _check_sc010(findings, program, expect_paged_gather)
    # gate the calibration only where the ring model applies: the
    # ga-scan path hides per-microbatch traffic in loop bodies whose
    # trip counts the text dump does not carry, and callers whose comm
    # pattern is not the dp gradient exchange (ParallelWrapper's
    # parameter averaging) opt out with check_cost=False; an sp mesh
    # adds per-layer ring traffic the dp-update model does not cover
    if check_cost and sp == 1:
        _check_sc007(findings, program, wus, dp, gradient_accumulation,
                     param_count, cost_tolerance,
                     gate=gradient_accumulation == 1)
    return findings


# ---------------------------------------------------------------------------
# convenience: capture + check a container / trainer step
# ---------------------------------------------------------------------------

def param_leaf_sizes(params) -> List[int]:
    """Flattened element count per param leaf — the census context."""
    import jax
    import numpy as np
    return [int(np.prod(np.shape(leaf))) if np.shape(leaf) else 1
            for leaf in jax.tree_util.tree_leaves(params)]


def net_step_program(net, batch) -> StepProgram:
    """Capture a container's own jitted train step (the single-device
    program) for ``batch`` — the seam ``net.shardcheck`` uses."""
    from deeplearning4j_tpu.profiling.cost import step_example_args
    net._check_init()
    if net._train_step_fn is None:
        net._train_step_fn = net._build_train_step()
    return lower_step_program(net._train_step_fn,
                              *step_example_args(net, batch))
