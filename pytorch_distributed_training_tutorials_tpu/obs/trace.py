"""Trace classification: where did the step's device time actually go.

:class:`StepReport` grows :func:`utils.profiling.device_op_durations` into a
categorized breakdown — convolution / matmul / collectives split by kind /
dynamic-update-slice / convert-copy / reduce / elementwise — the round-4 profile
analysis as one library call instead of a hand-run script.

The classifier exists because name-matching trace events is how round 2's
"BatchNorm is ~60% of the step" misread happened: XLA fuses convolutions
*with* the BN-stat reduces into fusions named ``convert_reduce_fusion``, so
the fusion's display name is marketing, not truth (a round-4 finding). Two
defenses are built in:

- pass the compiled module's HLO text (``compiled.as_text()``) and every
  fusion is classified by what its *called fused computation* actually
  contains (convolution > dot > reduce > ...), never by its name;
- without HLO, fusions fall back to name tokens but their time is tallied
  separately as ``heuristic_us`` — a report that leans on guessed fusion
  classes says so instead of presenting the guess as ground truth.

Reference gap being closed: the source tutorial's observability is one
rank-tagged print (ddp_gpus.py:44); it declares profilers it never uses
(environment.yml:78-79; SURVEY.md section 5.5).
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

from pytorch_distributed_training_tutorials_tpu.utils.profiling import device_op_durations

# Category names (stable strings: they appear in receipts and tests).
CONVOLUTION = "convolution"
MATMUL = "matmul"
REDUCE = "reduce"
COPY = "convert/copy"
DUS = "dynamic-update-slice"
ELEMENTWISE = "elementwise"
OTHER = "other"
COLLECTIVE_PREFIX = "collective:"

# Collective opcodes -> split-by-kind category. Ordered: longer opcode
# strings first so "all-reduce-scatter"-style compounds can't mismatch
# ("reduce-scatter" must win before a bare "all-reduce" substring test).
_COLLECTIVES = (
    ("reduce-scatter", COLLECTIVE_PREFIX + "reduce-scatter"),
    ("all-reduce", COLLECTIVE_PREFIX + "all-reduce"),
    ("all-gather", COLLECTIVE_PREFIX + "all-gather"),
    ("all-to-all", COLLECTIVE_PREFIX + "all-to-all"),
    ("collective-permute", COLLECTIVE_PREFIX + "permute"),
)

# Data-movement / layout opcodes (one bucket: none of them is compute).
_COPY_OPS = frozenset({
    "copy", "copy-start", "copy-done", "convert", "transpose", "bitcast",
    "reshape", "pad",
})

# Compute opcodes that are honestly "elementwise or cheap memory traffic".
# Gather/slice/concatenate land here deliberately: on the workloads this
# repo profiles they are epsilon, and a wrong *named* bucket is worse than
# a coarse one (the misread lesson).
_ELEMENTWISE_OPS = frozenset({
    "add", "subtract", "multiply", "divide", "maximum", "minimum",
    "exponential", "exponential-minus-one", "power", "rsqrt", "sqrt",
    "tanh", "logistic", "log", "log-plus-one", "negate", "abs", "sign",
    "compare", "select", "and", "or", "not", "xor", "clamp", "floor",
    "ceil", "round-nearest-afz", "round-nearest-even", "is-finite",
    "cosine", "sine", "atan2", "remainder", "shift-left",
    "shift-right-logical", "shift-right-arithmetic", "broadcast", "iota",
    "constant", "rng", "rng-bit-generator", "gather", "scatter", "slice",
    "dynamic-slice", "concatenate", "reverse", "partition-id",
    "replica-id", "tuple", "get-tuple-element", "bitcast-convert",
    "stochastic-convert", "cbrt", "erf", "expm1", "log1p", "popcnt",
    "clz", "map", "sort", "reduce-precision", "real", "imag", "complex",
    "after-all", "add-dependency", "optimization-barrier", "domain",
})

# Trailing ``.3`` / ``.clone`` / ``.3.clone`` disambiguators XLA appends to
# duplicated instruction names (observed on the CPU-mesh traces).
_SUFFIX = re.compile(r"(\.(\d+|clone|remat|sunk))+$")


def base_name(op: str) -> str:
    """Instruction name with XLA's clone/ordinal suffixes stripped."""
    return _SUFFIX.sub("", op)


_RENDEZVOUS_SPANS = (
    "Rendezvous", "InvokeRendezvous", "Wait: ", "Wait for rendezvous",
)


def is_wrapper(op: str) -> bool:
    """True for events that overlap leaf ops (counting them double-counts).

    Four families, all observed in real traces:

    - host-executor infra, C++-scoped names (``ThunkExecutor::Execute``,
      ``TfrtCpuExecutable::ExecuteHelper``, ``ThreadpoolListener::...``) —
      these dominate raw CPU-mesh totals and are pure bookkeeping;
    - XLA region wrappers: the module-level event (a bare ordinal like
      ``0``), ``jit_*`` program regions, ``while`` loop bodies, ``call``
      computation frames;
    - profiler metadata lanes;
    - the CPU runtime's collective rendezvous spans (``Rendezvous``,
      ``InvokeRendezvous``, ``Wait: pending_threads=3/8``, ``Wait for
      rendezvous callback``): they sit INSIDE the collective op's own
      event, whose duration already covers them.
    """
    if "::" in op or op.startswith(_RENDEZVOUS_SPANS):
        return True
    b = base_name(op)
    return (
        b.isdigit()
        or b.startswith("jit_")
        or b == "while"
        or b.startswith("while_")
        or b == "call"
        or b.startswith("call_")
    )


def _classify_opcode(opcode: str) -> str:
    """Category for a bare (non-fusion) HLO opcode."""
    if "convolution" in opcode:
        return CONVOLUTION
    for coll, cat in _COLLECTIVES:
        if coll in opcode:
            return cat
    if "dynamic-update-slice" in opcode:
        return DUS
    if opcode == "dot":
        return MATMUL
    if opcode in ("reduce", "reduce-window") or opcode.startswith("reduce."):
        return REDUCE
    if opcode in _COPY_OPS:
        return COPY
    if opcode in _ELEMENTWISE_OPS:
        return ELEMENTWISE
    return OTHER


def _classify_fusion_body(body: str) -> str:
    """Category for a fusion by what its fused computation CONTAINS.

    Priority mirrors scripts/profile_step.py's HLO-verified rules (the fix
    for the ``convert_reduce_fusion`` misread): the most expensive op class
    present names the fusion. A fusion with none of the heavy ops is
    elementwise by construction.
    """
    if "convolution(" in body:
        return CONVOLUTION
    if "dot(" in body:
        return MATMUL
    for coll, cat in _COLLECTIVES:
        if coll + "(" in body:
            return cat
    if "dynamic-update-slice(" in body:
        return DUS
    if "reduce(" in body or "reduce-window(" in body:
        return REDUCE
    return ELEMENTWISE


def _classify_name(base: str) -> str:
    """Name-token fallback for events with no HLO backing.

    Fusion names list (some of) the fused ops joined by ``_``; bare names
    are opcodes. Priority matches the HLO-body rules so the two paths can
    only disagree when the fusion NAME omits its heaviest op — exactly the
    case ``heuristic_us`` accounts for.
    """
    if "convolution" in base:
        return CONVOLUTION
    for coll, cat in _COLLECTIVES:
        if coll in base:
            return cat
    if "dynamic-update-slice" in base:
        return DUS
    tokens = [t for t in base.split("_") if t and t != "fusion"]
    if base == "dot" or "dot" in tokens:
        return MATMUL
    if base in ("reduce", "reduce-window") or "reduce" in tokens:
        return REDUCE
    if base in _COPY_OPS or any(t in _COPY_OPS for t in tokens):
        return COPY
    if base.endswith("fusion"):
        # a fusion whose name shows none of the heavy classes: elementwise
        # body (profile_step's fallback), but flagged heuristic upstream
        return ELEMENTWISE
    if base in _ELEMENTWISE_OPS:
        return ELEMENTWISE
    return OTHER


def classify_hlo(hlo: str) -> dict[str, tuple[str, str]]:
    """Map HLO instruction name -> (category, metadata op_name).

    The ground-truth classifier: fusions are resolved through their
    ``calls=%computation`` body. Generalizes scripts/profile_step.py's
    ``parse_hlo`` with collectives split by kind and dynamic-update-slice
    as its own class (the nn.scan layout lesson, round 5).
    """
    comps: dict[str, str] = {}
    cur: str | None = None
    body: list[str] = []
    for line in hlo.splitlines():
        if cur is None and line.startswith("%") and line.rstrip().endswith("{"):
            cur = line.split()[0].lstrip("%")
            body = []
        elif cur is not None and line.startswith("}"):
            comps[cur] = "\n".join(body)
            cur = None
        elif cur is not None:
            body.append(line)
    info: dict[str, tuple[str, str]] = {}
    # "[ROOT] %name = <type> opcode(operands)...": the type may be a tuple
    # full of layout parens like (f32[64]{0:T(128)S(1)}, ...), so the
    # opcode is the first *lowercase* word directly preceding a "(" after
    # the type
    inst_re = re.compile(
        r"^\s+(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s+"
        r"(?:\([^=]*?\)|[^\s(]+)\s+([a-z][\w\-]*)\("
    )
    for line in hlo.splitlines():
        m = inst_re.match(line)
        if not m:
            continue
        name, opcode = m.group(1), m.group(2)
        call = re.search(r"calls=%?([\w\.\-]+)", line)
        meta = re.search(r'op_name="([^"]+)"', line)
        op_name = meta.group(1) if meta else ""
        if opcode == "fusion" and call:
            cls = _classify_fusion_body(comps.get(call.group(1), ""))
        else:
            cls = _classify_opcode(opcode)
        info[name] = (cls, op_name)
    return info


@dataclass
class StepReport:
    """Categorized device-time breakdown of a captured trace.

    ``total_us`` is leaf device time (wrapper events that *contain* leaves
    are excluded and tallied in ``wrapper_us``); ``by_category`` always sums
    to ``total_us`` exactly. ``heuristic_us`` is the share classified from
    fusion *names* with no HLO to verify against — if it is large, pass
    ``hlo=compiled.as_text()`` before trusting the split.
    """

    total_us: float
    wrapper_us: float
    by_category: dict[str, float]
    ops: list[tuple[str, float, str]] = field(default_factory=list)
    heuristic_us: float = 0.0
    steps: int = 1

    @classmethod
    def from_trace(
        cls, logdir: str, hlo: str | None = None, steps: int = 1
    ) -> "StepReport":
        """Build a report from a trace directory written by profiling.trace.

        ``steps``: how many train steps the traced region executed (a jitted
        ``lax.scan`` chain counts as its length) — used only for the
        per-step convenience properties.
        """
        durations = device_op_durations(logdir)
        hlo_info = classify_hlo(hlo) if hlo else {}
        total = 0.0
        wrapper = 0.0
        heuristic = 0.0
        by_cat: dict[str, float] = {}
        ops: list[tuple[str, float, str]] = []
        for op, us in durations.items():
            if is_wrapper(op):
                wrapper += us
                continue
            # the CPU runtime also stamps "end: <op>" completion markers:
            # they belong to <op>'s class
            name = op.removeprefix("end: ")
            base = base_name(name)
            known = hlo_info.get(name) or hlo_info.get(base)
            if known is not None:
                cat = known[0]
            else:
                cat = _classify_name(base)
                if base.endswith("fusion"):
                    heuristic += us
            total += us
            by_cat[cat] = by_cat.get(cat, 0.0) + us
            ops.append((op, us, cat))
        ops.sort(key=lambda r: -r[1])
        return cls(
            total_us=total,
            wrapper_us=wrapper,
            by_category=dict(
                sorted(by_cat.items(), key=lambda kv: -kv[1])
            ),
            ops=ops,
            heuristic_us=heuristic,
            steps=max(1, steps),
        )

    @property
    def step_us(self) -> float:
        return self.total_us / self.steps

    @property
    def unclassified_fraction(self) -> float:
        if self.total_us <= 0:
            return 0.0
        return self.by_category.get(OTHER, 0.0) / self.total_us

    @property
    def collective_us(self) -> dict[str, float]:
        """Collective time split by kind (the SPMD cost surface)."""
        return {
            k: v
            for k, v in self.by_category.items()
            if k.startswith(COLLECTIVE_PREFIX)
        }

    def fraction(self, category: str) -> float:
        if self.total_us <= 0:
            return 0.0
        return self.by_category.get(category, 0.0) / self.total_us

    def render(self, top: int = 0) -> str:
        """The "where did the step go" table."""
        lines = [
            f"device time: {self.total_us / 1e3:.2f} ms over "
            f"{self.steps} step(s) -> {self.step_us / 1e3:.3f} ms/step",
            "| class | ms | % of device time |",
            "|---|---|---|",
        ]
        for cat, us in self.by_category.items():
            lines.append(
                f"| {cat} | {us / 1e3:.2f} | "
                f"{100 * us / self.total_us:.1f}% |"
                if self.total_us
                else f"| {cat} | 0.00 | 0.0% |"
            )
        if self.heuristic_us:
            lines.append(
                f"(name-heuristic share, no HLO backing: "
                f"{100 * self.heuristic_us / self.total_us:.1f}% — pass "
                "hlo=compiled.as_text() to verify)"
            )
        for op, us, cat in self.ops[: top or 0]:
            lines.append(f"  {op}: {us / 1e3:.3f} ms [{cat}]")
        return "\n".join(lines)

    def to_dict(self) -> dict:
        """JSON-ready payload (the receipt-pipeline form)."""
        return {
            "total_us": round(self.total_us, 3),
            "wrapper_us": round(self.wrapper_us, 3),
            "step_us": round(self.step_us, 3),
            "steps": self.steps,
            "by_category": {
                k: round(v, 3) for k, v in self.by_category.items()
            },
            "heuristic_us": round(self.heuristic_us, 3),
            "unclassified_fraction": round(self.unclassified_fraction, 4),
        }
