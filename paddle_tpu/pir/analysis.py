"""Dataflow analysis framework over pir.Program.

reference: paddle/pir/include/pass/analysis_manager.h (pir analyses
feeding passes) — here a generic join-semilattice worklist engine over
the straight-line SSA op list, so future passes (the ROADMAP's
GSPMD-style sharding propagation, collective-overlap scheduling) are
written as pure transfer functions instead of ad-hoc graph walks.

Three concrete analyses ship with the framework:

* **ShapeDtypeInference** (forward): re-derives every Value's abstract
  type from the program inputs/constants — eqn-backed ops from the
  jaxpr avals they replay, fused ``pt.*`` ops through ``jax.eval_shape``
  of their callable. Backs the verifier's ``type-mismatch`` rule.
* **Liveness** (backward): live-Value sets per program point plus
  use/def indices; feeds ``check_donation_safety`` which statically
  rejects the donated-double-buffer hazard COMPILER.md previously only
  documented (a donated buffer read again after the in-place-style op
  that aliases over it).
* **ShardingConsistency** (forward): propagates optional per-Value
  sharding annotations (``Value.sharding``) and reports conflicts —
  the seed of the sharding-propagation pass: that pass will *choose*
  shardings; this analysis already proves a chosen assignment coherent.

Programs here are topologically-ordered straight-line SSA (no control
flow at this level — scans/whiles are single ops), so the fixpoint
converges in one sweep; the worklist engine still re-enqueues dependents
so transfer functions may be written without ordering assumptions.
"""

from __future__ import annotations

from collections import deque
from typing import Any, Optional

from .ir import Operation, Program, Value

__all__ = ["Lattice", "FlatLattice", "DataflowAnalysis",
           "ShapeDtypeInference", "Liveness", "ShardingConsistency",
           "DonationHazard", "check_donation_safety", "CONFLICT",
           "CostModel", "ProgramCost", "OpCost", "DEFAULT_ROOFLINE",
           "DEFAULT_INTERCONNECT"]


class _Conflict:
    """Lattice top: irreconcilable facts met."""

    def __repr__(self):
        return "<CONFLICT>"


CONFLICT = _Conflict()


class Lattice:
    """Join-semilattice interface: ``bottom`` (no information) joined
    upward toward ``CONFLICT`` (contradictory information)."""

    def bottom(self):
        return None

    def join(self, a, b):  # pragma: no cover - interface
        raise NotImplementedError


class FlatLattice(Lattice):
    """bottom (None) < any concrete fact < CONFLICT. Two distinct
    concrete facts join to CONFLICT — the shape every annotation-
    consistency analysis (sharding, layout, memory space) starts from."""

    def join(self, a, b):
        if a is None:
            return b
        if b is None:
            return a
        if a is CONFLICT or b is CONFLICT:
            return CONFLICT
        return a if a == b else CONFLICT


class DataflowAnalysis:
    """Worklist fixpoint over a Program's op list.

    Subclasses set ``direction`` ("forward" | "backward") and implement
    ``boundary(prog)`` (seed facts) and ``transfer(op, facts)`` which
    updates ``facts`` in place and returns True when anything changed.
    ``run`` returns the fact map after convergence. Facts are keyed by
    ``id(Value)`` (or anything else the subclass chooses — the engine
    only re-enqueues dependent ops on change).
    """

    direction = "forward"
    name = "analysis"

    def boundary(self, prog: Program) -> dict:
        return {}

    def transfer(self, op: Operation, facts: dict) -> bool:
        raise NotImplementedError  # pragma: no cover - interface

    def run(self, prog: Program) -> dict:
        facts = self.boundary(prog)
        forward = self.direction == "forward"
        order = prog.ops if forward else list(reversed(prog.ops))
        # dependents: forward -> ops consuming my outputs; backward ->
        # ops defining my inputs
        users = prog.users()
        dependents: dict[int, list[Operation]] = {}
        for op in prog.ops:
            if forward:
                deps = [u for o in op.outputs for u in users.get(o, ())
                        if u is not None]
            else:
                deps = [v.op for v in op.inputs if v.op is not None]
            dependents[id(op)] = deps
        worklist = deque(order)
        queued = {id(op) for op in order}
        steps = 0
        budget = max(16, len(prog.ops)) * 8    # straight-line: 1 sweep;
        while worklist:                        # budget guards bad transfers
            op = worklist.popleft()
            queued.discard(id(op))
            steps += 1
            if steps > budget:
                raise RuntimeError(
                    f"dataflow analysis {self.name!r} did not converge "
                    f"on {prog.name!r} within {budget} steps")
            if self.transfer(op, facts):
                for dep in dependents[id(op)]:
                    if id(dep) not in queued:
                        worklist.append(dep)
                        queued.add(id(dep))
        return facts


# --------------------------------------------------------------------------
# shape/dtype inference
# --------------------------------------------------------------------------

class ShapeDtypeInference(DataflowAnalysis):
    """facts: id(Value) -> (shape tuple, dtype str). Inputs/constants
    seed from their stamped types (the program boundary is trusted);
    eqn ops derive outputs from the replayed jaxpr's avals; fused ops
    re-derive through jax.eval_shape of the fused callable (cached per
    op). The verifier compares these derived facts against the stamped
    ``Value.shape/dtype`` (rule ``type-mismatch``)."""

    direction = "forward"
    name = "shape_dtype"

    def __init__(self):
        self._fused_cache: dict[int, Optional[list]] = {}

    @staticmethod
    def _key(shape, dtype):
        return (tuple(shape), str(dtype))

    def boundary(self, prog: Program) -> dict:
        facts = {}
        for v in prog.inputs:
            facts[id(v)] = self._key(v.shape, v.dtype)
        for v in prog.constants:
            facts[id(v)] = self._key(v.shape, v.dtype)
        return facts

    def derived_out_types(self, op: Operation, facts: dict):
        """[(shape, dtype_str)] for op's outputs, or None when underived
        (fused op whose abstract eval is unavailable)."""
        if op.eqn is not None:
            return [self._key(tuple(getattr(ov.aval, "shape", ())),
                              getattr(ov.aval, "dtype", None))
                    for ov in op.eqn.outvars]
        cached = self._fused_cache.get(id(op), False)
        if cached is not False:
            return cached
        import jax
        try:
            in_avals = [jax.ShapeDtypeStruct(facts[id(v)][0],
                                             facts[id(v)][1])
                        for v in op.inputs]
            outs = jax.eval_shape(lambda *a: op.evaluate(list(a)), *in_avals)
            derived = [self._key(o.shape, o.dtype) for o in outs]
        except Exception:  # noqa: BLE001 — an un-abstractable fused op
            derived = None  # just opts out of derivation (stays checkable
        self._fused_cache[id(op)] = derived   # structurally, not by type)
        return derived

    def derived_in_types(self, op: Operation):
        """Expected operand types, or None (only eqn ops pin operands)."""
        if op.eqn is None:
            return None
        return [self._key(tuple(getattr(iv.aval, "shape", ())),
                          getattr(iv.aval, "dtype", None))
                for iv in op.eqn.invars]

    def transfer(self, op: Operation, facts: dict) -> bool:
        if any(id(v) not in facts for v in op.inputs):
            return False            # operands not yet derived
        derived = self.derived_out_types(op, facts)
        if derived is None:
            derived = [self._key(o.shape, o.dtype) for o in op.outputs]
        changed = False
        for v, d in zip(op.outputs, derived):
            if facts.get(id(v)) != d:
                facts[id(v)] = d
                changed = True
        return changed


# --------------------------------------------------------------------------
# liveness + donation safety
# --------------------------------------------------------------------------

class Liveness(DataflowAnalysis):
    """Backward liveness. After ``run``, facts map ``("after", i)`` (op
    index) -> frozenset of Value ids live *after* op i executes; the
    boundary ``("after", len(ops)-1)``... is seeded from the program
    outputs. Also exposes ``last_use``/``uses`` index maps (computed in
    run()) for clients that want ranges rather than sets."""

    direction = "backward"
    name = "liveness"

    def __init__(self):
        self.index: dict[int, int] = {}
        self.uses: dict[int, list[int]] = {}       # id(Value) -> op idxs
        self.last_use: dict[int, int] = {}         # id(Value) -> op idx

    def boundary(self, prog: Program) -> dict:
        self.index = {id(op): i for i, op in enumerate(prog.ops)}
        self.uses = {}
        for i, op in enumerate(prog.ops):
            for v in op.inputs:
                self.uses.setdefault(id(v), []).append(i)
        self.last_use = {vid: idxs[-1] for vid, idxs in self.uses.items()}
        out_live = frozenset(id(v) for v in prog.outputs)
        n = len(prog.ops)
        facts = {("after", n - 1): out_live} if n else {}
        facts["exit"] = out_live
        return facts

    def transfer(self, op: Operation, facts: dict) -> bool:
        i = self.index[id(op)]
        live_after = facts.get(("after", i), frozenset())
        live_before = (live_after - {id(o) for o in op.outputs}) \
            | {id(v) for v in op.inputs}
        changed = False
        if facts.get(("before", i)) != live_before:
            facts[("before", i)] = live_before
            changed = True
        if i > 0:
            prev = facts.get(("after", i - 1), frozenset())
            merged = prev | live_before
            if merged != prev:
                facts[("after", i - 1)] = merged
                changed = True
        return changed


# ops that alias an operand's buffer into a same-typed output under
# donation — the "in-place" shapes XLA folds a donated input into. A
# donated Value must be DEAD after the first of these consumes it;
# elementwise reuse (x*2) is not an overwrite and stays unrestricted.
_OVERWRITE_OPS = ("dynamic_update_slice", "dynamic-update-slice",
                  "scatter", "scatter-add", "scatter_add", "scan", "while")


class DonationHazard:
    __slots__ = ("value", "overwrite_op", "overwrite_index", "use_index")

    def __init__(self, value, overwrite_op, overwrite_index, use_index):
        self.value = value
        self.overwrite_op = overwrite_op
        self.overwrite_index = overwrite_index
        self.use_index = use_index

    def __repr__(self):
        return (f"DonationHazard(%{self.value.vid} overwritten by "
                f"{self.overwrite_op.name!r} at op {self.overwrite_index}, "
                f"read again at op {self.use_index})")


def check_donation_safety(prog: Program, donate_argnums) -> list:
    """Statically reject the donated-double-buffer hazard: a donated
    program input consumed by an overwrite-shaped op (its buffer aliased
    into a same-shape/dtype output) and then *read again* later — on
    device the second read would see the overwritten buffer. Returns
    [DonationHazard]; empty = safe. The real serving decode programs
    pass (each donated KV pool feeds exactly its fused scan, last use ==
    overwrite point)."""
    lv = Liveness()
    lv.run(prog)
    hazards = []
    for argnum in donate_argnums or ():
        if argnum >= len(prog.inputs):
            continue
        d = prog.inputs[argnum]
        use_idxs = lv.uses.get(id(d), [])
        if len(use_idxs) < 2:
            continue                    # single consumer: trivially safe
        for i in use_idxs:
            op = prog.ops[i]
            bare = op.name.split(".")[-1]
            if op.name not in _OVERWRITE_OPS and bare not in _OVERWRITE_OPS:
                continue
            dkey = (tuple(d.shape), str(d.dtype))
            if not any((tuple(o.shape), str(o.dtype)) == dkey
                       for o in op.outputs):
                continue
            later = [j for j in use_idxs if j > i]
            if later:
                hazards.append(DonationHazard(d, op, i, later[0]))
                break
    return hazards


# --------------------------------------------------------------------------
# sharding-annotation consistency
# --------------------------------------------------------------------------

# --------------------------------------------------------------------------
# static cost model (FLOPs / bytes / roofline seconds)
# --------------------------------------------------------------------------

# TPU v5 lite: peak dense throughput, the dense-matmul efficiency
# fraction round 5 measured, and HBM bandwidth.
DEFAULT_ROOFLINE = {
    "peak_flops": 197e12,
    "efficiency": 0.068,
    "hbm_bps": 820e9,
}

# Interconnect (TPU v5 lite ICI): effective per-direction link bandwidth
# and per-collective launch latency. Feeds
# the CostModel's exposed-communication term — comm seconds for a
# collective-bearing op are wire_bytes / ici_bps + latency, and compute
# scheduled between the collective and its first consumer earns overlap
# credit against them (pir/overlap.py maximizes that credit).
DEFAULT_INTERCONNECT = {
    "ici_bps": 4.5e10,
    "link_latency_s": 1e-6,
}

_DTYPE_BYTES = {
    "float64": 8, "int64": 8, "uint64": 8, "complex64": 8,
    "float32": 4, "int32": 4, "uint32": 4,
    "bfloat16": 2, "float16": 2, "int16": 2, "uint16": 2,
    "int8": 1, "uint8": 1, "bool": 1, "float8_e4m3fn": 1,
    "float8_e5m2": 1,
}


def _numel(shape):
    n = 1
    for d in shape:
        n *= int(d)
    return n


def _aval_bytes(aval):
    return _numel(getattr(aval, "shape", ())) \
        * _DTYPE_BYTES.get(str(getattr(aval, "dtype", "float32")), 4)


def _inner_jaxprs(params):
    """Closed/open jaxprs nested in an eqn's params (scan's `jaxpr`,
    while's cond/body, pjit's `jaxpr`, custom-call `call_jaxpr`, ...)."""
    found = []
    for v in params.values():
        inner = getattr(v, "jaxpr", None)      # ClosedJaxpr
        if inner is not None and hasattr(inner, "eqns"):
            found.append(inner)
        elif hasattr(v, "eqns"):               # bare Jaxpr
            found.append(v)
    return found


def _jaxpr_cost(jaxpr, depth=0):
    """(flops, bytes) for one jaxpr body; recurses into control-flow
    primitives (scan multiplied by its trip count)."""
    flops = 0.0
    nbytes = 0.0
    if depth > 8:           # pathological nesting: stop pricing, stay finite
        return flops, nbytes
    for eqn in jaxpr.eqns:
        f, b = _eqn_cost(eqn, depth)
        flops += f
        nbytes += b
    return flops, nbytes


def _eqn_cost(eqn, depth=0):
    name = eqn.primitive.name
    out_elems = sum(_numel(getattr(ov.aval, "shape", ()))
                    for ov in eqn.outvars)
    io_bytes = float(sum(_aval_bytes(iv.aval) for iv in eqn.invars
                         if hasattr(iv, "aval"))
                     + sum(_aval_bytes(ov.aval) for ov in eqn.outvars))
    inner = _inner_jaxprs(eqn.params)
    if inner:
        trips = float(eqn.params.get("length", 1) or 1)
        f = b = 0.0
        for j in inner:
            jf, jb = _jaxpr_cost(j, depth + 1)
            f += jf
            b += jb
        return f * trips, b * trips
    if name == "dot_general":
        try:
            (lc, _rc), _batch = eqn.params["dimension_numbers"]
            lhs_shape = eqn.invars[0].aval.shape
            k = _numel([lhs_shape[d] for d in lc])
            first_out = _numel(eqn.outvars[0].aval.shape)
            return 2.0 * first_out * k, io_bytes
        except Exception:  # noqa: BLE001 — odd dnums: elementwise floor
            pass
    if name in ("conv_general_dilated",):
        # not emitted by the llama stack; price as heavy elementwise
        return 10.0 * out_elems, io_bytes
    return float(out_elems), io_bytes


class OpCost:
    __slots__ = ("flops", "bytes")

    def __init__(self, flops=0.0, bytes=0.0):
        self.flops = float(flops)
        self.bytes = float(bytes)

    def __repr__(self):
        return f"OpCost(flops={self.flops:.3g}, bytes={self.bytes:.3g})"


class ProgramCost:
    """Aggregate static price of one compiled program, stamped on its
    CompileReport so every dispatch carries predicted-vs-measured cost.
    ``raw_seconds`` is the uncalibrated roofline estimate; callers apply
    a measured calibration scale (platform + overhead) on top."""

    __slots__ = ("name", "flops", "bytes", "raw_seconds", "per_op",
                 "comm_seconds", "exposed_comm_seconds")

    def __init__(self, name, flops, bytes, raw_seconds, per_op,
                 comm_seconds=0.0, exposed_comm_seconds=0.0):
        self.name = name
        self.flops = flops
        self.bytes = bytes
        self.raw_seconds = raw_seconds
        self.per_op = per_op        # [(op name, OpCost)] heaviest-first
        # interconnect traffic of collective-bearing ops (0.0 for the
        # common single-chip program); "exposed" is what overlap credit
        # did not hide — the objective pir/overlap.py minimizes
        self.comm_seconds = float(comm_seconds)
        self.exposed_comm_seconds = float(exposed_comm_seconds)

    def summary(self):
        out = {"name": self.name, "flops": self.flops,
               "bytes": self.bytes, "raw_seconds": self.raw_seconds,
               "top_ops": [(n, c.flops, c.bytes)
                           for n, c in self.per_op[:5]]}
        if self.comm_seconds:
            out["comm_seconds"] = self.comm_seconds
            out["exposed_comm_seconds"] = self.exposed_comm_seconds
        return out

    def __repr__(self):
        return (f"ProgramCost({self.name!r}, {self.flops:.3g} flops, "
                f"{self.bytes:.3g} B, {self.raw_seconds:.3g}s raw)")


class CostModel(DataflowAnalysis):
    """Forward pricing pass: facts map id(op) -> OpCost computed from
    the op's stamped operand/result types (eqn-backed ops price from
    their jaxpr avals, control flow recursively with scan trip counts;
    fused ``pt.*`` ops are priced memory-bound from value byte traffic).
    ``analyze`` folds the facts into a ProgramCost with a roofline time
    estimate t = max(flops / (peak * eff), bytes / hbm_bps)."""

    direction = "forward"
    name = "cost"

    def __init__(self, roofline=None, interconnect=None):
        self.roofline = dict(DEFAULT_ROOFLINE)
        if roofline:
            self.roofline.update(roofline)
        self.interconnect = dict(DEFAULT_INTERCONNECT)
        if interconnect:
            self.interconnect.update(interconnect)

    @staticmethod
    def _value_bytes(values):
        return float(sum(
            _numel(v.shape) * _DTYPE_BYTES.get(str(v.dtype), 4)
            for v in values))

    def _op_cost(self, op: Operation) -> OpCost:
        try:
            if op.eqn is not None:
                f, b = _eqn_cost(op.eqn)
                return OpCost(f, b)
        except Exception:  # noqa: BLE001 — never fail a compile over pricing
            pass
        # fused regions carry their roofline provenance: the members'
        # summed flops (the math still happens — an absorbed dot_general
        # must not look memory-bound to shard_search/overlap) over the
        # fused boundary traffic
        fg = op.attrs.get("fusion_group")
        if isinstance(fg, dict) and "flops" in fg:
            try:
                return OpCost(float(fg["flops"]), float(fg["bytes"]))
            except Exception:  # noqa: BLE001 — malformed attrs: estimate
                pass
        # other fused pt.* op (or unpriceable eqn): memory-bound estimate
        # from the stamped value types; 2 flops/output element keeps the
        # compute axis populated
        out_b = self._value_bytes(op.outputs)
        in_b = self._value_bytes(op.inputs)
        out_elems = sum(_numel(v.shape) for v in op.outputs)
        return OpCost(2.0 * out_elems, in_b + out_b)

    def transfer(self, op: Operation, facts: dict) -> bool:
        if id(op) in facts:
            return False
        facts[id(op)] = self._op_cost(op)
        return True

    def group_bytes_saved(self, members, boundary_inputs, outputs):
        """Predicted HBM bytes a fusion group saves: the unfused members'
        summed operand+result traffic minus the fused op's boundary
        traffic (each boundary input read once, each result written
        once). Positive iff intermediates that used to round-trip HBM
        now die inside the fused kernel — the fuse pass's strict commit
        criterion. Duplicable members are excluded by the caller (their
        traffic persists either way and cancels)."""
        unfused = sum(self._op_cost(op).bytes for op in members)
        fused = (self._value_bytes(boundary_inputs)
                 + self._value_bytes(outputs))
        return unfused - fused

    def epilogue_bytes_saved(self, anchor, members, boundary_inputs,
                             outputs):
        """Predicted HBM bytes an anchored (epilogue) group saves. Same
        strict fused-vs-unfused comparison as ``group_bytes_saved`` but
        the compute anchor (a dot_general or nested fused region) is
        priced by its STAMPED value traffic, not ``_op_cost``: the
        anchor's flops happen either way, its operand reads cancel
        exactly against the fused op's boundary reads (or against an
        in-group producer's saved intermediate), and what fusion
        actually eliminates is the anchor's result write — the matmul
        output that used to round-trip HBM before the epilogue chain
        re-read it — unless that result is promoted to a group output.
        Pricing the anchor through ``_eqn_cost`` instead would let its
        accumulation-traffic estimate leak into the decision and
        overstate the win."""
        chain = [op for op in members if op is not anchor]
        unfused = (sum(self._op_cost(op).bytes for op in chain)
                   + self._value_bytes(anchor.inputs)
                   + self._value_bytes(anchor.outputs))
        fused = (self._value_bytes(boundary_inputs)
                 + self._value_bytes(outputs))
        return unfused - fused

    def analyze(self, prog: Program) -> ProgramCost:
        facts = self.run(prog)
        flops = sum(c.flops for c in facts.values())
        nbytes = sum(c.bytes for c in facts.values())
        eff_flops = self.roofline["peak_flops"] * self.roofline["efficiency"]
        raw = max(flops / eff_flops if eff_flops > 0 else 0.0,
                  nbytes / self.roofline["hbm_bps"]
                  if self.roofline["hbm_bps"] > 0 else 0.0)
        per_op = sorted(
            ((op.name, facts[id(op)]) for op in prog.ops),
            key=lambda nc: -(nc[1].flops + nc[1].bytes))
        comm = exposed = 0.0
        try:
            rep = self.exposed_comm_seconds(prog, facts)
            comm, exposed = rep["comm_seconds"], rep["exposed_seconds"]
        except Exception:  # noqa: BLE001 — pricing may never cost a compile
            pass
        return ProgramCost(prog.name, flops, nbytes, raw, per_op,
                           comm_seconds=comm, exposed_comm_seconds=exposed)

    # -- exposed-communication term (interconnect ledger row) ---------------
    def comm_seconds(self, op: Operation) -> float:
        """Interconnect seconds this op spends moving bytes: every
        collective reachable from its eqn (ops/collectives.py tags),
        priced on the baked ICI ledger row. 0.0 for pure-compute ops."""
        if op.eqn is None:
            return 0.0
        from ..ops.collectives import collective_traffic
        hits = collective_traffic(op.eqn)
        if not hits:
            return 0.0
        bps = self.interconnect["ici_bps"]
        lat = self.interconnect["link_latency_s"]
        return sum(nbytes / bps + lat for _, nbytes in hits if bps > 0)

    def _compute_seconds(self, cost: OpCost) -> float:
        eff = self.roofline["peak_flops"] * self.roofline["efficiency"]
        return max(cost.flops / eff if eff > 0 else 0.0,
                   cost.bytes / self.roofline["hbm_bps"]
                   if self.roofline["hbm_bps"] > 0 else 0.0)

    def exposed_comm_seconds(self, prog: Program, facts=None) -> dict:
        """Schedule-aware communication price of the program as ordered:
        for each collective-bearing op, the compute ops scheduled between
        it and the first consumer of any of its results earn overlap
        credit (async dispatch hides comm under them); what the credit
        does not cover is *exposed*. Windows are credited independently
        (optimistic: interconnect contention between overlapping windows
        is ignored, but other collectives never count as credit)."""
        if facts is None:
            facts = self.run(prog)
        comm_s = [self.comm_seconds(op) for op in prog.ops]
        compute_s = [self._compute_seconds(facts[id(op)])
                     for op in prog.ops]
        first_use = {}
        for i, op in enumerate(prog.ops):
            for v in op.inputs:
                first_use.setdefault(id(v), i)
        total = exposed = 0.0
        n = 0
        for i, op in enumerate(prog.ops):
            if comm_s[i] <= 0.0:
                continue
            n += 1
            total += comm_s[i]
            j = min((first_use.get(id(o), len(prog.ops))
                     for o in op.outputs), default=len(prog.ops))
            credit = sum(compute_s[k] for k in range(i + 1, j)
                         if comm_s[k] <= 0.0)
            exposed += max(0.0, comm_s[i] - credit)
        return {"comm_seconds": total, "exposed_seconds": exposed,
                "collectives": n}


class ShardingConsistency(DataflowAnalysis):
    """Forward propagation of optional ``Value.sharding`` annotations
    over a FlatLattice: an op whose annotated operands agree propagates
    that sharding to unannotated outputs; operands that disagree (and
    shape-preserving ops whose stamped output annotation contradicts the
    propagated one) join to CONFLICT. A join conflict only becomes a
    reported inconsistency once the op's outputs are annotated —
    annotated inputs feeding a not-yet-propagated interior (the window
    between annotate_inputs and the shard_prop pass, which every
    earlier pass's verifier run observes) are pending constraints, not
    an error. ``conflicts`` lists (op, detail) after ``run``. This is deliberately the *consistency* half of GSPMD
    propagation — the sharding-propagation pass (pir/shard_prop.py)
    supplies the decision procedure, then re-runs this to prove its
    assignment. Ops stamped with an ``attrs["sharding_rule"]`` contract
    (a contracting dot, a transpose, a cost-chosen reshard point) are
    their own boundary: operands legitimately carry different shardings
    there and the outputs take exactly their stamped annotation — but a
    declared rule whose outputs are NOT all annotated is itself flagged,
    so a forged or half-applied stamp cannot silence the check."""

    direction = "forward"
    name = "sharding"

    def __init__(self):
        self.lattice = FlatLattice()
        self.conflicts: list[tuple[Operation, str]] = []
        self._flagged: set[int] = set()

    @staticmethod
    def _annot(v: Value):
        return getattr(v, "sharding", None)

    def boundary(self, prog: Program) -> dict:
        facts = {}
        for v in list(prog.inputs) + list(prog.constants):
            facts[id(v)] = self._annot(v)
        return facts

    def transfer(self, op: Operation, facts: dict) -> bool:
        rule = op.attrs.get("sharding_rule") if op.attrs else None
        if rule is not None:
            # declared operand->result contract: no operand join; the
            # stamped output annotations ARE the facts (and must exist)
            if any(self._annot(o) is None for o in op.outputs) \
                    and id(op) not in self._flagged:
                self._flagged.add(id(op))
                self.conflicts.append(
                    (op, f"sharding_rule {rule!r} declared but not every "
                         f"output carries an annotation"))
            changed = False
            for o in op.outputs:
                fact = self._annot(o)
                if facts.get(id(o), None) != fact:
                    facts[id(o)] = fact
                    changed = True
            return changed
        joined = None
        for v in op.inputs:
            fact = self.lattice.join(facts.get(id(v)), self._annot(v))
            joined = self.lattice.join(joined, fact)
        # a join conflict is an ERROR only once this op's outputs carry
        # annotations — i.e. somebody claims propagation committed
        # through here without declaring a sharding_rule. Annotated
        # inputs feeding a not-yet-propagated interior (the state
        # between annotate_inputs and the shard_prop pass) are pending
        # constraints, not an inconsistency.
        committed = any(self._annot(o) is not None for o in op.outputs)
        if joined is CONFLICT and committed and id(op) not in self._flagged:
            self._flagged.add(id(op))
            annots = [(v.vid, facts.get(id(v), self._annot(v)))
                      for v in op.inputs]
            self.conflicts.append(
                (op, f"operands carry irreconcilable shardings: "
                     f"{[(f'%{vid}', s) for vid, s in annots if s]}"))
        changed = False
        for o in op.outputs:
            fact = self.lattice.join(joined, self._annot(o))
            if fact is CONFLICT and joined is not CONFLICT \
                    and id(op) not in self._flagged:
                self._flagged.add(id(op))
                self.conflicts.append(
                    (op, f"output %{o.vid} annotated {self._annot(o)!r} "
                         f"but operands propagate {joined!r}"))
            if facts.get(id(o), None) != fact:
                facts[id(o)] = fact
                changed = True
        return changed
