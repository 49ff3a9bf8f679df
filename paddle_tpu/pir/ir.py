"""PIR-lite program IR: Value / Operation / Program.

reference: paddle/pir/include/core/ (Operation/Value/Block SSA IR) and
paddle/fluid/pir/ — the reference's layer between program capture and
the backend compiler, where pattern rewriting (DRR), DCE/CSE and the
compile cache key all live.

TPU-native design: the captured program already exists as a jaxpr, so
the IR is a THIN, mutable SSA view over it — each Operation either
wraps one ``JaxprEqn`` (replayed verbatim through ``primitive.bind``)
or is a *fused* op carrying a Python callable installed by a rewrite
pattern. That keeps the evaluator trivially faithful (non-rewritten
ops execute byte-for-byte what jax traced) while making the program a
first-class object we can print, hash, transform and key a persistent
compile cache on — the capability COVERAGE.md row 12 previously mapped
wholesale onto "jaxpr/StableHLO" and never exercised.
"""

from __future__ import annotations

import hashlib
import re
from typing import Any, Callable, Optional

__all__ = ["Value", "Operation", "Program", "canonical_attr_text"]

_ADDR_RE = re.compile(r"0x[0-9a-fA-F]+")


def _scrub(text: str) -> str:
    """Make repr-derived text process-stable (drop heap addresses)."""
    return _ADDR_RE.sub("0x", text)


def canonical_attr_text(v) -> str:
    """Deterministic, process-stable rendering of an op attribute /
    eqn param — the piece of the canonical hash that must not pick up
    object identities. Nested jaxprs render via jax's printer (stable
    alphabetic var names) with addresses scrubbed; arrays render as a
    content digest; callables by name only."""
    import numpy as np

    if v is None or isinstance(v, (bool, int, float, complex, str, bytes)):
        return repr(v)
    if isinstance(v, np.dtype):
        return f"dtype({v.name})"
    if isinstance(v, type):
        return f"type({v.__module__}.{v.__name__})"
    if isinstance(v, dict):
        items = ", ".join(f"{canonical_attr_text(k)}: {canonical_attr_text(x)}"
                          for k, x in sorted(v.items(), key=lambda kv: repr(kv[0])))
        return "{" + items + "}"
    if isinstance(v, (tuple, list, set, frozenset)):
        body = ", ".join(canonical_attr_text(x) for x in v)
        open_, close = ("(", ")") if isinstance(v, tuple) else ("[", "]")
        if isinstance(v, (set, frozenset)):
            open_, close = "{", "}"
        return open_ + body + close
    if hasattr(v, "jaxpr") or type(v).__name__ in ("Jaxpr", "ClosedJaxpr"):
        return "jaxpr<" + _scrub(str(v)) + ">"
    if hasattr(v, "shape") and hasattr(v, "dtype"):  # ndarray-like
        arr = np.asarray(v)
        digest = hashlib.sha256(arr.tobytes()).hexdigest()[:16]
        return f"ndarray({arr.dtype}, {tuple(arr.shape)}, {digest})"
    if callable(v):
        return f"fn<{getattr(v, '__name__', type(v).__name__)}>"
    return _scrub(repr(v))


def _constrained(v, x):
    """Replay hook for annotated values: re-assert ``v.sharding``
    through with_sharding_constraint while a shard_prop mesh scope is
    active (no scope / any failure -> x unchanged). Lazy import: only
    programs the propagation pass annotated ever reach this."""
    from .shard_prop import apply_constraint
    return apply_constraint(x, v.sharding)


class Value:
    """One SSA value: produced by exactly one Operation (or a program
    input / constant), consumed by any number. ``sharding`` is an
    optional annotation (mesh-axes spec) consumed by the sharding
    consistency analysis (pir/analysis.py) — None everywhere until a
    sharding-propagation pass stamps it; it does not participate in
    canonical hashing."""

    __slots__ = ("vid", "shape", "dtype", "op", "sharding")

    def __init__(self, vid: int, shape, dtype, op: Optional["Operation"] = None):
        self.vid = vid
        self.shape = tuple(shape)
        self.dtype = dtype
        self.op = op          # defining op; None for inputs / constants
        self.sharding = None  # optional sharding annotation

    @property
    def type_str(self) -> str:
        return f"{self.dtype}[{','.join(str(s) for s in self.shape)}]"

    @property
    def sharding_str(self) -> str:
        """Printable sharding suffix (``<dp,*>`` style; empty when
        unannotated). Display only — NEVER part of canonical_text:
        identical programs must hash identically whether or not the
        propagation pass annotated them."""
        if self.sharding is None:
            return ""
        return ("<" + ",".join("*" if a is None else str(a)
                               for a in self.sharding) + ">")

    def __repr__(self):
        return f"%{self.vid}: {self.type_str}{self.sharding_str}"


class Operation:
    """One op. Either a replayed jaxpr eqn (``eqn`` set, executed via
    ``eqn.primitive.bind``) or a fused op (``fn`` set, a Python callable
    installed by a rewrite pattern; name prefixed ``pt.``)."""

    __slots__ = ("name", "inputs", "outputs", "attrs", "eqn", "fn", "scope",
                 "_canon")

    def __init__(self, name: str, inputs: list, outputs: list,
                 attrs: Optional[dict] = None, eqn=None,
                 fn: Optional[Callable] = None, scope=None):
        self.name = name
        self.inputs = list(inputs)
        self.outputs = list(outputs)
        self.attrs = dict(attrs or {})
        self.eqn = eqn
        self.fn = fn
        # the jax.named_scope stack the op was traced under (a replayed
        # eqn's own; a rewrite hands a fused op its root's). Re-entered
        # by evaluate(), so component scopes (observability/catalog.py
        # TRACE_SCOPES) and kv.* effect scopes reach the lowered program.
        # Metadata only: never part of attr_text / canonical_text.
        if scope is None and eqn is not None:
            scope = eqn.source_info.name_stack
        self.scope = scope if getattr(scope, "stack", None) else None
        self._canon = None
        for o in self.outputs:
            o.op = self

    def has_effects(self) -> bool:
        return self.eqn is not None and bool(getattr(self.eqn, "effects", ()))

    def attr_text(self) -> str:
        if self._canon is None:
            params = self.eqn.params if self.eqn is not None else self.attrs
            self._canon = canonical_attr_text(params)
        return self._canon

    def evaluate(self, in_vals: list) -> list:
        """Execute this op on concrete or traced arrays. Replayed eqns
        rebind exactly the way jax.core.eval_jaxpr does — through
        get_bind_params, so call-like primitives (pjit, custom_jvp/vjp,
        scan, ...) reconstruct their callable sub-terms — and, like it,
        under the eqn's own name stack (appended to the ambient one)."""
        if self.scope is None:
            return self._evaluate(in_vals)
        from jax.extend import source_info_util as siu
        with siu.set_name_stack(siu.current_name_stack() + self.scope):
            return self._evaluate(in_vals)

    def _evaluate(self, in_vals: list) -> list:
        if self.fn is not None:
            out = self.fn(*in_vals)
            return list(out) if isinstance(out, (tuple, list)) else [out]
        prim = self.eqn.primitive
        subfuns, bind_params = prim.get_bind_params(self.eqn.params)
        out = prim.bind(*subfuns, *in_vals, **bind_params)
        return list(out) if prim.multiple_results else [out]

    def __repr__(self):
        outs = ", ".join(repr(o) for o in self.outputs)
        ins = ", ".join(f"%{v.vid}" for v in self.inputs)
        return f"{outs} = {self.name}({ins})"


class Program:
    """A captured program: inputs -> ops (topological) -> outputs, plus
    bound constants (jaxpr consts and inlined literals)."""

    def __init__(self, name: str = "program"):
        self.name = name
        self.inputs: list[Value] = []
        self.ops: list[Operation] = []
        self.outputs: list[Value] = []
        self.constants: dict[Value, Any] = {}   # Value -> array
        self._next_vid = 0

    # -- construction -------------------------------------------------------
    def new_value(self, shape, dtype, op=None) -> Value:
        v = Value(self._next_vid, shape, dtype, op)
        self._next_vid += 1
        return v

    def add_constant(self, arr) -> Value:
        import numpy as np
        a = np.asarray(arr) if not hasattr(arr, "dtype") else arr
        v = self.new_value(getattr(a, "shape", ()), a.dtype)
        self.constants[v] = arr
        return v

    # -- queries ------------------------------------------------------------
    def users(self) -> dict:
        """Value -> [Operation] consumer map (outputs count as users via
        the None sentinel)."""
        u: dict[Value, list] = {}
        for op in self.ops:
            for v in op.inputs:
                u.setdefault(v, []).append(op)
        for v in self.outputs:
            u.setdefault(v, []).append(None)
        return u

    def num_ops(self) -> int:
        return len(self.ops)

    # -- mutation (rewrites) ------------------------------------------------
    def replace_region(self, region_ops: list, new_op: Operation):
        """Replace a connected set of ops with one fused op. The fused
        op must produce the exact Value objects the region produced (so
        downstream users need no rewiring) and consume only values
        defined outside the region."""
        region = set(map(id, region_ops))
        idx = max(i for i, op in enumerate(self.ops) if id(op) in region)
        # splice the fused op where the last region op sat
        out = []
        for i, op in enumerate(self.ops):
            if id(op) not in region:
                out.append(op)
            elif i == idx:
                out.append(new_op)
        self.ops = out

    # -- execution ----------------------------------------------------------
    def bind(self, *args):
        """Evaluate the program on arrays (concrete or tracers) — the
        faithful interpreter: replayed eqns go through primitive.bind,
        fused ops through their callables. jit-ing this function yields
        the post-rewrite XLA program."""
        if len(args) != len(self.inputs):
            raise TypeError(f"{self.name}: expected {len(self.inputs)} "
                            f"args, got {len(args)}")
        env: dict[int, Any] = {}
        for v, a in zip(self.inputs, args):
            env[id(v)] = a if v.sharding is None else _constrained(v, a)
        for v, c in self.constants.items():
            env[id(v)] = c
        for op in self.ops:
            in_vals = [env[id(v)] for v in op.inputs]
            for v, o in zip(op.outputs, op.evaluate(in_vals)):
                env[id(v)] = o if v.sharding is None else _constrained(v, o)
        return tuple(env[id(v)] for v in self.outputs)

    # -- printing / hashing -------------------------------------------------
    def to_string(self, include_attrs: bool = True, max_ops: int = 0) -> str:
        """Paddle-parity IR dump (reference: pir Program::Print /
        static Program.__str__): one op per line, SSA-numbered."""
        lines = [f"program @{self.name} ("
                 + ", ".join(repr(v) for v in self.inputs) + ") {"]
        for v in self.constants:
            lines.append(f"  %{v.vid} = const : {v.type_str}")
        shown = self.ops if not max_ops else self.ops[:max_ops]
        for op in shown:
            outs = ", ".join(repr(o) for o in op.outputs)
            ins = ", ".join(f"%{v.vid}" for v in op.inputs)
            attr = ""
            if include_attrs:
                params = (op.eqn.params if op.eqn is not None else op.attrs)
                shown_attrs = {k: v for k, v in params.items()
                               if not hasattr(v, "jaxpr")
                               and not callable(v)} if params else {}
                if shown_attrs:
                    attr = " {" + ", ".join(
                        f"{k}={canonical_attr_text(v)}"
                        for k, v in sorted(shown_attrs.items())) + "}"
            lines.append(f"  {outs} = \"{op.name}\"({ins}){attr}")
        if max_ops and len(self.ops) > max_ops:
            lines.append(f"  ... ({len(self.ops) - max_ops} more ops)")
        lines.append("  return " + ", ".join(f"%{v.vid}" for v in self.outputs))
        lines.append("}")
        return "\n".join(lines)

    __str__ = to_string
    __repr__ = lambda self: (f"<pir.Program @{self.name}: "
                             f"{len(self.ops)} ops, "
                             f"{len(self.inputs)} inputs>")

    def canonical_text(self) -> str:
        """Stable renumbered rendering used for hashing: value ids are
        assigned by first use order, constants render as content
        digests, attrs via canonical_attr_text — identical programs
        captured in different processes produce identical text."""
        renum: dict[int, int] = {}

        def rn(v: Value) -> str:
            n = renum.setdefault(id(v), len(renum))
            return f"%{n}:{v.type_str}"

        lines = ["in " + ", ".join(rn(v) for v in self.inputs)]
        for v, c in self.constants.items():
            lines.append(f"{rn(v)} = const {canonical_attr_text(c)}")
        for op in self.ops:
            ins = ", ".join(rn(v) for v in op.inputs)
            outs = ", ".join(rn(v) for v in op.outputs)
            lines.append(f"{outs} = {op.name}({ins}) {op.attr_text()}")
        lines.append("out " + ", ".join(rn(v) for v in self.outputs))
        return "\n".join(lines)

    def canonical_hash(self) -> str:
        return hashlib.sha256(self.canonical_text().encode()).hexdigest()
