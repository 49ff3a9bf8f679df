"""DRR-lite pattern rewriting.

reference: paddle/fluid/pir/drr/ — declarative rewrite rules: a source
pattern (a small graph of ops + constraint functions) and a result
pattern (one fused op). This is the -lite edition: patterns are Python
classes with an explicit ``match`` (structural walk + constraints over
folded constants) and ``rewrite`` (splice one fused Operation whose
callable routes between the hand-written kernel and a byte-faithful
replay of the matched region).

Production patterns:

* ``sdpa_route`` — the scaled-dot-product-attention subgraph
  (QK dot_general -> scale -> causal mask -> softmax -> PV dot_general)
  becomes one ``pt.sdpa`` op that dispatches by the attention rule
  (ops/pallas/attention_router.route): Pallas flash on a TPU where the
  rule says so, otherwise an exact replay of the captured region
  (identical numerics by construction).
* ``rms_epilogue`` — ``rmsnorm(pt.sdpa + residual) * gamma`` becomes
  ``pt.sdpa_rms_epilogue``, dispatching to
  ``flash_attention_rms_epilogue_bshd`` (the attention output never
  round-trips HBM unnormalized) where routed, else replay.

Constraint discipline: a pattern only fires when it can *prove* the
structure — e.g. causality is established by constant-folding the mask
subgraph (the fold pass runs first) and comparing against tril(ones),
never by guessing from op names.
"""

from __future__ import annotations

from typing import Optional

from .ir import Operation, Program
from .passes import Pass, PassResult

__all__ = ["RewritePattern", "PatternRewriter", "SdpaRoutePattern",
           "RmsEpiloguePattern", "region_replay"]

# ops the matcher walks through when following an edge (layout/dtype
# plumbing that does not change the math being matched)
_PASSTHROUGH = ("broadcast_in_dim", "convert_element_type", "reshape",
                "stop_gradient")


def region_replay(prog, region_ops, boundary_in, out_value):
    """Build a callable replaying `region_ops` from the boundary values:
    the fused op's mathematically-exact fallback path. Ops run in
    program (topological) order; constants are snapshotted now (a later
    DCE pruning the originals must not break the replay). Fused ops
    inside the region (pattern-over-pattern) replay through their own
    fn."""
    rid = set(map(id, region_ops))
    ordered = [op for op in prog.ops if id(op) in rid]
    const_env = {id(v): c for v, c in prog.constants.items()}

    def replay(*args):
        env = dict(const_env)
        for v, a in zip(boundary_in, args):
            env[id(v)] = a
        for op in ordered:
            ins = [env[id(v)] for v in op.inputs]
            for v, o in zip(op.outputs, op.evaluate(ins)):
                env[id(v)] = o
        return env[id(out_value)]

    return replay


class RewritePattern:
    name = "pattern"

    def match(self, prog: Program, op: Operation, users: dict):
        raise NotImplementedError

    def rewrite(self, prog: Program, m: dict) -> Operation:
        raise NotImplementedError


# --------------------------------------------------------------------------
# matching helpers
# --------------------------------------------------------------------------

def _is_const(prog, v):
    return v in prog.constants


def _const_of(prog, v):
    import numpy as np
    return np.asarray(prog.constants[v])


def _walk_up(v, names, collect):
    """Follow defining ops up through `names`, collecting them; returns
    the first value whose producer is not in `names`."""
    while v.op is not None and v.op.name in names:
        collect.append(v.op)
        # pass-throughs are single-math-input ops; pick the non-const
        # operand when an op like max(scalar, x) carries a bound
        ins = v.op.inputs
        v = ins[0] if len(ins) == 1 else next(
            (x for x in ins if x.op is not None or x.shape), ins[0])
    return v

def _sole_user(users, v, skip_none=False):
    us = [u for u in users.get(v, []) if not (skip_none and u is None)]
    return us[0] if len(us) == 1 and us[0] is not None else None


def _region_closed(users, region_ops, outs_allowed):
    """Every value produced inside the region is consumed only inside
    it, except the designated outputs."""
    rid = set(map(id, region_ops))
    allowed = set(map(id, outs_allowed))
    for op in region_ops:
        for o in op.outputs:
            if id(o) in allowed:
                continue
            for u in users.get(o, []):
                if u is None or id(u) not in rid:
                    return False
    return True


def _route_decision(bh, sq, sk, d, dtype, causal):
    from ..ops.pallas.attention_router import route
    return route(int(bh), int(sq), int(sk), int(d), dtype, bool(causal))


def _on_tpu():
    import jax
    return jax.default_backend() == "tpu"


# --------------------------------------------------------------------------
# sdpa -> routed attention backend
# --------------------------------------------------------------------------

_QK_DIMS = (((3,), (3,)), ((0, 2), (0, 2)))       # bshd x bshd -> bhqk
_PV_DIMS_VP = (((1,), (3,)), ((0, 2), (0, 1)))    # v as lhs, probs as rhs
_PV_DIMS_PV = (((3,), (1,)), ((0, 1), (0, 2)))    # probs as lhs, v as rhs


class SdpaRoutePattern(RewritePattern):
    name = "sdpa_route"

    def match(self, prog, op, users):
        import numpy as np
        if op.name != "div" or len(op.inputs) != 2:
            return None
        num, den = op.inputs
        exp_op = num.op
        if exp_op is None or exp_op.name != "exp":
            return None
        # denominator: reduce_sum(exp) through broadcasts
        chain_d: list = []
        dv = _walk_up(den, _PASSTHROUGH, chain_d)
        sum_op = dv.op
        if sum_op is None or sum_op.name != "reduce_sum" \
                or sum_op.inputs[0] is not exp_op.outputs[0] \
                or tuple(sum_op.eqn.params.get("axes") or ()) != (3,):
            return None
        # exp input: sub(logits, reduce_max(logits) [through guards])
        sub_op = exp_op.inputs[0].op
        if sub_op is None or sub_op.name != "sub":
            return None
        logits = sub_op.inputs[0]
        chain_m: list = []
        mv = _walk_up(sub_op.inputs[1], _PASSTHROUGH + ("max",), chain_m)
        max_op = mv.op
        if max_op is None or max_op.name != "reduce_max" \
                or max_op.inputs[0] is not logits \
                or tuple(max_op.eqn.params.get("axes") or ()) != (3,):
            return None

        softmax_ops = [exp_op, sub_op, sum_op, op, max_op] + chain_d + chain_m

        # upstream: optional where-mask, then optional scale-mul, then QK dot
        region = list(softmax_ops)
        causal = False
        cur = logits
        prod = cur.op
        mask_sq_sk = None
        if prod is not None and (
                prod.name == "select_n"
                or (prod.name == "pjit"
                    and prod.eqn.params.get("name") == "_where")):
            consts = [v for v in prod.inputs if _is_const(prog, v)]
            lives = [v for v in prod.inputs if not _is_const(prog, v)]
            if len(lives) != 1 or len(consts) != 2:
                return None
            mask_v = next((v for v in consts
                           if _const_of(prog, v).ndim == 2), None)
            fill_v = next((v for v in consts
                           if _const_of(prog, v).ndim == 0), None)
            if mask_v is None or fill_v is None:
                return None
            if float(_const_of(prog, fill_v)) > -1e9:
                return None
            mask = _const_of(prog, mask_v).astype(bool)
            sq, sk = mask.shape
            if not np.array_equal(
                    mask, np.tril(np.ones((sq, sk), bool), k=sk - sq)):
                return None           # only provable-causal masks rewrite
            causal = True
            mask_sq_sk = (sq, sk)
            region.append(prod)
            cur = lives[0]
            prod = cur.op
        scale = 1.0
        if prod is not None and prod.name in ("mul", "div"):
            sc = next((v for v in prod.inputs if _is_const(prog, v)
                       and _const_of(prog, v).ndim == 0), None)
            live = next((v for v in prod.inputs if not _is_const(prog, v)),
                        None)
            if sc is None or live is None:
                return None
            if prod.name == "div":
                if prod.inputs[0] is not live:     # const/x is not a scale
                    return None
                scale = 1.0 / float(_const_of(prog, sc))
            else:
                scale = float(_const_of(prog, sc))
            region.append(prod)
            cur = live
            prod = cur.op
        if prod is None or prod.name != "dot_general":
            return None
        qk = prod
        if qk.eqn.params.get("dimension_numbers") != _QK_DIMS:
            return None
        q, k = qk.inputs
        if len(q.shape) != 4 or len(k.shape) != 4:
            return None
        b, sq_, h, d = q.shape
        sk_ = k.shape[1]
        if k.shape[0] != b or k.shape[2] != h or k.shape[3] != d:
            return None
        if causal and mask_sq_sk != (sq_, sk_):
            return None
        region.append(qk)

        # downstream: probs (-> convert) -> PV dot_general -> transpose
        probs = op.outputs[0]
        pv_in = probs
        down: list = []
        u = _sole_user(users, pv_in)
        if u is not None and u.name == "convert_element_type":
            down.append(u)
            pv_in = u.outputs[0]
            u = _sole_user(users, pv_in)
        if u is None or u.name != "dot_general":
            return None
        pv = u
        dims = pv.eqn.params.get("dimension_numbers")
        if pv.inputs[1] is pv_in and dims == _PV_DIMS_VP:
            v_val = pv.inputs[0]
            want_perm = (0, 3, 1, 2)     # (b,h,d,q) -> (b,q,h,d)
        elif pv.inputs[0] is pv_in and dims == _PV_DIMS_PV:
            v_val = pv.inputs[1]
            want_perm = (0, 2, 1, 3)     # (b,h,q,d) -> (b,q,h,d)
        else:
            return None
        if v_val.shape[:3] != (b, sk_, h):
            return None
        down.append(pv)
        tr = _sole_user(users, pv.outputs[0])
        if tr is None or tr.name != "transpose" \
                or tuple(tr.eqn.params.get("permutation") or ()) != want_perm:
            return None
        down.append(tr)
        out_val = tr.outputs[0]
        if out_val.shape != (b, sq_, h, v_val.shape[3]):
            return None
        region += down
        if not _region_closed(users, region, [out_val]):
            return None
        return {"region": region, "q": q, "k": k, "v": v_val,
                "out": out_val, "causal": causal, "scale": scale,
                "shape": (b, sq_, sk_, h, d)}

    def rewrite(self, prog, m):
        b, sq, sk, h, d = m["shape"]
        q, k, v, out = m["q"], m["k"], m["v"], m["out"]
        causal, scale = m["causal"], m["scale"]
        dec = _route_decision(b * h, sq, sk, d, q.dtype, causal)
        replay = region_replay(prog, m["region"], [q, k, v], out)
        route_fwd = dec.fwd

        def fn(q_, k_, v_):
            if route_fwd == "pallas" and _on_tpu():
                from ..ops.pallas.flash_attention import flash_attention_bshd
                return flash_attention_bshd(q_, k_, v_, causal=causal,
                                            scale=scale)
            return replay(q_, k_, v_)

        new_op = Operation(
            "pt.sdpa", [q, k, v], [out],
            attrs={"causal": causal, "scale": scale, "route_fwd": route_fwd,
                   "route_why": dec.why,
                   "shape": (b, sq, sk, h, d)},
            fn=fn, scope=out.op.scope)
        prog.replace_region(m["region"], new_op)
        return new_op


# --------------------------------------------------------------------------
# rmsnorm(sdpa + residual) * gamma -> fused epilogue
# --------------------------------------------------------------------------

class RmsEpiloguePattern(RewritePattern):
    """Anchors on a ``pt.sdpa`` produced by SdpaRoutePattern (pattern-
    over-pattern: DRR result ops are legal source ops)."""

    name = "rms_epilogue"

    def match(self, prog, op, users):
        if op.name != "pt.sdpa":
            return None
        att = op.outputs[0]
        add = _sole_user(users, att)
        if add is None or add.name != "add":
            return None
        residual = add.inputs[1] if add.inputs[0] is att else add.inputs[0]
        region = [op, add]
        hh = add.outputs[0]
        cv = _sole_user(users, hh)
        if cv is not None and cv.name == "convert_element_type":
            region.append(cv)
            hh = cv.outputs[0]
        hh_users = [u for u in users.get(hh, []) if u is not None]
        sq_op = next((u for u in hh_users if u.name == "mul"
                      and u.inputs[0] is hh and u.inputs[1] is hh), None)
        if sq_op is None:
            return None
        region.append(sq_op)
        rs = _sole_user(users, sq_op.outputs[0])
        if rs is None or rs.name != "reduce_sum":
            return None
        axes = rs.eqn.params.get("axes")
        if tuple(axes or ()) != (len(hh.shape) - 1,):
            return None                     # norm axis must be head dim
        region.append(rs)
        # mean = sum/d (div by const), then + eps, rsqrt
        chain: list = []
        cur_op = _sole_user(users, rs.outputs[0])
        d = hh.shape[-1]
        saw_div = saw_eps = False
        eps = 0.0
        import numpy as np
        while cur_op is not None and cur_op.name in (
                "div", "mul", "add", "broadcast_in_dim", "reshape",
                "convert_element_type"):
            if cur_op.name in ("div", "mul", "add"):
                sc = next((v for v in cur_op.inputs if _is_const(prog, v)
                           and _const_of(prog, v).ndim == 0), None)
                if sc is None:
                    return None
                val = float(_const_of(prog, sc))
                if cur_op.name == "div" and abs(val - d) < 0.5:
                    saw_div = True
                elif cur_op.name == "mul" and abs(val - 1.0 / d) < 1e-12:
                    saw_div = True
                elif cur_op.name == "add":
                    saw_eps, eps = True, val
                else:
                    return None
            chain.append(cur_op)
            cur_op = _sole_user(users, cur_op.outputs[0])
        if cur_op is None or cur_op.name != "rsqrt" \
                or not (saw_div and saw_eps):
            return None
        region += chain + [cur_op]
        inv = cur_op.outputs[0]
        bchain: list = []
        nv = inv
        u = _sole_user(users, nv)
        while u is not None and u.name in ("broadcast_in_dim", "reshape",
                                           "convert_element_type"):
            bchain.append(u)
            nv = u.outputs[0]
            u = _sole_user(users, nv)
        norm_mul = u
        if norm_mul is None or norm_mul.name != "mul" \
                or hh not in norm_mul.inputs:
            return None
        region += bchain + [norm_mul]
        # * gamma: mul with a broadcast of a rank-1 weight value
        wmul = _sole_user(users, norm_mul.outputs[0])
        if wmul is None or wmul.name != "mul":
            return None
        wside = (wmul.inputs[1] if wmul.inputs[0] is norm_mul.outputs[0]
                 else wmul.inputs[0])
        wchain: list = []
        w_val = _walk_up(wside, _PASSTHROUGH, wchain)
        if len(w_val.shape) != 1 or w_val.shape[0] != hh.shape[-1]:
            return None
        region += wchain + [wmul]
        out_val = wmul.outputs[0]
        u = _sole_user(users, out_val)
        if u is not None and u.name == "convert_element_type":
            region.append(u)
            out_val = u.outputs[0]
        if not _region_closed(users, region, [out_val]):
            return None
        return {"region": region, "q": op.inputs[0], "k": op.inputs[1],
                "v": op.inputs[2], "residual": residual, "w": w_val,
                "out": out_val, "eps": eps, "sdpa": op}

    def rewrite(self, prog, m):
        sdpa = m["sdpa"]
        causal = sdpa.attrs["causal"]
        scale = sdpa.attrs["scale"]
        b, sq, sk, h, d = sdpa.attrs["shape"]
        eps = m["eps"]
        q, k, v, residual, w = (m["q"], m["k"], m["v"], m["residual"],
                                m["w"])
        dec = _route_decision(b * h, sq, sk, d, q.dtype, causal)
        route_fwd = dec.fwd
        replay = region_replay(prog, m["region"],
                               [q, k, v, residual, w], m["out"])
        out_dtype = m["out"].dtype

        def fn(q_, k_, v_, res_, w_):
            if route_fwd == "pallas" and _on_tpu():
                from ..ops.pallas.flash_attention import (
                    flash_attention_rms_epilogue_bshd)
                out = flash_attention_rms_epilogue_bshd(
                    q_, k_, v_, res_, w_, causal=causal, scale=scale,
                    eps=eps)
                return out.astype(out_dtype)
            return replay(q_, k_, v_, res_, w_)

        new_op = Operation(
            "pt.sdpa_rms_epilogue", [q, k, v, residual, w], [m["out"]],
            attrs={"causal": causal, "scale": scale, "eps": eps,
                   "route_fwd": route_fwd,
                   "route_why": dec.why,
                   "shape": (b, sq, sk, h, d)},
            fn=fn, scope=m["out"].op.scope)
        prog.replace_region(m["region"], new_op)
        return new_op


# --------------------------------------------------------------------------
# the pass
# --------------------------------------------------------------------------

_MAX_REWRITES = 64


class PatternRewriter(Pass):
    """Apply all registered patterns to fixpoint (bounded). Each applied
    rewrite is one edit; per-pattern counts go in the notes."""

    name = "pattern"

    def __init__(self, patterns: Optional[list] = None):
        self.patterns = (list(patterns) if patterns is not None
                         else [SdpaRoutePattern(), RmsEpiloguePattern()])

    def run(self, prog: Program) -> PassResult:
        counts: dict[str, int] = {}
        total = 0
        progress = True
        while progress and total < _MAX_REWRITES:
            progress = False
            for pat in self.patterns:
                users = prog.users()
                for op in prog.ops:
                    m = pat.match(prog, op, users)
                    if m is None:
                        continue
                    pat.rewrite(prog, m)
                    counts[pat.name] = counts.get(pat.name, 0) + 1
                    total += 1
                    progress = True
                    break   # program changed: rescan with fresh users
        notes = " ".join(f"{k}={v}" for k, v in sorted(counts.items()))
        return PassResult(total, notes or "no-match")
